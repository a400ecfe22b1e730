"""Plain reference of the Kimi-Linear decoder (moonshotai's published
``config.json`` and ``modeling_kimi.py``; the KDA recurrence as in
fla's ``chunk_kda``, written here token by token): pre-norm RMSNorm
blocks, three KDA layers (gated delta-rule linear attention behind a
width-4 causal depthwise convolution) to one MLA layer (latent
attention, no rotary: ``mla_use_nope``), a dense SwiGLU FFN in the
first layer and a sigmoid-routed mixture of gated experts with one
shared expert in the others, untied head.  Straightforward jax.numpy
in float32; no kernels, no cache, no chunking, no batching.  Imports
nothing of the program under test.

One chip's share of a deployment (the configuration file's
``deployment``): the router keeps its published width; of its experts
this chip holds ``assumed.held_experts = [first, count]`` and adds what
THEY give for the tokens routed to them.  What the absent experts
would have added is left out, and the partial result goes on to the
next layer.  The shared expert is computed whole.  A sliced vocabulary
is a smaller vocabulary.

``q`` selects the arithmetic: ``None`` is float32 (callers set
``jax.default_matmul_precision("highest")``); ``"fp8"`` rounds both
operands of every linear layer to float8_e4m3 (per-tensor scales) --
the control, the nearest precision below the bf16 the configuration
states.  The router, the norms, the decay and the recurrence stay
float32 in both, as the configuration states them.

Departures from the public implementation are listed in the
configuration file under ``assumed``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
GLOBAL_LEAVES = ("embed", "norm", "head")
# the published pattern (``linear_attn_config.full_attn_layers``,
# 1-indexed) and ``first_k_dense_replace``; ``param_specs`` checks the
# configuration against them
FULL_ATTN_LAYERS = (4, 8, 12, 16, 20, 24, 27)
FIRST_K_DENSE = 1
KDA_LEAVES = ("kq", "kk", "kv", "cq", "ck", "cv", "fa", "fb", "alog",
              "dtb", "wb", "ga", "gb", "onorm", "ko")
MLA_LEAVES = ("mq", "mkva", "mkvn", "mkvb", "mo")
DENSE_LEAVES = ("wg", "wu", "wd")
MOE_LEAVES = ("router", "rbias", "sg", "su", "sd")
EXPERT_LEAVES = ("eg", "eu", "ed")


def is_mla(l: int) -> bool:
    return (l + 1) in FULL_ATTN_LAYERS


def is_moe(l: int) -> bool:
    return l >= FIRST_K_DENSE


def held(c):
    """(first, count) of the routed experts this chip holds."""
    first, count = c.get("assumed", {}).get(
        "held_experts", [0, c["num_experts"]])
    if count != c["num_experts"]:
        raise ValueError("num_experts is the count of experts held here")
    return int(first), int(count)


def router_width(c) -> int:
    return int(c.get("published", {}).get("num_experts", c["num_experts"]))


def layer_names(l: int):
    """The leaves of layer ``l``.  Each stacked expert leaf is a group
    of its own (``weights.group_of`` takes the first two name parts),
    so that no single random draw is larger than one expert matrix of
    a layer."""
    names = [f"layers.{l}.ln1"]
    names += [f"layers.{l}.{k}" for k in
              (MLA_LEAVES if is_mla(l) else KDA_LEAVES)]
    names.append(f"layers.{l}.ln2")
    if is_moe(l):
        names += [f"layers.{l}.{k}" for k in MOE_LEAVES]
        names += [f"layers.{l}{k}.{k}" for k in EXPERT_LEAVES]
    else:
        names += [f"layers.{l}.{k}" for k in DENSE_LEAVES]
    return names


def param_specs(c: dict) -> dict:
    """name -> (shape, init).  Linear weights are [in, out]."""
    la = c["linear_attn_config"]
    if (tuple(la["full_attn_layers"]) != FULL_ATTN_LAYERS
            or c["first_k_dense_replace"] != FIRST_K_DENSE
            or c["num_shared_experts"] != 1 or not c["mla_use_nope"]
            or c["q_lora_rank"] is not None):
        raise ValueError("not the architecture this reference states")
    a = c.get("assumed", {})
    h, v = c["hidden_size"], c["vocab_size"]
    hk, dk, cw = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    kd = hk * dk
    r = a.get("gate_low_rank", dk)
    nh = c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    kvr = c["kv_lora_rank"]
    f, fe = c["intermediate_size"], c["moe_intermediate_size"]
    _, ne = held(c)
    std = ("normal", a.get("initializer_range", 0.02))
    one = ("ones", a.get("norm_jitter", 0.02))
    small = ("zeros", a.get("small_init", 0.1))
    conv = ("normal", a.get("conv_init_std", 0.3))
    kda = {"kq": ((h, kd), std), "kk": ((h, kd), std), "kv": ((h, kd), std),
           "cq": ((cw, kd), conv), "ck": ((cw, kd), conv),
           "cv": ((cw, kd), conv), "fa": ((h, r), std), "fb": ((r, kd), std),
           "alog": ((hk,), small), "dtb": ((kd,), small),
           "wb": ((h, hk), std), "ga": ((h, r), std), "gb": ((r, kd), std),
           "onorm": ((dk,), one), "ko": ((kd, h), std)}
    mla = {"mq": ((h, nh * (dn + dr)), std), "mkva": ((h, kvr + dr), std),
           "mkvn": ((kvr,), one), "mkvb": ((kvr, nh * (dn + dv)), std),
           "mo": ((nh * dv, h), std)}
    dense = {"wg": ((h, f), std), "wu": ((h, f), std), "wd": ((f, h), std)}
    moe = {"router": ((h, router_width(c)), std),
           "rbias": ((router_width(c),), ("zeros", std[1])),
           "sg": ((h, fe), std), "su": ((h, fe), std), "sd": ((fe, h), std),
           "eg": ((ne, h, fe), std), "eu": ((ne, h, fe), std),
           "ed": ((ne, fe, h), std)}
    per = {"ln1": ((h,), one), "ln2": ((h,), one), **kda, **mla, **dense,
           **moe}
    specs = {"embed": ((v, h), std), "norm": ((h,), one),
             "head": ((h, v), std)}
    for l in range(c["num_hidden_layers"]):
        for n in layer_names(l):
            specs[n] = per[n.split(".")[-1]]
    return specs


def _q(x, dt):
    """Round to a float8 type with a per-tensor scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(dt).astype(F32) * s


def mm(a, b, q=None):
    if q == "fp8":
        return _q(a, jnp.float8_e4m3fn) @ _q(b, jnp.float8_e4m3fn)
    if q is not None:
        raise ValueError(f"unknown arithmetic {q!r}")
    return a @ b


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def short_conv(x, w):
    """Causal depthwise convolution without bias over one sequence:
    x [T, C], w [W, C]; y_t = sum_j w[j] * x[t - (W-1) + j], the
    positions before the sequence reading zero."""
    W = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((W - 1, x.shape[1]), x.dtype), x])
    return sum(w[j] * xp[j:j + x.shape[0]] for j in range(W))


def kda(c, lp, x, q=None):
    """Kimi Delta Attention over one sequence, token by token."""
    la = c["linear_attn_config"]
    hk, dk = la["num_heads"], la["head_dim"]
    T = x.shape[0]

    def heads(y):
        return y.reshape(T, hk, dk)
    qh = heads(jax.nn.silu(short_conv(mm(x, lp["kq"], q), lp["cq"])))
    kh = heads(jax.nn.silu(short_conv(mm(x, lp["kk"], q), lp["ck"])))
    vh = heads(jax.nn.silu(short_conv(mm(x, lp["kv"], q), lp["cv"])))
    qh = l2_norm(qh) * dk ** -0.5
    kh = l2_norm(kh)
    g = -jnp.exp(lp["alog"])[None, :, None] * heads(jax.nn.softplus(
        mm(mm(x, lp["fa"], q), lp["fb"], q) + lp["dtb"]))
    beta = jax.nn.sigmoid(mm(x, lp["wb"], q))           # [T, hk]

    def step(S, t):
        qt, kt, vt, gt, bt = t
        S = jnp.exp(gt)[:, :, None] * S
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)
    _, o = jax.lax.scan(step, jnp.zeros((hk, dk, dk), F32),
                        (qh, kh, vh, g, beta))
    o = rms_norm(o, lp["onorm"], c["rms_norm_eps"])
    o = o * heads(jax.nn.sigmoid(mm(mm(x, lp["ga"], q), lp["gb"], q)))
    return mm(o.reshape(T, hk * dk), lp["ko"], q)


def mla(c, lp, x, q=None, qblock=512):
    """Latent attention of one sequence, expanded, causal, no rotary:
    the ``qk_rope_head_dim`` dims are plain dims shared by the heads."""
    nh = c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    kvr = c["kv_lora_rank"]
    T = x.shape[0]
    qh = mm(x, lp["mq"], q).reshape(T, nh, dn + dr)
    kva = mm(x, lp["mkva"], q)
    lat = rms_norm(kva[:, :kvr], lp["mkvn"], c["rms_norm_eps"])
    kvb = mm(lat, lp["mkvb"], q).reshape(T, nh, dn + dv)
    kh = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(
        kva[:, None, kvr:], (T, nh, dr))], -1)
    vh = kvb[..., dn:]
    out = []
    for s in range(0, T, qblock):
        e = min(s + qblock, T)
        sc = jnp.einsum("qhd,khd->hqk", qh[s:e], kh[:e]) \
            / jnp.sqrt(F32(dn + dr))
        mask = jnp.arange(s, e)[:, None] >= jnp.arange(e)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, vh[:e]))
    return mm(jnp.concatenate(out).reshape(T, nh * dv), lp["mo"], q)


def swiglu(x, wg, wu, wd, q=None):
    return mm(jax.nn.silu(mm(x, wg, q)) * mm(x, wu, q), wd, q)


def route(c, lp, x):
    """[T, router width] combine weights, nought off the chosen
    ``num_experts_per_token``: sigmoid scores, the choice by score +
    bias, the weights the chosen scores renormalised and scaled."""
    s = jax.nn.sigmoid(x @ lp["router"])
    _, idx = jax.lax.top_k(s + lp["rbias"], c["num_experts_per_token"])
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * c["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], idx].set(w)


# an expert's tokens are multiplied as one block of at most this many
# rows (the dense pass over every token is taken where more chose it)
EXPERT_ROWS = 256


def moe(c, lp, x, q=None, shared=True):
    """The held experts' part of the expert layer, expert by expert,
    plus the shared expert (``shared=False`` leaves it out: the share
    test counts it once).  Each expert is applied to the tokens that
    chose it: the ``EXPERT_ROWS`` of largest weight, gathered, when no
    more than that did (the rest of those rows weigh nought), and
    every token otherwise -- the same sum either way, at a twelfth of
    the operations for a sequence of 3,072 tokens."""
    first, count = held(c)
    w = route(c, lp, x)[:, first:first + count]         # [T, count]
    rows = min(EXPERT_ROWS, x.shape[0])

    def one(y, e):
        eg, eu, ed, we = e

        def few(y):
            wt, at = jax.lax.top_k(we, rows)
            return y.at[at].add(wt[:, None] * swiglu(x[at], eg, eu, ed, q))

        def every(y):
            return y + we[:, None] * swiglu(x, eg, eu, ed, q)
        return jax.lax.cond(jnp.sum(we > 0) <= rows, few, every, y), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (lp["eg"], lp["eu"], lp["ed"], w.T))
    if shared:
        y = y + swiglu(x, lp["sg"], lp["su"], lp["sd"], q)
    return y


def layer_apply(c, lp, h, pos, q=None):
    """One decoder layer over one sequence: h [T, H] f32, pos [T]
    (unused: no layer of this model reads a position).  The kind of
    the layer is read off the leaves it is given."""
    eps = c["rms_norm_eps"]
    x = rms_norm(h, lp["ln1"], eps)
    h = h + (kda(c, lp, x, q) if "kq" in lp else mla(c, lp, x, q))
    x = rms_norm(h, lp["ln2"], eps)
    if "eg" in lp:
        return h + moe(c, lp, x, q)
    return h + swiglu(x, lp["wg"], lp["wu"], lp["wd"], q)


def embed(c, gp, ids):
    return gp["embed"][ids].astype(F32)


def logits(c, gp, h, q=None):
    return mm(rms_norm(h, gp["norm"], c["rms_norm_eps"]), gp["head"], q)


def layer_params(params, l):
    return {n.split(".")[-1]: params[n] for n in layer_names(l)}


def forward_logits(c, params, ids, q=None):
    """Logits [T, V] of one sequence; ``params`` is the flat tree."""
    h = embed(c, params, ids)
    pos = jnp.arange(ids.shape[0])
    for l in range(c["num_hidden_layers"]):
        h = layer_apply(c, layer_params(params, l), h, pos, q)
    return logits(c, params, h, q)
