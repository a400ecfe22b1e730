"""Plain reference of the LFM2-MoE decoder (LiquidAI's published
``config.json`` of LFM2-8B-A1B and ``transformers``'
``modeling_lfm2_moe.py``; the convolution operator, the attention and
the norms as in the dense sibling's ``modeling_lfm2.py``,
``Lfm2ShortConv.slow_forward``, ``Lfm2Attention``,
``Lfm2DecoderLayer``): pre-norm RMSNorm blocks; a gated short
convolution (``[B | C | X] = x W_in``, ``u = B * X``, causal depthwise
width 3, ``y = (C * conv(u)) W_out``, no bias, no activation) in the
``conv`` layers; grouped-query attention with RMS-normalised q and k
heads, THEN rotary (rotate-half, whole head), in the
``full_attention`` layers; a dense SwiGLU FFN in the first
``num_dense_layers`` layers and a sigmoid-routed mixture of gated
experts (top 4 of 32 by score + bias, weights the chosen scores over
their sum + 1e-6, no shared expert) in the others; one RMSNorm after
the last layer and the head tied to the embedding.  Straightforward
jax.numpy in float32; no kernels, no cache, no batching.  Imports
nothing of the program under test.

``q`` selects the arithmetic: ``None`` is float32 (callers set
``jax.default_matmul_precision("highest")``); ``"fp8"`` rounds both
operands of every linear layer to float8_e4m3 (per-tensor scales) --
the control, the nearest precision below the bf16 the configuration
states.  The router, the norms, the rotary and the convolution stay
float32 in both, as the configuration states them.

Departures from the public implementation are listed in the
configuration file under ``assumed``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
GLOBAL_LEAVES = ("embed", "norm")
# the published pattern; a cut in depth runs its first
# ``num_hidden_layers`` entries
LAYER_TYPES = ("conv", "conv") + ("full_attention", "conv", "conv",
                                  "conv") * 4 \
    + ("full_attention", "conv", "conv", "full_attention", "conv", "conv")
NUM_DENSE_LAYERS = 2
NORM_TOPK_EPS = 1e-6
CONV_LEAVES = ("win", "cw", "wout")
ATTN_LEAVES = ("wq", "wk", "wv", "wo", "qn", "kn")
DENSE_LEAVES = ("w1", "w3", "w2")
MOE_LEAVES = ("router", "rbias")
EXPERT_LEAVES = ("eg", "eu", "ed")


def is_attention(l: int) -> bool:
    return LAYER_TYPES[l] == "full_attention"


def is_moe(l: int) -> bool:
    return l >= NUM_DENSE_LAYERS


def held(c):
    """(first, count) of the routed experts this chip holds: all of
    them unless ``assumed.held_experts`` says otherwise."""
    first, count = c.get("assumed", {}).get(
        "held_experts", [0, c["num_experts"]])
    return int(first), int(count)


def layer_names(l: int):
    """The leaves of layer ``l``.  Each stacked expert leaf is a group
    of its own (``weights.group_of`` takes the first two name parts),
    so that no single random draw is larger than one expert matrix of
    a layer."""
    names = [f"layers.{l}.ln1"]
    names += [f"layers.{l}.{k}" for k in
              (ATTN_LEAVES if is_attention(l) else CONV_LEAVES)]
    names.append(f"layers.{l}.ln2")
    if is_moe(l):
        names += [f"layers.{l}.{k}" for k in MOE_LEAVES]
        names += [f"layers.{l}{k}.{k}" for k in EXPERT_LEAVES]
    else:
        names += [f"layers.{l}.{k}" for k in DENSE_LEAVES]
    return names


def param_specs(c: dict) -> dict:
    """name -> (shape, init).  Linear weights are [in, out]."""
    n = c["num_hidden_layers"]
    if (tuple(c["layer_types"][:n]) != LAYER_TYPES[:n]
            or c["num_dense_layers"] != NUM_DENSE_LAYERS or c["conv_bias"]
            or not c["norm_topk_prob"] or not c["use_expert_bias"]):
        raise ValueError("not the architecture this reference states")
    a = c.get("assumed", {})
    h, v = c["hidden_size"], c["vocab_size"]
    nh, kh = c["num_attention_heads"], c["num_key_value_heads"]
    d = h // nh
    f, fe = c["intermediate_size"], c["moe_intermediate_size"]
    _, ne = held(c)
    std = ("normal", a.get("initializer_range", 0.02))
    one = ("ones", a.get("norm_jitter", 0.02))
    conv = ("normal", a.get("conv_init_std", 0.3))
    per = {"ln1": ((h,), one), "ln2": ((h,), one),
           "win": ((h, 3 * h), std), "cw": ((c["conv_L_cache"], h), conv),
           "wout": ((h, h), std),
           "wq": ((h, nh * d), std), "wk": ((h, kh * d), std),
           "wv": ((h, kh * d), std), "wo": ((nh * d, h), std),
           "qn": ((d,), one), "kn": ((d,), one),
           "w1": ((h, f), std), "w3": ((h, f), std), "w2": ((f, h), std),
           "router": ((h, c["num_experts"]), std),
           "rbias": ((c["num_experts"],), ("zeros", std[1])),
           "eg": ((ne, h, fe), std), "eu": ((ne, h, fe), std),
           "ed": ((ne, fe, h), std)}
    specs = {"embed": ((v, h), std), "norm": ((h,), one)}
    for l in range(n):
        for name in layer_names(l):
            specs[name] = per[name.split(".")[-1]]
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


def short_conv(c, lp, x, q=None):
    """The gated short convolution over one sequence: x [T, H]; the
    positions before the sequence read zero."""
    h = x.shape[1]
    bcx = mm(x, lp["win"], q)
    u = bcx[:, :h] * bcx[:, 2 * h:]
    W = lp["cw"].shape[0]
    up = jnp.concatenate([jnp.zeros((W - 1, h), u.dtype), u])
    v = sum(lp["cw"][j] * up[j:j + x.shape[0]] for j in range(W))
    return mm(bcx[:, h:2 * h] * v, lp["wout"], q)


def rope(x, pos, theta):
    """Rotate-half rotary over the whole head: x [T, heads, D]."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos[:, None].astype(F32) * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def attention(c, lp, x, pos, q=None, qblock=512):
    """Causal grouped-query attention of one sequence, q and k heads
    RMS-normalised and then rotated."""
    nh, kh = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["hidden_size"] // nh
    T, eps = x.shape[0], c["norm_eps"]
    qh = rope(rms_norm(mm(x, lp["wq"], q).reshape(T, nh, d), lp["qn"], eps),
              pos, c["rope_theta"])
    k = rope(rms_norm(mm(x, lp["wk"], q).reshape(T, kh, d), lp["kn"], eps),
             pos, c["rope_theta"])
    v = mm(x, lp["wv"], q).reshape(T, kh, d)
    k, v = (jnp.repeat(t, nh // kh, axis=1) for t in (k, v))
    out = []
    for s in range(0, T, qblock):
        e = min(s + qblock, T)
        sc = jnp.einsum("qhd,khd->hqk", qh[s:e], k[:e]) / jnp.sqrt(F32(d))
        mask = jnp.arange(s, e)[:, None] >= jnp.arange(e)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v[:e]))
    return mm(jnp.concatenate(out).reshape(T, nh * d), lp["wo"], q)


def swiglu(x, wg, wu, wd, q=None):
    return mm(jax.nn.silu(mm(x, wg, q)) * mm(x, wu, q), wd, q)


def route(c, lp, x):
    """[T, router width] combine weights, nought off the chosen
    ``num_experts_per_tok``: sigmoid scores, the choice by score +
    bias, the weights the chosen scores over their sum + 1e-6, times
    ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(x @ lp["router"])
    _, idx = jax.lax.top_k(s + lp["rbias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + NORM_TOPK_EPS) \
        * c["routed_scaling_factor"]
    return jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], idx].set(w)


def moe(c, lp, x, q=None):
    """The held experts' part of the expert layer, expert by expert:
    every expert over every token, weighed by the combine weights
    (nought where the token did not choose it)."""
    first, count = held(c)
    w = route(c, lp, x)[:, first:first + count]         # [T, count]

    def one(y, e):
        eg, eu, ed, we = e
        return y + we[:, None] * swiglu(x, eg, eu, ed, q), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (lp["eg"], lp["eu"], lp["ed"], w.T))
    return y


def layer_apply(c, lp, h, pos, q=None):
    """One decoder layer over one sequence: h [T, H] f32, pos [T].  The
    kind of the layer is read off the leaves it is given."""
    eps = c["norm_eps"]
    x = rms_norm(h, lp["ln1"], eps)
    h = h + (attention(c, lp, x, pos, q) if "wq" in lp
             else short_conv(c, lp, x, q))
    x = rms_norm(h, lp["ln2"], eps)
    if "eg" in lp:
        return h + moe(c, lp, x, q)
    return h + swiglu(x, lp["w1"], lp["w3"], lp["w2"], q)


def embed(c, gp, ids):
    return gp["embed"][ids].astype(F32)


def logits(c, gp, h, q=None):
    return mm(rms_norm(h, gp["norm"], c["norm_eps"]), gp["embed"].T, q)


def layer_params(params, l):
    return {n.split(".")[-1]: params[n] for n in layer_names(l)}


def forward_logits(c, params, ids, q=None):
    """Logits [T, V] of one sequence; ``params`` is the flat tree."""
    h = embed(c, params, ids)
    pos = jnp.arange(ids.shape[0])
    for l in range(c["num_hidden_layers"]):
        h = layer_apply(c, layer_params(params, l), h, pos, q)
    return logits(c, params, h, q)
