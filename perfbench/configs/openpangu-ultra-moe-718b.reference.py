"""Plain reference of the openPangu-Ultra-MoE decoder (the published
``config.json``, ``model_type`` ``pangu_ultra_moe``; the family's code
is DeepSeek-V3's with sandwich norms): latent attention with a low-rank
query and rotary in every layer, a norm before AND after each sublayer,
a dense SwiGLU FFN in the leading layers and a sigmoid-routed mixture
of gated experts with one shared expert behind them, an untied head,
and DeepSeek-V3's multi-token-prediction module.  Straightforward
jax.numpy in float32; no kernels, no cache, no absorbed attention (K
and V are expanded from the latent for every position), no batching.
Imports nothing of the program under test.

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
states.  The router, the norms, rotary and the softmax stay float32 in
both, as the configuration states them.

What the published config leaves open is listed in the configuration
file under ``assumed``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
GLOBAL_LEAVES = ("embed", "norm", "head")
NORM_LEAVES = ("ln1", "ln2", "ln3", "ln4")
MLA_LEAVES = ("qa", "qan", "qb", "kva", "kvn", "kvb", "wo")
DENSE_LEAVES = ("wg", "wu", "wd")
MOE_LEAVES = ("router", "rbias", "sg", "su", "sd")
EXPERT_LEAVES = ("eg", "eu", "ed")
MTP_LEAVES = ("enorm", "hnorm", "eh", "mnorm")
# the served cut's ``first_k_dense_replace`` (the three leading dense
# layers count once); ``param_specs`` checks the configuration
FIRST_K_DENSE = 1


def held(c):
    """(first, count) of the routed experts this chip holds."""
    first, count = c.get("assumed", {}).get(
        "held_experts", [0, c["n_routed_experts"]])
    if count != c["n_routed_experts"]:
        raise ValueError("n_routed_experts is the count of experts held "
                         "here")
    return int(first), int(count)


def router_width(c) -> int:
    return int(c.get("published", {}).get("n_routed_experts",
                                          c["n_routed_experts"]))


def is_moe(l: int) -> bool:
    return l >= FIRST_K_DENSE


# leaves that are a group of their own (``weights.group_of`` takes the
# first two name parts): the weight maker's temporaries grow with its
# largest group (16 bytes an element), and a layer's leaves drawn as one
# group (621M elements in the dense layer) do not fit beside the 9.84 GB
# they make; the largest group is now the embedding with the head
OWN_GROUP = ("wo", "wg", "wu", "wd") + EXPERT_LEAVES


def _block_names(prefix: str, moe: bool):
    """The leaves of one decoder layer under ``prefix``."""
    kinds = NORM_LEAVES + MLA_LEAVES + (
        MOE_LEAVES + EXPERT_LEAVES if moe else DENSE_LEAVES)
    return [f"{prefix}{k}.{k}" if k in OWN_GROUP else f"{prefix}.{k}"
            for k in kinds]


def layer_names(l: int):
    return _block_names(f"layers.{l}", is_moe(l))


def mtp_names(k: int = 0):
    """The leaves of MTP module ``k``: its two norms, ``eh_proj``, its
    final norm, and one decoder layer of the expert kind."""
    return ([f"layers.mtp{k}.{n}" for n in MTP_LEAVES]
            + _block_names(f"layers.mtp{k}", True))


def param_specs(c: dict) -> dict:
    """name -> (shape, init).  Linear weights are [in, out]."""
    if (not c["sandwich_norm"] or c["n_shared_experts"] != 1
            or not c["norm_topk_prob"] or c["tie_word_embeddings"]
            or c["attention_bias"] or c["hidden_act"] != "silu"
            or c["first_k_dense_replace"] != FIRST_K_DENSE):
        raise ValueError("not the architecture this reference states")
    a = c.get("assumed", {})
    h, v = c["hidden_size"], c["vocab_size"]
    nh, qr, kvr = (c["num_attention_heads"], c["q_lora_rank"],
                   c["kv_lora_rank"])
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    f, fe = c["intermediate_size"], c["moe_intermediate_size"]
    _, ne = held(c)
    std = ("normal", a.get("initializer_range", 0.02))
    one = ("ones", a.get("norm_jitter", 0.02))
    per = {"ln1": ((h,), one), "ln2": ((h,), one), "ln3": ((h,), one),
           "ln4": ((h,), one),
           "qa": ((h, qr), std), "qan": ((qr,), one),
           "qb": ((qr, nh * (dn + dr)), std),
           "kva": ((h, kvr + dr), std), "kvn": ((kvr,), one),
           "kvb": ((kvr, nh * (dn + dv)), std), "wo": ((nh * dv, h), std),
           "wg": ((h, f), std), "wu": ((h, f), std), "wd": ((f, h), std),
           "router": ((h, router_width(c)), std),
           # the architecture has no selection bias: the program's
           # expert layer keeps the leaf, and it is nought
           "rbias": ((router_width(c),), ("zeros", 0.0)),
           "sg": ((h, fe), std), "su": ((h, fe), std), "sd": ((fe, h), std),
           "eg": ((ne, h, fe), std), "eu": ((ne, h, fe), std),
           "ed": ((ne, fe, h), std),
           "enorm": ((h,), one), "hnorm": ((h,), one),
           "eh": ((2 * h, h), std), "mnorm": ((h,), one)}
    specs = {"embed": ((v, h), std), "norm": ((h,), one),
             "head": ((h, v), std)}
    for l in range(c["num_hidden_layers"]):
        for n in layer_names(l):
            specs[n] = per[n.split(".")[-1]]
    for k in range(c["num_nextn_predict_layers"]):
        for n in mtp_names(k):
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


def rope(x, pos, theta):
    """Rotary embedding, rotate-half pairing (dim i with dim i + D/2):
    x [T, ..., D] at positions pos [T]."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv[None, :]           # [T, D/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def mla(c, lp, x, pos, q=None, qblock=512):
    """Latent attention of one sequence, expanded, causal: a low-rank
    query with a norm between its two factors, the
    ``qk_rope_head_dim`` dims of every query head and of the ONE
    shared key vector a token rotated at the token's position."""
    nh = c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    kvr, eps, theta = c["kv_lora_rank"], c["rms_norm_eps"], c["rope_theta"]
    T = x.shape[0]
    cq = rms_norm(mm(x, lp["qa"], q), lp["qan"], eps)
    qh = mm(cq, lp["qb"], q).reshape(T, nh, dn + dr)
    qh = jnp.concatenate([qh[..., :dn], rope(qh[..., dn:], pos, theta)], -1)
    kva = mm(x, lp["kva"], q)
    lat = rms_norm(kva[:, :kvr], lp["kvn"], eps)
    kpe = rope(kva[:, kvr:], pos, theta)                    # [T, dr]
    kvb = mm(lat, lp["kvb"], q).reshape(T, nh, dn + dv)
    kh = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(
        kpe[:, None, :], (T, nh, dr))], -1)
    vh = kvb[..., dn:]
    out = []
    for s in range(0, T, qblock):
        e = min(s + qblock, T)
        sc = jnp.einsum("qhd,khd->hqk", qh[s:e], kh[:e]) \
            / jnp.sqrt(F32(dn + dr))
        mask = jnp.arange(s, e)[:, None] >= jnp.arange(e)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, vh[:e]))
    return mm(jnp.concatenate(out).reshape(T, nh * dv), lp["wo"], q)


def swiglu(x, wg, wu, wd, q=None):
    return mm(jax.nn.silu(mm(x, wg, q)) * mm(x, wu, q), wd, q)


def route(c, lp, x):
    """[T, router width] combine weights, nought off the chosen
    ``num_experts_per_tok``: sigmoid scores in float32, the largest
    chosen (no bias, no groups), the weights the chosen scores over
    their sum + 1e-20, times ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(x @ lp["router"])
    w, idx = jax.lax.top_k(s, c["num_experts_per_tok"])
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
    every token otherwise -- the same sum either way."""
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
    """One decoder layer over one sequence: h [T, H] f32, pos [T].
    The kind of the FFN is read off the leaves it is given.  Sandwich
    norm: each sublayer's OUTPUT is normalised before the residual
    add."""
    eps = c["rms_norm_eps"]
    a = mla(c, lp, rms_norm(h, lp["ln1"], eps), pos, q)
    h = h + rms_norm(a, lp["ln2"], eps)
    z = rms_norm(h, lp["ln3"], eps)
    f = moe(c, lp, z, q) if "eg" in lp \
        else swiglu(z, lp["wg"], lp["wu"], lp["wd"], q)
    return h + rms_norm(f, lp["ln4"], eps)


def embed(c, gp, ids):
    return gp["embed"][ids].astype(F32)


def logits(c, gp, h, q=None):
    return mm(rms_norm(h, gp["norm"], c["rms_norm_eps"]), gp["head"], q)


def layer_params(params, names):
    return {n.split(".")[-1]: params[n] for n in names}


def hidden_states(c, params, ids, q=None):
    """The residual stream behind the last layer, [T, H]."""
    h = embed(c, params, ids)
    pos = jnp.arange(ids.shape[0])
    for l in range(c["num_hidden_layers"]):
        h = layer_apply(c, layer_params(params, layer_names(l)), h, pos, q)
    return h


def forward_logits(c, params, ids, q=None):
    """Logits [T, V] of one sequence; ``params`` is the flat tree."""
    return logits(c, params, hidden_states(c, params, ids, q), q)


def mtp_logits(c, params, hidden, next_ids, q=None, k: int = 0):
    """DeepSeek-V3's multi-token prediction, module ``k``: from the
    main model's residual stream ``hidden`` [T, H] at positions i and
    the tokens ``next_ids`` [T] at i + 1, logits [T, V] for the tokens
    at i + 2: ``u = [RMSNorm(E(next); enorm) | RMSNorm(hidden; hnorm)]
    W_eh``, one decoder layer of the expert kind over ``u`` (positions
    from 0), the module's own final norm, the main model's embedding
    and head."""
    eps = c["rms_norm_eps"]
    mp = layer_params(params, mtp_names(k))
    u = mm(jnp.concatenate(
        [rms_norm(embed(c, params, next_ids), mp["enorm"], eps),
         rms_norm(hidden, mp["hnorm"], eps)], -1), mp["eh"], q)
    u = layer_apply(c, mp, u, jnp.arange(u.shape[0]), q)
    return mm(rms_norm(u, mp["mnorm"], eps), params["head"], q)
