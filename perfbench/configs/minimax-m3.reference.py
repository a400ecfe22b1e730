"""Plain reference of the MiniMax-M3 decoder (the published
``config.json``'s language model): grouped-query attention with
per-head Gemma norms and partial rotary, made block-sparse by a learned
indexer (MSA) in every layer, a dense FFN in the leading layers and
sigmoid-routed experts with a routing bias and one shared expert
behind them, all in the clamped SwiGLU form (``swigluoai``), Gemma
norms, an untied head.  Straightforward jax.numpy in float32; no
kernels, no cache, no pages in memory, no batching.  Imports nothing
of the program under test.

MSA, per query position ``t`` and kv head ``g``: the indexer's query
``ubar = sum_j rope(N(x W_I[g, j]; inn))``; each block of ``msa_block``
positions has the pooled key ``kmax = max`` over its keys (normalised,
rotated), elementwise; the block score is ``ubar . kmax / sqrt(D)``;
the query attends over block 0, its own block and the ``msa_local - 1``
before it, and the ``msa_top_k`` best-scored blocks among the others
before it (a stable sort: ties to the lower block), never past ``t``.
The selection is computed for every query on its own, here by a sort
and not by a top-k, and taken as a mask over the causal square, which
the reference computes whole, a block of queries at a time.

One chip's share of a deployment (the configuration file's
``deployment``): the router keeps its published width; of its experts
this chip holds ``assumed.held_experts = [first, count]`` and adds what
THEY give for the tokens routed to them.  What the absent experts
would have added is left out, and the partial result goes on to the
next layer.  The shared expert is computed whole.  A sliced vocabulary
is a smaller vocabulary.

``q`` selects the arithmetic: ``None`` is float32 (callers set
``jax.default_matmul_precision("highest")``); ``"fp8"`` rounds both
operands of every linear layer to float8_e4m3 (per-tensor scales; the
query, indexer and output projections' activations a block of
``QBLOCK`` queries at a time) --
the control, the nearest precision below the bf16 the configuration
states.  The router, the norms, rotary, the indexer's scores and the
softmax stay float32 in both.

What the published config leaves open is listed in the configuration
file under ``assumed``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
GLOBAL_LEAVES = ("embed", "norm", "head")
ATTN_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "qn", "kn", "wi",
               "inn")
DENSE_LEAVES = ("wg", "wu", "wd")
MOE_LEAVES = ("router", "rbias", "sg", "su", "sd")
EXPERT_LEAVES = ("eg", "eu", "ed")
# leaves that are a group of their own (``weights.group_of`` takes the
# first two name parts): the weight maker's temporaries grow with its
# largest group (16 bytes an element); the largest is then the
# embedding with the head (307M elements)
OWN_GROUP = ("wq", "wo", "wg", "wu", "wd") + EXPERT_LEAVES
# queries of the attention square computed at a time, and rows of an
# FFN: what one step holds at 43,008 positions (a [64, 64, 43008]
# float32 score block, 0.7 GB; a [2048, 12288] gate, 0.1 GB) beside
# the sampled sequences' residual streams
QBLOCK = 64
ROWS = 2048


def held(c):
    """(first, count) of the routed experts this chip holds."""
    first, count = c.get("assumed", {}).get(
        "held_experts", [0, c["num_local_experts"]])
    if count != c["num_local_experts"]:
        raise ValueError("num_local_experts is the count of experts held "
                         "here")
    return int(first), int(count)


def router_width(c) -> int:
    return int(c.get("published", {}).get("num_local_experts",
                                          c["num_local_experts"]))


def msa(c):
    """(block, top_k, local, index heads) of the indexer."""
    a = c["assumed"]
    return (int(a["msa_block"]), int(a["msa_top_k"]), int(a["msa_local"]),
            int(a["msa_index_heads"]))


def is_moe(c, l: int) -> bool:
    return int(c["moe_layer_freq"][l]) == 1


def layer_names_of(l: int, moe: bool):
    kinds = ATTN_LEAVES + (MOE_LEAVES + EXPERT_LEAVES if moe
                           else DENSE_LEAVES)
    return [f"layers.{l}{k}.{k}" if k in OWN_GROUP else f"layers.{l}.{k}"
            for k in kinds]


def layer_names(l: int):
    """Every leaf layer ``l`` may have, of either kind: what
    ``correct.serve_gaps`` asks the weight maker for, which makes those
    the specs hold."""
    return layer_names_of(l, False) + layer_names_of(l, True)[
        len(ATTN_LEAVES):]


def param_specs(c: dict) -> dict:
    """name -> (shape, init).  Linear weights are [in, out]."""
    if (c["hidden_act"] != "swigluoai" or not c["use_gemma_norm"]
            or not c["use_qk_norm"] or c["qk_norm_type"] != "per_head"
            or c["attention_output_gate"] or c["tie_word_embeddings"]
            or c["n_shared_experts"] != 1 or c["scoring_func"] != "sigmoid"
            or not c["use_routing_bias"] or c["num_mtp_modules"]
            or c["num_nextn_predict_layers"]):
        raise ValueError("not the architecture this reference states")
    a = c.get("assumed", {})
    h, v, d = c["hidden_size"], c["vocab_size"], c["head_dim"]
    nh, kh = c["num_attention_heads"], c["num_key_value_heads"]
    f, fd, fs = (c["intermediate_size"], c["dense_intermediate_size"],
                 c["shared_intermediate_size"])
    _, ne = held(c)
    _, _, _, ih = msa(c)
    std = ("normal", a.get("initializer_range", 0.02))
    # Gemma norms scale by 1 + w
    gw = ("zeros", a.get("norm_jitter", 0.02))
    per = {"ln1": ((h,), gw), "ln2": ((h,), gw),
           "wq": ((h, nh * d), std), "wk": ((h, kh * d), std),
           "wv": ((h, kh * d), std), "wo": ((nh * d, h), std),
           "qn": ((d,), gw), "kn": ((d,), gw),
           "wi": ((h, kh * ih * d), std), "inn": ((d,), gw),
           "wg": ((h, fd), std), "wu": ((h, fd), std), "wd": ((fd, h), std),
           "router": ((h, router_width(c)), std),
           "rbias": ((router_width(c),),
                     ("zeros", a.get("router_bias_std", 0.02))),
           "sg": ((h, fs), std), "su": ((h, fs), std), "sd": ((fs, h), std),
           "eg": ((ne, h, f), std), "eu": ((ne, h, f), std),
           "ed": ((ne, f, h), std)}
    specs = {"embed": ((v, h), std), "norm": ((h,), gw),
             "head": ((h, v), std)}
    for l in range(c["num_hidden_layers"]):
        for n in layer_names_of(l, is_moe(c, l)):
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


def gnorm(x, w, eps):
    """Gemma norm: x / rms(x) * (1 + w)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def rope(x, pos, theta, rd):
    """Rotary, rotate-half pairing (dim i with i + rd/2), on the first
    ``rd`` dims of x [T, ..., D] at positions pos [T]; the rest as is."""
    inv = 1.0 / theta ** (jnp.arange(0, rd, 2, dtype=F32) / rd)
    ang = pos.astype(F32)[:, None] * inv[None, :]           # [T, rd/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (rd // 2,))
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    r = x[..., :rd]
    rot = jnp.concatenate([-r[..., rd // 2:], r[..., :rd // 2]], -1)
    return jnp.concatenate([r * cos + rot * sin, x[..., rd:]], -1)


def selection(c, ubar, kmax, tq):
    """bool [Q, KH, NB]: the blocks each query reads.  ``ubar`` [Q, KH,
    D] at positions ``tq`` [Q], ``kmax`` [NB, KH, D]."""
    bs, top_k, local, _ = msa(c)
    nb = kmax.shape[0]
    score = jnp.einsum("qgd,bgd->qgb", ubar, kmax) / jnp.sqrt(
        F32(c["head_dim"]))
    blk = jnp.arange(nb)
    bt = (tq // bs)[:, None, None]
    cand = (blk >= 1) & (blk <= bt - local)
    forced = (blk == 0) | ((blk > bt - local) & (blk <= bt))
    ranked = jnp.where(cand, score, -jnp.inf)
    order = jnp.argsort(-ranked, axis=-1, stable=True)[..., :top_k]
    best = jnp.take_along_axis(ranked, order, -1) > -jnp.inf
    chosen = jnp.zeros(ranked.shape, bool)
    q_, g_ = jnp.meshgrid(jnp.arange(ranked.shape[0]),
                          jnp.arange(ranked.shape[1]), indexing="ij")
    chosen = chosen.at[q_[..., None], g_[..., None], order].set(best)
    return forced | chosen


def attention(c, lp, x, pos, q=None):
    """MSA of one sequence (module doc): x [T, H], pos [T] from 0, T a
    whole number of blocks and of ``QBLOCK``.  The queries and the
    output projection are computed a block of queries at a time, so
    that no float32 tensor of every query's heads is live."""
    nh, kh, d = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, theta, rd = c["rms_norm_eps"], c["rope_theta"], c["rotary_dim"]
    bs, _, _, ih = msa(c)
    T = x.shape[0]
    kh_ = rope(gnorm(mm(x, lp["wk"], q).reshape(T, kh, d), lp["kn"], eps),
               pos, theta, rd)
    vh = mm(x, lp["wv"], q).reshape(T, kh, d)
    kmax = kh_.reshape(T // bs, bs, kh, d).max(1)          # [NB, KH, D]
    r = nh // kh          # query head i reads kv head i // r
    key_blk = jnp.arange(T) // bs

    def block(s):
        tq = s + jnp.arange(QBLOCK)
        xb = jax.lax.dynamic_slice_in_dim(x, s, QBLOCK)
        qb = rope(gnorm(mm(xb, lp["wq"], q).reshape(QBLOCK, nh, d),
                        lp["qn"], eps), tq, theta, rd).reshape(
                            QBLOCK, kh, r, d)
        ub = rope(gnorm(mm(xb, lp["wi"], q).reshape(QBLOCK, kh, ih, d),
                        lp["inn"], eps), tq, theta, rd).sum(2)
        sel = selection(c, ub, kmax, tq)                   # [Q, KH, NB]
        seen = sel[:, :, key_blk] & (
            jnp.arange(T)[None, None, :] <= tq[:, None, None])  # [Q, KH, T]
        lg = jnp.einsum("qgrd,kgd->qgrk", qb, kh_) / jnp.sqrt(F32(d))
        p = jax.nn.softmax(jnp.where(seen[:, :, None], lg, -jnp.inf),
                           axis=-1)
        o = jnp.einsum("qgrk,kgd->qgrd", p, vh)
        return mm(o.reshape(QBLOCK, nh * d), lp["wo"], q)
    return jax.lax.map(block, jnp.arange(0, T, QBLOCK)).reshape(T, -1)


def glu(x, wg, wu, wd, c, q=None):
    """The clamped SwiGLU (``swigluoai``), ``ROWS`` rows at a time
    where they divide."""
    alpha, limit = c["swiglu_alpha"], c["swiglu_limit"]

    def rows(x):
        g = jnp.minimum(mm(x, wg, q), limit)
        u = jnp.clip(mm(x, wu, q), -limit, limit)
        return mm((u + 1.0) * g * jax.nn.sigmoid(alpha * g), wd, q)
    T = x.shape[0]
    if T <= ROWS or T % ROWS:
        return rows(x)
    return jax.lax.map(rows, x.reshape(T // ROWS, ROWS, -1)).reshape(T, -1)


def route(c, lp, x):
    """[T, router width] combine weights, nought off the chosen
    ``num_experts_per_tok``: sigmoid scores in float32, chosen by
    score + routing bias, the weights the chosen SCORES over their sum
    + 1e-20, times ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(x @ lp["router"])
    _, idx = jax.lax.top_k(s + lp["rbias"], c["num_experts_per_tok"])
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
    more than that did, and every token otherwise -- the same sum."""
    first, count = held(c)
    w = route(c, lp, x)[:, first:first + count]         # [T, count]
    rows = min(EXPERT_ROWS, x.shape[0])

    def one(y, e):
        eg, eu, ed, we = e

        def few(y):
            wt, at = jax.lax.top_k(we, rows)
            return y.at[at].add(wt[:, None] * glu(x[at], eg, eu, ed, c, q))

        def every(y):
            return y + we[:, None] * glu(x, eg, eu, ed, c, q)
        return jax.lax.cond(jnp.sum(we > 0) <= rows, few, every, y), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (lp["eg"], lp["eu"], lp["ed"], w.T))
    if shared:
        y = y + glu(x, lp["sg"], lp["su"], lp["sd"], c, q)
    return y


def layer_apply(c, lp, h, pos, q=None):
    """One decoder layer over one sequence: h [T, H] f32, pos [T].  The
    kind of the FFN is read off the leaves it is given."""
    eps = c["rms_norm_eps"]
    h = h + attention(c, lp, gnorm(h, lp["ln1"], eps), pos, q)
    z = gnorm(h, lp["ln2"], eps)
    f = moe(c, lp, z, q) if "eg" in lp \
        else glu(z, lp["wg"], lp["wu"], lp["wd"], c, q)
    return h + f


def embed(c, gp, ids):
    return gp["embed"][ids].astype(F32)


def logits(c, gp, h, q=None):
    return mm(gnorm(h, gp["norm"], c["rms_norm_eps"]), gp["head"], q)


def layer_params(params, names):
    return {n.split(".")[-1]: params[n] for n in names}


def forward_logits(c, params, ids, q=None):
    """Logits [T, V] of one sequence (padded here to whole pages and
    blocks of ``QBLOCK``; a position never sees a later one, so the
    padding changes nothing before it); ``params`` is the flat tree."""
    T = ids.shape[0]
    m = max(QBLOCK, msa(c)[0])
    n = -(-T // m) * m
    ids = jnp.pad(ids, (0, n - T))
    h = embed(c, params, ids)
    pos = jnp.arange(n)
    for l in range(c["num_hidden_layers"]):
        h = layer_apply(c, layer_params(params, layer_names_of(
            l, is_moe(c, l))), h, pos, q)
    return logits(c, params, h, q)[:T]
