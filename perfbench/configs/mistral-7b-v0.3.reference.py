"""Plain reference of the Mistral-7B-v0.3 decoder (mistralai's
published architecture: pre-norm RMSNorm, grouped-query attention with
interleaved-pair rotary embedding as in mistral-inference, SwiGLU MLP,
untied head, no sliding window).  Straightforward jax.numpy in
float32; no kernels, no cache, no batching tricks.  Imports nothing of
the program under test.

``q`` selects the arithmetic: ``None`` is float32 (callers set
``jax.default_matmul_precision("highest")``); ``"fp8"`` rounds both
operands of every linear layer to float8_e4m3 and the gradient that
comes back to float8_e5m2 (per-tensor scales) -- the control, the
nearest precision below the bf16 the configuration states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")
GLOBAL_LEAVES = ("embed", "norm", "head")


def head_dim(c):
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def param_specs(c: dict) -> dict:
    """name -> (shape, init).  Linear weights are [in, out]."""
    h, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    hd = head_dim(c)
    nq, nkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    std = ("normal", c.get("initializer_range", 0.02))
    one = ("ones", c.get("assumed", {}).get("norm_jitter", 0.02))
    specs = {"embed": ((v, h), std), "norm": ((h,), one),
             "head": ((h, v), std)}
    per = {"ln1": ((h,), one), "wq": ((h, nq), std), "wk": ((h, nkv), std),
           "wv": ((h, nkv), std), "wo": ((nq, h), std), "ln2": ((h,), one),
           "wg": ((h, f), std), "wu": ((h, f), std), "wd": ((f, h), std)}
    for l in range(c["num_hidden_layers"]):
        for k, s in per.items():
            specs[f"layers.{l}.{k}"] = s
    return specs


def layer_names(l: int):
    return [f"layers.{l}.{k}" for k in LAYER_LEAVES]


def _q(x, dt):
    """Round to a float8 type with a per-tensor scale."""
    top = 448.0 if dt == jnp.float8_e4m3fn else 57344.0
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dt).astype(F32) * s


@jax.custom_vjp
def _mm_fp8(a, b):
    """The fp8 recipe a later PR would be tempted by: operands in
    e4m3 forward, the incoming gradient in e5m2 backward, float32
    accumulation."""
    return _q(a, jnp.float8_e4m3fn) @ _q(b, jnp.float8_e4m3fn)


def _mm_fp8_fwd(a, b):
    qa, qb = _q(a, jnp.float8_e4m3fn), _q(b, jnp.float8_e4m3fn)
    return qa @ qb, (qa, qb)


def _mm_fp8_bwd(res, g):
    qa, qb = res
    g = _q(g, jnp.float8_e5m2)
    k, n = qb.shape
    return g @ qb.T, qa.reshape(-1, k).T @ g.reshape(-1, n)


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm(a, b, q=None):
    if q == "fp8":
        return _mm_fp8(a, b)
    if q is not None:
        raise ValueError(f"unknown arithmetic {q!r}")
    return a @ b


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [T, H, D]: rotate the pairs (x[2i], x[2i+1]) by pos*theta^(-2i/D)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * freq            # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(c, q, k, v, qblock=512):
    """Causal grouped-query attention of one sequence.  q [T, NH, D],
    k and v [T, NKV, D]; query head h reads kv head h // (NH/NKV).
    Computed in blocks of queries so that the scores fit."""
    T, nh, d = q.shape
    rep = nh // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    out = []
    for s in range(0, T, qblock):
        e = min(s + qblock, T)
        sc = jnp.einsum("qhd,khd->hqk", q[s:e], k[:e]) / jnp.sqrt(F32(d))
        mask = (jnp.arange(s, e)[:, None] >= jnp.arange(e)[None, :])
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v[:e]))
    return jnp.concatenate(out, axis=0)


def layer_apply(c, lp, h, pos, q=None):
    """One decoder layer over one sequence: h [T, H] f32, pos [T]."""
    hd = head_dim(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    x = rms_norm(h, lp["ln1"], eps)
    qh = mm(x, lp["wq"], q).reshape(-1, c["num_attention_heads"], hd)
    kh = mm(x, lp["wk"], q).reshape(-1, c["num_key_value_heads"], hd)
    vh = mm(x, lp["wv"], q).reshape(-1, c["num_key_value_heads"], hd)
    a = attention(c, rope(qh, pos, theta), rope(kh, pos, theta), vh)
    h = h + mm(a.reshape(h.shape[0], -1), lp["wo"], q)
    x = rms_norm(h, lp["ln2"], eps)
    return h + mm(jax.nn.silu(mm(x, lp["wg"], q)) * mm(x, lp["wu"], q),
                  lp["wd"], q)


def embed(c, gp, ids):
    return gp["embed"][ids].astype(F32)


def logits(c, gp, h, q=None):
    return mm(rms_norm(h, gp["norm"], c["rms_norm_eps"]), gp["head"], q)


def forward_logits(c, params, ids, q=None, remat=False):
    """Logits [T, V] of one sequence; ``params`` is the flat tree."""
    h = embed(c, params, ids)
    pos = jnp.arange(ids.shape[0])
    for l in range(c["num_hidden_layers"]):
        lp = {k: params[f"layers.{l}.{k}"] for k in LAYER_LEAVES}
        f = (lambda lp_, h_: layer_apply(c, lp_, h_, pos, q))
        h = (jax.checkpoint(f) if remat else f)(lp, h)
    return logits(c, params, h, q)


def loss_share(c, params, batch, total_rows, q=None):
    """This block of rows' share of the full batch's mean next-token
    cross-entropy (label = the next id; every position but the last
    predicts).  The shares of all blocks add up to the loss."""
    ids = batch["ids"]
    S = ids.shape[1]

    def one(row):
        lg = forward_logits(c, params, row, q, remat=True)[:-1]
        lp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(lp, row[1:, None], axis=-1).sum()
    return jax.vmap(one)(ids).sum() / (total_rows * (S - 1))
