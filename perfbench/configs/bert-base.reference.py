"""Plain reference of BERT-base pretraining (google-research/bert:
post-norm encoder, erf-GELU, LayerNorm eps 1e-12, MLM head = dense +
GELU + LayerNorm + decoder tied to the word embeddings + bias, NSP head
on the tanh-pooled [CLS]; loss = masked-LM mean cross-entropy + NSP
mean cross-entropy).  Straightforward jax.numpy in float32; imports
nothing of the program under test.  Dropout is off (see the
configuration's file).  ``q``: None = float32, "fp8" = the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def param_specs(c: dict) -> dict:
    h, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    a = c.get("assumed", {})
    std = ("normal", c.get("initializer_range", 0.02))
    one = ("ones", a.get("norm_jitter", 0.02))
    zero = ("zeros", a.get("bias_jitter", 0.02))
    s = {"emb.word": ((v, h), std),
         "emb.pos": ((c["max_position_embeddings"], h), std),
         "emb.type": ((c["type_vocab_size"], h), std),
         "emb.ln.w": ((h,), one), "emb.ln.b": ((h,), zero),
         "pooler.w": ((h, h), std), "pooler.b": ((h,), zero),
         "mlm.w": ((h, h), std), "mlm.b": ((h,), zero),
         "mlm.ln.w": ((h,), one), "mlm.ln.b": ((h,), zero),
         "mlm.bias": ((v,), zero),
         "nsp.w": ((h, 2), std), "nsp.b": ((2,), zero)}
    per = {"attn.wqkv": ((h, 3 * h), std), "attn.bqkv": ((3 * h,), zero),
           "attn.wo": ((h, h), std), "attn.bo": ((h,), zero),
           "ffn.w1": ((h, f), std), "ffn.b1": ((f,), zero),
           "ffn.w2": ((f, h), std), "ffn.b2": ((h,), zero),
           "ln1.w": ((h,), one), "ln1.b": ((h,), zero),
           "ln2.w": ((h,), one), "ln2.b": ((h,), zero)}
    for l in range(c["num_hidden_layers"]):
        for k, sp in per.items():
            s[f"layers.{l}.{k}"] = sp
    return s


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


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(F32(2.0))))


def encoder_layer(c, p, l, x, q=None):
    """x [S, H] of one sequence; full (unmasked) attention."""
    nh = c["num_attention_heads"]
    S, h = x.shape
    d = h // nh
    g = lambda k: p[f"layers.{l}.{k}"]           # noqa: E731
    qkv = mm(x, g("attn.wqkv"), q) + g("attn.bqkv")
    qh, kh, vh = (t.reshape(S, nh, d) for t in jnp.split(qkv, 3, -1))
    sc = jnp.einsum("qhd,khd->hqk", qh, kh) / jnp.sqrt(F32(d))
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), vh)
    x = layer_norm(x + mm(a.reshape(S, h), g("attn.wo"), q) + g("attn.bo"),
                   g("ln1.w"), g("ln1.b"), c["layer_norm_eps"])
    ff = mm(gelu(mm(x, g("ffn.w1"), q) + g("ffn.b1")), g("ffn.w2"), q) \
        + g("ffn.b2")
    return layer_norm(x + ff, g("ln2.w"), g("ln2.b"), c["layer_norm_eps"])


def _row_losses(c, p, row, q=None):
    """(sum of masked-LM nll, NSP nll) of one sequence."""
    eps = c["layer_norm_eps"]
    S = row["input_ids"].shape[0]
    x = (p["emb.word"][row["input_ids"]] + p["emb.pos"][jnp.arange(S)]
         + p["emb.type"][row["token_type_ids"]])
    x = layer_norm(x, p["emb.ln.w"], p["emb.ln.b"], eps)
    for l in range(c["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda p_, x_, l=l: encoder_layer(c, p_, l, x_, q))(p, x)
    hm = x[row["masked_positions"]]
    hm = layer_norm(gelu(mm(hm, p["mlm.w"], q) + p["mlm.b"]),
                    p["mlm.ln.w"], p["mlm.ln.b"], eps)
    lg = mm(hm, p["emb.word"].T, q) + p["mlm.bias"]
    lp = jax.nn.log_softmax(lg, -1)
    mlm = -jnp.take_along_axis(lp, row["mlm_labels"][:, None], -1).sum()
    pooled = jnp.tanh(mm(x[0], p["pooler.w"], q) + p["pooler.b"])
    ns = jax.nn.log_softmax(mm(pooled, p["nsp.w"], q) + p["nsp.b"])
    return mlm, -ns[row["nsp_labels"]]


def loss_share(c, params, batch, total_rows, q=None):
    """This block of rows' share of the full batch's loss (MLM mean over
    all masked positions + NSP mean over rows); shares add up."""
    mlm, nsp = jax.vmap(lambda r: _row_losses(c, params, r, q))(batch)
    M = batch["masked_positions"].shape[1]
    return mlm.sum() / (total_rows * M) + nsp.sum() / total_rows
