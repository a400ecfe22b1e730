"""Attention functionals.

The 2021-era reference has no fused attention op (only
operators/fused/multihead_matmul_op.* for inference); long-context
attention is greenfield here per SURVEY.md §5.7. The public entry is
``scaled_dot_product_attention``; on TPU it dispatches to a Pallas
flash-attention kernel when shapes allow (ops/flash_attention.py),
falling back to the XLA softmax composition.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework.core import Tensor, _apply
from ...framework.random import split_key

__all__ = ["scaled_dot_product_attention"]


def _sdpa_ref(q, k, v, mask, dropout_p, causal, scale, dropout_key=None):
    # q,k,v: (B, S, H, D) paddle layout
    qh = jnp.swapaxes(q, 1, 2)  # B,H,S,D
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
    if causal:
        S_q, S_k = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((S_q, S_k), bool), S_k - S_q)
        logits = jnp.where(causal_mask, logits, jnp.asarray(-1e30, logits.dtype))
    if mask is not None:
        logits = logits + mask
    w = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, w.shape)
        w = jnp.where(keep, w / (1.0 - dropout_p), jnp.zeros((), w.dtype))
    out = jnp.einsum("bhqk,bhkd->bhqd", w, vh)
    return jnp.swapaxes(out, 1, 2)  # B,S,H,D


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None, name=None):
    """Inputs in paddle layout (batch, seq, heads, head_dim).

    On TPU, uses the Pallas flash-attention kernel (ops/flash_attention.py)
    for long sequences; XLA composition otherwise (XLA already fuses the
    softmax chain well for short seqs).
    """
    drop = dropout_p if training else 0.0
    use_flash = False
    from ...ops.flash_attention import flash_eligible
    qv = query._value
    if qv.ndim == 4:
        mv = attn_mask._value if attn_mask is not None else None
        use_flash = flash_eligible(
            qv.shape[1], qv.shape[3],
            has_mask=mv is not None, dropout=drop,
            mask_shape=None if mv is None else tuple(mv.shape),
            mask_dtype=None if mv is None else mv.dtype,
            kv_seq_len=key._value.shape[1])

    if use_flash:
        from ...ops.flash_attention import flash_attention as _fa

        if attn_mask is None:
            if drop > 0.0:
                # flash_eligible only admits dropout>0 mask-free, where
                # the kernel applies it via the on-chip PRNG — seed
                # minted per call from the framework RNG chain so it
                # advances like the XLA path's key.  The seed rides as
                # an OPERAND (keyed by aval in the eager vjp cache, so
                # repeat steps stay cached) rather than a closure cell
                # (unhashable -> full Pallas re-trace every call).
                from ...ops.flash_attention import dropout_seed
                seed = dropout_seed(split_key())

                def f(q, k, v, s):
                    return _fa(q, k, v, causal=is_causal, scale=scale,
                               dropout_p=drop, seed=s)
                return _apply(f, query, key, value, seed,
                              op_name="flash_attention")

            def f(q, k, v):
                return _fa(q, k, v, causal=is_causal, scale=scale)
            return _apply(f, query, key, value,
                          op_name="flash_attention")

        def f(q, k, v, m):
            return _fa(q, k, v, bias=m.astype(q.dtype), causal=is_causal,
                       scale=scale)
        return _apply(f, query, key, value, attn_mask,
                      op_name="flash_attention")

    dk = split_key() if drop > 0.0 else None
    if attn_mask is not None:
        def f(q, k, v, m):
            return _sdpa_ref(q, k, v, m, drop, is_causal, scale, dk)
        return _apply(f, query, key, value, attn_mask, op_name="sdpa")

    def f(q, k, v):
        return _sdpa_ref(q, k, v, None, drop, is_causal, scale, dk)
    return _apply(f, query, key, value, op_name="sdpa")
