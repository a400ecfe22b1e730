"""Fused int8-KV dequant-attention for paged decode/verify (kernel 3).

PR 11's int8 KV pools quantize on write and dequantize on gather —
but the XLA gather path is two passes over the pools: gather+dequant
materializes the full f32 ``[B, T, KH, D]`` cache view, then attention
reads it again.  This kernel applies the per-(block, slot) scales
INSIDE the attention gather: the int8 pools are read ONCE, block by
block through the sequence's block table (scalar-prefetched so the
table drives the DMA index map), dequantized in VMEM, and folded into
a blockwise online-softmax accumulation — the ROADMAP-named follow-up
to PR 11.

Grid ``(B, G, M)`` — batch x kv-head x table block, M innermost so the
running (max, denom, acc) scratch carries across a sequence's blocks.
The validity mask is the same ``slot <= position`` inequality the XLA
path uses (simultaneously the causal mask within a verify block and
the prefix mask against the cache); trash-block (physical block 0)
slots always fail it, and a fully-masked block contributes exactly
zero via the masked ``p`` term (never via ``exp(-inf)`` arithmetic).

Parity vs :func:`paged_attention_ref` (the XLA gather path, lifted
verbatim from ``LlamaAttention.forward_paged`` so the non-pallas
serving contracts — replay, prefix sharing, eviction — are pinned by
the SAME function): online softmax re-associates the f32
exp/sum/weighted-sum chain, documented tolerance atol 2e-5 /
rtol 1e-4.  The quantization itself is exact (the kernel multiplies
the same int8 codes by the same f32 scales).

TPU status (PR 21): the kernel runs under the interpreter only.  Its
block specs tile the serving pools as they are laid out,
``[nb, bs, KH, D]`` (``LlamaForCausalLM.init_paged_cache``): a
``(1, bs, 1, D)`` pool block, ``(1, bs)`` scale blocks and a
``(1, S*R)`` position block.  Mosaic requires a block's last two dims
to be multiples of (8, 128) or the whole array dims, and a one-head
slice of the ``KH`` dim is neither — the lowering error is quoted in
PERF.md ("Bring-up on v5e").  A real repair is a head-major pool layout
(ROADMAP S6 / D3), not a spec tweak, so the registration defaults this
ONE kernel to ``xla_ref`` on TPU: a ``kv_cache_dtype="int8"`` server
traces the gather path there instead of raising.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

__all__ = ["paged_attention_ref", "int8_paged_attention"]

_NEG = -1e30


def paged_attention_ref(qh, kpool, vpool, kscale, vscale, tbl, pos,
                        kv_heads):
    """The XLA gather/dequant/attend path — math lifted VERBATIM from
    ``LlamaAttention.forward_paged`` (decode/verify branch).  This is
    simultaneously the fallback the CPU serving tests run (keeping PR
    11's bit contracts byte-identical) and the kernel's parity oracle.

    ``qh``: [B, S, H, D] roped queries; pools [nb, bs, KH, D] (int8
    when ``kscale/vscale`` are given, else the compute dtype);
    ``tbl`` [B, M] int32; ``pos`` [B, S] int32.  Returns [B, S, G, R, D]
    in ``qh``'s dtype.
    """
    B, S, H, D = qh.shape
    bs = kpool.shape[1]
    T = tbl.shape[1] * bs
    kg = kpool[tbl].reshape(B, T, kv_heads, D)
    vg = vpool[tbl].reshape(B, T, kv_heads, D)
    kgf = kg.astype(jnp.float32)
    vgf = vg.astype(jnp.float32)
    if kscale is not None:
        kgf = kgf * kscale[tbl].reshape(B, T)[:, :, None, None]
        vgf = vgf * vscale[tbl].reshape(B, T)[:, :, None, None]
    G = kv_heads
    R = H // G
    qg = qh.reshape(B, S, G, R, D)
    scale = 1.0 / (D ** 0.5)
    logits = jnp.einsum("bsgrd,btgd->bgrst", qg.astype(jnp.float32),
                        kgf) * scale                   # [B,G,R,S,T]
    valid = (jnp.arange(T)[None, None, None, None, :]
             <= pos[:, None, None, :, None])
    logits = jnp.where(valid, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bgrst,btgd->bsgrd", w, vgf).astype(qh.dtype)


def _int8_kv_attn_kernel(bs, sr, d, scale, tbl_ref, qpos_ref, q_ref, k_ref, v_ref,
            ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref):
    # tbl_ref (the scalar-prefetched block table) already did its job
    # in the index maps; the body never reads it
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    k = (k_ref[0, :, 0, :].astype(jnp.float32)
         * ks_ref[0, :][:, None])                      # (bs, D)
    v = (v_ref[0, :, 0, :].astype(jnp.float32)
         * vs_ref[0, :][:, None])
    q = q_ref[0, 0].astype(jnp.float32)                # (SR, D)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    t_glob = mi * bs + jax.lax.broadcasted_iota(jnp.int32, (sr, bs), 1)
    valid = t_glob <= qpos_ref[0, :][:, None]
    s = jnp.where(valid, s, _NEG)

    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    # masked slots contribute EXACT zeros (not exp(-big)): a block that
    # is entirely beyond this query's position adds nothing to l/acc
    p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, 0] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new[:, None]
    l_ref[...] = l_new[:, None]

    @pl.when(mi == pl.num_programs(2) - 1)
    def _():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(
            l_ref[:, 0], 1e-30)[:, None]


def int8_paged_attention(qh, kpool, vpool, kscale, vscale, tbl, pos,
                         kv_heads, *, interpret=False):
    """Fused dequant-attention over int8 paged pools (see module doc).

    Layout transform: queries regroup to ``[B, G, S*R, D]`` so one
    grid step covers every query row attending one kv head's pool
    block; the output transposes back to the reference's
    ``[B, S, G, R, D]``.
    """
    B, S, H, D = qh.shape
    G = kv_heads
    R = H // G
    bs = kpool.shape[1]
    M = tbl.shape[1]
    sr = S * R
    qg = jnp.transpose(qh.reshape(B, S, G, R, D),
                       (0, 2, 1, 3, 4)).reshape(B, G, sr, D)
    qg = qg.astype(jnp.float32)
    # per-query-row absolute position: row j of the (S*R) block is
    # query s = j // R (R head-replicas share a position)
    qpos = jnp.broadcast_to(pos.astype(jnp.int32)[:, :, None],
                            (B, S, R)).reshape(B, sr)
    scale = 1.0 / (D ** 0.5)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, G, M),
        in_specs=[
            pl.BlockSpec((1, sr), lambda b, g, m, tbl: (b, 0)),
            pl.BlockSpec((1, 1, sr, D), lambda b, g, m, tbl: (b, g, 0, 0)),
            pl.BlockSpec((1, bs, 1, D),
                         lambda b, g, m, tbl: (tbl[b, m], 0, g, 0)),
            pl.BlockSpec((1, bs, 1, D),
                         lambda b, g, m, tbl: (tbl[b, m], 0, g, 0)),
            pl.BlockSpec((1, bs), lambda b, g, m, tbl: (tbl[b, m], 0)),
            pl.BlockSpec((1, bs), lambda b, g, m, tbl: (tbl[b, m], 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, sr, D),
                               lambda b, g, m, tbl: (b, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((sr, D), jnp.float32),
            pltpu.VMEM((sr, 1), jnp.float32),
            pltpu.VMEM((sr, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_int8_kv_attn_kernel, bs, sr, D, scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, sr, D), jnp.float32),
        interpret=interpret,
    )(tbl.astype(jnp.int32), qpos, qg, kpool, vpool, kscale, vscale)
    o = jnp.transpose(out.reshape(B, G, S, R, D), (0, 2, 1, 3, 4))
    return o.astype(qh.dtype)


def _eligible(qh, kpool, vpool, kscale, vscale, tbl, pos, kv_heads):
    # compiled-mode tile gate: MXU-friendly head dims, sublane-aligned
    # block size, int8 pools with their scale tensors present
    D = qh.shape[-1]
    return (kscale is not None and kpool.dtype == jnp.int8
            and D in (64, 128, 256) and kpool.shape[1] % 8 == 0)


registry.register(
    "int8_kv_attention", int8_paged_attention, paged_attention_ref,
    tolerance="atol 2e-5 / rtol 1e-4 vs xla_ref (f32 online softmax "
              "re-association; the int8 dequant itself is exact)",
    eligible=_eligible,
    tpu_default="xla_ref",
    doc="paged decode/verify attention reading int8 KV pools once: "
        "per-(block,slot) scales applied inside the table-driven "
        "gather, blockwise online softmax",
)
