"""Paged decode attention over the serving KV pools: the XLA gather
reference and two Pallas kernels that read the pools through the block
table.

``paged_attention_ref`` is the XLA gather/attend path, lifted verbatim
from ``LlamaAttention.forward_paged`` so the non-pallas serving
contracts (replay, prefix sharing, eviction) are pinned by the SAME
function.  It is the fallback and the parity oracle of both kernels.
What it costs on the chip (PERF.md, PR 26): ``kpool[tbl]`` is
materialised, and where ``num_slots * max_blocks == num_blocks`` that
gather is as large as the pool; the einsums then run over every
position of the table, masked afterwards.

``paged_attention`` (kernel ``paged_attention``, PR 26) is the decode
path (``S == 1``) for bf16 pools.  Grid ``(B,)``; the lengths
(``pos + 1``) and the flattened table are scalar-prefetched; the pools
stay in HBM (``memory_space=ANY``) and each row copies only its pages
``0 .. pos // bs`` into VMEM, ``pages_per_block`` at a time, double
buffered, the first block of the next row in flight while the last of
this one is computed.  Blocks are folded in LOGICAL order into an
online softmax (f32 logits, f32 statistics, f32 accumulation), so the
output depends neither on physical block ids nor on the other rows of
the batch; positions past the length are masked to an exact zero
weight, pages past it are neither copied nor computed.

Layouts Mosaic took (compiled for v5e, ``tests/test_tpu_lowering.py``;
run on one, PERF.md PR 26): the pool stays ``[nb, bs, KH, D]``.  A
``(1, bs, 1, D)`` block of it does not lower (PR 21: a one-head slice
of ``KH`` is neither a multiple of 8 nor the whole dim), but the whole
page does: ``[nb, bs, KH, D]`` viewed as ``[nb, bs*KH, D]`` is a
bitcast in XLA's tiled HBM layout (``T(8,128)(2,1)`` either way), and a
``(bs*KH, D)`` page, (128, 128) bf16 at the serving shapes, is a legal
DMA source and a tile-aligned VMEM block.  Its rows interleave the kv
heads ``(t, g)``, so the kernel multiplies ALL query heads by the whole
block in one MXU call and masks the 7/8 of the logits whose kv head is
not the query's; a head-major pool ``[nb, KH, bs, D]`` would avoid that
but turns the write of a token into ``KH`` rows of 256 B, and the
measured kernel is DMA-bound as it is.

A head narrower than a lane tile (``D`` = 64, PR 33) takes the ``wide``
page: the pool is ``[nb, bs, KH*D]`` (or ``[nb, bs, KH, D]`` viewed so),
a page ``(bs, KH*D)`` = (16, 512) bf16, whole lane tiles with every kv
head's 64 lanes side by side, so HBM pads nothing and a head's row is
read once.  Rows are tokens alone; each query head is laid into its own
kv head's lanes of an ``(H, KH*D)`` row, zero elsewhere, and one
product with the block gives every head its own logits: the same MXU
work as the narrow page's masked product, an eighth of its logits.
``p . v`` sums into ``(H, KH*D)`` and each head's own lanes are taken
at the end.  A model that keeps such a pool allocates it 3-D: XLA lays
out a ``[.., KH, 64]`` array as it likes, 64 lanes padded or the
dimensions transposed, and a Mosaic operand would be re-laid each call.

``int8_paged_attention`` (kernel ``int8_kv_attention``, PR 13) fuses the
dequant of int8 pools into the gather for decode/verify: grid
``(B, G, M)``, M innermost so the running (max, denom, acc) scratch
carries across a sequence's blocks; per-(block, slot) scales applied
in VMEM.  Parity vs the reference: atol 2e-5 / rtol 1e-4 (online
softmax re-associates the f32 exp/sum/weighted-sum chain; the dequant
is exact).  It runs under the interpreter only: its ``(1, bs, 1, D)``
pool block is the one that does not lower, so the registration defaults
it to ``xla_ref`` on TPU.  The repair is the whole-page read above with
the scales applied to the ``(bs*KH, D)`` rows (ROADMAP M4).

In every path the validity mask is the same ``slot <= position``
inequality; trash-block (physical block 0) slots always fail it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

__all__ = ["paged_attention_ref", "paged_attention",
           "int8_paged_attention"]

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


# ---------------------------------------------------------------------
# bf16 decode kernel (PR 26)
# ---------------------------------------------------------------------

# pages per grid-step block: 16 was the fastest on a v5e at both serving
# shapes (PERF.md, PR 26: 4 / 8 / 16 / 32 pages read 4.13 / 2.78 /
# 2.56 / 2.93 ms for 8 layers of 128 rows x ~320 positions)
_PAGES_PER_BLOCK = 16


def _paged_attn_kernel(P, bs, kh, r, m_tbl, n_rows, scale, wide,
                       len_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref,
                       kbuf, vbuf, sem, slot_ref):
    b = pl.program_id(0)
    # rows of one page: (t, g) order, or t alone with the kv heads side
    # by side in the lanes (``wide``, module doc)
    rows = bs if wide else bs * kh
    n = P * rows
    h = kh * r

    def n_pages(bb):
        return (len_ref[bb] + bs - 1) // bs

    def each_page(bb, i, slot, op):
        """``start`` or ``wait`` the K and V copies of block ``i`` of
        row ``bb``: one page each, and none past the row's length.  A
        loop on the scalar core, not ``P`` unrolled copies: the kernel
        is traced and lowered once per layer at every start-up, and
        unrolled it cost a server seconds of set-up (PERF.md, PR 26)."""
        def one(p, carry):
            page = tbl_ref[bb * m_tbl + i * P + p]
            dst = pl.ds(pl.multiple_of(p * rows, rows), rows)
            for hbm, buf, which in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                getattr(pltpu.make_async_copy(
                    hbm.at[page], buf.at[slot, dst],
                    sem.at[which, slot]), op)()
            return carry
        jax.lax.fori_loop(0, jnp.clip(n_pages(bb) - i * P, 0, P), one, 0)

    @pl.when(b == 0)
    def _():
        # rows no copy ever reached must hold finite values: their
        # weight is an exact zero, and 0 * NaN is not
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        each_page(0, 0, 0, "start")

    length = len_ref[b]
    nblk = (n_pages(b) + P - 1) // P
    q = q_ref[0]                                       # (H, D)
    d = q.shape[-1]
    iota = jax.lax.broadcasted_iota
    col = iota(jnp.int32, (h, n), 1)
    if wide:
        # each query head into its own kv head's lanes of an (H, KH*D)
        # row, zero elsewhere (module doc); q . (0/1 matrix) moves
        # values and rounds none
        w = kh * d
        own_lanes = (iota(jnp.int32, (h, w), 1) // d
                     == iota(jnp.int32, (h, w), 0) // r)
        spread = (iota(jnp.int32, (d, w), 0)
                  == iota(jnp.int32, (d, w), 1) % d).astype(q.dtype)
        q = jnp.where(own_lanes, jnp.dot(
            q, spread, preferred_element_type=jnp.float32),
            0.0).astype(q.dtype)
        t_loc = col
    else:
        w = d
        row = iota(jnp.int32, (h, n), 0)
        own_head = (col % kh) == (row // r)
        t_loc = col // kh

    def body(i, carry):
        m, l, acc = carry
        slot = slot_ref[0]
        nxt = 1 - slot
        last = i == nblk - 1

        # the next block is in flight while this one is computed: the
        # row's own, or the first of the next row
        @pl.when(jnp.logical_not(last))
        def _():
            each_page(b, i + 1, nxt, "start")

        @pl.when(jnp.logical_and(last, b + 1 < n_rows))
        def _():
            each_page(b + 1, 0, nxt, "start")
        slot_ref[0] = nxt
        each_page(b, i, slot, "wait")
        k = kbuf[slot]                                 # (P*bs*KH, D)
        v = vbuf[slot]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        valid = t_loc < length - i * (P * bs)
        if not wide:
            valid = jnp.logical_and(own_head, valid)
        s = jnp.where(valid, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        # masked slots contribute EXACT zeros, whatever the buffer holds
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=1, keepdims=True)
        if v.dtype == jnp.bfloat16:
            # f32 p . bf16 v in ONE pass over V in the MXU: p = hi + lo,
            # both bf16, stacked as rows; what is dropped is under
            # 2**-17 of p.  (Mosaic's own f32 dot rounds p to bf16 on
            # the chip: 4% faster, four times the error, PERF.md PR 26)
            hi = p.astype(jnp.bfloat16)
            lo = (p - hi.astype(jnp.float32)).astype(jnp.bfloat16)
            pv = jnp.dot(jnp.concatenate([hi, lo], axis=0), v,
                         preferred_element_type=jnp.float32)
            pv = pv[:h] + pv[h:]
        else:
            pv = jnp.dot(p, v.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    m, l, acc = jax.lax.fori_loop(
        0, nblk, body,
        (jnp.full((h, 1), _NEG, jnp.float32),
         jnp.zeros((h, 1), jnp.float32),
         jnp.zeros((h, w), jnp.float32)))
    out = acc / l
    if wide:
        # each head's own D lanes of the (H, KH*D) sum, moved to the
        # front by a 0/1 matrix; for a bf16 result in hi + lo halves,
        # as p . v above
        out = jnp.where(own_lanes, out, 0.0)
        gather = (iota(jnp.int32, (w, d), 0) % d
                  == iota(jnp.int32, (w, d), 1))
        if o_ref.dtype == jnp.bfloat16:
            hi = out.astype(jnp.bfloat16)
            lo = (out - hi.astype(jnp.float32)).astype(jnp.bfloat16)
            out = jnp.dot(jnp.concatenate([hi, lo], axis=0),
                          gather.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
            out = out[:h] + out[h:]
        else:
            out = jnp.dot(out, gather.astype(jnp.float32),
                          preferred_element_type=jnp.float32)
    o_ref[0] = out.astype(o_ref.dtype)


def _wide_page(kv_heads: int, D: int) -> bool:
    return D % 128 != 0 and (kv_heads * D) % 128 == 0


@functools.partial(jax.jit, static_argnames=("kv_heads", "interpret"))
def paged_attention(qh, kpool, vpool, kscale, vscale, tbl, pos,
                    kv_heads, *, interpret=False):
    """Decode attention (``S == 1``) reading K and V through the block
    table, up to each row's length (see module doc).  Same arguments
    and result as :func:`paged_attention_ref`; ``kscale``/``vscale``
    must be None (int8 pools take ``int8_paged_attention``).  Jitted,
    so that the layers of one decode program share ONE trace and ONE
    lowering of the kernel."""
    B, S, H, D = qh.shape
    if S != 1 or kscale is not None or vscale is not None:
        raise ValueError("paged_attention is the S == 1 kernel for "
                         "unquantized pools")
    nb, bs = kpool.shape[:2]
    KH = kv_heads
    R = H // KH
    M = tbl.shape[1]
    P = min(_PAGES_PER_BLOCK, M)
    # a head narrower than a lane tile: the page is read (bs, KH*D),
    # whole lane tiles (module doc); else (bs*KH, D)
    wide = _wide_page(KH, D)
    rows, width = (bs, KH * D) if wide else (bs * KH, D)
    # the step's own K/V are scattered before the attention reads; a
    # length of at least 1 keeps every row's first block in the chain
    # of copies (position 0 of an inactive slot is the trash block's)
    lengths = jnp.clip(pos[:, 0].astype(jnp.int32) + 1, 1, M * bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P * rows, width), kpool.dtype),
            pltpu.VMEM((2, P * rows, width), vpool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, P, bs, KH, R, M, B,
                          1.0 / math.sqrt(D), wide),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), qh.dtype),
        # rows run in order: each starts the next one's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(lengths, tbl.astype(jnp.int32).reshape(-1), qh.reshape(B, H, D),
      kpool.reshape(nb, rows, width), vpool.reshape(nb, rows, width))
    return out.reshape(B, 1, KH, R, D)


def _paged_eligible(qh, kpool, vpool, kscale, vscale, tbl, pos,
                    kv_heads):
    # compiled-mode gate, from what the call can see in its input: one
    # query per row, bf16 pools in the queries' dtype, a page that is
    # whole (16, 128) bf16 tiles, and no multi-device mesh (Mosaic
    # calls cannot be partitioned; the reference can)
    from ...distributed import mesh as mesh_mod
    mesh = mesh_mod.get_mesh(create=False)
    D, bs = qh.shape[-1], kpool.shape[1]
    # (bs*KH, D) pages of whole tiles, or, for a head narrower than a
    # lane tile, (bs, KH*D) pages of whole tiles
    tiled = ((D % 128 == 0 and (bs * kv_heads) % 16 == 0)
             or (_wide_page(kv_heads, D) and bs % 16 == 0))
    return (qh.shape[1] == 1 and kscale is None
            and kpool.dtype == jnp.bfloat16 and qh.dtype == kpool.dtype
            and tiled and (mesh is None or mesh.size == 1))


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


registry.register(
    "paged_attention", paged_attention, paged_attention_ref,
    tolerance="atol 2e-5 / rtol 1e-4 vs xla_ref on f32 inputs (f32 "
              "online softmax re-association; p.v in bf16 hi+lo halves "
              "drops under 2**-17 of p); bit-identical across physical "
              "block ids and batch neighbours",
    eligible=_paged_eligible,
    doc="decode attention over bf16 paged KV pools read through the "
        "block table up to each row's length: whole pages DMA'd from "
        "HBM, double buffered, blockwise online softmax; nothing is "
        "gathered into HBM",
)
