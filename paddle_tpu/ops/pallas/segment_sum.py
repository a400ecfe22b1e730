"""Segment-sum embedding-grad kernel (ISSUE 13 kernel 4).

``native/ps_core.cc`` fuses the sparse push on HOST: dedup +
segment-sum + optimizer apply in one C pass.  The DEVICE path
(``fleet/heter.py`` ``DeviceCachedTable._push_rows``) still ran the
merge as ``jax.ops.segment_sum`` — a scatter-add XLA lowers to
gather/scatter soup over the whole segment buffer.  This kernel
mirrors the native fused push on device: the inverse indices (from the
host-side ``np.unique`` dedup that produced the slot plan) ride in as
scalar prefetch, the gradient rows stream through VMEM once, and the
per-segment sums accumulate in a VMEM-resident output in one
sequential pass — the same id-ordered accumulation ``ps_segsum_inv``
performs, feeding the device cache's bucketed apply.

Parity vs ``jax.ops.segment_sum``: both accumulate rows in ascending
row order on this backend, and f32 addition of the same values in the
same order is bit-stable — measured exact; documented bound atol 1e-6
(scatter-add ordering inside XLA is not contractually fixed).
Integer-valued gradients (< 2^24) are exact under ANY ordering, which
is what the bit-level test pins.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

__all__ = ["segment_sum_ref", "segment_sum_pallas",
           "segment_sum_sorted_ref", "segment_sum_sorted_pallas",
           "merge_segments", "SORTED_NSEG_MIN"]

# one pass holds grads [n, dim] + out [nseg, dim] in VMEM
_MAX_ELEMS = 1 << 21

# segment count at which merge_segments switches to the sorted-segment
# kernel: below this the whole [nseg, dim] output fits VMEM comfortably
# and the sequential one-pass kernel wins; above it (vocab-scale
# tables) the dense output is the working set that must stream instead
SORTED_NSEG_MIN = 4096


def segment_sum_ref(grads, inverse, num_segments):
    """XLA reference: exactly the call the device cache ran before."""
    return jax.ops.segment_sum(grads, inverse,
                               num_segments=num_segments)


def _segment_sum_kernel(n, inv_ref, g_ref, o_ref):
    o_ref[...] = jnp.zeros_like(o_ref)

    def body(i, _):
        seg = inv_ref[i]
        o_ref[pl.ds(seg, 1), :] += g_ref[pl.ds(i, 1), :]
        return 0

    jax.lax.fori_loop(0, n, body, 0)


def segment_sum_pallas(grads, inverse, num_segments, *,
                       interpret=False):
    """Fused dedup-merge on device (see module docstring).  Rows are
    padded to a sublane multiple with zero gradients aimed at segment
    0 — an exact no-op contribution."""
    grads = jnp.asarray(grads, jnp.float32)
    n, dim = grads.shape
    npad = (-(-max(n, 1) // 8)) * 8 - n
    grads = jnp.pad(grads, ((0, npad), (0, 0)))
    inv = jnp.pad(jnp.asarray(inverse, jnp.int32), (0, npad))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec((n + npad, dim), lambda i, inv: (0, 0))],
        out_specs=pl.BlockSpec((num_segments, dim),
                               lambda i, inv: (0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_segment_sum_kernel, n + npad),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_segments, dim),
                                       jnp.float32),
        interpret=interpret,
    )(inv, grads)


def _eligible(grads, inverse, num_segments):
    n, dim = grads.shape
    return (n + num_segments) * dim <= _MAX_ELEMS


# -- sorted-segment variant for vocab-scale nseg (ISSUE 14 satellite,
# PR 13's named follow-up) ----------------------------------------------
#
# The sequential kernel above holds the WHOLE [nseg, dim] output in
# VMEM — right for recsys dims (nseg = unique ids in a batch), wrong
# for vocab-scale tables where nseg dwarfs n.  This variant takes the
# segment ids PRE-SORTED (the caller's np.unique/argsort already
# produced the order): sorted rows touch contiguous output rows, so
# the OUTPUT streams through VMEM in [block, dim] windows while the
# (small) gradient batch stays resident.  Per-window row ranges ride
# in as scalar prefetch (host searchsorted over the sorted segment
# ids) — the same scalar-prefetch-drives-the-DMA pattern as the
# int8-KV block tables.


def segment_sum_sorted_ref(grads, seg_sorted, num_segments):
    """XLA reference: plain segment_sum (sortedness declared so XLA
    may skip its scatter combine)."""
    return jax.ops.segment_sum(grads, seg_sorted,
                               num_segments=num_segments,
                               indices_are_sorted=True)


_SORT_BLOCK = 512   # output rows per grid step


def _segment_sum_sorted_kernel(bounds_ref, seg_ref, g_ref, o_ref):
    i = pl.program_id(0)
    o_ref[...] = jnp.zeros_like(o_ref)
    base = i * _SORT_BLOCK

    def body(r, _):
        o_ref[pl.ds(seg_ref[r] - base, 1), :] += g_ref[pl.ds(r, 1), :]
        return 0

    jax.lax.fori_loop(bounds_ref[i], bounds_ref[i + 1], body, 0)


def segment_sum_sorted_pallas(grads, seg_sorted, num_segments, *,
                              interpret=False):
    """Sorted-segment sum (see block comment).  ``seg_sorted`` must be
    ascending; rows for output block ``i`` are exactly
    ``[bounds[i], bounds[i+1])`` — each gradient row is read by ONE
    grid step, each output row written by ONE grid step, so the
    accumulation order per segment equals the row order, bit-matching
    the sequential kernel and (measured) the XLA reference."""
    grads = jnp.asarray(grads, jnp.float32)
    n, dim = grads.shape
    npad = (-(-max(n, 1) // 8)) * 8 - n
    grads = jnp.pad(grads, ((0, npad), (0, 0)))
    # pad rows aim at the LAST segment of the last block with zero
    # gradients — an exact no-op that keeps bounds monotone
    seg = np.asarray(seg_sorted, np.int64)
    nblocks = -(-max(int(num_segments), 1) // _SORT_BLOCK)
    nseg_pad = nblocks * _SORT_BLOCK
    seg_p = np.concatenate(
        [seg, np.full(npad, max(int(num_segments) - 1, 0), np.int64)])
    bounds = np.searchsorted(
        seg_p, np.arange(nblocks + 1, dtype=np.int64) * _SORT_BLOCK,
        side="left").astype(np.int32)
    bounds[-1] = n + npad
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((n + npad, dim),
                               lambda i, bounds, seg: (0, 0))],
        out_specs=pl.BlockSpec((_SORT_BLOCK, dim),
                               lambda i, bounds, seg: (i, 0)),
    )
    out = pl.pallas_call(
        _segment_sum_sorted_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nseg_pad, dim), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(bounds, jnp.int32), jnp.asarray(seg_p, jnp.int32),
      grads)
    return out[:num_segments]


def _sorted_eligible(grads, seg_sorted, num_segments):
    n, dim = grads.shape
    # only the gradient batch + one output window must fit VMEM
    return (n + _SORT_BLOCK) * dim <= _MAX_ELEMS


def merge_segments(grads, inverse, num_segments):
    """Segment-count dispatch for the embedding-grad merge: small
    ``num_segments`` takes the sequential one-VMEM-pass kernel, vocab-
    scale takes the sorted-segment kernel (sorting the batch by
    segment first — a stable argsort, so within-segment row order and
    therefore the f32 accumulation order is preserved).  This is the
    streaming trainer's client-side pre-merge."""
    if int(num_segments) < SORTED_NSEG_MIN:
        return registry.dispatch("segment_sum", grads, inverse,
                                 num_segments=num_segments)
    inv = np.asarray(inverse)
    order = np.argsort(inv, kind="stable")
    g = jnp.asarray(grads)[jnp.asarray(order)]
    return registry.dispatch("segment_sum_sorted", g,
                             inv[order], num_segments=num_segments)


registry.register(
    "segment_sum_sorted", segment_sum_sorted_pallas,
    segment_sum_sorted_ref,
    tolerance="measured exact vs xla_ref on this backend; documented "
              "atol 1e-6 (per-segment accumulation order equals row "
              "order in both); bit-exact for integer-valued grads",
    eligible=_sorted_eligible,
    doc="sorted-segment embedding-grad merge for vocab-scale nseg: "
        "output streams in blocks, scalar-prefetched row bounds drive "
        "the per-block ranges; the streaming trainer's pre-merge "
        "picks it via merge_segments when nseg >= SORTED_NSEG_MIN",
)

registry.register(
    "segment_sum", segment_sum_pallas, segment_sum_ref,
    tolerance="measured exact vs xla_ref on this backend; documented "
              "atol 1e-6 (XLA scatter-add ordering is not pinned); "
              "bit-exact for integer-valued grads by construction",
    eligible=_eligible,
    doc="device-side fused sparse-grad merge: inverse-indexed "
        "segment-sum in one VMEM pass, mirroring ps_core.cc's "
        "ps_segsum_inv",
)
