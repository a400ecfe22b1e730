"""Kernel-level A/B: fused matmul+BN-stats (Pallas) vs XLA matmul +
separate stat reductions, at ResNet-50 bottleneck 1x1-conv shapes.

VERDICT r4 item 3: the ResNet per-op profile shows 23 ms/step (20%) in
BN statistics (convert_reduce_fusion + reduce — memory-bound re-reads
of every activation); a 1x1 conv IS a matmul, so the candidate kernel
computes per-channel sum and sum-of-squares in the matmul epilogue
while the output tile is still in VMEM.  This script decides whether
the fusion wins at kernel level BEFORE any model integration; either
way the outcome is recorded in PERF.md.

Usage: python tools/exp_conv_bn_kernel.py  (needs the chip to itself).
"""
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, y_ref, s1_ref, s2_ref):
    acc = jnp.dot(x_ref[...], w_ref[...],
                  preferred_element_type=jnp.float32)
    y_ref[...] = acc.astype(y_ref.dtype)
    # per-channel stats while the tile is in VMEM: the whole point —
    # the activation is never re-read from HBM for BN statistics.
    # (the [8, bn] stats tile is the minimum f32 TPU tile; row 0 holds
    # the partial, the rest is zero padding)
    bn = acc.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (8, bn), 0)
    s1_ref[0, ...] = jnp.where(row == 0, acc.sum(axis=0)[None, :], 0.0)
    s2_ref[0, ...] = jnp.where(row == 0,
                               (acc * acc).sum(axis=0)[None, :], 0.0)


def fused_matmul_bn_stats(x, w, bm=512, bn=256):
    """y = x @ w (bf16) plus per-output-channel (sum, sum_sq) partials.

    Returns (y [M,N], s1 [N], s2 [N]); partial per-row-block stats are
    reduced by XLA afterwards (tiny [M/bm, N] tensors)."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and M % bm == 0 and N % bn == 0, (x.shape, w.shape)
    gi, gj = M // bm, N // bn
    y, p1, p2 = pl.pallas_call(
        _kernel,
        grid=(gi, gj),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 8, bn), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 8, bn), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), x.dtype),
            jax.ShapeDtypeStruct((gi, 8, N), jnp.float32),
            jax.ShapeDtypeStruct((gi, 8, N), jnp.float32),
        ],
    )(x, w)
    return y, p1.sum((0, 1)), p2.sum((0, 1))


def xla_matmul_then_stats(x, w):
    """The status quo: matmul, then stat reductions re-reading y."""
    y = jnp.dot(x, w)                      # bf16 out
    yf = y.astype(jnp.float32)
    return y, yf.sum(0), (yf * yf).sum(0)


def bench_one(M, K, N, iters=30):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(M, K), jnp.bfloat16)
    w = jnp.asarray(rng.randn(K, N) * 0.05, jnp.bfloat16)
    plain = jax.jit(xla_matmul_then_stats)

    def timed(fn):
        # median of 3 windows: single windows wandered +-15% when
        # this was recorded
        out = fn(x, w)
        jax.block_until_ready(out)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(x, w)
            jax.block_until_ready(out)
            ts.append((time.perf_counter() - t0) / iters * 1e3)
        return sorted(ts)[1]

    yp, s1p, s2p = plain(x, w)
    # small tile autotune for the fused kernel (the integration would
    # bake the winning tile per shape, like the reference's conv algo
    # cache framework/conv_search_cache.h)
    best, best_cfg = None, None
    for bm in (1024, 512, 256):
        if M % bm:
            continue
        # bn == N is always legal (full-array lane dim), covering the
        # N=64 stage-2 shapes the 128-divisibility rule would exclude
        for bn in {512, 256, 128, N} - {b for b in (512, 256, 128)
                                        if N % b}:
            if N % bn or bn > N:
                continue
            try:
                fused = jax.jit(functools.partial(
                    fused_matmul_bn_stats, bm=bm, bn=bn))
                yf, s1f, s2f = fused(x, w)
                np.testing.assert_allclose(np.asarray(s1f),
                                           np.asarray(s1p),
                                           rtol=2e-2, atol=M * 2e-3)
                t = timed(fused)
            except Exception:
                continue
            if best is None or t < best:
                best, best_cfg = t, (bm, bn)
    tp = timed(plain)
    mm = jax.jit(lambda a, b: a @ b)
    tm = timed(mm)
    return dict(M=M, K=K, N=N, tile=best_cfg,
                fused_ms=round(best, 3), xla_ms=round(tp, 3),
                matmul_only_ms=round(tm, 3),
                speedup=round(tp / best, 3),
                stats_overhead_fused_ms=round(best - tm, 3),
                stats_overhead_xla_ms=round(tp - tm, 3))


def main():
    # ResNet-50 batch-256 bottleneck 1x1 shapes (M = B*H*W)
    shapes = [
        (256 * 56 * 56, 256, 64),      # stage2 reduce (biggest act)
        (256 * 56 * 56, 64, 256),      # stage2 expand
        (256 * 28 * 28, 512, 128),     # stage3 reduce
        (256 * 28 * 28, 128, 512),     # stage3 expand
        (256 * 14 * 14, 1024, 256),    # stage4 reduce
        (256 * 14 * 14, 256, 1024),    # stage4 expand
        (256 * 7 * 7, 2048, 512),      # stage5 reduce
        (256 * 7 * 7, 512, 2048),      # stage5 expand
    ]
    out = []
    for M, K, N in shapes:
        r = bench_one(M, K, N)
        print(json.dumps(r))
        out.append(r)
    won = sum(1 for r in out if r["speedup"] > 1.05)
    print(f"# fused wins (>5%) on {won}/{len(out)} shapes")


if __name__ == "__main__":
    main()
