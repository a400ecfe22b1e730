#!/usr/bin/env python3
"""Chip microbench of the absorbed latent decode (PERF.md): the
decode attention of one step's latent layers at the two latent cells'
shapes (openPangu: 128 heads, 5 layers, 128 rows over 256 blocks of
16; Kimi-Linear: 32 heads, 2 layers, 128 rows over 192 blocks of 16;
rows 640 lanes of which 576 hold the latent, V the first 512), rows
as long as the cells' live lengths (a prompt quantile plus a uniform
share of an answer quantile of the cell's traffic, stratified as the
traffic draws them), blocks at shuffled physical ids.

For each shape: ``paged_attention`` called with the pool as K and as V
and the output's first 512 lanes kept (the call the latent models made
before the latent kernel), then ``paged_latent_attention`` at 16 / 32 /
64 pages a grid-step block. Each is timed as one jitted program over
the layers and as one over twice as many calls (the second set of
queries its own), median of 10 runs each; the difference is the device
time of the layers' calls without the program's own launch and fetch
(≈ 0.9 ms, which is as large as two of the Kimi shape's calls).  It is
given in ns per (live position, layer), beside the largest difference
of each output from the first and from the float32 reference.

    python3 tools/latent_decode_bench.py   # on one chip

Needs an accelerator: a CPU gives no device time."""
import math
import os
import statistics
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)
import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402

from paddle_tpu.ops.pallas import kv_attention as kv   # noqa: E402

W, LATENT, VW, BS, ROWS = 640, 576, 512, 16, 128
SCALE = (128 + 64) ** -0.5
# (name, heads, layers, blocks a row, prompt range, answer range)
CELLS = [("openpangu", 128, 5, 256, (768, 3072), (256, 1024)),
         ("kimi", 32, 2, 192, (256, 2048), (128, 768))]
PAGES = (16, 32, 64)


def live_lengths(rng, prompt, answer, cap):
    """The traffic's stratified log-uniform quantiles, a prompt each
    plus a uniform share of an answer, in a seeded order."""
    u = (np.arange(ROWS) + 0.5) / ROWS
    logq = lambda lo, hi: np.exp(np.log(lo) + u * np.log(hi / lo))
    p = rng.permutation(logq(*prompt))
    a = rng.permutation(logq(*answer)) * rng.random(ROWS)
    return np.clip(np.round(p + a), 1, cap).astype(np.int32)


def timed(f, *args):
    out = jax.block_until_ready(f(*args))
    ts = []
    for _ in range(10):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t)
    return out, statistics.median(ts)


def bench_cell(rng, name, H, L, M, prompt, answer):
    """One cell's shape: the two-pool call, then the latent kernel at
    each page count (module doc)."""
    lengths = live_lengths(rng, prompt, answer, M * BS)
    nb = ROWS * M + 1
    tbl = np.zeros((ROWS, M), np.int32)
    ids = 1 + rng.permutation(nb - 1)
    for i, n in enumerate(lengths):
        used = -(-int(n) // BS)
        tbl[i, :used] = ids[i * M:i * M + used]
    tbl, pos = jnp.asarray(tbl), jnp.asarray(lengths[:, None] - 1)
    lane = jnp.arange(W) < LATENT          # the pad lanes are zero
    keys = jax.random.split(jax.random.key(H), 3 * L)
    pools = [jnp.where(lane, jax.random.normal(
        k, (nb, BS, 1, W), jnp.bfloat16), 0) for k in keys[:L]]
    qs = [jnp.where(lane, jax.random.normal(
        k, (ROWS, 1, H, W), jnp.float32), 0) for k in keys[L:]]
    work = float(lengths.sum()) * L
    print(f"{name}: {H} heads, {L} layers, {ROWS} rows, live "
          f"positions mean {lengths.mean():.1f} (min {lengths.min()}, "
          f"max {lengths.max()})", flush=True)

    one_ref = jax.jit(lambda q, p: kv.paged_latent_attention_ref(
        q, p, tbl, pos, VW, SCALE))
    ref = jnp.stack([one_ref(q, p) for q, p in zip(qs[:L], pools)])

    def two_pools(qs, pools):
        # the call before the latent kernel: the kernel's 1/sqrt(W) undone in
        # the query, K = V = the pool, the first 512 lanes kept
        return [kv.paged_attention(
            (q * (SCALE * math.sqrt(W))).astype(p.dtype), p, p, None,
            None, tbl, pos, 1).reshape(ROWS, 1, H, W)[..., :VW]
            for q, p in zip(qs, pools)]
    runs = [("paged_attention, two pools", two_pools)]
    pages = kv._LATENT_PAGES_PER_BLOCK
    for P in PAGES:
        def latent(qs, pools, P=P):
            kv._LATENT_PAGES_PER_BLOCK = P
            return [kv.paged_latent_attention(
                q.astype(p.dtype), p, tbl, pos, VW, SCALE)
                for q, p in zip(qs, pools)]
        runs.append((f"paged_latent_attention, {P} pages a block",
                     latent))
    first = None
    for label, fn in runs:
        jax.clear_caches()
        out, t_one = timed(jax.jit(fn), qs[:L], pools)
        _, t_two = timed(jax.jit(fn), qs, pools + pools)
        t = t_two - t_one
        out = jnp.stack(out).astype(jnp.float32)
        first = out if first is None else first
        err = float(jnp.abs(out - ref).max())
        diff = float(jnp.abs(out - first).max())
        print(f"  {label}: {t * 1e3:.3f} ms = "
              f"{t / work * 1e9:.3f} ns a position-layer ({t_one * 1e3:.3f}"
              f" ms with the launch); max|diff| "
              f"vs two pools {diff:.4g}, vs f32 reference {err:.4g} "
              f"(largest |out| {float(jnp.abs(first).max()):.3g})",
              flush=True)
    kv._LATENT_PAGES_PER_BLOCK = pages


def main():
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        sys.exit("latent_decode_bench: no accelerator (a CPU gives no "
                 "device time)")
    print(f"{dev.device_kind}; tree {ROOT}", flush=True)
    rng = np.random.default_rng(40)
    for cell in CELLS:
        bench_cell(rng, *cell)


if __name__ == "__main__":
    main()
