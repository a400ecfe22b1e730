#!/usr/bin/env python3
"""Chip microbench of ``kimi_linear._attend`` (PERF.md, PR 36): one
layer of prefill attention at the two latent cells' shapes (128 heads
for openPangu, 32 for Kimi-Linear; head sizes 192 and 128; bfloat16),
the tree's body against the whole square in query chunks (the body of
before PR 36, kept here as the reference), median of 8 calls, the
largest difference between the two and the plan's share of the square.

    chiprun -- python3 tools/attend_bench.py

Needs an accelerator: a CPU gives no device time."""
import os
import statistics
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)
import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402

from paddle_tpu.text.models import kimi_linear as KL   # noqa: E402

SHAPES = {128: [(1, 1024), (1, 1536), (1, 2048), (1, 3072), (1, 4096),
                (2, 1024), (2, 1536), (2, 2048), (2, 3072), (2, 4096)],
          32: [(1, 256), (4, 512), (1, 1024), (4, 1024), (1, 2048),
               (4, 2048)]}
F32 = jnp.float32


def whole_square(q, k, v, scale, chunk):
    """Every query chunk over all keys, the upper half masked; the
    softmax normalised on the score block."""
    B, S, H, _ = q.shape
    k_pos = jnp.arange(S)[None, :]

    def block(qc, q0):
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, k,
                       preferred_element_type=F32) * scale
        live = (q0 + jnp.arange(qc.shape[1]))[:, None] >= k_pos
        p = jax.nn.softmax(jnp.where(live, s, -1e30), -1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                          preferred_element_type=F32).astype(v.dtype)
    if S <= chunk or S % chunk:
        return block(q, 0)
    n = S // chunk
    out = jax.lax.map(lambda t: block(*t), (
        jnp.moveaxis(q.reshape(B, n, chunk, H, -1), 1, 0),
        jnp.arange(n) * chunk))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, -1)


def timed(f, *args):
    out = jax.block_until_ready(f(*args))
    ts = []
    for _ in range(8):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t)
    return out.astype(F32), statistics.median(ts) * 1e3


def main():
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        sys.exit("attend_bench: no accelerator (a CPU gives no device time)")
    print(f"{dev.device_kind}; tree {ROOT}")
    scale = 192 ** -0.5
    for H, shapes in SHAPES.items():
        for B, S in shapes:
            ks = jax.random.split(jax.random.key(B * S + H), 3)
            q, k = (jax.random.normal(x, (B, S, H, 192), jnp.bfloat16)
                    for x in ks[:2])
            v = jax.random.normal(ks[2], (B, S, H, 128), jnp.bfloat16)
            chunk, groups, done, square = KL.attend_plan(B, S, H)
            ref, t_ref = timed(jax.jit(
                lambda *a: whole_square(*a, scale, chunk)), q, k, v)
            got, t_new = timed(jax.jit(
                lambda *a: KL._attend(*a, scale)), q, k, v)
            print(f"H={H} B={B} S={S} chunk={chunk} groups={groups} "
                  f"share={done / square:.3f}: whole square {t_ref:.2f} ms"
                  f" -> tree {t_new:.2f} ms; max|diff| "
                  f"{float(jnp.abs(got - ref).max()):.4g} (largest |out| "
                  f"{float(jnp.abs(ref).max()):.3g})", flush=True)


if __name__ == "__main__":
    main()
