#!/usr/bin/env python3
"""Records the small trace that the trace reduction is tested on
(``perfbench/fixtures/small.xplane.pb``): a few jitted steps with
host gaps under ``bench.*`` spans, and on several chips a psum.  Run
on the chip; writes into chiprun_out/."""
from __future__ import annotations

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    n = len(jax.devices())
    out = os.path.join(ROOT, "chiprun_out", f"fixture_trace_{n}")
    shutil.rmtree(out, ignore_errors=True)

    @jax.jit
    def fixture_step(x, w):
        for _ in range(3):
            x = jnp.tanh(x @ w)
        return x

    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.ones((512, 512), jnp.bfloat16) * 0.01
    if n > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(jax.devices(), ("x",))
        x = jax.device_put(x, NamedSharding(mesh, P("x")))

        @jax.jit
        def fixture_step(x, w):      # noqa: F811
            y = jnp.tanh(x @ w)
            s = jax.lax.with_sharding_constraint(
                y.sum(0, keepdims=True), NamedSharding(mesh, P()))
            return jnp.tanh(y @ w) + s.astype(y.dtype)
    fixture_step(x, w).block_until_ready()
    jax.profiler.start_trace(out)
    for i in range(4):
        with jax.profiler.TraceAnnotation("bench.stage_batch"):
            time.sleep(0.002)
        y = fixture_step(x, w)
        with jax.profiler.TraceAnnotation("bench.wait_loss"):
            y.block_until_ready()
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    dst = os.path.join(ROOT, "chiprun_out", f"small_{n}chip.xplane.pb")
    shutil.copy(pb[0], dst)
    shutil.rmtree(out, ignore_errors=True)
    print("fixture", dst, os.path.getsize(dst), "bytes")
    sys.path.insert(0, ROOT)
    from perfbench.harness import trace_reduce as TR
    ev = TR.events_from_xplane(dst)
    planes = {}
    for e in ev:
        planes.setdefault((e.plane, e.line), []).append(e)
    for (p, l), es in sorted(planes.items()):
        print(p, "|", l, "|", len(es), "|", [x.name for x in es[:6]])
    s = TR.summarize(ev)
    print({k: v for k, v in s.items()})


if __name__ == "__main__":
    main()
