"""Worker payload for the 2-process localhost distributed test.

Launched (twice) via ``python -m paddle_tpu.distributed.launch`` by
tests/test_multiprocess.py — the analog of the reference's collective
payload scripts run by _run_cluster (reference:
python/paddle/fluid/tests/unittests/test_collective_base.py:34,162).

Exercises the full multi-host path on the CPU backend: launcher env →
init_parallel_env → jax.distributed rendezvous → a cross-process
collective → a global-batch SPMD train step.  Prints ``MP_OK rank=N
loss0=... loss1=...`` on success; any failure exits nonzero.
"""
import os
import sys

# 2 virtual CPU devices per process → 4 global devices over 2 processes
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
from jax.experimental import multihost_utils  # noqa: E402


def main():
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.dist_step import DistributedTrainStep

    env = dist.init_parallel_env()  # rendezvous via PADDLE_COORDINATOR
    rank, world = dist.get_rank(), dist.get_world_size()
    assert world == 2, f"expected 2 processes, got {world}"
    assert len(jax.devices()) == 4, jax.devices()
    assert env.world_size == 2 and env.rank == rank

    # ---- collective across processes: psum of (rank+1) over all 4 devices
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed.collective import shard_map
    mesh = mesh_mod.get_mesh()  # all-dp over the 4 global devices

    def _sum(x):
        return jax.lax.psum(x, "dp")

    local = np.full((2, 3), float(rank + 1), np.float32)  # per-device rows
    garr = jax.make_array_from_process_local_data(
        mesh_mod.named_sharding(P(("dp",), None), mesh), local)
    out = jax.jit(shard_map(_sum, mesh=mesh,
                            in_specs=(P(("dp",), None),),
                            out_specs=P()))(garr)
    # devices hold 1,1,2,2 → psum = 6 per element; the result is globally
    # replicated, so this process's local shard carries the full value
    got = np.asarray(out.addressable_data(0))
    assert np.allclose(got, 6.0), got

    # ---- one SPMD train step over a global batch (fleet path)
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 2))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    strategy = fleet.DistributedStrategy()

    def loss_fn(x, y):
        return F.cross_entropy(model(x), y).mean()

    step = DistributedTrainStep(model, loss_fn, opt, strategy, mesh=mesh)
    rng = np.random.RandomState(7)  # same on both ranks
    x_all = rng.randn(8, 8).astype(np.float32)
    y_all = rng.randint(0, 2, (8,)).astype(np.int64)
    lo, hi = rank * 4, rank * 4 + 4  # each process owns half the batch
    x = dist.global_batch(x_all[lo:hi])
    y = dist.global_batch(y_all[lo:hi])
    l0 = float(step(x, y))
    l1 = float(step(x, y))
    assert l1 < l0, (l0, l1)

    # losses must agree across processes (same global program + data)
    ls = np.asarray(multihost_utils.process_allgather(
        np.asarray([l0, l1], np.float32)))
    assert np.allclose(ls[0], ls[-1], rtol=1e-6), ls

    print(f"MP_OK rank={rank} loss0={l0:.6f} loss1={l1:.6f}", flush=True)


if __name__ == "__main__":
    main()
