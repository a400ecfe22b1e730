"""Device-native engine for the elastic data plane (ISSUE 17).

PR 9's elastic trainer was correct but host-bound: slot-ordered
gradient reduction and the flat ZeRO optimizer apply ran in plain
numpy, a parallel universe to ``DistributedTrainStep``'s compiled
path.  This module is the merge point: the same math, compiled.

:class:`DeviceZeroEngine` owns the two compiled programs a generation
needs and is REBUILT on every membership transition (the per-mesh
recompile hook — ``ElasticTrainer`` calls :meth:`rebuild` right after
``mesh.reform_mesh()``, inside the reshard window, so steady-state
steps never pay a compile):

* ``reduce`` — the slot-ordered gradient reduction as ONE jitted
  program whose accumulation order is the fixed slot order
  ``0..G-1``, statically unrolled.  XLA preserves float semantics
  (no reassociation), every rank runs the identical program over the
  byte-identical wire copies, and the world size never enters the
  program — so the full ``gsum`` is bit-identical across ranks AND
  across world sizes, the exact property PR 9's host loop provided.
* the fused optimizer apply — routed through PR 13's ``opt_apply``
  kernel (``dist_step.fused_optimizer_apply``; registry dispatch:
  pallas on TPU, xla_ref elsewhere), reading grad+param+moments and
  writing param+moments in one pass.  Its update is strictly
  elementwise, so a shard's update equals the same slice of the
  full-vector update for any world size (the ZeRO invariant,
  asserted bit-for-bit in tests/test_pallas_kernels.py).

Engine choice is RUN-SCOPED (PR 13 finding: XLA CPU FMA-contracts
mul+add chains, so the device engine and the host-numpy engine differ
~1 ulp on ~1% of elements; bit-contracts hold within either engine,
never across).  ``ElasticTrainer(engine="host")`` or
``PADDLE_ELASTIC_ENGINE=host`` selects the PR 9 reference path.

:class:`ReshardMeter` is the accounting side of the O(max shard)
guarantee: every transient staging buffer the reshard/checkpoint
machinery holds (exchange rounds, streamed-writer chunks, ranged
reads) registers with the owning trainer's meter
(``ElasticTrainer.reshard_meter`` — per-trainer so the in-process
multi-rank tests model per-HOST accounting; the module-level
``reshard_meter`` is a default for ad-hoc use), and the device-path
test asserts the observed peak stays a small multiple of one shard —
i.e. the global flat f32 vector is never materialized by the
machinery.  (The model replica itself is full-size by the
``grad_fn(params, batch)`` host contract; the bound governs the
reshard/checkpoint plumbing, which is what breaks first at 7B-scale
state.)
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import List

import numpy as np

from ...observability import flight_recorder as _flight

__all__ = ["DeviceZeroEngine", "ReshardMeter", "reshard_meter"]


class ReshardMeter:
    """Tracks transient host staging held by the reshard/checkpoint
    machinery: ``hold(buf)`` is a context manager bracketing a
    buffer's lifetime; ``peak_bytes`` is the high-water mark of
    concurrently held staging, ``total_bytes`` everything that ever
    moved through.  Thread-safe (exchange rounds run per-rank in
    threads in the in-process tests)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._live = 0
        self.peak_bytes = 0
        self.total_bytes = 0

    def reset(self):
        with self._lock:
            self._live = 0
            self.peak_bytes = 0
            self.total_bytes = 0

    @contextlib.contextmanager
    def hold(self, buf):
        n = int(getattr(buf, "nbytes", buf))
        with self._lock:
            self._live += n
            self.total_bytes += n
            if self._live > self.peak_bytes:
                self.peak_bytes = self._live
        try:
            yield buf
        finally:
            with self._lock:
                self._live -= n


#: process-wide meter — the elastic trainer's streamed save/restore
#: paths account here; tests/test_elastic_device.py reads and resets it
reshard_meter = ReshardMeter()


class DeviceZeroEngine:
    """Compiled device-side math for one elastic trainer (see module
    docstring).  ``micro`` and ``numel`` are run constants; everything
    world-dependent is (re)built by :meth:`rebuild`."""

    def __init__(self, micro: int, numel: int):
        self._micro = int(micro)
        self._numel = int(numel)
        self._reduce = None
        self.world = None
        self.rank = None
        self.compiles = 0

    def rebuild(self, opt, world: int, rank: int, lo: int, hi: int,
                gen: int = -1):
        """Per-mesh recompile: build the slot-ordered reduce for this
        run's (micro, numel) and warm the fused-apply jit cache for
        the NEW shard length — both inside the reshard window, timed
        and flight-recorded as ``elastic.reshard.compile``."""
        import jax

        from .dist_step import fused_optimizer_apply

        t0 = time.perf_counter()
        G = self._micro

        def _slot_ordered_sum(stack):
            # static unroll: the accumulation order IS the slot order,
            # identical for every rank and every world size
            acc = stack[0]
            for s in range(1, G):
                acc = acc + stack[s]
            return acc

        self._reduce = jax.jit(_slot_ordered_sum)
        np.asarray(self._reduce(
            np.zeros((G, self._numel), np.float32)))   # compile now
        z = np.zeros(max(hi - lo, 1), np.float32)
        fused_optimizer_apply(
            opt.KIND, z, z, {k: z.copy() for k in opt.SLOTS},
            t=max(int(getattr(opt, "t", 1)), 1), **opt._hyper())
        self.world, self.rank = int(world), int(rank)
        self.compiles += 1
        _flight.record(
            "elastic.reshard.compile",
            ms=round((time.perf_counter() - t0) * 1e3, 3),
            gen=int(gen), world=int(world), rank=int(rank),
            shard_len=int(hi - lo))

    def reduce(self, slot_grads: List[np.ndarray]) -> np.ndarray:
        """Slot-ordered reduction of the G wire copies -> full f32
        gsum, as one compiled program."""
        stack = np.stack([np.asarray(g, np.float32)
                          for g in slot_grads])
        return np.asarray(self._reduce(stack), np.float32)
