"""Heterogeneous PS training — host (CPU) sparse embeddings + TPU dense
compute, overlapped.

Parity target (SURVEY §2.6 "Heterogeneous PS / PS-GPU"): the reference
splits rec-model training between CPU workers holding huge sparse
embedding tables and GPU/XPU workers running the dense net
(framework/heterxpu_trainer.cc, heter_ps/ GPU hashtable cache,
DownpourWorker's PullSparse -> forward/backward -> PushSparse loop,
framework/fleet/fleet_wrapper.h:111-185).

TPU-native shape: the sparse tables are the host-side
:class:`~paddle_tpu.distributed.fleet.ps.SparseTable` (native C++ shards);
the dense step is ONE jit'd XLA program taking the pulled embedding rows
as an input and returning (metrics, embedding-row gradients). The trainer
runs a software pipeline across three lanes so the TPU never waits on the
host:

    lane P (host threads): pull rows for batch i+1
    lane C (TPU):          dense step on batch i
    lane U (host threads): push grads of batch i-1 (async, like the
                           reference's PushSparseVarsWithLabelAsync)

``sync_mode=True`` degrades to pull->step->push per batch (the
reference's sync communicator mode).

Hot-path note (r6): a push into a plain native ``SparseTable`` is ONE
fused C call — dedup + segment-sum + optimizer apply happen inside
ps_core.cc, with no ``jax.ops.segment_sum`` dispatch and no Python
per-id work.  On a 1-core host (the r5 roofline) this is the fast
wide_deep configuration; ``DeviceCachedTable`` remains the right shape
when a real device sits close enough that HBM-resident rows pay off.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, Iterable, Optional

import numpy as np

from .ps import SparseTable

__all__ = ["HeterTrainer", "DeviceCachedTable", "RemoteTable"]


class RemoteTable:
    """A table living behind the PS service, presented with the local
    ``SparseTable`` pull/push surface so :class:`HeterTrainer` (and the
    bench's wide_deep loop) can train against a remote — and, with an
    endpoint list per shard, fault-tolerant — PS cluster instead of an
    in-process table.

    The wrapped :class:`~paddle_tpu.distributed.fleet.ps_service.
    PSClient` owns retries, idempotent seq numbering and replica
    failover; this adapter only pins the table name and dim.
    """

    def __init__(self, client, name: str, dim: int):
        self._client = client
        self.name = name
        self.dim = dim

    def pull(self, ids: np.ndarray) -> np.ndarray:
        return self._client.pull(self.name, ids)

    def pull_q8(self, ids: np.ndarray):
        """int8 wire pull (ISSUE 16): per-row quantized rows straight
        off the q8 wire — ``(codes int8 [n, dim], scales f32 [n])``
        aligned to ``ids`` order.  The device cache's miss fill feeds
        these to the on-device pull_dequant kernel."""
        return self._client.pull_q8(self.name, ids)

    def push(self, ids: np.ndarray, grads: np.ndarray):
        ids = np.asarray(ids).reshape(-1)
        self._client.push(self.name, ids,
                          np.asarray(grads, np.float32).reshape(
                              ids.size, self.dim))

    def push_delta(self, ids: np.ndarray, deltas: np.ndarray):
        self._client.push_delta(self.name, ids, deltas)


class _NativeCacheDir:
    """ctypes wrapper over native/cache_dir.cc — the cache DIRECTORY
    (id->slot, LRU, pins, admission planning) as one C call per
    transaction.  The r3 profile put the wide&deep residual step time in
    exactly this bookkeeping (~27k unique-id dict/LRU operations per
    batch in Python on the 1-core host); the reference keeps the same
    structure native too (heter_ps/hashtable.h)."""

    def __init__(self, lib, capacity: int):
        import ctypes
        self._lib = lib
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        lib.cache_dir_create.restype = ctypes.c_void_p
        lib.cache_dir_create.argtypes = [ctypes.c_int64]
        lib.cache_dir_destroy.argtypes = [ctypes.c_void_p]
        lib.cache_dir_pull.restype = ctypes.c_int64
        lib.cache_dir_pull.argtypes = [
            ctypes.c_void_p, i64p, ctypes.c_int64, ctypes.c_int32,
            i64p, i64p, i64p, i64p, i64p, i64p, i64p]
        lib.cache_dir_lookup.restype = ctypes.c_int64
        lib.cache_dir_lookup.argtypes = [
            ctypes.c_void_p, i64p, ctypes.c_int64, ctypes.c_int32,
            i64p, i64p, i64p, i64p]
        lib.cache_dir_ids_of.argtypes = [ctypes.c_void_p, i64p,
                                         ctypes.c_int64, i64p]
        lib.cache_dir_unpin_slots.argtypes = [ctypes.c_void_p, i64p,
                                              ctypes.c_int64]
        lib.cache_dir_unpin_ids.argtypes = [ctypes.c_void_p, i64p,
                                            ctypes.c_int64]
        lib.cache_dir_load.restype = ctypes.c_int64
        lib.cache_dir_load.argtypes = [ctypes.c_void_p]
        self._h = lib.cache_dir_create(capacity)

    def __del__(self):
        try:
            self._lib.cache_dir_destroy(self._h)
        except Exception:
            pass

    def pull(self, ids: np.ndarray, pin: bool):
        n = len(ids)
        uniq = np.empty(n, np.int64)
        inverse = np.empty(n, np.int64)
        slots = np.empty(n, np.int64)
        miss_pos = np.empty(n, np.int64)
        ev_slots = np.empty(n, np.int64)
        ev_ids = np.empty(n, np.int64)
        counts = np.empty(3, np.int64)
        rc = self._lib.cache_dir_pull(
            self._h, np.ascontiguousarray(ids), n, 1 if pin else 0,
            uniq, inverse, slots, miss_pos, ev_slots, ev_ids, counts)
        u, nm, ne = int(counts[0]), int(counts[1]), int(counts[2])
        if rc != 0:
            return None, u, nm      # thrash: directory unchanged
        return (uniq[:u], inverse, slots[:u], miss_pos[:nm],
                ev_slots[:ne], ev_ids[:ne]), u, nm

    def lookup(self, ids: np.ndarray, unpin: bool):
        n = len(ids)
        uniq = np.empty(n, np.int64)
        inverse = np.empty(n, np.int64)
        slots = np.empty(n, np.int64)
        counts = np.empty(1, np.int64)
        rc = self._lib.cache_dir_lookup(
            self._h, np.ascontiguousarray(ids), n, 1 if unpin else 0,
            uniq, inverse, slots, counts)
        if rc != 0:
            return None
        u = int(counts[0])
        return uniq[:u], inverse, slots[:u]

    def unpin_slots(self, slots: np.ndarray):
        self._lib.cache_dir_unpin_slots(
            self._h, np.ascontiguousarray(slots, dtype=np.int64),
            len(slots))

    def unpin_ids(self, ids: np.ndarray):
        """Tolerant unpin: non-resident ids (already evicted) are
        skipped, resident ids' pins decrement — the all-or-nothing
        lookup(unpin=True) would leak the survivors' pins forever
        after a partial eviction."""
        self._lib.cache_dir_unpin_ids(
            self._h, np.ascontiguousarray(ids, dtype=np.int64), len(ids))

    def ids_of(self, slots: np.ndarray) -> np.ndarray:
        out = np.empty(len(slots), np.int64)
        self._lib.cache_dir_ids_of(
            self._h, np.ascontiguousarray(slots, dtype=np.int64),
            len(slots), out)
        return out

    def load(self) -> int:
        return int(self._lib.cache_dir_load(self._h))


class DeviceCachedTable:
    """Device-resident cache over a host :class:`SparseTable` — the TPU
    analog of the reference's GPU embedding cache
    (framework/fleet/heter_ps/hashtable.h + heter_comm.h, and
    PSGPUWrapper's BuildGPUTask/EndPass lifecycle).

    Hot rows live in one HBM buffer ``[capacity, dim]``; the host keeps
    the id->slot map and LRU order. ``pull`` returns device rows (a
    single gather — no host<->device row traffic on a hit), misses
    pull-through from the host table and evict cold slots. ``push``
    applies the optimizer ON DEVICE (scatter update), so a training step
    over cached rows never ships embedding rows across the host link.
    Evicted/flushed rows write back exactly via ``push_delta`` (value
    delta against the row as it was admitted), matching the reference's
    end-of-pass sync. Divergence from the reference, by design: adagrad
    accumulator state is cache-resident and restarts on re-admission
    (the reference ships moments with the row; a delta-merge of
    accumulators across workers is not well-defined anyway).
    """

    def __init__(self, table: SparseTable, capacity: int,
                 optimizer: str = "sgd", lr: float = 0.01,
                 eps: float = 1e-6, wire: str = "f32"):
        import jax.numpy as jnp
        if optimizer not in ("sgd", "adagrad"):
            raise ValueError(f"device cache optimizer must be sgd|adagrad, "
                             f"got {optimizer!r}")
        # miss-fill wire (ISSUE 16): "q8" ships int8 codes + per-row
        # scales from the host/PS table and reconstructs ON DEVICE via
        # the ops/pallas pull_dequant kernel — a serving cache pays 1/4
        # of the row bytes per miss on both the PS link and the
        # host->device copy.  Lossy by design (scale = amax/127), so
        # the TRAINING default stays exact f32.
        if wire not in ("f32", "q8"):
            raise ValueError(f"wire must be f32|q8, got {wire!r}")
        if wire == "q8" and not hasattr(table, "pull_q8"):
            raise ValueError(
                f"wire='q8' needs a table with pull_q8 (got "
                f"{type(table).__name__})")
        self._wire = wire
        self._table = table
        self._cap = int(capacity)
        self._dim = table.dim
        self._opt = optimizer
        self._lr = lr
        self._eps = eps
        # one extra SCRATCH row at index cap: variable-length device ops
        # (install/write-back/push) pad their index vectors to power-of-2
        # buckets pointing at it, so every op reuses a handful of
        # compiled shapes — without this, each batch's unique-id count
        # produced a fresh XLA compile (seconds per step)
        self._buf = jnp.zeros((self._cap + 1, self._dim), jnp.float32)
        self._acc = (jnp.zeros((self._cap + 1, self._dim), jnp.float32)
                     if optimizer == "adagrad" else None)
        self._orig = np.zeros((self._cap, self._dim), np.float32)
        self._slot_of: Dict[int, int] = {}
        self._id_of = np.full(self._cap, -1, np.int64)
        self._dirty = np.zeros(self._cap, bool)
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self._free = list(range(self._cap - 1, -1, -1))
        self.hits = self.misses = self.evictions = 0
        # HeterTrainer's async pipeline calls pull(i+1) from a pool
        # thread while push(i) is still pending on another: all state
        # mutation is serialized by _lock, and pulls made with pin=True
        # keep their slots un-evictable until the matching push lands
        # (plain pulls keep pure LRU semantics for pull-only use).
        self._lock = threading.RLock()
        self._pins: Dict[tuple, list] = {}   # uniq-ids key -> [slots, n]
        # recent pull plans keyed by raw-id bytes: with overlapped lanes
        # (r5) pull(i+1) may land BEFORE push(i), so a single last-plan
        # slot would miss; bounded so an abandoned pull cannot grow it.
        # Plans are invalidated whenever one of their slots is evicted —
        # a push popping a stale plan would otherwise scatter its
        # gradients into rows that now belong to a DIFFERENT batch
        # (silent host-table corruption; the pre-r5 single-slot cache
        # failed loudly via the strict lookup instead)
        self._plans: "OrderedDict[bytes, tuple]" = OrderedDict()
        # native directory (id->slot/LRU/pins/admission in one C call);
        # Python bookkeeping below is the reference the tests compare
        # against (PADDLE_TPU_DISABLE_NATIVE_CACHE_DIR=1 selects it)
        self._ndir = None
        import os as _os
        if _os.environ.get("PADDLE_TPU_DISABLE_NATIVE_CACHE_DIR") != "1":
            from ...native import load_library
            self._ndir = _NativeCacheDir(load_library("cache_dir"),
                                         self._cap)
        # native segment-sum for host-resident gradients (ps_core.cc
        # ps_segsum_inv): replaces the per-push jax.ops.segment_sum
        # DISPATCH — on a 1-core host the dispatch, not the sum, was the
        # measured cost (PERF.md r5 roofline)
        from ...native import ps_core
        self._pslib = ps_core()

    @staticmethod
    def _bucket(n: int) -> int:
        b = 1
        while b < n:
            b <<= 1
        return b

    def _pad_slots(self, slots: np.ndarray) -> np.ndarray:
        """Pad a slot-index vector to its power-of-2 bucket with the
        scratch row (index cap) so device scatter/gather shapes repeat."""
        b = self._bucket(max(len(slots), 1))
        out = np.full(b, self._cap, np.int64)
        out[:len(slots)] = slots
        return out

    def _invalidate_plans(self, evicted_slots):
        """Drop any retained pull plan touching an evicted slot (see
        the _plans comment in __init__)."""
        if not self._plans:
            return
        ev = {int(s) for s in np.asarray(evicted_slots).tolist()}
        for key in [k for k, (_, _, slots) in self._plans.items()
                    if ev.intersection(int(s) for s in
                                       np.asarray(slots).tolist())]:
            del self._plans[key]

    def _fill_rows(self, miss_ids: np.ndarray, nsp: int):
        """Miss-fill rows padded to ``nsp`` slots: returns (device
        ``[nsp, dim]`` f32 install payload, host np rows for ``_orig``).
        On the q8 wire the install payload is reconstructed on device
        by the pull_dequant kernel; the host copy uses the numpy
        dequant — bit-exact equal by the kernel's tolerance-0 contract,
        so delta write-back stays exact."""
        import jax.numpy as jnp
        k = len(miss_ids)
        if self._wire == "q8":
            from ...ops.pallas import registry as _preg
            from .ps import dequantize_rows_q8
            codes, scales = self._table.pull_q8(miss_ids)
            dev = _preg.dispatch("pull_dequant", codes, scales)
            rows = dequantize_rows_q8(np.asarray(codes, np.int8),
                                      np.asarray(scales, np.float32))
            dev_p = jnp.zeros((nsp, self._dim),
                              jnp.float32).at[:k].set(dev)
            return dev_p, rows
        rows = self._table.pull(miss_ids)
        rows_p = np.zeros((nsp, self._dim), np.float32)
        rows_p[:k] = rows
        return jnp.asarray(rows_p), rows

    # -- admission / eviction -----------------------------------------
    def _admit(self, miss_ids: np.ndarray, pinned: set) -> np.ndarray:
        """Allocate slots for ``miss_ids`` (evicting LRU slots not pinned
        by the current batch), pull rows from the host table, install."""
        import jax.numpy as jnp
        n = len(miss_ids)
        # plan the whole admission BEFORE mutating: raising mid-loop
        # would orphan already-evicted slots (gone from _lru/_slot_of,
        # never returned to _free)
        evict = []
        if len(self._free) < n:
            live = pinned.union(
                *(p[0] for p in self._pins.values())) \
                if self._pins else pinned
            for k in self._lru:
                if len(self._free) + len(evict) >= n:
                    break
                if k not in live:
                    evict.append(k)
            if len(self._free) + len(evict) < n:
                raise RuntimeError(
                    f"device cache thrashing: current batch plus "
                    f"in-flight (unpushed) batches pin more unique "
                    f"rows than capacity={self._cap}")
        for s in evict:
            del self._lru[s]
            del self._slot_of[int(self._id_of[s])]
            self.evictions += 1
        if evict:
            self._invalidate_plans(evict)
        slots = np.asarray(
            [self._free.pop() for _ in range(n - len(evict))] + evict,
            np.int64)
        if evict:
            self._write_back(np.asarray(evict, np.int64))
        sp = self._pad_slots(slots)
        rows_p, rows = self._fill_rows(miss_ids, len(sp))
        self._buf = self._buf.at[jnp.asarray(sp)].set(rows_p)
        if self._acc is not None:
            self._acc = self._acc.at[jnp.asarray(sp)].set(0.0)
        self._orig[slots] = rows
        self._id_of[slots] = miss_ids
        self._dirty[slots] = False
        for s, i in zip(slots.tolist(), miss_ids.tolist()):
            self._slot_of[i] = s
            self._lru[s] = None
        return slots

    def _write_back(self, slots: np.ndarray):
        """Exact sync of dirty rows to the host table: push the value
        delta accumulated since admission (push_delta adds raw)."""
        import jax.numpy as jnp
        d = slots[self._dirty[slots]]
        if d.size == 0:
            return
        dp = self._pad_slots(d)
        vals = np.asarray(self._buf[jnp.asarray(dp)])[:d.size]
        self._table.push_delta(self._id_of[d], vals - self._orig[d])
        self._orig[d] = vals
        self._dirty[d] = False

    # -- SparseTable-compatible surface --------------------------------
    def pull(self, ids: np.ndarray, pin: bool = False):
        """Device rows for ``ids`` (duplicates allowed) — one HBM gather.

        ``pin=True`` (used by HeterTrainer's async pipeline) keeps the
        batch's slots un-evictable until the matching :meth:`push` lands,
        so a concurrent pull for the next batch cannot evict rows whose
        gradients are still in flight."""
        ids = np.ascontiguousarray(np.asarray(ids, np.int64)).reshape(-1)
        if self._ndir is not None:
            return self._pull_native(ids, pin)
        uniq, inverse = np.unique(ids, return_inverse=True)
        with self._lock:
            slots = np.empty(len(uniq), np.int64)
            miss_j = []
            for j, i in enumerate(uniq.tolist()):
                s = self._slot_of.get(i)
                if s is None:
                    miss_j.append(j)
                else:
                    slots[j] = s
                    self._lru.move_to_end(s)
                    self.hits += 1
            if miss_j:
                self.misses += len(miss_j)
                missing = set(miss_j)
                pinned = {int(s) for j, s in enumerate(slots)
                          if j not in missing}
                slots[miss_j] = self._admit(uniq[miss_j], pinned)
            if pin:
                ent = self._pins.setdefault(uniq.tobytes(), [set(), 0])
                ent[0] = {int(s) for s in slots}
                ent[1] += 1
            # push() fast path (bounded one-shot plan cache, r5: with
            # overlapped lanes pull(i+1) may land before push(i))
            self._plans[uniq.tobytes()] = (uniq, None, slots)
            while len(self._plans) > 8:
                self._plans.popitem(last=False)
            return self._buf[np.asarray(slots)[inverse]]

    def _pull_native(self, ids: np.ndarray, pin: bool):
        import jax.numpy as jnp
        with self._lock:
            ret, n_uniq, n_miss = self._ndir.pull(ids, pin)
            if ret is None:
                # stat accounting matches the Python fallback, which
                # counts the failed batch's hits+misses before _admit
                # raises
                self.hits += n_uniq - n_miss
                self.misses += n_miss
                raise RuntimeError(
                    f"device cache thrashing: current batch plus "
                    f"in-flight (unpushed) batches pin more unique "
                    f"rows than capacity={self._cap}")
            uniq, inverse, slots, miss_pos, ev_slots, ev_ids = ret
            self.hits += len(uniq) - len(miss_pos)
            self.misses += len(miss_pos)
            self.evictions += len(ev_slots)
            if ev_slots.size:
                # directory entries are gone; write dirty VALUES back
                # with the ids the native call reported
                self._invalidate_plans(ev_slots)
                self._write_back_rows(ev_slots, ev_ids)
            if miss_pos.size:
                miss_slots = slots[miss_pos]
                sp = self._pad_slots(miss_slots)
                rows_p, rows = self._fill_rows(uniq[miss_pos], len(sp))
                self._buf = self._buf.at[jnp.asarray(sp)].set(rows_p)
                if self._acc is not None:
                    self._acc = self._acc.at[jnp.asarray(sp)].set(0.0)
                self._orig[miss_slots] = rows
                self._dirty[miss_slots] = False
            # push() fast path: the async pipeline pushes EXACTLY the
            # ids it pulled, so the plan can be reused by raw-id match;
            # plans are one-shot (popped by push) and bounded
            self._plans[ids.tobytes()] = (uniq, inverse, slots)
            while len(self._plans) > 8:
                self._plans.popitem(last=False)
            return self._buf[np.asarray(slots)[inverse]]

    def _write_back_rows(self, slots: np.ndarray, ids: np.ndarray):
        """Write dirty rows among ``slots`` (owned by ``ids``) back to
        the host table — the native-directory variant of _write_back."""
        import jax.numpy as jnp
        m = self._dirty[slots]
        d = slots[m]
        if d.size == 0:
            return
        dp = self._pad_slots(d)
        vals = np.asarray(self._buf[jnp.asarray(dp)])[:d.size]
        self._table.push_delta(np.asarray(ids)[m], vals - self._orig[d])
        self._orig[d] = vals
        self._dirty[d] = False

    def push(self, ids: np.ndarray, grads):
        """Apply the optimizer on device to the rows of ``ids``;
        duplicate ids' grads are segment-summed first."""
        import jax
        import jax.numpy as jnp
        ids = np.ascontiguousarray(np.asarray(ids, np.int64)).reshape(-1)
        if self._ndir is not None:
            with self._lock:
                # pop = one-shot, like the pull/push pairing it models:
                # a second push of the same raw ids without a fresh
                # pull must NOT reuse the plan (it would decrement
                # another in-flight batch's pin on a shared slot)
                plan = self._plans.pop(ids.tobytes(), None)
                if plan is not None:
                    uniq, inverse, slots = plan
                    self._ndir.unpin_slots(slots)
                else:
                    ret = self._ndir.lookup(ids, unpin=True)
                    if ret is None:
                        raise KeyError(
                            "push() of ids not resident in the device "
                            "cache")
                    uniq, inverse, slots = ret
                self._push_rows(uniq, inverse, slots, grads)
            return
        uniq, inverse = np.unique(ids, return_inverse=True)
        with self._lock:
            plan = self._plans.pop(uniq.tobytes(), None)
            if plan is not None:
                slots = plan[2]
            else:
                slots = np.asarray(
                    [self._slot_of[i] for i in uniq.tolist()], np.int64)
            self._push_rows(uniq, inverse, slots, grads)
            self._unpin(uniq)

    def _push_rows(self, uniq, inverse, slots, grads):
        """Shared device-side optimizer apply (segment-sum + scatter).

        Host-resident grads take the native segment-sum (one C call, no
        XLA dispatch, no grads host->device transfer before the merge);
        device-resident grads keep the on-device ``jax.ops.segment_sum``
        so they never round-trip through the host link."""
        import jax
        import jax.numpy as jnp
        nseg = self._bucket(max(len(uniq), 1))
        if (isinstance(grads, np.ndarray) and self._pslib is not None
                and inverse is not None):
            import ctypes
            inv = np.ascontiguousarray(np.asarray(inverse), np.int64)
            gr = np.ascontiguousarray(grads.reshape(-1, self._dim),
                                      np.float32)
            sums = np.zeros((nseg, self._dim), np.float32)
            self._pslib.ps_segsum_inv(
                inv.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                inv.size, self._dim,
                gr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                sums.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
            g = jnp.asarray(sums)
        else:
            # ISSUE 13: device-resident grads merge through the Pallas
            # tier's ``segment_sum`` kernel (registry-dispatched:
            # xla_ref == the old jax.ops.segment_sum on CPU, the fused
            # one-pass kernel on TPU — the device mirror of
            # ps_core.cc's fused push)
            from ...ops.pallas import registry as _kreg
            g = _kreg.dispatch("segment_sum",
                               jnp.asarray(grads, jnp.float32),
                               jnp.asarray(inverse),
                               num_segments=nseg)
        sl = jnp.asarray(self._pad_slots(np.asarray(slots, np.int64)))
        if self._opt == "adagrad":
            self._acc = self._acc.at[sl].add(g * g)
            step = g / (jnp.sqrt(self._acc[sl]) + self._eps)
        else:
            step = g
        self._buf = self._buf.at[sl].add(-self._lr * step)
        self._dirty[slots] = True

    def _unpin(self, uniq: np.ndarray):
        key = uniq.tobytes()
        ent = self._pins.get(key)
        if ent is not None:
            ent[1] -= 1
            if ent[1] <= 0:
                del self._pins[key]

    def release(self, ids: np.ndarray):
        """Release the pin of a ``pull(..., pin=True)`` whose push will
        never come (frozen table, failed step) — eviction may then
        reclaim the slots."""
        ids = np.ascontiguousarray(np.asarray(ids, np.int64)).reshape(-1)
        with self._lock:
            # the released batch's plan must go too: a later push of the
            # same raw ids after an eviction would otherwise reuse it
            self._plans.pop(ids.tobytes(), None)
            self._plans.pop(np.unique(ids).tobytes(), None)
            if self._ndir is not None:
                self._ndir.unpin_ids(ids)
            else:
                self._unpin(np.unique(ids))

    def flush(self):
        """Write every dirty row back to the host table (the reference's
        PSGPUWrapper::EndPass)."""
        with self._lock:
            dirty = np.flatnonzero(self._dirty).astype(np.int64)
            if self._ndir is not None:
                self._write_back_rows(dirty, self._ndir.ids_of(dirty))
            else:
                self._write_back(dirty)

    end_pass = flush

    def prime(self, max_ids: Optional[int] = None):
        """Pre-compile the bucketed device programs (install scatter,
        adagrad clear, push segment-sum + apply) for every power-of-2
        bucket up to ``max_ids`` (default: capacity), aimed at the
        scratch row so no real state changes.

        Variable miss/unique counts walk through a handful of bucket
        shapes; each first sight costs an XLA compile (seconds each —
        once ~90% of a 20-step wide&deep window).
        Priming moves those compiles out of the serving path, the moral
        equivalent of the reference's BuildGPUTask warm build phase."""
        import jax
        import jax.numpy as jnp
        raw = int(max_ids or self._cap)
        b = 1
        buckets = [1]
        while b < raw:
            b <<= 1
            buckets.append(b)
        raw_data = jnp.zeros((raw, self._dim), jnp.float32)
        # dtypes must derive exactly like the serving paths (np.int64
        # through jnp.asarray — canonicalized identically with or
        # without x64), or the primed executables miss the cache
        raw_seg = jnp.asarray(np.zeros(raw, np.int64))
        with self._lock:
            # the pull-side [raw] gather
            _ = self._buf[jnp.asarray(np.full(raw, self._cap, np.int64))]
            for n in buckets:
                sp = jnp.asarray(np.full(n, self._cap, np.int64))
                zeros = jnp.zeros((n, self._dim), jnp.float32)
                # install scatter (+ adagrad clear)
                self._buf = self._buf.at[sp].set(zeros)
                if self._acc is not None:
                    self._acc = self._acc.at[sp].set(0.0)
                    self._acc = self._acc.at[sp].add(zeros * zeros)
                # write-back gather (eviction/flush path)
                _ = self._buf[sp]
                # push: [raw, dim] grads segment-summed to n buckets,
                # then the bucketed optimizer apply — the exact shapes
                # _push_rows compiles
                g = jax.ops.segment_sum(raw_data, raw_seg,
                                        num_segments=n)
                if self._acc is not None:
                    step = g / (jnp.sqrt(self._acc[sp]) + self._eps)
                else:
                    step = g
                self._buf = self._buf.at[sp].add(-self._lr * step)
            jax.block_until_ready(self._buf)

    def has(self, id_) -> bool:
        """Residency probe (directory-backend-agnostic)."""
        with self._lock:
            if self._ndir is not None:
                return self._ndir.lookup(
                    np.asarray([int(id_)], np.int64), unpin=False) \
                    is not None
            return int(id_) in self._slot_of

    @property
    def load(self) -> float:
        if self._ndir is not None:
            return self._ndir.load() / self._cap
        return 1.0 - len(self._free) / self._cap


class HeterTrainer:
    def __init__(self, tables: Dict[str, SparseTable],
                 dense_step: Callable,
                 sync_mode: bool = False, pull_threads: int = 2,
                 push_lag: int = 0):
        """``dense_step(embeddings: dict[str, np.ndarray], batch) ->
        (result, grads: dict[str, np.ndarray])`` — typically a jitted
        closure over the dense params; grads are d(loss)/d(rows), one row
        per pulled id (duplicate ids get summed by SparseTable.push).

        ``push_lag`` (async mode): how many push futures may remain in
        flight when the NEXT batch's pull is submitted.  0 (default)
        is the lockstep schedule (guaranteed one-batch staleness,
        capacity covers 2 batches); 1 lets push(i) overlap both
        compute(i) and pull(i+1) — device ordering stays exact
        regardless (every cache op consumes the previous device
        buffer), the lag widens the HOST-table staleness window for
        miss rows to ``1 + push_lag`` batches and the pinned working
        set to ``2 + push_lag`` batches, the reference
        async-communicator trade (framework/trainer.h:233 heter
        pipelines)."""
        self._tables = tables
        self._dense_step = dense_step
        self._sync = sync_mode
        self._push_lag = max(0, int(push_lag))
        self._pool = ThreadPoolExecutor(max_workers=pull_threads,
                                        thread_name_prefix="heter_ps")
        self._pending_push = []

    # -- lanes ---------------------------------------------------------
    def _pull(self, ids_map: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        out = {}
        for name, ids in ids_map.items():
            t = self._tables[name]
            ids = np.ascontiguousarray(np.asarray(ids), np.int64)
            # async mode: pin cached rows until this batch's push lands,
            # so pull(i+1)'s eviction can't claim batch i's slots
            if not self._sync and isinstance(t, DeviceCachedTable):
                out[name] = t.pull(ids, pin=True)
            else:
                out[name] = t.pull(ids)
        return out

    def _push(self, ids_map, grads: Dict[str, np.ndarray]):
        for name, g in grads.items():
            t = self._tables[name]
            if not (isinstance(t, DeviceCachedTable)
                    and hasattr(g, "devices")):
                # host table: grads land in numpy.  Device-resident
                # grads feeding a device-resident cache stay on device
                # (an np.asarray would round-trip the whole grad block
                # host<->device every step).
                g = np.asarray(g)
            t.push(np.ascontiguousarray(
                np.asarray(ids_map[name]), np.int64), g)
        for name in ids_map.keys() - grads.keys():
            # pulled but no grad (frozen/eval-only table): the pin from
            # the async pull must still come off or it leaks forever
            self._release(name, ids_map[name])

    def _release(self, name, ids):
        t = self._tables[name]
        if isinstance(t, DeviceCachedTable):
            t.release(np.ascontiguousarray(np.asarray(ids), np.int64))

    def _release_all(self, ids_map):
        if not self._sync:
            for name, ids in ids_map.items():
                self._release(name, ids)

    def _drain_pushes(self, keep: int = 0):
        while len(self._pending_push) > keep:
            self._pending_push.pop(0).result()

    # -- run loop ------------------------------------------------------
    def run(self, batches: Iterable, ids_fn: Callable,
            on_result: Optional[Callable] = None) -> int:
        """Train over ``batches``. ``ids_fn(batch) -> {table: int64 ids}``
        names which rows each batch needs. Returns the step count.

        Pipeline: pull(i+1) on host threads overlaps the TPU dense step
        on batch i; pushes are fire-and-forget futures drained with one
        batch of lag (async mode) or inline (sync mode).

        Async mode over a :class:`DeviceCachedTable` pins batch i's rows
        until its push lands, so the cache capacity must cover
        ``2 + push_lag`` consecutive batches' unique rows; a tighter
        cache raises the thrashing error instead of silently corrupting
        in-flight rows.
        """
        it = iter(batches)
        try:
            batch = next(it)
        except StopIteration:
            return 0
        ids = ids_fn(batch)
        pull_f = self._pool.submit(self._pull, ids)
        steps = 0
        while True:
            try:
                nxt = next(it)
            except StopIteration:
                nxt = None
            nxt_ids = ids_fn(nxt) if nxt is not None else None
            emb = pull_f.result()
            if nxt is not None:  # prefetch lane for the NEXT batch
                # bounded push queue: at most push_lag pushes stay in
                # flight when pull(i+1) is submitted.  Device-value
                # ordering is exact either way (each cache op consumes
                # the previous device buffer under the table lock); the
                # bound caps host-table miss-row staleness at
                # 1 + push_lag batches and pinned batches at
                # 2 + push_lag (the thrash guard raises if capacity
                # cannot hold them)
                self._drain_pushes(keep=self._push_lag)
                pull_f = self._pool.submit(self._pull, nxt_ids)
            try:
                result, grads = self._dense_step(emb, batch)  # TPU lane
            except BaseException:
                self._release_all(ids)   # a retry must not inherit pins
                raise
            if self._sync:
                self._push(ids, grads)
            else:
                self._pending_push.append(
                    self._pool.submit(self._push, ids, grads))
            if on_result is not None:
                on_result(steps, result)
            steps += 1
            if nxt is None:
                break
            batch, ids = nxt, nxt_ids
        self._drain_pushes(keep=0)
        return steps

    def shutdown(self):
        self._drain_pushes(keep=0)
        self._pool.shutdown(wait=True)
