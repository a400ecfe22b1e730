"""Streaming trainer: the unbounded-event-feed half of the online loop.

The trainer consumes an ENDLESS stream of events through the iterable
:class:`~paddle_tpu.io.DataLoader` path (PR 9's cursor machinery is the
resume story) and pushes sparse gradient updates to the PS primary
while read replicas serve the same tables to query traffic.

Exactly-once across kill/resume, with NO coordination:

- the DataLoader cursor counts batches YIELDED; the trainer checkpoints
  it (atomically, write-then-rename) every ``ckpt_every`` batches, so a
  restarted trainer resumes the stream element-exact — no event skipped,
  none double-seen by the TRAINER;
- the push idempotency stamp is a PURE FUNCTION of the cursor:
  ``seq == global batch index`` under a fixed ``src``
  (:meth:`PSClient.push_stamped`).  A batch replayed after a crash
  (pushed before the kill, behind the checkpoint cursor) re-sends the
  SAME ``(src, seq)`` and the server acks it as a duplicate without
  re-applying — so no event is double-APPLIED either, which is the half
  the cursor alone cannot give.  The server's dedup window (4096 seqs)
  bounds how far behind the cursor checkpoint may lag: keep
  ``ckpt_every`` well under it.

Freshness: every event batch carries its ingest timestamp (stamped by
the source, or at dequeue when the source does not); the push stamps it
through as the mutation's ``iwm`` watermark, replicas applying the
record observe event-ingested -> servable-at-THIS-replica latency into
the ``ps_freshness_ms`` histogram — the SLO (``tests/test_online_loop.py``
holds it) reads its percentiles from that real data path, not a synthetic
probe.

Client-side pre-merge: duplicate ids inside a batch merge BEFORE the
RPC (sum of duplicates' grads — the table would do the same, this just
ships fewer rows).  The merge dispatches through the Pallas tier's
segment-sum (``merge_segments``): the sequential one-VMEM-pass kernel
for recsys-scale unique counts, the sorted-segment kernel at
vocab-scale (ISSUE 14 satellite) — or plain numpy when the batch is
too small to be worth a device dispatch.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Optional

import numpy as np

from ..framework import monitor as _monitor
from ..observability import flight_recorder as _flight

__all__ = ["StreamingTrainer"]

# below this many rows a device dispatch costs more than the merge
_DEVICE_MERGE_MIN_ROWS = 4096


class StreamingTrainer:
    """Consume an unbounded event feed and push sparse updates.

    ``loader``: an iterable-dataset :class:`~paddle_tpu.io.DataLoader`
    over the event stream.  Each batch is passed to ``step_fn``.

    ``step_fn(batch, pull) -> (ids, grads)``: the training step — it
    may call ``pull(ids)`` to fetch current rows from the primary and
    must return the sparse ids and their gradients.  (The dense side
    of a real model trains on-device as usual; this class owns only
    the sparse PS loop.)

    ``client``: a sync-mode :class:`PSClient` at the primary group.
    ``table``: the sparse table name.

    ``ingest_ts_fn(batch) -> float | None``: extract the batch's event
    ingest timestamp (defaults to ``batch["ingest_ts"]`` max when the
    batch is a dict carrying one; falls back to dequeue time).

    ``src``: the STABLE idempotency source id — two incarnations of
    the same logical trainer must share it, or replayed batches
    double-apply.  Defaults to ``stream-<table>``.

    ``state_path``: where the cursor checkpoint lives; None disables
    checkpointing (a restart then replays from the stream head).

    ``dense_step(batch)`` (ISSUE 17): the DENSE half of the model,
    trained through the same compiled engine the elastic data plane
    runs (a bound ``DistributedTrainStep.step`` — or any closure over
    the fused ``opt_apply`` path).  Called once per consumed batch,
    after the sparse push.  Semantics are AT-LEAST-ONCE across a
    kill/resume: dense updates carry no idempotency stamp, so the few
    batches between the last cursor checkpoint and the crash re-apply
    on replay — for SGD-family dense updates that is a bounded,
    decaying perturbation, and the sparse side's exactly-once is
    untouched.  Callers needing exact dense replay should checkpoint
    dense state together with the cursor (``ckpt_every``-aligned).
    """

    def __init__(self, loader, client, table: str,
                 step_fn: Callable,
                 src: Optional[str] = None,
                 state_path: Optional[str] = None,
                 ckpt_every: int = 64,
                 ingest_ts_fn: Optional[Callable] = None,
                 merge_duplicates: bool = True,
                 device_merge: bool = False,
                 dense_step: Optional[Callable] = None):
        self._loader = loader
        self._client = client
        self._table = str(table)
        self._step_fn = step_fn
        self.src = src or f"stream-{table}"
        self._state_path = state_path
        self._ckpt_every = max(int(ckpt_every), 1)
        self._ingest_ts_fn = ingest_ts_fn
        self._merge = bool(merge_duplicates)
        self._device_merge = bool(device_merge)
        self._dense_step = dense_step
        self.dense_steps = 0     # dense-engine steps this process
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        # global batch index == the push idempotency seq (+1: server
        # seqs start at 1) — restored from the cursor checkpoint
        self.events = 0          # events (rows) consumed this process
        self.batches = 0         # batches pushed this process
        self.seq = 0             # global batch cursor (all incarnations)
        self.dup_acks = 0        # replayed batches acked as duplicates
        if state_path is not None and os.path.exists(state_path):
            self._restore(state_path)

    # -- cursor checkpoint ----------------------------------------------
    def _restore(self, path: str):
        with open(path) as f:
            st = json.load(f)
        self._loader.load_state_dict(st["loader"])
        self.seq = int(st["seq"])

    def _checkpoint(self):
        if self._state_path is None:
            return
        st = {"loader": self._loader.state_dict(),
              "seq": int(self.seq), "src": self.src}
        tmp = f"{self._state_path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            f.write(json.dumps(st))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._state_path)

    # -- the loop ---------------------------------------------------------
    def run(self, max_batches: Optional[int] = None):
        """Consume the stream (forever, or ``max_batches`` for tests /
        bounded drains).  Re-raises the first error."""
        pull = lambda ids: self._client.pull(self._table, ids)  # noqa: E731
        for batch in self._loader:
            if self._stop_evt.is_set():
                return
            t0 = time.perf_counter()
            iwm = self._ingest_ts(batch)
            ids, grads = self._step_fn(batch, pull)
            ids = np.ascontiguousarray(
                np.asarray(ids).reshape(-1), np.int64)
            grads = np.ascontiguousarray(
                np.asarray(grads, np.float32).reshape(ids.size, -1))
            n_events = int(ids.size)
            if self._merge and ids.size:
                ids, grads = self._merge_batch(ids, grads)
            self.seq += 1
            applied = self._client.push_stamped(
                self._table, ids, grads, seq=self.seq, src=self.src,
                wm=iwm)
            if not applied:
                # a replayed batch (cursor behind the last pre-crash
                # push): the server saw this (src, seq) and acked
                # without re-applying — exactly-once held
                self.dup_acks += 1
                _monitor.stat_add("online_replayed_batches")
            if self._dense_step is not None:
                # dense half through the shared compiled engine
                # (at-least-once on replay — see class docstring)
                self._dense_step(batch)
                self.dense_steps += 1
                _monitor.stat_add("online_dense_steps")
            self.batches += 1
            self.events += n_events
            _monitor.stat_add("online_events", n_events)
            _monitor.stat_add("online_batches")
            if _monitor.metrics_enabled():
                _monitor.hist_observe(
                    "online_step_ms",
                    (time.perf_counter() - t0) * 1e3)
                if iwm is not None:
                    _monitor.hist_observe(
                        "online_ingest_to_push_ms",
                        max((time.time() - iwm) * 1e3, 0.0))
            # stall-watchdog progress: a wedged feed or a wedged push
            # shows up as this kind going silent
            _flight.record("online.ingest", seq=int(self.seq),
                           n=int(ids.size), dup=not applied,
                           iwm=iwm)
            if self.seq % self._ckpt_every == 0:
                self._checkpoint()
            if max_batches is not None and self.batches >= max_batches:
                self._checkpoint()
                return
        # a finite feed ran dry (tests): persist the final cursor
        self._checkpoint()

    def _ingest_ts(self, batch) -> Optional[float]:
        if self._ingest_ts_fn is not None:
            v = self._ingest_ts_fn(batch)
            return None if v is None else float(v)
        if isinstance(batch, dict) and "ingest_ts" in batch:
            a = np.asarray(batch["ingest_ts"])
            if a.dtype == np.float32 and float(np.max(np.abs(a))) > 2**24:
                # an f32 epoch-second stamp has lost sub-second
                # precision (the DataLoader's device transfer narrows
                # float64 arrays — carry the stamp as a python float to
                # keep it f64): fall back to dequeue-time stamping
                # rather than report ±128 s garbage latencies
                return time.time()
            return float(np.max(a))
        return time.time()

    def _merge_batch(self, ids, grads):
        """Sum duplicate ids' grads client-side (the table's own merge
        semantics — push applies the optimizer once per unique id
        either way; this just ships fewer rows).  Large batches merge
        on device through the Pallas segment-sum tier, picking the
        sorted-segment kernel at vocab-scale unique counts."""
        uniq, inverse = np.unique(ids, return_inverse=True)
        if uniq.size == ids.size:
            return ids, grads
        if self._device_merge and ids.size >= _DEVICE_MERGE_MIN_ROWS:
            from ..ops.pallas.segment_sum import merge_segments
            sums = np.asarray(merge_segments(grads, inverse,
                                             int(uniq.size)),
                              np.float32)
        else:
            sums = np.zeros((uniq.size, grads.shape[1]), np.float32)
            np.add.at(sums, inverse, grads)
        return uniq, np.ascontiguousarray(sums)

    # -- background lifecycle ------------------------------------------
    def start(self, max_batches: Optional[int] = None
              ) -> "StreamingTrainer":
        def _run():
            try:
                self.run(max_batches=max_batches)
            except BaseException as e:   # surfaced by stop()/join()
                self._error = e
        self._thread = threading.Thread(target=_run,
                                        name="online-trainer",
                                        daemon=True)
        self._thread.start()
        return self

    def join(self, timeout: Optional[float] = None):
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                raise TimeoutError("streaming trainer did not finish")
        if self._error is not None:
            raise self._error

    def stop(self, timeout: float = 30.0):
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
        if self._error is not None:
            raise self._error
