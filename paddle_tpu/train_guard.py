"""TrainGuard — in-step numerics health checks, loss-spike skip/rewind,
batch blame, and numeric chaos integration.

PR 3 made the PS *transport* survive crashes and retries; this module is
the numerical counterpart for the training loop itself.  Production LLM
runs treat bf16 loss spikes / NaN gradients as a first-class robustness
problem: PaLM (Chowdhery et al., 2022) restarted from a checkpoint ~100
steps back and skipped the offending data batches; the OPT-175B logbook
records dozens of such manual restarts.  TrainGuard automates that
detect -> skip -> rewind -> blame pipeline on top of pieces the repo
already has (GradScaler inf-skip, CheckpointManager + exact
failure-resume, the PR 3 chaos harness):

1. **Fused health check** (:func:`health_check`): ONE jit-compiled
   reduction over the whole grad tree producing ``[global_norm,
   nonfinite_count, loss]`` as a single 3-element device array.  The
   caller pays exactly one device->host transfer per step for all guard
   state (the old GradScaler.unscale_ paid one ``bool(isfinite.all())``
   round trip *per parameter*).  Every host sync funnels through
   :func:`_host_fetch` so tests can spy the count (the same discipline
   as test_serving's ``num_compiles``).

2. **Policy engine** (:class:`TrainGuard`): skip the step on nonfinite
   grads/loss; detect loss spikes against a rolling median/MAD window;
   after ``max_consecutive_bad`` bad steps rewind to the last-healthy
   (pinned) CheckpointManager step and continue with the NEXT data
   batches — the offending data window is skipped, like PaLM, so the
   post-rewind trajectory intentionally diverges from the fault-free
   one.  When the rewind budget is exhausted a typed
   :class:`NumericalDivergence` is raised.

3. **Batch blame** (:meth:`TrainGuard.blame`): on a skipped step, bisect
   the batch by microbatch halves to identify the poisoned rows;
   counts land in framework.monitor StatRegistry counters
   (``guard_skips`` / ``guard_rewinds`` / ``guard_blamed_rows``).

4. **Numeric chaos**: fleet/chaos.py gains ``nan``/``inf`` fault kinds
   (``PADDLE_CHAOS="nan:grad:step=50"``); :func:`chaos_corrupt` is the
   injection hook the guard (grads) and hapi/tools (batch, activation)
   call, so every guard path is exercised deterministically in tier-1
   (tools/chaos_numerics.py is the driver).
"""
from __future__ import annotations

import collections
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .framework import monitor as _monitor
from .framework.errors import EnforceNotMet
from .framework.monitor import gauge_set, stat_add, stat_get
from .observability import flight_recorder as _flight
from .observability import trace as _obs_trace

__all__ = ["TrainGuard", "HealthState", "NumericalDivergence",
           "health_check", "fused_health", "chaos_corrupt",
           "host_sync_count", "GUARD_STAT_NAMES"]

# StatRegistry counter names the guard reports through (framework.monitor)
GUARD_STAT_NAMES = ("guard_skips", "guard_rewinds", "guard_blamed_rows")


class NumericalDivergence(EnforceNotMet):
    """Raised when the guard's rewind budget is exhausted and the run is
    still numerically diverging — the automatic-recovery analog of the
    reference's FatalError: nothing left to do but page a human."""


# ----------------------------------------------------------------------
# fused in-step health check
# ----------------------------------------------------------------------

def _health_reduce(loss, grads):
    """Pure: grad leaves + loss -> f32[3] = [global_norm, nonfinite_count,
    loss].  Nonfinite entries are masked out of the norm so the norm stays
    informative even on a poisoned step (an all-NaN norm says nothing
    about the healthy remainder)."""
    sq = jnp.zeros((), jnp.float32)
    bad = jnp.zeros((), jnp.float32)
    for g in grads:
        finite = jnp.isfinite(g)
        bad += jnp.sum(~finite).astype(jnp.float32)
        g32 = jnp.where(finite, g, 0).astype(jnp.float32)
        sq += jnp.sum(g32 * g32)
    if loss is None:
        lv = jnp.float32(0)
    else:
        lv = jnp.asarray(loss, jnp.float32).reshape(())
        bad += (~jnp.isfinite(lv)).astype(jnp.float32)
    return jnp.stack([jnp.sqrt(sq), bad, lv])


def _health_reduce_fast(loss, grads):
    """Single-reduction variant for compiled hot paths: ONE pass over
    each grad (sum of squares only).  NaN/Inf propagate into the sum, so
    badness falls out of the result's own finiteness — no isfinite/mask
    passes over the tree.  Trade-off vs the precise reduce: slot [1] is
    a 0/1 indicator (not an element count) and the norm reads nonfinite
    on a bad step; both are exactly what the skip policy needs.  An f32
    square-sum can also overflow to inf on ~1e19 finite grads — a
    magnitude that IS divergence, so flagging it is correct."""
    sq = jnp.zeros((), jnp.float32)
    for g in grads:
        g32 = g.astype(jnp.float32)
        sq += jnp.sum(g32 * g32)
    if loss is None:
        lv = jnp.float32(0)
        bad = (~jnp.isfinite(sq)).astype(jnp.float32)
    else:
        lv = jnp.asarray(loss, jnp.float32).reshape(())
        bad = (~(jnp.isfinite(sq) & jnp.isfinite(lv))).astype(jnp.float32)
    return jnp.stack([jnp.sqrt(sq), bad, lv])


_fused = jax.jit(_health_reduce, static_argnames=())

# every guard device->host transfer funnels through _host_fetch so the
# count is spy-able; architecture rule: NOTHING else in this module may
# call np.asarray/float/bool on a device value
_host_syncs = 0


def host_sync_count() -> int:
    return _host_syncs


def _host_fetch(dev_arr) -> np.ndarray:
    global _host_syncs
    _host_syncs += 1
    if _obs_trace.enabled() or _monitor.metrics_enabled():
        # the funnel doubles as the step timeline's "health fetch"
        # phase: this transfer is the guard's only device sync, so its
        # duration IS the time the host stalls on guard state
        import time as _time
        with _obs_trace.span("step.health_fetch", cat="step"):
            t0 = _time.perf_counter()
            out = np.asarray(dev_arr)
        if _monitor.metrics_enabled():
            _monitor.hist_observe("step_health_fetch_ms",
                                  (_time.perf_counter() - t0) * 1e3)
        return out
    return np.asarray(dev_arr)


def _grad_leaves(source) -> List:
    """Raw grad arrays from an Optimizer, a list of Tensors/arrays, or a
    parameter list.  SelectedRows contribute their (unmerged) value
    blocks — duplicates inflate the norm slightly but finiteness, the
    guard's signal, is exact."""
    from .framework.core import Tensor
    from .framework.selected_rows import SelectedRows
    if hasattr(source, "grad_leaves"):       # an Optimizer
        return list(source.grad_leaves())
    if hasattr(source, "_parameter_list"):
        source = [p.grad for p in source._parameter_list
                  if p.grad is not None]
    leaves = []
    for g in source:
        if g is None:
            continue
        if isinstance(g, SelectedRows):
            leaves.append(g.values)
        elif isinstance(g, Tensor):
            leaves.append(g._value)
        else:
            leaves.append(jnp.asarray(g))
    return leaves


class HealthState:
    """One step's health: wraps the 3-element device array; ``.fetch()``
    is the single host transfer (cached)."""

    __slots__ = ("device", "_host")

    def __init__(self, device_arr):
        self.device = device_arr
        self._host = None

    def fetch(self) -> np.ndarray:
        if self._host is None:
            self._host = _host_fetch(self.device)
        return self._host

    @property
    def global_norm(self) -> float:
        return float(self.fetch()[0])

    @property
    def nonfinite_count(self) -> int:
        # inf-marked loss contributes; count is clamped sane for display
        v = self.fetch()[1]
        return int(v) if np.isfinite(v) else 1

    @property
    def loss(self) -> float:
        return float(self.fetch()[2])

    @property
    def ok(self) -> bool:
        h = self.fetch()
        return bool(h[1] == 0 and np.isfinite(h[2]))


def fused_health(grads: Sequence, loss=None, precise: bool = True):
    """In-jit building block: returns the f32[3] health array WITHOUT any
    host transfer — compose it into a jitted train step and hand the
    result to :meth:`TrainGuard.check` (DistributedTrainStep
    guard_health does this: tests/test_train_guard.py).  ``precise=False``
    selects the single-pass reduction (indicator instead of element
    count, unmasked norm) — the right choice inside a hot step."""
    reduce = _health_reduce if precise else _health_reduce_fast
    return reduce(loss, list(grads))


def health_check(grads, loss=None) -> HealthState:
    """Run the fused health reduction over ``grads`` (an Optimizer, or a
    list of Tensors / SelectedRows / arrays).  No host sync happens until
    the returned state's ``.fetch()``/properties are read — and then
    exactly one."""
    leaves = _grad_leaves(grads)
    lv = getattr(loss, "_value", loss)
    if not leaves:
        dev = _fused(jnp.float32(0) if lv is None else lv, [jnp.zeros((1,))])
    else:
        dev = _fused(lv, leaves)
    return HealthState(dev)


# ----------------------------------------------------------------------
# numeric chaos injection hook
# ----------------------------------------------------------------------

def chaos_corrupt(op: str, arrays):
    """If a chaos plan with a matching numeric fault (kinds ``nan`` /
    ``inf``, op ``grad`` / ``batch`` / ``activation`` / ``loss``) is
    active and scheduled to fire NOW, corrupt ``arrays`` (list of
    numpy/jax arrays or a single array) and return (arrays, fired).

    Corruption is deterministic: the first ``max(1, int(arg))`` rows (or
    flat elements, for 0/1-d arrays) of the FIRST float array are set to
    the fault value — so batch blame can assert exactly which rows were
    poisoned."""
    from .distributed.fleet import chaos as _chaos
    plan = _chaos.active()
    if plan is None:
        return arrays, False
    single = not isinstance(arrays, (list, tuple))
    arrs = [arrays] if single else list(arrays)
    fault = plan.match_numeric(op)
    if fault is None:
        return arrays, False
    val = np.nan if fault.kind == "nan" else np.inf
    n = max(1, int(fault.arg))
    out = []
    done = False
    for a in arrs:
        is_float = "float" in str(getattr(a, "dtype", ""))
        if done or not is_float:
            out.append(a)
            continue
        if isinstance(a, np.ndarray):
            b = a.copy()
            if b.ndim >= 2:
                b[:n] = val
            else:
                b.reshape(-1)[:min(n, b.size)] = val
            out.append(b)
        else:
            b = jnp.asarray(a)
            if b.ndim >= 2:
                b = b.at[:n].set(val)
            else:
                flat = b.reshape(-1).at[:min(n, b.size)].set(val)
                b = flat.reshape(b.shape)
            out.append(b)
        done = True
    plan.stats[f"{fault.kind}:{op}"] += 1
    return (out[0] if single else out), True


def _corrupt_optimizer_grads(optimizer) -> bool:
    """Apply a scheduled ``nan:grad``/``inf:grad`` fault to the REAL
    p.grad tensors (not a copy), so the guard is exercised against the
    state the optimizer would actually consume."""
    from .framework.core import Tensor
    # dense grads only: SelectedRows stay clean (their corruption story
    # is the PS-side chaos of PR 3)
    params = [p for p in optimizer._parameter_list
              if isinstance(p.grad, Tensor)]
    if not params:
        return False
    vals = [p.grad._value for p in params]
    new, fired = chaos_corrupt("grad", vals)
    if fired:
        for p, v in zip(params, new):
            p.grad = Tensor(v)
    return fired


# ----------------------------------------------------------------------
# policy engine
# ----------------------------------------------------------------------

class TrainGuard:
    """Automatic detection -> skip -> rewind -> blame for a training loop.

    ::

        guard = TrainGuard(optimizer=opt, manager=ckpt_mgr,
                           state_fn=lambda: {...}, restore_fn=restore)
        for step, batch in enumerate(loader):
            loss = loss_fn(batch); loss.backward()
            verdict = guard.step(loss, step=step,
                                 blame_fn=lambda rows: ...)
            # verdict: "ok" (stepped), "skip" (grads dropped),
            #          "rewind" (state restored to last healthy ckpt)

    * ``state_fn()`` -> nested state dict (model/opt/sched/rng) saved via
      ``manager`` every ``checkpoint_every`` healthy steps; the newest
      healthy step is PINNED in the manager so ``max_to_keep`` rotation
      can never delete the rewind target.
    * ``restore_fn(state)`` must restore EXACTLY what a fresh-process
      resume would (test_failure_resume proves that contract) — the
      in-process rewind then equals kill+resume, minus the data batches
      of the bad window, which are intentionally skipped (PaLM-style).
    * Detection: nonfinite grads/loss always skip; a finite loss further
      than ``spike_factor`` * MAD from the rolling median (after
      ``min_history`` healthy steps) is a spike.  ``max_consecutive_bad``
      bad steps escalate skip -> rewind; ``rewind_budget`` rewinds
      escalate to :class:`NumericalDivergence`.
    """

    def __init__(self, optimizer=None, manager=None, state_fn=None,
                 restore_fn=None, scaler=None, window: int = 32,
                 min_history: int = 8, spike_factor: float = 10.0,
                 mad_floor: float = 1e-3, max_consecutive_bad: int = 3,
                 rewind_budget: int = 2, checkpoint_every: int = 1,
                 blame_fn: Optional[Callable] = None):
        self.optimizer = optimizer
        self.manager = manager
        self.state_fn = state_fn
        self.restore_fn = restore_fn
        self.scaler = scaler
        # default blame hook: hapi's fit loop passes its own row-slicing
        # blame_fn per batch UNLESS this explicit override is set (the
        # PR 4 caller-provided contract, kept)
        self.blame_fn = blame_fn
        self.window = int(window)
        self.min_history = int(min_history)
        self.spike_factor = float(spike_factor)
        self.mad_floor = float(mad_floor)
        self.max_consecutive_bad = int(max_consecutive_bad)
        self.rewind_budget = int(rewind_budget)
        self.checkpoint_every = int(checkpoint_every)

        self._history: collections.deque = collections.deque(
            maxlen=self.window)
        self._bad_streak = 0
        self._healthy_since_ckpt = 0
        self.last_healthy_step: Optional[int] = None
        self.skips = 0
        self.rewinds = 0
        # gauges mirror THIS guard's live counts (hapi/ProgBar read
        # them); a fresh guard zeroes the previous run's values
        for k in GUARD_STAT_NAMES:
            gauge_set(k, 0)
        self.blamed_rows: List = []          # (step, [row indices])
        self.events: List[Dict] = []         # audit log of skip/rewind
        self.last_health: Optional[HealthState] = None

    # -- detection -----------------------------------------------------
    def _spike(self, loss_val: float) -> bool:
        if len(self._history) < self.min_history:
            return False
        med = float(np.median(self._history))
        mad = float(np.median(np.abs(np.asarray(self._history) - med)))
        dev = max(mad, self.mad_floor)
        # only upward excursions are divergence; a sudden *drop* is luck
        return loss_val - med > self.spike_factor * dev

    def check(self, health, step: Optional[int] = None) -> str:
        """Classify one step's health (no optimizer/manager actions —
        :meth:`step` drives those).  ``health``: a HealthState, or the
        raw f32[3] array a jitted step computed via :func:`fused_health`.
        Returns "ok" | "skip" | "rewind"."""
        if not isinstance(health, HealthState):
            health = HealthState(health)
        self.last_health = health
        h = health.fetch()               # the step's ONE host transfer
        nonfinite = h[1] != 0 or not np.isfinite(h[2])
        reason = None
        if nonfinite:
            reason = "nonfinite"
        elif self._spike(float(h[2])):
            reason = "loss_spike"
        if reason is None:
            self._history.append(float(h[2]))
            self._bad_streak = 0
            self._flight_health(step, h, "ok", None)
            return "ok"
        self._bad_streak += 1
        self.events.append({"step": step, "reason": reason,
                            "loss": float(h[2]),
                            "nonfinite": int(h[1]) if np.isfinite(h[1])
                            else -1, "streak": self._bad_streak})
        if (self._bad_streak >= self.max_consecutive_bad
                and self._can_rewind()):
            self._flight_health(step, h, "rewind", reason)
            return "rewind"
        self._flight_health(step, h, "skip", reason)
        return "skip"

    @staticmethod
    def _flight_health(step, h, verdict, reason):
        """Flight-recorder copy of the step's health vector + verdict —
        the per-step history a postmortem bundle replays (a diverging
        run's last N health vectors including the fatal one)."""
        if not _flight.enabled():
            return
        ev = {"step": step, "norm": float(h[0]),
              "nonfinite": float(h[1]), "loss": float(h[2]),
              "verdict": verdict}
        if reason is not None:
            ev["reason"] = reason
        _flight.record("health", **ev)

    def _can_rewind(self) -> bool:
        return (self.manager is not None and self.restore_fn is not None
                and self.last_healthy_step is not None)

    # -- actions -------------------------------------------------------
    def mark_healthy(self, step: int):
        """Record a healthy step; checkpoint + pin every
        ``checkpoint_every`` healthy steps (pinning keeps the rewind
        target alive through max_to_keep rotation)."""
        if self.manager is None or self.state_fn is None:
            self.last_healthy_step = step
            return
        self._healthy_since_ckpt += 1
        if (self.last_healthy_step is None
                or self._healthy_since_ckpt >= self.checkpoint_every):
            self.manager.save(step, self.state_fn())
            prev = self.last_healthy_step
            self.manager.pin(step)
            if prev is not None:
                self.manager.unpin(prev)
            self.last_healthy_step = step
            self._healthy_since_ckpt = 0

    def rewind(self, at_step: Optional[int] = None) -> int:
        """Restore the last-healthy checkpoint (raises
        NumericalDivergence once the budget is spent).  Returns the
        checkpoint step rewound to.  The data batches between that step
        and ``at_step`` are NOT replayed — the caller just continues
        with its next batch (the PaLM skip-data semantics)."""
        if not self._can_rewind():
            _flight.record("divergence", step=at_step,
                           detail="no rewind target")
            _flight.maybe_dump("NumericalDivergence")
            raise NumericalDivergence(
                "TrainGuard cannot rewind: no CheckpointManager/"
                "restore_fn/healthy checkpoint available")
        if self.rewinds >= self.rewind_budget:
            # the fatal path: the bundle written here carries the whole
            # skip/rewind history plus the last health vectors
            _flight.record("divergence", step=at_step,
                           rewinds=self.rewinds,
                           budget=self.rewind_budget)
            _flight.maybe_dump("NumericalDivergence")
            raise NumericalDivergence(
                f"rewind budget exhausted ({self.rewinds}/"
                f"{self.rewind_budget}) and the run is still diverging "
                f"(last events: {self.events[-3:]})")
        target = self.last_healthy_step
        state = self.manager.restore(target)
        self.restore_fn(state)
        self.rewinds += 1
        stat_add("guard_rewinds")
        gauge_set("guard_rewinds", self.rewinds)
        self.events.append({"step": at_step, "reason": "rewind",
                            "to_step": target})
        _flight.record("rewind", step=at_step, to_step=target,
                       rewinds=self.rewinds)
        # the diverged region poisoned the rolling window; restart it
        self._history.clear()
        self._bad_streak = 0
        if self.optimizer is not None:
            self.optimizer.clear_grad()
        return target

    def blame(self, blame_fn: Callable, n_rows: int,
              step: Optional[int] = None) -> List[int]:
        """Bisect the batch by microbatch halves to find poisoned rows.
        ``blame_fn(row_indices: np.ndarray) -> bool`` returns True when
        that sub-batch is HEALTHY (recompute forward/loss/grads on the
        slice and check finiteness).  O(k log n) evaluations for k bad
        rows.  Found rows are quarantined on ``self.blamed_rows`` and
        counted in the ``guard_blamed_rows`` stat."""
        bad: List[int] = []

        def _bisect(idx: np.ndarray):
            if blame_fn(idx):
                return
            if idx.size == 1:
                bad.append(int(idx[0]))
                return
            mid = idx.size // 2
            _bisect(idx[:mid])
            _bisect(idx[mid:])

        _bisect(np.arange(n_rows))
        if bad:
            self.blamed_rows.append((step, sorted(bad)))
            stat_add("guard_blamed_rows", len(bad))
            _flight.record("blame", step=step, rows=sorted(bad))
        gauge_set("guard_blamed_rows",
                  sum(len(r) for _, r in self.blamed_rows))
        return sorted(bad)

    def step(self, loss=None, step: Optional[int] = None,
             optimizer=None, health=None, blame_fn=None,
             n_rows: Optional[int] = None) -> str:
        """Drive one full guarded step: (chaos grad injection) -> fused
        health check -> policy -> act.

        "ok":     optimizer.step() + clear_grad + mark_healthy
        "skip":   grads dropped (clear_grad), GradScaler told (its
                  dynamic-scale backoff still sees the inf), blame run
                  when ``blame_fn``/``n_rows`` given
        "rewind": state restored to the last healthy checkpoint
        """
        opt = optimizer or self.optimizer
        if blame_fn is None:
            blame_fn = self.blame_fn         # explicit ctor override
        if opt is not None:
            _corrupt_optimizer_grads(opt)    # deterministic chaos hook
        if health is None:
            source = opt if opt is not None else []
            health = health_check(source, loss=loss)
        verdict = self.check(health, step=step)
        if verdict == "ok":
            if opt is not None:
                opt.step()
                opt.clear_grad()
            if self.scaler is not None:
                self.scaler._found_inf = False
                self.scaler.update()
                self.scaler._unscaled.discard(id(opt))
            if step is not None:
                self.mark_healthy(step)
            return verdict
        # bad step: never let the poisoned grads reach the weights
        if opt is not None:
            if hasattr(opt, "skip_step"):
                opt.skip_step()
            else:
                opt.clear_grad()
        if self.scaler is not None:
            # dynamic loss scaling backs off exactly as if its own
            # found_inf check had fired
            self.scaler._found_inf = True
            self.scaler.update()
            self.scaler._unscaled.discard(id(opt))
        if verdict == "rewind":
            self.rewind(at_step=step)
            return verdict
        self.skips += 1
        stat_add("guard_skips")
        gauge_set("guard_skips", self.skips)
        if blame_fn is not None and n_rows:
            self.blame(blame_fn, n_rows, step=step)
        return verdict

    # -- reporting -----------------------------------------------------
    def stats(self) -> Dict:
        return {
            "skips": self.skips,
            "rewinds": self.rewinds,
            "blamed_rows": sum(len(r) for _, r in self.blamed_rows),
            "quarantine": list(self.blamed_rows),
            "last_healthy_step": self.last_healthy_step,
            "host_syncs": host_sync_count(),
            "registry": {k: stat_get(k) for k in GUARD_STAT_NAMES},
            "events": list(self.events),
        }
