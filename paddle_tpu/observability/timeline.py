"""Step timeline: attribute each step of a loop to phases.

"Why was step 4812 slow" decomposes into a handful of host-side phases
— waiting on the DataLoader, staging the batch to device, dispatching
the compiled step, fetching the result, and the leftover host work.
:class:`StepTimeline` measures those phases at the loops that own them
(``DistributedTrainStep.__call__``, the hapi fit loop and the serving
scheduler ``GenerationServer._loop``) and emits them four ways:

- **profiler spans**, always: ``step(i)`` is a
  ``jax.profiler.StepTraceAnnotation(name, step_num=i)`` and
  ``phase(p)`` a ``jax.profiler.TraceAnnotation(f"{name}.{p}")``.  They
  land on the calling thread's line of the profiler's ``.xplane.pb``,
  on the clock of the device's "XLA Ops", so an idle gap of the device
  can be put down to the phase the host was in.  With no profiler
  session active the annotation is a no-op;
- **an in-memory ring**, always: every finished phase and step appends
  one :class:`SpanRow` (``time.perf_counter()`` seconds, the small
  counts given to ``phase(p, **args)`` / ``.set(**args)``, and which
  timeline instance wrote it) to ONE bounded ring owned by this module.
  :func:`spans` reads it after the fact, when the loop's owner may be
  gone — what a benchmark's per-layer readers do;
- **JSONL spans** (``step`` root + ``step.<phase>`` children) into the
  trace sink, only with ``PADDLE_TRACE=1`` and only on SAMPLED steps
  (``trace_every=N``, env ``PADDLE_TRACE_EVERY``): serializing JSON on
  every step of an ~8 ms loop would cost a measurable share of it;
- **histograms** (``step_<phase>_ms`` in the StatRegistry) on every
  step while ``PADDLE_METRICS=1``.

With the JSONL sink and the metrics off a phase still costs two clock
reads, one no-op annotation and one ring append: about 1.5 us in a
loop of its own, about 7 us in place in the serving scheduler, whose
thread shares the GIL with the client's (PERF.md section 6).
"""
from __future__ import annotations

from collections import deque
from itertools import count
from threading import get_ident as _get_ident
from time import perf_counter
from typing import Deque, Dict, List, NamedTuple, Optional

from ..framework import monitor as _monitor
from . import trace as _trace

__all__ = ["StepTimeline", "SpanRow", "spans", "RING_MAXLEN"]

RING_MAXLEN = 65536


class SpanRow(NamedTuple):
    """One finished phase (``name`` = ``<timeline>.<phase>``) or step
    (``name`` = ``<timeline>``).  ``t_start`` and ``t_end`` are
    ``time.perf_counter()`` seconds; ``step`` is None for a phase
    outside any step; ``tl`` numbers the :class:`StepTimeline` instance
    that wrote the row, so that two loops of one name (two servers in a
    process, each counting its steps from 0) can be told apart."""
    name: str
    t_start: float
    t_end: float
    step: Optional[int]
    tid: int
    args: dict
    tl: int


_ring: Deque[tuple] = deque(maxlen=RING_MAXLEN)   # tuples in SpanRow's order
_serial = count()      # numbers the StepTimeline instances
_profiler = None       # jax.profiler, imported at the first timeline


def spans(name: str, since: Optional[float] = None,
          until: Optional[float] = None) -> List[SpanRow]:
    """A copy of the rows of the timelines called ``name``, oldest
    first; with ``since`` / ``until`` (``time.perf_counter()`` seconds)
    only the rows that lie wholly inside ``[since, until]``."""
    pre = name + "."
    while True:
        try:
            return [SpanRow._make(r) for r in _ring
                    if (r[0] == name or r[0].startswith(pre))
                    and (since is None or r[1] >= since)
                    and (until is None or r[2] <= until)]
        except RuntimeError:     # appended to while it was read
            continue


class _Phase:
    """Scope of one phase.  ``t0`` / ``t1`` are its clock reads, for a
    caller that wants the same times for a counter of its own."""
    __slots__ = ("_tl", "_name", "_args", "_ann", "_span", "t0", "t1")

    def __init__(self, tl: "StepTimeline", name: str, args: dict, span):
        self._tl = tl
        self._name = name
        self._args = args
        self._ann = _profiler.TraceAnnotation(name)
        self._span = span
        self.t0 = self.t1 = 0.0

    def set(self, **args):
        """Attach small counts known only at the phase's end (to the
        ring's row; the profiler's span carries the name alone)."""
        self._args.update(args)

    def __enter__(self):
        if self._span is not None:
            self._span.__enter__()
        self._ann.__enter__()
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = t1 = perf_counter()
        self._ann.__exit__(*exc)
        tl = self._tl
        _ring.append((self._name, self.t0, t1, tl._step_i, _get_ident(),
                      self._args, tl._serial))
        if self._span is not None:
            self._span.__exit__(*exc)
        if _monitor.metrics_enabled():
            _monitor.hist_observe(
                f"step_{self._name[len(tl.name) + 1:]}_ms",
                (t1 - self.t0) * 1e3)
        return False


class _StepScope:
    __slots__ = ("_tl", "_i", "_ann", "_span", "_t0")

    def __init__(self, tl: "StepTimeline", step_i: int, span):
        self._tl = tl
        self._i = step_i
        self._ann = _profiler.StepTraceAnnotation(tl.name, step_num=step_i)
        self._span = span
        self._t0 = 0.0

    def __enter__(self):
        if self._span is not None:
            self._span.__enter__()
        self._tl._step_i = self._i
        self._ann.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = perf_counter()
        self._ann.__exit__(*exc)
        tl = self._tl
        _ring.append((tl.name, self._t0, t1, self._i, _get_ident(),
                      {}, tl._serial))
        # a phase outside any step (an idle wait) belongs to no step,
        # and does not inherit the last one's JSONL sampling verdict
        tl._step_i = None
        tl._sampled = False
        if self._span is not None:
            self._span.__exit__(*exc)
        return False


class StepTimeline:
    """Per-loop phase attributor; one loop (one thread) per instance.

    ::

        tl = StepTimeline("train")
        with tl.step(i):
            with tl.phase("data_wait"): batch = next(it)
            with tl.phase("dispatch") as ph:
                loss = step(*batch)
                ph.set(rows=len(batch))
        timeline.spans("train")     # the rows, after the fact
    """

    def __init__(self, name: str = "step", every: Optional[int] = None):
        global _profiler
        if _profiler is None:
            # lazily: the package stays importable without jax
            import jax.profiler
            _profiler = jax.profiler
        self.name = name
        self._every = every        # None -> follow PADDLE_TRACE_EVERY
        self._sampled = False      # current step emits JSONL spans?
        self._step_i: Optional[int] = None
        self._serial = next(_serial)
        self._names: Dict[str, str] = {}     # phase -> "<name>.<phase>"

    def _period(self) -> int:
        return self._every if self._every else _trace.trace_every()

    def step(self, step_i: int):
        """Scope for one whole step.  Decides the JSONL sampling verdict
        every phase of this step inherits."""
        step_i = int(step_i)
        self._sampled = (_trace.enabled()
                         and step_i % self._period() == 0)
        span = (_trace.Span(self.name, cat="step", step=step_i)
                if self._sampled else None)
        return _StepScope(self, step_i, span)

    def phase(self, name: str, **args):
        """Scope for one phase of the current step; ``args`` are small
        counts taken at the phase's boundary (more with ``.set()``)."""
        full = self._names.get(name)
        if full is None:
            full = self._names[name] = f"{self.name}.{name}"
        span = (_trace.Span(full, cat="step") if self._sampled else None)
        return _Phase(self, full, args, span)
