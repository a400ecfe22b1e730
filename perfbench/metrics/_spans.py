"""What the ``sched_*`` span readers share (not a metric: no
BENCHMARK.json entry names it).

The scheduler loop of ``GenerationServer`` brackets its phases with
``StepTimeline("serve")``; every finished phase is one row
``(name, t_start, t_end, step, tid, args, tl)`` on ``time.perf_counter()``,
the clock of ``ctx["t0"]`` / ``ctx["t1"]``, in a ring that
``paddle_tpu.observability.timeline.spans`` reads after the run.  A
program from before those spans has no such function: every reader
then finds nothing and returns None.
"""
from __future__ import annotations

from collections import defaultdict

from perfbench.harness.stats import percentile

DECODE_STEP_MARK = "serve.decode.dispatch"


def serve_spans(ctx):
    """The ``serve`` timeline's rows that lie inside the window."""
    from paddle_tpu.observability import timeline
    read = getattr(timeline, "spans", None)
    if read is None:
        return []
    return read("serve", since=ctx["t0"], until=ctx["t1"])


def ms(row) -> float:
    return (row.t_end - row.t_start) * 1e3


def decode_phase_p50_ms(ctx, names):
    """Median over the window's decode steps of the milliseconds spent
    in the phases ``names`` (summed within a step).  A decode step is
    a ``serve`` step that dispatched ``decode_fn``; one cut by an edge
    of the window (its own row is then not among the rows) is left
    out.  A step is known by the timeline that wrote it and its number:
    every server of a process counts its own steps from 0."""
    per_step = defaultdict(float)
    whole, decode = set(), set()
    for r in serve_spans(ctx):
        step = (getattr(r, "tl", 0), r.step)
        if r.name == "serve":
            whole.add(step)
        elif r.name == DECODE_STEP_MARK:
            decode.add(step)
        if r.name in names:
            per_step[step] += ms(r)
    return percentile([per_step[s] for s in whole & decode], 50)
