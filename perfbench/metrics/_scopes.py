"""What the readers of device time by model block share (not a metric:
no BENCHMARK.json entry names it).

They read the trace summary's ``scopes``, ``{program: {scope: self
seconds}}`` (``perfbench/harness/op_scopes.reduce``: each device op's
time less what the ops nested in it cover, under the innermost name of
the model's scope vocabulary on its ``tf_op``, ops with none under
``"(none)"``).  A summary without the key (a CPU trace, which has no
``tf_op``; a reduction that does not read it) or a program that writes
no scope gives every reader nothing to read: None, never 0.
"""
from __future__ import annotations

from perfbench.harness.op_scopes import UNSCOPED
from perfbench.metrics._util import program_runs, trace


def tables(ctx, needle):
    """The scope tables of the traced programs whose name holds
    ``needle``, those that write some scope."""
    t = trace(ctx)
    return [v for k, v in ((t or {}).get("scopes") or {}).items()
            if needle in k and set(v) - {UNSCOPED}]


def seconds(ctx, needle, names):
    """Self seconds under ``names`` in those programs, or None."""
    tabs = tables(ctx, needle)
    if not tabs:
        return None
    return sum(t.get(n, 0.0) for t in tabs for n in names)


def decode_step_ms(ctx, names):
    """Milliseconds under ``names`` per ``decode_fn`` run in the trace."""
    s, runs = seconds(ctx, "decode_fn", names), program_runs(ctx,
                                                             "decode_fn")
    return s * 1e3 / len(runs) if s is not None and runs else None


def prefill_ms_per_ktok(ctx, names):
    """Milliseconds under ``names`` in the prefill programs per thousand
    prompt tokens prefilled in the trace (``prefill_ms_per_ktok``'s
    denominator)."""
    s, tc = seconds(ctx, "prefill_fn", names), ctx.get("trace_counters")
    if s is None or not tc or not tc["prefill_tokens"]:
        return None
    return s * 1e3 / (tc["prefill_tokens"] / 1e3)
