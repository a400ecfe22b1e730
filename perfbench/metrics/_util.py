"""Helpers the small readers share (not a metric: no BENCHMARK.json
entry names it)."""
from __future__ import annotations

import statistics


def peaks(ctx):
    """The chip's peaks, or None off the chip (a CPU rehearsal writes
    no device metric)."""
    if ctx["device"]["platform"] != "tpu":
        return None
    from perfbench.harness.peaks import peaks_for
    return peaks_for(ctx["device"]["kind"])


def trace(ctx):
    t = ctx.get("trace")
    return t if t and t.get("busy_s", 0) > 0 else None


def program_runs(ctx, needle):
    t = trace(ctx)
    if not t:
        return []
    return [d for name, runs in t["programs"].items() if needle in name
            for d in runs]


def median_ms(values):
    return statistics.median(values) * 1e3 if values else None


def serve_work(ctx):
    """Tokens and attention contexts of the work served in the window,
    from the client's records: prompts whose first token arrived in the
    window count as prefilled in it; every later token delivered in it
    is one decode."""
    t0, t1 = ctx["t0"], ctx["t1"]
    w = {"prefill_tokens": 0, "prefill_rows": 0, "decode_tokens": 0,
         "prefill_ctx": 0, "decode_ctx": 0}
    for r in ctx["requests"]:
        L = len(r["prompt"])
        for j, t in enumerate(r["token_times"]):
            if not (t0 <= t < t1):
                continue
            if j == 0:
                w["prefill_tokens"] += L
                w["prefill_rows"] += 1
                w["prefill_ctx"] += L * (L + 1) // 2
            else:
                w["decode_tokens"] += 1
                w["decode_ctx"] += L + j
    return w
