"""Percentile and rate arithmetic of the end-to-end metrics."""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (q in 0..100) of all ``values``;
    ``None`` for no values.  Infinite values sort last, so a tail over
    requests that never answered is infinite, not hidden."""
    vals = sorted(values)
    if not vals:
        return None
    if len(vals) == 1:
        return float(vals[0])
    pos = (len(vals) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(vals) - 1)
    if math.isinf(vals[hi]) and pos > lo:
        return float(vals[hi])
    if math.isinf(vals[lo]):
        return float(vals[lo])
    return float(vals[lo] + (vals[hi] - vals[lo]) * (pos - lo))


def ttft_samples(requests: Iterable[dict], worst: float) -> List[float]:
    """Seconds from submit to first token, one per request.  A request
    that failed or never produced a token counts as ``worst`` (the
    longest any request could have waited: submit to end of drain)."""
    out = []
    for r in requests:
        ts = r["token_times"]
        if r.get("failed") or not ts:
            out.append(max(worst - r["t_submit"], 0.0))
        else:
            out.append(ts[0] - r["t_submit"])
    return out


def itl_samples(requests: Iterable[dict]) -> List[float]:
    """All gaps between consecutive tokens of all requests, seconds."""
    out = []
    for r in requests:
        ts = r["token_times"]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in_window(requests: Iterable[dict], t0: float,
                     t1: float) -> int:
    """Tokens delivered to clients in [t0, t1), whichever request they
    belong to (one submitted in warm-up still delivers into the
    window)."""
    return sum(1 for r in requests for t in r["token_times"]
               if t0 <= t < t1)


def train_rate(step_done_times: Sequence[float], t_first_dispatch: float,
               tokens_per_step: int, chips: int) -> Optional[float]:
    """Tokens of ALL steps completed, over the time from the first
    step's dispatch to the last step's completion, per chip.  The step
    in flight when the window closed is in ``step_done_times``: it was
    finished and is counted."""
    if not step_done_times:
        return None
    dt = step_done_times[-1] - t_first_dispatch
    if dt <= 0:
        return None
    return len(step_done_times) * tokens_per_step / dt / chips


def longest(values: Sequence[float]):
    """(index, value) of the longest entry; (None, None) if empty."""
    if not values:
        return None, None
    i = max(range(len(values)), key=values.__getitem__)
    return i, float(values[i])
