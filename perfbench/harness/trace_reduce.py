"""The one reduction from a profiler trace to numbers.

``events_from_xplane`` reads an ``.xplane.pb`` with nothing but JAX
into plain tuples; everything else is arithmetic on those tuples, so
it is checked in tier-1 on a small recorded trace and on hand-made
events.  Times are seconds.

A device plane is one whose name starts with ``/device:``; its line
"XLA Ops" holds one event per executed HLO op and "XLA Modules" one per
run of a jitted program.  Host planes hold the Python threads, with
the benchmark's own ``TraceAnnotation`` spans (``bench.*``) on them.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float
    dur: float


OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_RE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|all-to-all|collective-broadcast)", re.I)
_SHAPE_RE = re.compile(r"([a-z]+[0-9]+|pred)\[([0-9,]*)\]")


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    return hits[-1] if hits else None


def events_from_xplane(path: str) -> List[Event]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "host" not in plane.lower()


def device_ops(events: Sequence[Event]) -> List[Event]:
    return [e for e in events if is_device(e.plane) and e.line == OPS_LINE]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by
    the (disjoint, sorted) intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_of(events: Sequence[Event]) -> Tuple[float, float]:
    """First start and last end of any device op: the traced window as
    the device saw it."""
    ops = device_ops(events)
    if not ops:
        return (0.0, 0.0)
    return (min(e.start for e in ops), max(e.start + e.dur for e in ops))


def busy_by_device(events) -> Dict[str, float]:
    per = defaultdict(list)
    for e in device_ops(events):
        per[e.plane].append((e.start, e.start + e.dur))
    return {p: total(union(v)) for p, v in per.items()}


def busy_and_window(events) -> Tuple[float, float]:
    """(seconds an op ran on the device, averaged over the devices
    used; length of the traced window)."""
    busy = busy_by_device(events)
    if not busy:
        return (0.0, 0.0)
    w0, w1 = window_of(events)
    return (sum(busy.values()) / len(busy), w1 - w0)


def op_name(name: str) -> str:
    """The op of a device event.  The profiler names an event by the
    whole HLO instruction (``%fusion.4 = bf16[8192,16,8,128]{..}
    fusion(...)``); the op is what stands before `` = ``, without the
    ``%`` and the instance number: ``fusion``."""
    n = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", n)


def op_key(name: str) -> str:
    """Op plus the type and shape of its (first) result, so that the
    many ``fusion`` ops tell apart: ``fusion_bf16_8192_16_8_128_``."""
    key = op_name(name)
    if " = " in name:
        m = _SHAPE_RE.search(name.split(" = ", 1)[1])
        if m:
            key += "_" + m.group(1) + "_" + "".join(
                d + "_" for d in m.group(2).split(",") if d)
    return key


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE_RE.match(op_name(name)))


def is_mosaic_kernel(name: str) -> bool:
    """A Pallas/Mosaic kernel is the custom call whose target is
    ``tpu_custom_call``.  Only the instruction itself carries the
    attribute: an op that merely reads a kernel's result names it by
    ``%name`` alone."""
    return 'custom_call_target="tpu_custom_call"' in name


def time_per_op(events, top: int = 10) -> List[List]:
    """[[name, seconds], ...] of the device ops that took most time,
    summed over devices and divided by their number."""
    ops = device_ops(events)
    ndev = max(1, len({e.plane for e in ops}))
    acc = defaultdict(float)
    for e in ops:
        acc[op_key(e.name)] += e.dur
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / ndev] for k, v in rows]


def time_per_program(events) -> Dict[str, List[float]]:
    """jitted program name -> durations of its runs on the device
    (first device only: the programs run in lockstep across chips)."""
    mods = [e for e in events if is_device(e.plane)
            and e.line == MODULES_LINE]
    if not mods:
        return {}
    first = sorted({e.plane for e in mods})[0]
    out = defaultdict(list)
    for e in mods:
        if e.plane == first:
            m = re.match(r"(?:jit_)?([A-Za-z0-9_<>]+)", e.name)
            out[m.group(1) if m else e.name].append(e.dur)
    return dict(out)


def kernel_time(events, pred=is_mosaic_kernel) -> float:
    """Device seconds in the ops that ``pred`` picks, per device."""
    ops = device_ops(events)
    ndev = max(1, len({e.plane for e in ops}))
    return sum(e.dur for e in ops if pred(e.name)) / ndev


def exposed_collective_time(events) -> float:
    """Seconds, per device, in which a collective op ran and no other
    op did on that device."""
    per_c, per_x = defaultdict(list), defaultdict(list)
    for e in device_ops(events):
        (per_c if is_collective(e.name) else per_x)[e.plane].append(
            (e.start, e.start + e.dur))
    planes = set(per_c) | set(per_x)
    if not planes:
        return 0.0
    exp = sum(total(subtract(union(per_c[p]), union(per_x[p])))
              for p in planes)
    return exp / len(planes)


def idle_gaps(events, span_prefix: str = "bench.", top: int = 10,
              min_gap: float = 20e-6) -> List[List]:
    """[[what the host was doing, idle seconds], ...]: every gap
    between device ops on the first device is attributed to the
    ``bench.*`` span that covers most of it on the host (joined with
    the jitted call in flight there, if any), else ``no_bench_span``."""
    ops = device_ops(events)
    if not ops:
        return []
    first = sorted({e.plane for e in ops})[0]
    busy = union([(e.start, e.start + e.dur) for e in ops
                  if e.plane == first])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])
            if b[0] - a[1] >= min_gap]
    host = [e for e in events if not is_device(e.plane)]
    spans = sorted((e for e in host if e.name.startswith(span_prefix)),
                   key=lambda e: e.start)
    calls = sorted((e for e in host if e.name.startswith("PjitFunction")),
                   key=lambda e: e.start)

    def cover(cands, s, e):
        best, best_ov = None, 0.0
        for c in cands:
            if c.start >= e:
                break
            ov = min(e, c.start + c.dur) - max(s, c.start)
            if ov > best_ov:
                best, best_ov = c, ov
        return best

    acc = defaultdict(float)
    for s, e in gaps:
        sp, cl = cover(spans, s, e), cover(calls, s, e)
        name = sp.name if sp else "no_bench_span"
        if cl:
            name += "__" + re.sub(r"[^A-Za-z0-9_]", "_", cl.name)
        acc[name] += e - s
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v] for k, v in rows]


def summarize(events) -> dict:
    busy, window = busy_and_window(events)
    return {"busy_s": busy, "window_s": window,
            "device_ops": time_per_op(events),
            "idle_gaps": idle_gaps(events),
            "programs": time_per_program(events),
            "kernel_s": kernel_time(events),
            "mosaic_kernels": sorted({op_key(e.name) for e in
                                      device_ops(events)
                                      if is_mosaic_kernel(e.name)}),
            "custom_calls": sorted({op_key(e.name) + " " + (re.search(
                r'custom_call_target="([^"]*)"', e.name) or [0, "?"])[1]
                for e in device_ops(events)
                if op_name(e.name).startswith("custom")
                or "custom_call_target" in e.name})[:12],
            "exposed_collective_s": exposed_collective_time(events),
            "n_devices": len(busy_by_device(events))}
