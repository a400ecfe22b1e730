"""Device time by model block and idle time by scheduler phase, from
the ``.xplane.pb`` of a traced run.

``trace_reduce`` reads a trace through ``jax.profiler.ProfileData``,
which gives each event its own stats and not those of its metadata:
the op's ``tf_op`` (its ``op_name`` path, ``jit(decode_fn)/moe/...``)
lives in the metadata.  This module reads the few fields it needs off
the protobuf wire itself (the message layout of ``XSpace`` below, built
at run time; protobuf is there with JAX, TensorFlow need not be) into
the same plain tuples, each with its ``tf_op``, so that everything else
is arithmetic on tuples, checked in tier-1 on the recorded fixture and
on hand-made events.  Times are seconds.

A served model names its blocks with one vocabulary of
``jax.named_scope`` names (:data:`SCOPES`; the program keeps the same
tuple in ``paddle_tpu.observability.scopes``); an op's block is the
innermost of them on its path.  Ops nest on a line (a ``while`` holds
the ops of its body), so each op is given its SELF time, its duration
less what ops nested in it cover: summed, the self times of a line are
the union of its ops, and no instant counts twice.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from .trace_reduce import (MODULES_LINE, OPS_LINE, is_device, op_key,
                           union)

SCOPES = ("attn", "mixer", "moe", "ffn", "embed", "head", "sample", "mtp")
UNSCOPED = "(none)"
NO_PHASE = "no_serve_span"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float
    dur: float
    tf_op: str = ""


# -- the wire ---------------------------------------------------------
# field numbers of tsl/profiler/protobuf/xplane.proto, the fields read
_XPLANE_FIELDS = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "XPlane.EventMetadataEntry", True),
               ("stat_metadata", 5, "XPlane.StatMetadataEntry", True)],
    "XLine": [("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("str_value", 5, "string", False),
              ("ref_value", 7, "uint64", False)],
    "XEventMetadata": [("id", 1, "int64", False),
                       ("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("id", 1, "int64", False),
                      ("name", 2, "string", False)],
}
_XSPACE = None


def _xspace_class():
    """The ``XSpace`` message class for the fields above; a field left
    out is skipped on the wire as an unknown one."""
    global _XSPACE
    if _XSPACE is not None:
        return _XSPACE
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"string": F.TYPE_STRING, "int64": F.TYPE_INT64,
              "uint64": F.TYPE_UINT64}
    pkg = "perfbench_xplane"
    fd = descriptor_pb2.FileDescriptorProto(name=pkg + ".proto",
                                            package=pkg, syntax="proto3")

    def add_fields(msg, fields):
        for name, num, typ, rep in fields:
            f = msg.field.add(name=name, number=num, label=(
                F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL))
            if typ in scalar:
                f.type = scalar[typ]
            else:
                f.type = F.TYPE_MESSAGE
                f.type_name = f".{pkg}.{typ}"

    for name, fields in _XPLANE_FIELDS.items():
        msg = fd.message_type.add(name=name)
        add_fields(msg, fields)
        if name == "XPlane":
            for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                                 ("StatMetadataEntry", "XStatMetadata")):
                m = msg.nested_type.add(name=entry)
                m.options.map_entry = True
                add_fields(m, [("key", 1, "int64", False),
                               ("value", 2, value, False)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    _XSPACE = message_factory.GetMessageClass(
        pool.FindMessageTypeByName(pkg + ".XSpace"))
    return _XSPACE


def events_from_xplane(path: str) -> List[Event]:
    """Every event of every line, as ``trace_reduce.events_from_xplane``
    gives them, each device event with its metadata's ``tf_op`` ("" where
    it has none)."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        names = {k: m.name for k, m in plane.event_metadata.items()}
        ops = {}
        if is_device(plane.name):
            stat = {k: m.name for k, m in plane.stat_metadata.items()}
            key = [k for k, n in stat.items() if n == "tf_op"]
            for k, m in plane.event_metadata.items():
                for s in m.stats:
                    if key and s.metadata_id == key[0]:
                        ops[k] = s.str_value or stat.get(s.ref_value, "")
        for line in plane.lines:
            t0 = line.timestamp_ns
            # whole nanoseconds, as ``ProfileData`` gives them
            for ev in line.events:
                out.append(Event(plane.name, line.name,
                                 names.get(ev.metadata_id, ""),
                                 (t0 + ev.offset_ps // 1000) * 1e-9,
                                 (ev.duration_ps // 1000) * 1e-9,
                                 ops.get(ev.metadata_id, "")))
    return out


# -- device time by block ---------------------------------------------
def scope_of(tf_op: str) -> Optional[str]:
    """The innermost vocabulary name among the scopes of an op's path
    (the last part names the op; a trailing ``:type`` is dropped)."""
    for part in reversed(tf_op.split(":", 1)[0].split("/")[:-1]):
        if part in SCOPES:
            return part
    return None


def self_times(ops: Sequence[Event]) -> List[float]:
    """Each op's duration less what the ops nested in it on its line
    cover (ops of one line either nest or follow each other); in the
    order given."""
    out = [e.dur for e in ops]
    by_line = defaultdict(list)
    for i, e in enumerate(ops):
        by_line[(e.plane, e.line)].append(i)
    for idx in by_line.values():
        idx.sort(key=lambda i: (ops[i].start, -ops[i].dur))
        stack = []            # [index, end, end of what its children cover]
        for i in idx:
            s, e = ops[i].start, ops[i].start + ops[i].dur
            while stack and stack[-1][1] <= s:
                stack.pop()
            if stack:
                top = stack[-1]
                lo, hi = max(s, top[2]), min(e, top[1])
                if hi > lo:
                    out[top[0]] -= hi - lo
                    top[2] = hi
            stack.append([i, e, s])
    return out


def program_of(name: str) -> str:
    """A jitted program's name as ``trace_reduce.time_per_program``
    gives it (``jit_decode_fn(12)`` -> ``decode_fn``)."""
    m = re.match(r"(?:jit_)?([A-Za-z0-9_<>]+)", name)
    return m.group(1) if m else name


def _first_device(events) -> Optional[str]:
    planes = sorted({e.plane for e in events if is_device(e.plane)
                     and e.line == OPS_LINE})
    return planes[0] if planes else None


def scope_times(events, top: int = 3) -> Tuple[Dict[str, Dict[str, float]],
                                                Dict[str, List[List]]]:
    """(``{program: {scope: self seconds}}``, ``{program: [[op key,
    seconds], ...]}`` of its ``top`` largest ops with no scope) over the
    first device (the programs run in lockstep across chips).  An op's
    program is the "XLA Modules" run that holds its start; an op
    outside every run is left out, and so are programs none of whose
    ops carries a ``tf_op``."""
    first = _first_device(events)
    if first is None:
        return {}, {}
    ops = [e for e in events if e.plane == first and e.line == OPS_LINE]
    runs = sorted((e.start, e.start + e.dur, program_of(e.name))
                  for e in events if e.plane == first
                  and e.line == MODULES_LINE)
    starts = [r[0] for r in runs]
    acc = defaultdict(lambda: defaultdict(float))
    loose = defaultdict(lambda: defaultdict(float))
    named = set()
    for e, t in zip(ops, self_times(ops)):
        j = bisect.bisect_right(starts, e.start) - 1
        if j < 0 or e.start >= runs[j][1]:
            continue
        prog = runs[j][2]
        if e.tf_op:
            named.add(prog)
        sc = scope_of(e.tf_op)
        acc[prog][sc or UNSCOPED] += t
        if sc is None:
            loose[prog][op_key(e.name)] += t
    table = {p: dict(v) for p, v in acc.items() if p in named}
    worst = {p: [[k, s] for k, s in sorted(loose[p].items(),
                                           key=lambda kv: -kv[1])[:top]]
             for p in table if loose.get(p)}
    return table, worst


# -- idle time by scheduler phase ---------------------------------------
def _phase_name(name: str) -> str:
    return name.split("#", 1)[0]


def idle_by_phase(events, min_gap: float = 20e-6) -> List[List]:
    """[[phase, idle seconds], ...]: every gap of at least ``min_gap``
    between the ops of the first device goes to the innermost ``serve``
    span (the scheduler's step or one of its ``serve.*`` phases,
    ``serve.idle`` included) that covers most of it on the host, else
    to ``no_serve_span``."""
    first = _first_device(events)
    if first is None:
        return []
    busy = union([(e.start, e.start + e.dur) for e in events
                  if e.plane == first and e.line == OPS_LINE])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])
            if b[0] - a[1] >= min_gap]
    spans = sorted(((e.start, e.start + e.dur, _phase_name(e.name))
                    for e in events if not is_device(e.plane)
                    and re.match(r"serve(\.|$)", _phase_name(e.name))),
                   key=lambda s: s[0])
    starts = [s[0] for s in spans]
    longest = max((s[1] - s[0] for s in spans), default=0.0)
    acc = defaultdict(float)
    for g0, g1 in gaps:
        lo = bisect.bisect_left(starts, g0 - longest)
        hi = bisect.bisect_left(starts, g1)
        over = [s for s in spans[lo:hi] if min(g1, s[1]) > max(g0, s[0])]
        # innermost: a span that holds another overlapping span is not
        inner = [s for s in over if not any(
            o is not s and s[0] <= o[0] and o[1] <= s[1]
            and (o[1] - o[0]) < (s[1] - s[0]) for o in over)]
        best = max(inner, default=None,
                   key=lambda s: min(g1, s[1]) - max(g0, s[0]))
        acc[best[2] if best else NO_PHASE] += g1 - g0
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])]


def reduce(events) -> dict:
    """What this module adds to ``trace_reduce.summarize()``'s keys:
    ``scopes`` (``{program: {scope: self seconds}}``, ops with no scope
    under ``"(none)"``), ``unscoped_ops`` (each program's three largest
    such ops) and ``idle_by_phase``."""
    table, worst = scope_times(events)
    return {"scopes": table, "unscoped_ops": worst,
            "idle_by_phase": idle_by_phase(events)}
