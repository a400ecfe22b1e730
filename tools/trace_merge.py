#!/usr/bin/env python
"""Fuse per-process trace sinks into one Chrome/Perfetto trace.

Each traced process (trainer, PS primary, PS replica, serving) appends
span / clock records to its own JSONL sink
(``$PADDLE_TRACE_DIR/trace-<role>-<pid>.jsonl`` — see
``paddle_tpu/observability/trace.py``).  This tool merges any number of
sinks into a single ``chrome://tracing`` / https://ui.perfetto.dev
JSON file:

1. **Clock correction.**  Sinks record offset samples from RPC round
   trips (the PS register handshake): a ``clock`` record in sink A
   naming peer sink B estimates ``B_clock - A_clock`` at the midpoint
   of a round trip.  The samples form a graph over sinks; a BFS from
   the ROOT sink (the first file given — pass the trainer first)
   accumulates signed offsets along the lowest-RTT edges, and every
   span timestamp is shifted onto the root's timeline.  Sinks with no
   path to the root keep their own clock (reported on stderr).

2. **Parenting.**  Spans carry ``trace``/``span``/``parent`` ids; a
   parent living in ANOTHER sink (the client side of an RPC) becomes a
   Chrome flow arrow from the parent span to the child, so the merged
   view draws client->server causality across process tracks.

Usage::

    python tools/trace_merge.py trainer.jsonl ps0.jsonl ps0r.jsonl \
        -o merged_trace.json
    python tools/trace_merge.py --dir paddle_trace -o merged_trace.json

Open the output in chrome://tracing or the Perfetto UI.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple


def read_sink(path: str) -> dict:
    """Parse one sink file -> {sink, role, pid, spans, clocks}."""
    out = {"sink": None, "role": "proc", "pid": 0,
           "spans": [], "clocks": []}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue        # torn tail line (process died mid-write)
            t = rec.get("t")
            if t == "meta":
                out["sink"] = rec.get("sink")
                out["role"] = rec.get("role", "proc")
                out["pid"] = rec.get("pid", 0)
            elif t == "span":
                out["spans"].append(rec)
            elif t == "clock":
                out["clocks"].append(rec)
    if out["sink"] is None:
        # sink id is recoverable from the file name convention
        base = os.path.basename(path)
        if base.startswith("trace-") and base.endswith(".jsonl"):
            out["sink"] = base[len("trace-"):-len(".jsonl")]
        else:
            out["sink"] = base
    return out


def solve_offsets(sinks: List[dict]) -> Dict[str, Optional[float]]:
    """Per-sink clock offset (sink_clock - root_clock, microseconds)
    via BFS over the lowest-RTT clock edges; None = unreachable."""
    return solve_clocks(sinks)[0]


def solve_clocks(sinks: List[dict]) -> Tuple[
        Dict[str, Optional[float]], Dict[str, Optional[float]]]:
    """``(offsets, errors)`` per sink, microseconds.  An edge's offset
    is estimated at the midpoint of a round trip, so it is off by at
    most half that trip's ``rtt_us``; ``errors`` adds those halves up
    along the path from the root: how far a corrected timestamp of the
    sink can lie from where the root's clock would have put it."""
    ids = [s["sink"] for s in sinks]
    # best (lowest-rtt) sample per directed pair: offset of peer vs self
    best: Dict[Tuple[str, str], Tuple[float, float]] = {}
    for s in sinks:
        for c in s["clocks"]:
            key = (s["sink"], c.get("peer"))
            rtt = float(c.get("rtt_us", 0.0))
            if key not in best or rtt < best[key][1]:
                best[key] = (float(c.get("offset_us", 0.0)), rtt)
    # undirected adjacency with signed offsets
    adj: Dict[str, List[Tuple[str, float, float]]] = {i: [] for i in ids}
    for (a, b), (off, rtt) in best.items():
        if a in adj and b in adj:
            adj[a].append((b, off, rtt))  # b_clock - a_clock = off
            adj[b].append((a, -off, rtt))
    offsets: Dict[str, Optional[float]] = {i: None for i in ids}
    errors: Dict[str, Optional[float]] = {i: None for i in ids}
    root = ids[0]
    offsets[root] = errors[root] = 0.0
    frontier = [root]
    while frontier:
        cur = frontier.pop(0)
        for nxt, off, rtt in adj[cur]:
            if offsets.get(nxt) is None:
                offsets[nxt] = offsets[cur] + off
                errors[nxt] = errors[cur] + rtt / 2.0
                frontier.append(nxt)
    return offsets, errors


def merge_sinks(sinks: List[dict]) -> dict:
    """Merge parsed sinks into a Chrome trace event dict.

    A sink with no clock-offset path to the root DEGRADES, never
    fails: its spans are emitted on its own (uncorrected) timeline, a
    warning goes to stderr, and the sink is listed under
    ``metadata.uncorrected`` so tooling can tell estimated-aligned
    tracks from as-recorded ones."""
    offsets, errors = solve_clocks(sinks)
    uncorrected = []
    for s in sinks:
        if offsets[s["sink"]] is None:
            uncorrected.append(s["sink"])
            print(f"trace_merge: no clock path from {s['sink']} to "
                  f"root {sinks[0]['sink']}; leaving its clock "
                  f"uncorrected", file=sys.stderr)

    events = []
    span_site: Dict[str, Tuple[int, int, float]] = {}  # id->(pid,tid,ts)
    # synthetic pids: 1..n in input order (real pids can collide across
    # hosts); the process_name metadata keeps the human identity
    for i, s in enumerate(sinks):
        pid = i + 1
        off = offsets[s["sink"]] or 0.0
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {
                           "name": f"{s['role']} ({s['sink']})"}})
        # request lanes (ISSUE 12): a root "req" span carries a "lane"
        # arg naming its virtual tid — surface it as the Perfetto
        # thread name so the UI shows one named lane per request
        named = set()
        for sp in s["spans"]:
            lane = (sp.get("args") or {}).get("lane")
            tid = int(sp.get("tid", 0)) % (1 << 31)
            if lane and (pid, tid) not in named:
                named.add((pid, tid))
                events.append({"ph": "M", "name": "thread_name",
                               "pid": pid, "tid": tid,
                               "args": {"name": str(lane)}})
        for sp in s["spans"]:
            ts = float(sp["ts_us"]) - off
            tid = int(sp.get("tid", 0)) % (1 << 31)
            args = dict(sp.get("args") or {})
            args["trace"] = sp.get("trace")
            args["span"] = sp.get("span")
            if sp.get("parent") is not None:
                args["parent"] = sp["parent"]
            events.append({"ph": "X", "name": sp["name"],
                           "cat": sp.get("cat", "host"), "pid": pid,
                           "tid": tid, "ts": ts,
                           "dur": float(sp.get("dur_us", 0)),
                           "args": args})
            span_site[sp["span"]] = (pid, tid, ts)

    # flow arrows for cross-process parent links
    flow_ids: Dict[str, int] = {}
    for i, s in enumerate(sinks):
        pid = i + 1
        off = offsets[s["sink"]] or 0.0
        for sp in s["spans"]:
            par = sp.get("parent")
            if par is None or par not in span_site:
                continue
            ppid, ptid, pts = span_site[par]
            if ppid == pid:
                continue        # same-process nesting needs no arrow
            fid = flow_ids.setdefault(par + ">" + sp["span"],
                                      len(flow_ids) + 1)
            ts = float(sp["ts_us"]) - off
            events.append({"ph": "s", "id": fid, "name": "rpc",
                           "cat": "flow", "pid": ppid, "tid": ptid,
                           "ts": pts})
            events.append({"ph": "f", "bp": "e", "id": fid,
                           "name": "rpc", "cat": "flow", "pid": pid,
                           "tid": int(sp.get("tid", 0)) % (1 << 31),
                           "ts": ts})
    events.sort(key=lambda e: (e.get("ts", 0.0), e["ph"] != "M"))
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "metadata": {"clock_offsets_us": dict(offsets),
                         "clock_error_us": dict(errors),
                         "uncorrected": uncorrected}}


def merge_files(paths: List[str]) -> dict:
    return merge_sinks([read_sink(p) for p in paths])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("sinks", nargs="*",
                    help="sink files, ROOT (trainer) first")
    ap.add_argument("--dir", help="merge every trace-*.jsonl under DIR "
                                  "(sorted; combinable with positional "
                                  "sinks, which stay first)")
    ap.add_argument("-o", "--out", default="merged_trace.json")
    args = ap.parse_args(argv)
    paths = list(args.sinks)
    if args.dir:
        extra = sorted(glob.glob(os.path.join(args.dir,
                                              "trace-*.jsonl")))
        paths += [p for p in extra if p not in paths]
    if not paths:
        ap.error("no sink files given (positional or --dir)")
    merged = merge_files(paths)
    with open(args.out, "w") as f:
        json.dump(merged, f)
    n_spans = sum(1 for e in merged["traceEvents"] if e["ph"] == "X")
    n_flows = sum(1 for e in merged["traceEvents"] if e["ph"] == "s")
    print(f"trace_merge: {len(paths)} sink(s) -> {args.out} "
          f"({n_spans} spans, {n_flows} cross-process links)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
