"""BENCHMARK.json and the data files it names.

Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric is a file of its own, found by the NAME in
BENCHMARK.json -- a later PR adds files and entries and edits nothing.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TRAFFIC_EXTS = (".json", ".jsonl", ".toml", ".txt", ".csv")


def load_manifest(path=None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _modname(prefix, name):
    return prefix + re.sub(r"[^A-Za-z0-9_]", "_", name)


class Cell:
    """One entry of ``workloads`` with every file it names loaded."""

    def __init__(self, manifest: dict, workload: str, bench_dir=None):
        # ``bench_dir`` holds the data files (traffic/, cells/); tests
        # point it at toy-sized ones.  Code (references, bindings,
        # metric readers) is always the benchmark's own.
        d = bench_dir or BENCH_DIR
        self.dir = BENCH_DIR
        self.manifest = manifest
        cells = {w["name"]: w for w in manifest["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfgs = {c["name"]: c for c in manifest["configs"]}
        self.config_entry = cfgs[self.entry["config"]]
        self.config_name = self.entry["config"]
        self.config = _json(os.path.join(ROOT, self.config_entry["file"]))
        self.traffic_name = self.entry["traffic"]
        self.traffic = _json(os.path.join(
            d, "traffic", self.traffic_name + ".json"))
        self.spec = _json(os.path.join(d, "cells", workload + ".json"))
        self.end_to_end = [
            m for m in manifest["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]]
        self.per_layer = [
            m for m in manifest["per_layer"]
            if "workloads" not in m or workload in m["workloads"]]

    def reference(self):
        """The configuration's plain reference (imports nothing of the
        program)."""
        return load_module(
            os.path.join(self.dir, "configs",
                         self.config_name + ".reference.py"),
            _modname("perfbench_reference_", self.config_name))

    def binding(self):
        """The configuration's binding to the program under test."""
        return load_module(
            os.path.join(self.dir, "configs",
                         self.config_name + ".program.py"),
            _modname("perfbench_program_", self.config_name))

    def metric_reader(self, metric: str):
        path = os.path.join(self.dir, "metrics", metric + ".py")
        return load_module(path, _modname("perfbench_metric_", metric))


def lint(manifest: dict, bench_dir=None) -> list:
    """Faults of BENCHMARK.json against the contract, as strings."""
    d = bench_dir or BENCH_DIR
    out = []
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != keys:
        out.append(f"top-level keys {sorted(manifest)} != {sorted(keys)}")
        return out
    if not (1 <= int(manifest["run_seconds"]) <= 51):
        out.append("run_seconds outside 1..51")
    names = set()
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            out.append(f"config keys {sorted(c)}")
        if not NAME_RE.match(c["name"]):
            out.append(f"bad config name {c['name']!r}")
        for k in c["reduced"]:
            if not NAME_RE.match(k):
                out.append(f"bad reduced key {k!r}")
        if not any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in manifest["paths"]):
            out.append(f"config file {c['file']} outside paths")
        names.add(c["name"])
    seen = set()
    cells = {}
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            out.append(f"workload keys {sorted(w)}")
        for k in ("name", "config", "traffic"):
            if not NAME_RE.match(w[k]):
                out.append(f"bad {k} {w[k]!r}")
        if w["config"] not in names:
            out.append(f"workload {w['name']} names no config")
        if (w["config"], w["traffic"]) in seen:
            out.append(f"pair {w['config']}/{w['traffic']} twice")
        seen.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']} chips {w['chips']}")
        if not (1 <= len(w["why"]) <= 200) or "\n" in w["why"]:
            out.append(f"workload {w['name']} why too long")
        if not any(os.path.exists(os.path.join(
                d, "traffic", w["traffic"] + e)) for e in TRAFFIC_EXTS):
            out.append(f"traffic file for {w['traffic']} missing")
        cells[w["name"]] = w
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        out.append(f"{four} four-chip cells")
    for c in manifest["configs"]:
        if not any(w["config"] == c["name"]
                   for w in manifest["workloads"]):
            out.append(f"config {c['name']} used by no cell")
    e2e = {}
    for m in manifest["end_to_end"]:
        extra = set(m) - {"name", "unit", "better", "bound", "source",
                          "workloads"}
        if extra:
            out.append(f"end_to_end {m.get('name')} extra keys {extra}")
        if not NAME_RE.match(m["name"]) or not UNIT_RE.match(m["unit"]):
            out.append(f"bad name/unit {m['name']!r} {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            out.append(f"{m['name']} better {m['better']!r}")
        if not (0.01 <= m["bound"] <= 0.1):
            out.append(f"{m['name']} bound {m['bound']}")
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"{m['name']} source {m['source']}")
        if m["name"] in e2e:
            out.append(f"metric {m['name']} twice")
        e2e[m["name"]] = m
    if "setup_s" not in e2e:
        out.append("no setup_s")

    def cells_of(m):
        return set(m.get("workloads", cells))

    for m in manifest["per_layer"]:
        extra = set(m) - {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if extra:
            out.append(f"per_layer {m.get('name')} extra keys {extra}")
        if not NAME_RE.match(m["name"]) or not UNIT_RE.match(m["unit"]):
            out.append(f"bad name/unit {m['name']!r} {m['unit']!r}")
        if m["name"] in e2e:
            out.append(f"metric {m['name']} twice")
        if m["source"] not in ("device_trace", "program_span",
                               "program_counter", "host_clock"):
            out.append(f"{m['name']} source {m['source']}")
        if m["moves"] not in e2e:
            out.append(f"{m['name']} moves unknown {m['moves']}")
            continue
        # every cell this metric is read in reports the metric it moves
        missing = cells_of(m) - cells_of(e2e[m["moves"]])
        if missing:
            out.append(f"{m['name']} moves {m['moves']} which "
                       f"{sorted(missing)} do not report")
        unknown = cells_of(m) - set(cells)
        if unknown:
            out.append(f"{m['name']} lists unknown cells {unknown}")
        if not os.path.exists(os.path.join(BENCH_DIR, "metrics",
                                           m["name"] + ".py")):
            out.append(f"no reader metrics/{m['name']}.py")
    for w in cells:
        mine = [m for m in manifest["end_to_end"]
                if w in cells_of(m) and m["name"] != "setup_s"]
        if not mine:
            out.append(f"cell {w} reports no end-to-end metric")
        if not any(w in cells_of(m) for m in manifest["per_layer"]):
            out.append(f"cell {w} reports no per-layer metric")
        if not os.path.exists(os.path.join(d, "cells", w + ".json")):
            out.append(f"no cells/{w}.json")
    return out
