#!/usr/bin/env python3
"""Not the benchmark's command: the tool the limits of ``correct`` are
read with.  Runs a cell on several seeds in ONE process (short
windows), each run followed by its control (the reference in the
nearest precision below, put in the program's place) and, for training
cells, by the planted faults, and prints every reading and, for each
control and fault, the verdict of the harness's own comparison with its
numbers put in the program's place (it has to be ``correct=False``).

    python3 perfbench/tools/calibrate.py --workload W --seeds 1,2,3 \
        --seconds 10 [--controls fp8] [--faults half_batch] [--trace 1]

Writes one JSON line per run to chiprun_out/calibrate_<W>.jsonl.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="fp8")
    ap.add_argument("--faults", default="")
    ap.add_argument("--trace", type=int, default=0)
    a = ap.parse_args(argv)
    from perfbench import run as R
    R.pin_compile_cache()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"calibrate_{a.workload}.jsonl")
    kw = {"controls": tuple(c for c in a.controls.split(",") if c)}
    if a.faults:
        kw["faults"] = tuple(f for f in a.faults.split(",") if f)
    for s in a.seeds.split(","):
        t = time.perf_counter()
        res = R.run_cell(a.workload, int(s), a.seconds, bool(a.trace),
                         t_proc0=t, **kw)
        R.print_result(res)
        rec = {"workload": a.workload, "seed": int(s),
               "seconds": a.seconds, "wall_s": time.perf_counter() - t,
               **{k: res[k] for k in ("correct", "attempted", "failed",
                                      "metrics", "device", "compared",
                                      "verdicts", "breakdown")}}
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
