#!/usr/bin/env python3
"""One run of one cell:

    python3 perfbench/run.py --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

The last line of standard output is the result (one JSON object); the
numbers ``correct`` compared, each beside its limit, are the last lines
of standard error and the result's last key.  No accelerator, or fewer
chips than the cell asks for: a non-zero exit and no result.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()     # set-up is counted from here

import argparse    # noqa: E402
import os          # noqa: E402
import sys         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def pin_compile_cache():
    """JAX's persistent compile cache at a FIXED path inside the
    checkout, whatever the environment says, and with no eviction (a
    size limit from the environment makes JAX keep access times beside
    the entries, and one entry without its time then fails every later
    write: seen on the chip, PERF.md Findings).  Set before JAX is
    imported, so that the program, which honours the variable, sets no
    other directory in code."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_proc0=None, require_chip=True, manifest=None,
             bench_dir=None, **kw) -> dict:
    """Everything but the command line; tests call it with
    ``require_chip=False`` (and a toy manifest) to drive the whole run
    on the CPU."""
    from perfbench.harness import manifest as M
    cell = M.Cell(manifest or M.load_manifest(), workload,
                  bench_dir=bench_dir)
    kind = cell.spec["runner"]
    if kind == "serve":
        from perfbench.harness import serve_runner as runner
    elif kind == "train":
        from perfbench.harness import train_runner as runner
    else:
        raise ValueError(f"unknown runner {kind!r}")
    return runner.run(cell, seed, seconds, trace,
                      T_PROC0 if t_proc0 is None else t_proc0,
                      require_chip=require_chip, **kw)


def print_result(res: dict):
    from perfbench.harness import common as C
    for name, (val, lim) in res["compared"].items():
        print(f"perfbench: compared {name} = {val} (limit {lim})",
              file=sys.stderr, flush=True)
    print(C.result_line(res["correct"], res["attempted"], res["failed"],
                        res["metrics"], res["device"], res["breakdown"],
                        res["compared"]), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    pin_compile_cache()
    from perfbench.harness.common import NoChip
    try:
        res = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    print_result(res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
