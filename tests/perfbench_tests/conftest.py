"""Shared helpers of the benchmark's own tests: the toy-sized data
files under ``toy/`` drive the real harness end to end on the CPU."""
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "toy")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="session")
def toy_manifest():
    with open(os.path.join(TOY, "manifest.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def real_manifest():
    from perfbench.harness import manifest as M
    return M.load_manifest()


@pytest.fixture
def run_toy(toy_manifest):
    """Drive one whole run of a toy cell in-process, skipping only the
    harness's look for a chip."""
    from perfbench import run as R

    def go(workload, seed=2 ** 31 + 77, seconds=1.0, trace=False, **kw):
        return R.run_cell(workload, seed, seconds, trace,
                          t_proc0=time.perf_counter(), require_chip=False,
                          manifest=toy_manifest, bench_dir=TOY, **kw)
    return go
