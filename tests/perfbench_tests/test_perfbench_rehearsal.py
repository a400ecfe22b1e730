"""The one command at toy size on the CPU, every cell, in-process: the
whole run but the look for a chip.  It names its device as CPU and
writes no device metric."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

DEVICE_METRICS = {"train_mfu", "serve_mfu", "serve_hbm_bw_share",
                  "train_peak_hbm_gib", "serve_peak_hbm_gib",
                  "decode_step_ms", "prefill_ms_per_ktok",
                  "train_mosaic_kernel_share", "serve_mosaic_kernel_share",
                  "train_device_idle_share", "serve_device_idle_share"}
CELLS = ["mistral7b-serve-decode", "bert-base-train",
         "mistral7b-serve-prefill", "mistral7b-train-fsdp4",
         "mistral7b-serve-open"]


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_end_to_end(run_toy, toy_manifest, workload):
    from perfbench.harness.common import result_line
    res = run_toy(workload)
    line = json.loads(result_line(
        res["correct"], res["attempted"], res["failed"], res["metrics"],
        res["device"], res["breakdown"], res["compared"]))
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    cells = {w["name"] for w in toy_manifest["workloads"]}
    want = {m["name"] for m in toy_manifest["end_to_end"]
            if workload in m.get("workloads", cells)}
    assert set(line["metrics"]) == want
    for v in line["metrics"].values():
        assert v["value"] > 0 and v["unit"]
    for name, (val, lim) in line["compared"].items():
        assert val is not None, name


@pytest.mark.parametrize("workload", ["mistral7b-serve-decode",
                                      "bert-base-train"])
def test_traced_rehearsal_writes_no_device_metric(run_toy, workload):
    res = run_toy(workload, seconds=1.5, trace=True)
    assert res["correct"] is True
    assert res["metrics"], "a traced run reports per-layer metrics"
    assert not (set(res["metrics"]) & DEVICE_METRICS)
    assert "busy_s" not in res["device"]
    assert res["breakdown"] is None
    counts = [k for k in res["metrics"] if k.endswith("_in_window")]
    assert counts and all(res["metrics"][k]["value"] == 0 for k in counts)


def test_the_command_fails_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "mistral7b-serve-decode", "--seed", "1",
         "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]
    assert "needs a TPU" in p.stderr


def test_unknown_workload_is_an_error(run_toy):
    with pytest.raises(KeyError):
        run_toy("no-such-cell")
