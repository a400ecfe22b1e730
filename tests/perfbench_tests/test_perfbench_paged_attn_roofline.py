"""``paged_attn_roofline`` (ISSUE 26): the reader on a planted trace
against the hand-computed share, at and under 100; nothing (no raise)
off the chip, on a program that runs no Mosaic kernel in its decode
cell, and in a traced toy rehearsal."""
import copy
import time

import pytest

from conftest import TOY
from perfbench.harness import manifest as M

NAME = "paged_attn_roofline"
CELL = "mistral7b-serve-decode"


def _entry(real_manifest):
    (e,) = [e for e in real_manifest["per_layer"] if e["name"] == NAME]
    return e


def _ctx(real_manifest, kernel_s, platform="tpu", runs=3):
    """A window [10, 20) of 4 decode steps in which two requests got
    tokens, and a trace of ``runs`` decode steps whose Mosaic kernels
    took ``kernel_s``."""
    cell = M.Cell(real_manifest, CELL)
    reqs = [
        # prompt 100: token 0 is the prefill's, tokens 1..3 decode over
        # 101, 102, 103 positions
        {"prompt": [1] * 100, "token_times": [10.1, 10.2, 10.3, 10.4]},
        # prompt 50: tokens 5 and 6 fall in the window (55, 56); the
        # one before it and the one after do not
        {"prompt": [1] * 50,
         "token_times": [1, 2, 3, 4, 9.9, 19.0, 19.5, 20.0]},
    ]
    trace = {"busy_s": 0.05, "window_s": 0.06, "kernel_s": kernel_s,
             "programs": {"jit_decode_fn(123)": [0.015] * runs,
                          "jit_prefill_fn(4)": [0.009]}}
    return {"cfg": cell.config, "requests": reqs, "t0": 10.0, "t1": 20.0,
            "window": {"decode_steps": 4}, "trace": trace,
            "device": {"platform": platform, "kind": "TPU v5 lite"}}


def _read(real_manifest, ctx):
    return M.Cell(real_manifest, CELL).metric_reader(NAME).read(ctx)


def test_planted_trace_reads_the_hand_computed_share(real_manifest):
    cfg = M.Cell(real_manifest, CELL).config
    live = (101 + 102 + 103 + 55 + 56) / 4          # positions a step
    per_pos = (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
               * cfg["head_dim"] * 2)               # K and V, bf16
    assert per_pos == 32768                         # as ISSUE 26 reckons
    least_s = 3 * live * per_pos / 819e9            # three traced steps
    got = _read(real_manifest, _ctx(real_manifest, 4 * least_s))
    assert got == pytest.approx(25.0)
    # a kernel at the HBM peak reads 100, never more: the bytes are
    # the algorithm's, so no implementation can need fewer
    assert _read(real_manifest,
                 _ctx(real_manifest, least_s)) == pytest.approx(100.0)
    # the share follows the number of traced steps, not their length
    more = _ctx(real_manifest, 4 * least_s, runs=6)
    assert _read(real_manifest, more) == pytest.approx(50.0)


@pytest.mark.parametrize("why", ["off_the_chip", "no_mosaic_kernel",
                                 "no_decode_step_traced", "no_trace"])
def test_nothing_to_read_is_none_not_an_error(real_manifest, why):
    ctx = _ctx(real_manifest, 1e-3)
    if why == "off_the_chip":
        ctx["device"] = {"platform": "cpu", "kind": "cpu"}
    elif why == "no_mosaic_kernel":         # the parent of PR 26
        ctx["trace"]["kernel_s"] = 0.0
    elif why == "no_decode_step_traced":
        del ctx["trace"]["programs"]["jit_decode_fn(123)"]
    else:
        ctx["trace"] = None
    assert _read(real_manifest, ctx) is None


def test_entry_and_traced_rehearsal(toy_manifest, real_manifest):
    e = _entry(real_manifest)
    assert e == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "device_trace", "layer": "kernels",
                 "moves": "serve_out_tokens_per_s", "workloads": [CELL]}
    assert M.lint(real_manifest) == []
    from perfbench import run as R
    m = copy.deepcopy(toy_manifest)
    m["per_layer"].append(copy.deepcopy(e))
    assert M.lint(m, bench_dir=TOY) == []
    res = R.run_cell(CELL, 2 ** 31 + 26, 1.5, True,
                     t_proc0=time.perf_counter(), require_chip=False,
                     manifest=m, bench_dir=TOY)
    assert res["correct"] is True
    assert res["metrics"] and NAME not in res["metrics"]
