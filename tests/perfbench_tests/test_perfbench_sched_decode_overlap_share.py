"""``sched_decode_overlap_share`` (ISSUE 32): the share of the window's
decode steps that were dispatched while the step before them was still
unread, read off the ``overlapped`` count of ``serve.decode.dispatch``.
The reader on a hand-made ring against the hand-computed share: 0 for
a program that writes no such count, steps cut by the window's edge
left out, None where there is nothing to read; the entry a
``benchmark`` PR would add for it; and the metric in the result line
of a traced toy rehearsal of each of the three serving cells."""
import copy
import time
from collections import deque

import pytest

from conftest import TOY
from paddle_tpu.observability import timeline
from perfbench.harness import manifest as M

NAME = "sched_decode_overlap_share"
CELLS = ["mistral7b-serve-decode", "mistral7b-serve-prefill",
         "kimi-linear-serve-decode"]
ENTRY = {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_span", "layer": "scheduler",
         "moves": "serve_out_tokens_per_s", "workloads": CELLS}


def _read(ctx):
    cell = M.Cell(M.load_manifest(), CELLS[0])
    return cell.metric_reader(NAME).read(ctx)


@pytest.fixture
def ring(monkeypatch):
    """A hand-made ``serve`` ring on a clock the test sets:
    ``play(step, t, overlapped)`` writes one decode step of 20 ms that
    starts at ``t`` (``overlapped`` None: a program from before the
    pipeline, whose dispatch row carries no count; "no_decode": a step
    that dispatched no decode step)."""
    clock = [0.0]
    monkeypatch.setattr(timeline, "perf_counter", lambda: clock[0])
    monkeypatch.setattr(timeline, "_ring",
                        deque(maxlen=timeline.RING_MAXLEN))
    tl = timeline.StepTimeline("serve")

    def play(step, t, overlapped, tl=tl):
        clock[0] = t
        with tl.step(step):
            names = ["admit", "decode.fetch", "decode.emit"] \
                if overlapped == "no_decode" else \
                ["decode.stage", "decode.dispatch", "decode.fetch",
                 "decode.emit"]
            for i, name in enumerate(names):
                args = {"overlapped": overlapped} \
                    if name == "decode.dispatch" \
                    and overlapped is not None else {}
                clock[0] = t + 0.005 * i
                with tl.phase(name, **args):
                    clock[0] = t + 0.005 * (i + 1)
    play.tl = tl
    return play


WINDOW = {"t0": 10.0, "t1": 20.0}


def test_share_of_the_windows_decode_steps(ring):
    ring(0, 9.0, 1)                     # before the window
    ring(1, 9.99, 1)                    # cut by its start
    ring(2, 10.1, 0)                    # nothing was in flight
    for i, t in enumerate((10.2, 10.3, 10.4)):
        ring(3 + i, t, 1)
    ring(6, 10.5, "no_decode")          # read the last step, sent none
    ring(7, 11.0, 0)
    ring(8, 19.99, 1)                   # cut by its end
    assert _read(WINDOW) == pytest.approx(100.0 * 3 / 5)
    # every step of the ring, the two that were cut whole now
    assert _read({"t0": 0.0, "t1": 99.0}) == pytest.approx(100.0 * 6 / 8)


def test_a_program_from_before_the_pipeline_reads_nought(ring):
    for i in range(4):
        ring(i, 10.0 + i, None)
    assert timeline.spans("serve")[1].args == {}
    assert _read(WINDOW) == 0.0


def test_two_servers_steps_are_told_apart(ring):
    other = timeline.StepTimeline("serve")
    ring(0, 10.0, 1)
    ring(0, 11.0, 0, tl=other)
    ring(1, 12.0, 0, tl=other)
    assert _read(WINDOW) == pytest.approx(100.0 / 3)


@pytest.mark.parametrize("why", ["empty_ring", "nothing_in_the_window",
                                 "no_decode_step", "no_spans_function"])
def test_nothing_to_read_is_none_not_an_error(ring, why, monkeypatch):
    if why == "nothing_in_the_window":
        ring(0, 30.0, 1)
    elif why == "no_decode_step":
        ring(0, 10.0, "no_decode")
    elif why == "no_spans_function":
        ring(0, 10.0, 1)
        monkeypatch.delattr(timeline, "spans")
    assert _read(WINDOW) is None


def test_the_entry_a_benchmark_pr_would_add_is_clean(real_manifest):
    """``BENCHMARK.json`` does not list the metric yet: an appended
    entry turns two accepted tests red, which pin their own PR's
    entries as the last of ``per_layer`` (PERF.md section 7, T).  The
    reader stands ready, and this is the entry as the contract takes
    it, in all three serving cells."""
    m = copy.deepcopy(real_manifest)
    m["per_layer"] = [e for e in m["per_layer"] if e["name"] != NAME]
    m["per_layer"].append(copy.deepcopy(ENTRY))
    assert M.lint(m) == []
    cells = {w["name"]: w for w in m["workloads"]}
    rate = [e for e in m["end_to_end"] if e["name"] == ENTRY["moves"]][0]
    for name in ENTRY["workloads"]:
        assert name in cells
        assert name in rate.get("workloads", cells)


def _with_entry(manifest, workloads):
    m = copy.deepcopy(manifest)
    m["per_layer"].append(dict(ENTRY, workloads=list(workloads)))
    return m


@pytest.mark.parametrize("workload", CELLS[:2])
def test_traced_rehearsal_prints_the_share(toy_manifest, workload):
    from perfbench import run as R
    m = _with_entry(toy_manifest, CELLS[:2])
    assert M.lint(m, bench_dir=TOY) == []
    res = R.run_cell(workload, 2 ** 31 + 32, 2.0, True,
                     t_proc0=time.perf_counter(), require_chip=False,
                     manifest=m, bench_dir=TOY)
    assert res["correct"] is True
    got = res["metrics"][NAME]
    assert got["unit"] == "%" and 0 < got["value"] <= 100
    if workload.endswith("decode"):
        # closed-loop decode traffic: most steps find one in flight
        assert got["value"] >= 40


def test_traced_kimi_rehearsal_prints_the_share():
    """The third cell runs the same ``decode_fn`` behind per-slot
    recurrent state and step counters: the pipeline engages there
    too."""
    import json
    import os
    from perfbench import run as R
    with open(os.path.join(TOY, "manifest_kimi.json")) as f:
        m = _with_entry(json.load(f), CELLS[2:])
    assert M.lint(m, bench_dir=TOY) == []
    res = R.run_cell(CELLS[2], 2 ** 31 + 33, 2.0, True,
                     t_proc0=time.perf_counter(), require_chip=False,
                     manifest=m, bench_dir=TOY)
    assert res["correct"] is True
    got = res["metrics"][NAME]
    assert got["unit"] == "%" and 40 <= got["value"] <= 100
