"""The seven ``sched_*`` span readers (ISSUE 25): each on a hand-made
ring and window against the hand-computed value, None on an empty ring
and on a program from before the spans, and all of them in the result
line of a traced toy rehearsal."""
import copy
from collections import deque

import pytest

from conftest import TOY
from paddle_tpu.observability import timeline
from perfbench.harness import manifest as M

READERS = ["sched_decode_stage_ms", "sched_decode_dispatch_ms",
           "sched_decode_fetch_ms", "sched_decode_emit_ms",
           "sched_queue_wait_p50_ms", "sched_prefill_pad_share",
           "sched_longest_span_ms"]
CELLS = ["mistral7b-serve-decode", "mistral7b-serve-prefill"]


def _read(name, ctx):
    cell = M.Cell(M.load_manifest(), CELLS[0])
    return cell.metric_reader(name).read(ctx)


@pytest.fixture
def ring(monkeypatch):
    """A hand-made ``serve`` ring, times in seconds on a clock the
    test sets: ``play(step, [(phase, start, end, args), ...])``."""
    clock = [0.0]
    monkeypatch.setattr(timeline, "perf_counter", lambda: clock[0])
    monkeypatch.setattr(timeline, "_ring",
                        deque(maxlen=timeline.RING_MAXLEN))
    tl = timeline.StepTimeline("serve")

    def play(step, phases, tl=tl):
        clock[0] = phases[0][1]
        with tl.step(step):
            for name, start, end, args in phases:
                clock[0] = start
                with tl.phase(name, **args):
                    clock[0] = end
    play.clock, play.tl = clock, tl
    return play


def _decode_step(t, grow, stage, disp, fetch, emit):
    """One decode step starting at ``t``; durations in ms."""
    out, names = [], ("decode.grow", "decode.stage", "decode.dispatch",
                      "decode.fetch", "decode.emit")
    for name, d in zip(names, (grow, stage, disp, fetch, emit)):
        out.append((name, t, t + d * 1e-3, {}))
        t += d * 1e-3
    return out


@pytest.fixture
def window(ring):
    """Window [10, 20): one step before it, four inside (one of them a
    prefill-only step), one straddling its end."""
    ring(0, _decode_step(9.0, 9, 9, 9, 9, 9))                 # before
    ring(1, [("admit", 10.000, 10.001,
              {"queue_wait_ms": (4.0, 30.0)})]
         + [("prefill.stage", 10.001, 10.002,
             {"bucket": 64, "batch": 4, "tokens": 100}),
            ("prefill.dispatch", 10.002, 10.003, {}),
            ("prefill.fetch", 10.003, 10.053, {}),
            ("prefill.post", 10.053, 10.054, {})]
         + _decode_step(10.1, 0.1, 0.3, 2.0, 30.0, 1.0))
    ring(2, [("admit", 11.0, 11.0005,
              {"queue_wait_ms": (11.0,)})]
         + _decode_step(11.1, 0.2, 0.6, 4.0, 34.0, 3.0))
    ring(3, _decode_step(12.0, 0.1, 0.2, 3.0, 32.0, 2.0))
    # a step with nothing live: stage returns early, no dispatch
    ring(4, [("admit", 13.0, 13.0002, {"queue_wait_ms": ()}),
             ("prefill.stage", 13.001, 13.002,
              {"bucket": 128, "batch": 1, "tokens": 100}),
             ("decode.grow", 13.01, 13.02, {}),
             ("decode.stage", 13.02, 13.03, {})])
    ring(5, _decode_step(19.99, 1, 1, 1, 500, 1))              # straddles
    return {"t0": 10.0, "t1": 20.0}


EXPECTED = {
    # per decode step grow + stage: 0.4, 0.8, 0.3 -> median 0.4; the
    # step with nothing live dispatched no decode and does not count
    "sched_decode_stage_ms": 0.4,
    "sched_decode_dispatch_ms": 3.0,
    "sched_decode_fetch_ms": 32.0,
    "sched_decode_emit_ms": 2.0,
    "sched_queue_wait_p50_ms": 11.0,              # of 4, 30, 11
    # 200 tokens in 4 x 64 + 1 x 128 = 384 positions
    "sched_prefill_pad_share": 100.0 * (1 - 200 / 384),
    "sched_longest_span_ms": 50.0,                # step 1's prefill.fetch
}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_hand_made_ring(window, name, capsys):
    assert _read(name, window) == pytest.approx(EXPECTED[name], rel=1e-6)
    if name == "sched_longest_span_ms":
        err = capsys.readouterr().err
        assert "serve.prefill.fetch" in err and "in step 1" in err
        assert "0.003 s into the window" in err


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_and_says_none(ring, name, monkeypatch):
    ctx = {"t0": 10.0, "t1": 20.0}
    assert _read(name, ctx) is None                 # an empty ring
    ring(0, _decode_step(30.0, 1, 1, 1, 1, 1))
    assert _read(name, ctx) is None                 # nothing in the window
    # a program from before the spans has no reader function at all
    monkeypatch.delattr(timeline, "spans")
    assert _read(name, {"t0": 0.0, "t1": 99.0}) is None


def test_two_servers_steps_are_not_summed_into_one(ring):
    """Every server of a process names its timeline ``serve`` and
    counts its steps from 0: step 0 of one is not step 0 of another."""
    other = timeline.StepTimeline("serve")
    ring(0, _decode_step(10.0, 0.1, 0.3, 2.0, 30.0, 1.0))
    ring(0, _decode_step(10.0, 0.1, 0.5, 4.0, 36.0, 3.0), tl=other)
    ring(1, _decode_step(11.0, 0.1, 0.7, 3.0, 33.0, 2.0), tl=other)
    ctx = {"t0": 10.0, "t1": 20.0}
    assert {r.tl for r in timeline.spans("serve")} == \
        {ring.tl._serial, other._serial}
    assert _read("sched_decode_fetch_ms", ctx) == pytest.approx(33.0)
    assert _read("sched_decode_stage_ms", ctx) == pytest.approx(0.6)


def test_idle_is_no_phase_of_work(ring):
    ring(0, _decode_step(10.0, 1, 1, 1, 1, 1))
    ring.clock[0] = 10.5
    with ring.tl.phase("idle"):
        ring.clock[0] = 10.9
    last = timeline.spans("serve")[-1]
    assert last.name == "serve.idle" and last.step is None
    assert last.t_end - last.t_start == pytest.approx(0.4)
    # an idle wait is the absence of work, not the longest phase
    assert _read("sched_longest_span_ms",
                 {"t0": 0.0, "t1": 99.0}) == pytest.approx(1.0)


def _with_span_entries(toy_manifest, real_manifest):
    m = copy.deepcopy(toy_manifest)
    new = [e for e in real_manifest["per_layer"] if e["name"] in READERS]
    assert [e["name"] for e in new] == READERS
    m["per_layer"].extend(copy.deepcopy(new))
    return m


@pytest.mark.parametrize("workload", CELLS)
def test_traced_rehearsal_prints_the_span_metrics(
        toy_manifest, real_manifest, workload):
    import time

    from perfbench import run as R
    m = _with_span_entries(toy_manifest, real_manifest)
    assert M.lint(m, bench_dir=TOY) == []
    res = R.run_cell(workload, 2 ** 31 + 25, 3.0, True,
                     t_proc0=time.perf_counter(), require_chip=False,
                     manifest=m, bench_dir=TOY)
    assert res["correct"] is True
    want = set(READERS)
    if workload.endswith("prefill"):
        want.discard("sched_queue_wait_p50_ms")     # moves ttft_p95_ms
    got = {k: v["value"] for k, v in res["metrics"].items() if k in want}
    assert set(got) == want
    assert all(v > 0 for v in got.values()), got
    assert got["sched_prefill_pad_share"] < 100
    assert all(v["unit"] in ("ms", "%") for k, v in res["metrics"].items()
               if k in want)
    # the parts of a decode step do not exceed the longest phase
    assert max(got[k] for k in got if k.startswith("sched_decode_")) <= \
        got["sched_longest_span_ms"]


def test_real_manifest_is_clean_and_names_the_seven(real_manifest):
    assert M.lint(real_manifest) == []
    mine = [e for e in real_manifest["per_layer"] if e["name"] in READERS]
    assert [e["name"] for e in real_manifest["per_layer"]][-7:] == READERS
    for e in mine:
        assert e["source"] == "program_span" and e["layer"] == "scheduler"
        assert e["better"] == "lower"
        assert e["workloads"] == (CELLS[:1] if e["moves"] == "ttft_p95_ms"
                                  else CELLS)
