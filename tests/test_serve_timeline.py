"""ISSUE 25: the serving scheduler's loop on the profiler's clock.

``GenerationServer._loop`` brackets its phases with
``StepTimeline("serve")``; every phase is a ``TraceAnnotation`` (so it
lands in a profiler trace beside the device's ops) and one row of the
in-memory ring ``observability.timeline.spans`` reads.  Tested here:

- a toy server's ring holds every span name, each phase inside its
  step, phases of the scheduler thread never overlapping, and counts
  at the span boundaries that agree with ``stats()``;
- under ``jax.profiler.start_trace`` the xplane holds
  ``serve.decode.dispatch`` with the ``PjitFunction(decode_fn)`` event
  of that step inside it: one clock;
- ``trace_reduce.idle_gaps(span_prefix="serve.")`` puts a device gap
  down to the scheduler phase that covers it;
- the ring is bounded, ``since`` / ``until`` cut it, it tells two
  timelines of one name apart, and with tracing and metrics off
  nothing is written anywhere.
"""
import json
import os
import sys
import threading
import time
from collections import deque

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import monitor
from paddle_tpu.inference import GenerationServer
from paddle_tpu.observability import timeline, trace
from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PLAIN = {"serve", "serve.idle", "serve.admit", "serve.prefill.stage",
         "serve.prefill.dispatch", "serve.prefill.fetch",
         "serve.prefill.post", "serve.decode.grow", "serve.decode.stage",
         "serve.decode.dispatch", "serve.decode.fetch",
         "serve.decode.emit"}
LENS = (5, 9, 3, 12, 7, 4)
NEW = (6, 4, 8, 5, 3, 7)


@pytest.fixture(scope="module")
def lm():
    paddle.seed(0)
    cfg = llama_tiny(vocab_size=64, hidden_size=32, intermediate_size=64,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=2, max_position_embeddings=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _prompts(lens=LENS, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 64, (n,)).astype("int32") for n in lens]


def _serve(lm, **kw):
    """Serve LENS/NEW on a fresh toy server; returns (rows, stats)."""
    srv = GenerationServer(lm, num_slots=4, block_size=4, max_model_len=32,
                           request_timeout_s=120.0, **kw)
    srv.start()
    t0 = time.perf_counter()     # prewarm traffic is not the session
    try:
        streams = [srv.submit(p, max_new_tokens=n)
                   for p, n in zip(_prompts(), NEW)]
        for s in streams:
            s.result(timeout=120)
        # with nothing in flight the loop waits in serve.idle: let one
        # such wait (50 ms) begin and end inside the session
        deadline = time.monotonic() + 60
        while not any(r.name == "serve.idle"
                      for r in timeline.spans("serve", since=t0)):
            assert time.monotonic() < deadline
            time.sleep(0.01)
    finally:
        # a stream ends inside the emit phase: stop() joins the
        # scheduler thread, so the last step and its counters are whole
        srv.stop()
    return timeline.spans("serve", since=t0), srv.stats()


@pytest.fixture(scope="module")
def session(lm):
    return _serve(lm)


def _named(rows, name):
    return [r for r in rows if r.name == name]


def test_every_span_of_the_plain_loop_is_in_the_ring(session):
    rows, _ = session
    assert {r.name for r in rows} == PLAIN
    for r in rows:
        assert r.t_end >= r.t_start
    assert len({r.tl for r in rows}) == 1        # one server wrote them
    assert all(r.step is None for r in _named(rows, "serve.idle"))
    # only the rows a reader needs carry counts
    assert {r.name for r in rows if r.args} == \
        {"serve.admit", "serve.prefill.stage", "serve.prefill.fetch",
         "serve.decode.dispatch"}


def test_spec_loop_is_one_span_and_leaves_no_hole(lm):
    rows, stats = _serve(lm, draft_model=lm, spec_k=3)
    spec = _named(rows, "serve.spec")
    assert len(spec) == stats["spec_verify_steps"] > 0
    assert not _named(rows, "serve.decode.dispatch")
    assert _named(rows, "serve.prefill.dispatch")


def test_phases_lie_inside_their_step_and_never_overlap(session):
    rows, _ = session
    steps = {r.step: r for r in _named(rows, "serve")}
    assert sorted(steps) == list(range(len(steps)))
    phases = [r for r in rows if "." in r.name]
    assert len({r.tid for r in rows}) == 1       # the scheduler thread
    for r in phases:
        if r.step is None:
            continue
        s = steps[r.step]
        assert s.t_start <= r.t_start and r.t_end <= s.t_end, r
    phases.sort(key=lambda r: r.t_start)
    for a, b in zip(phases, phases[1:]):
        assert a.t_end <= b.t_start, (a, b)
    # within a decode step the phases come in the order of the work:
    # the step is dispatched, then the one before it is read
    order = ["serve.admit", "serve.decode.grow", "serve.decode.stage",
             "serve.decode.dispatch", "serve.decode.fetch",
             "serve.decode.emit"]
    last = max(r.step for r in _named(rows, "serve.decode.dispatch")
               if r.args["overlapped"])
    got = [r.name for r in phases if r.step == last]
    assert [n for n in got if n in order] == order
    # a step dispatched with nothing in flight is left unread: the
    # iteration that dispatches the next one (or finds none to
    # dispatch) reads it
    first = min(r.step for r in _named(rows, "serve.decode.dispatch"))
    assert not [r for r in phases if r.step == first
                and r.name in ("serve.decode.fetch", "serve.decode.emit")]


def test_counts_at_the_span_boundaries_agree_with_stats(session):
    rows, st = session
    stage = _named(rows, "serve.prefill.stage")
    assert len(stage) == st["prefill_batches"]
    assert sum(r.args["tokens"] for r in stage) == st["prefill_tokens"] \
        == sum(LENS)
    for r in stage:
        a = r.args
        assert set(a) == {"bucket", "batch", "tokens"}
        assert 0 < a["tokens"] <= a["batch"] * a["bucket"]
    assert len(_named(rows, "serve.prefill.post")) == len(stage)
    admit = _named(rows, "serve.admit")
    waits = [w for r in admit for w in r.args["queue_wait_ms"]]
    assert len(waits) == st["admitted"] == len(LENS)
    assert all(w >= 0 for w in waits)
    assert len(_named(rows, "serve.decode.emit")) == st["decode_steps"]


def test_a_model_without_an_attention_plan_counts_no_pairs(session):
    """Only a model that answers ``prefill_attn_pairs`` (the latent
    models) adds to the two counters, and no span carries a share of
    them: the counters are where it is read."""
    rows, st = session
    assert st["prefill_batches"] > 0
    assert (st["prefill_attn_pairs_multiplied"],
            st["prefill_attn_pairs_square"]) == (0, 0)
    assert not [r for r in rows if "attn_pairs_share" in r.args]


def test_decode_ms_and_prefill_ms_are_read_off_the_spans(session):
    """The fetch of a decode step comes one iteration after its
    dispatch, behind the next step's dispatch and whatever prefill lay
    between: ``decode_ms`` takes a step from its dispatch, or from the
    end of the fetch before it where that is later, to the end of its
    own fetch, so that ``decode_ms`` + ``prefill_ms`` count no instant
    twice and stay inside the loop's wall time."""
    rows, st = session
    marks = sorted((r for r in rows if r.name in (
        "serve.decode.dispatch", "serve.decode.fetch",
        "serve.prefill.dispatch", "serve.prefill.fetch")),
        key=lambda r: r.t_start)
    total = {"decode": 0.0, "prefill": 0.0}
    unread = {"decode": [], "prefill": []}
    until = 0.0
    for r in marks:
        _, kind, what = r.name.split(".")
        if what == "dispatch":
            unread[kind].append(r.t_start)
        else:                 # results are read in the order dispatched
            total[kind] += r.t_end - max(unread[kind].pop(0), until)
            until = r.t_end
    assert unread == {"decode": [], "prefill": []}
    assert st["decode_ms"] == pytest.approx(total["decode"] * 1e3, rel=1e-9)
    assert st["prefill_ms"] == pytest.approx(total["prefill"] * 1e3,
                                             rel=1e-9)
    steps = _named(rows, "serve")
    wall_ms = (max(r.t_end for r in steps)
               - min(r.t_start for r in steps)) * 1e3
    assert 0 < st["decode_ms"] + st["prefill_ms"] <= wall_ms


def test_overlapped_is_an_arg_of_every_decode_dispatch(session):
    rows, st = session
    disp = _named(rows, "serve.decode.dispatch")
    assert len(disp) == st["decode_steps"] > 0
    assert all(set(r.args) == {"overlapped"} and r.args["overlapped"]
               in (0, 1) for r in disp)
    assert sum(r.args["overlapped"] for r in disp) == \
        st["decode_steps_overlapped"] > 0
    # an overlapped step's dispatch lies between the dispatch and the
    # fetch of the step before it
    fetch = _named(rows, "serve.decode.fetch")
    for before, d, f in zip(disp, disp[1:], fetch):
        if d.args["overlapped"]:
            assert before.t_end <= d.t_start and d.t_end <= f.t_start


def test_a_prefill_call_is_read_behind_the_decode_dispatch_after_it(
        session, lm):
    """ISSUE 34: an iteration dispatches its prefill calls, then the
    next decode step with their rows in it, and only then reads: the
    calls, in the order dispatched, then the step that was in flight.
    ``overlapped`` on ``serve.prefill.fetch`` says a decode dispatch
    came between, and ``stats()["prefills_overlapped"]`` counts it."""
    rows, st = session
    fetch = _named(rows, "serve.prefill.fetch")
    assert len(fetch) == st["prefill_batches"] > 0
    assert all(set(r.args) == {"overlapped"} for r in fetch)
    assert sum(r.args["overlapped"] for r in fetch) == \
        st["prefills_overlapped"] > 0
    by_step = {}
    for r in rows:
        if r.step is not None and "." in r.name:
            by_step.setdefault(r.step, []).append(r)
    for f in fetch:
        phases = sorted(by_step[f.step], key=lambda r: r.t_start)
        names = [r.name for r in phases]
        disp = [r for r in phases if r.name == "serve.decode.dispatch"]
        # every request here asks for more than one token and the pool
        # is ample: a step is dispatched behind every call
        assert f.args["overlapped"] == 1 and len(disp) == 1
        assert disp[0].t_end <= f.t_start
        # the iteration's own calls, all dispatched before the step
        calls = [r for r in phases if r.name == "serve.prefill.dispatch"]
        assert calls and all(c.t_end <= disp[0].t_start for c in calls)
        # the step that was in flight is read behind the calls
        if "serve.decode.fetch" in names:
            assert names.index("serve.decode.fetch") \
                > len(names) - 1 - names[::-1].index("serve.prefill.post")
        assert names.count("serve.prefill.fetch") == len(calls) \
            == names.count("serve.prefill.post")
    # a server in speculative mode reads every call at once
    rows, st = _serve(lm, draft_model=lm, spec_k=3)
    fetch = _named(rows, "serve.prefill.fetch")
    assert len(fetch) == st["prefill_batches"] > 0
    assert st["prefills_overlapped"] == 0
    assert not any(r.args["overlapped"] for r in fetch)
    for f in fetch:
        d = max((r for r in _named(rows, "serve.prefill.dispatch")
                 if r.t_end <= f.t_start), key=lambda r: r.t_end)
        assert d.step == f.step
        assert not [r for r in rows if r.name != "serve"
                    and d.t_end <= r.t_start and r.t_end <= f.t_start]


def test_a_readmission_waits_once_more(lm):
    # 13 allocatable blocks for 4 sequences that each grow to 6:
    # concurrent traffic must evict
    srv = GenerationServer(lm, num_slots=4, block_size=4, num_blocks=14,
                           max_model_len=24, check_replay=True,
                           request_timeout_s=120.0)
    srv.start()
    t0 = time.perf_counter()
    try:
        streams = [srv.submit(p, max_new_tokens=12, priority=i)
                   for i, p in enumerate(_prompts(lens=(6, 10, 4, 8),
                                                  seed=1))]
        for s in streams:
            s.result(timeout=120)
    finally:
        srv.stop()
    st, rows = srv.stats(), timeline.spans("serve", since=t0)
    assert st["evicted"] > 0
    # a re-admission is admitted (and its wait read) once more
    waits = [w for r in _named(rows, "serve.admit")
             for w in r.args["queue_wait_ms"]]
    assert len(waits) == st["admitted"] == 4 + st["readmitted"]
    # a step that only replays still is a decode step with all its spans
    assert len(_named(rows, "serve.decode.emit")) == st["decode_steps"] \
        == len(_named(rows, "serve.decode.grow"))


def test_profiler_trace_holds_the_serve_spans_on_the_ops_clock(lm,
                                                               tmp_path):
    import jax
    from perfbench.harness import trace_reduce as TR
    srv = GenerationServer(lm, num_slots=4, block_size=4, max_model_len=32,
                           request_timeout_s=120.0)
    srv.start()
    try:
        srv.submit(_prompts()[0], max_new_tokens=2).result(timeout=120)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # as SubWindowTrace sets it
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for s in [srv.submit(p, max_new_tokens=5)
                      for p in _prompts()[:3]]:
                s.result(timeout=120)
            # a stream ends inside the emit phase: join the scheduler
            # thread so that its last step closes under the trace
            srv.stop()
        finally:
            jax.profiler.stop_trace()
    finally:
        srv.stop()
    events = TR.events_from_xplane(TR.find_xplane(str(tmp_path)))
    host = [e for e in events if not TR.is_device(e.plane)]
    names = {e.name for e in host}
    assert {"serve", "serve.admit", "serve.prefill.dispatch",
            "serve.decode.stage", "serve.decode.dispatch",
            "serve.decode.fetch", "serve.decode.emit"} <= names
    # a span that was open when the trace started is not in it: look
    # from the first step that began under the trace
    steps = [e for e in host if e.name == "serve"]
    t_first = min(e.start for e in steps)
    disp = [e for e in host if e.name == "serve.decode.dispatch"
            and e.start >= t_first]
    calls = [e for e in host if e.name.startswith("PjitFunction(decode_fn")
             and e.start >= t_first]
    assert disp and calls

    def inside(a, b):
        return b.start <= a.start and a.start + a.dur <= b.start + b.dur
    # every jitted decode call lies inside one dispatch span on the
    # scheduler thread's line, every dispatch span holds one, and every
    # dispatch span lies inside a step
    for c in calls:
        assert any(d.line == c.line and inside(c, d) for d in disp), c
    for d in disp:
        assert any(inside(c, d) for c in calls), d
        assert any(inside(d, st) for st in steps), d


def test_idle_gap_is_put_down_to_the_scheduler_phase():
    from perfbench.harness import trace_reduce as TR
    E = TR.Event
    dev, host = "/device:TPU:0", "/host:CPU"
    op = "%fusion.1 = f32[8] fusion()"
    events = [
        E(dev, TR.OPS_LINE, op, 0.000, 0.010),      # decode step k
        E(dev, TR.OPS_LINE, op, 0.013, 0.010),      # decode step k+1
        E(dev, TR.OPS_LINE, op, 0.0235, 0.010),     # ... k+2
        # the client thread polls all along; the scheduler thread:
        E(host, "client", "bench.wait_streams", 0.0, 0.040),
        E(host, "sched", "serve.decode.fetch", 0.001, 0.0092),
        E(host, "sched", "serve.decode.emit", 0.0102, 0.0020),
        E(host, "sched", "serve.decode.stage", 0.0122, 0.0004),
        E(host, "sched", "serve.decode.dispatch", 0.0126, 0.0010),
        E(host, "sched", "PjitFunction(decode_fn)", 0.0127, 0.0008),
        E(host, "sched", "serve.decode.fetch", 0.0136, 0.0096),
        E(host, "sched", "serve.decode.dispatch", 0.0232, 0.0006),
    ]
    gaps = dict(TR.idle_gaps(events, span_prefix="serve."))
    # the 3 ms gap: 2.0 ms of it under emit, more than under any other
    assert gaps["serve.decode.emit__PjitFunction_decode_fn_"] == \
        pytest.approx(0.003)
    assert gaps["serve.decode.dispatch"] == pytest.approx(0.0005)
    # the benchmark's own prefix sees one span for both
    assert [k.split("__")[0] for k, _ in TR.idle_gaps(events)] == \
        ["bench.wait_streams", "bench.wait_streams"]


@pytest.fixture
def fresh_ring(monkeypatch):
    ring = deque(maxlen=timeline.RING_MAXLEN)
    monkeypatch.setattr(timeline, "_ring", ring)
    return ring


def test_ring_is_bounded_and_cut_by_since_and_until(fresh_ring,
                                                    monkeypatch):
    name = "ring_probe"
    tl = timeline.StepTimeline(name)
    clock = iter(range(10 ** 6))
    monkeypatch.setattr(timeline, "perf_counter", lambda: float(next(clock)))
    for i in range(5):                  # phase i spans [2i, 2i + 1]
        with tl.phase("p", i=i):
            pass
    rows = timeline.spans(name)
    assert [(r.t_start, r.t_end) for r in rows] == \
        [(2.0 * i, 2.0 * i + 1) for i in range(5)]
    assert [r.args["i"] for r in timeline.spans(name, since=2.0)] == \
        [1, 2, 3, 4]
    assert [r.args["i"] for r in timeline.spans(name, until=5.0)] == \
        [0, 1, 2]
    # a row that straddles an edge is out: only whole phases count
    assert [r.args["i"] for r in
            timeline.spans(name, since=2.5, until=8.5)] == [2, 3]
    rows.clear()                        # a copy: the ring is untouched
    assert len(timeline.spans(name)) == 5
    # bounded: the oldest rows go once RING_MAXLEN is reached
    assert timeline.RING_MAXLEN == 65536 == fresh_ring.maxlen
    for i in range(5, timeline.RING_MAXLEN + 7):
        with tl.phase("p", i=i):
            pass
    rows = timeline.spans(name)
    assert len(rows) == timeline.RING_MAXLEN
    assert rows[0].args["i"] == 7 and \
        rows[-1].args["i"] == timeline.RING_MAXLEN + 6
    assert timeline.spans("never_made") == []


def test_one_ring_tells_names_and_instances_apart(fresh_ring):
    a, b = timeline.StepTimeline("serve"), timeline.StepTimeline("serve")
    other = timeline.StepTimeline("server")    # "serve" is its prefix
    for tl in (a, b, other):
        with tl.step(0):
            with tl.phase("admit"):
                pass
    rows = timeline.spans("serve")
    assert [r.name for r in rows] == ["serve.admit", "serve"] * 2
    assert [r.tl for r in rows] == [a._serial] * 2 + [b._serial] * 2
    assert a._serial != b._serial and {r.step for r in rows} == {0}
    assert [r.name for r in timeline.spans("server")] == \
        ["server.admit", "server"]


def test_timeline_with_sinks_off_records_and_writes_no_file(
        fresh_ring, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("PADDLE_TRACE_DIR", str(tmp_path))
    assert not trace.enabled() and not monitor.metrics_enabled()
    before = monitor.metrics_snapshot()
    tl = timeline.StepTimeline("quiet")
    for i in range(3):
        with tl.step(i):
            with tl.phase("dispatch", rows=2) as ph:
                ph.set(done=True)
    rows = timeline.spans("quiet")
    assert [r.name for r in rows] == ["quiet.dispatch", "quiet"] * 3
    assert rows[0].args == {"rows": 2, "done": True}
    assert rows[0].tid == threading.get_ident() and rows[0].step == 0
    assert not list(tmp_path.iterdir())
    assert monitor.metrics_snapshot() == before


def test_phase_outside_a_step_is_not_sampled_into_the_jsonl(
        fresh_ring, tmp_path):
    """An idle wait after a sampled step belongs to no step: it is a
    ring row, and not one more JSONL span every 50 ms."""
    trace.enable(dir=str(tmp_path), role="idle", every=1)
    try:
        tl = timeline.StepTimeline("serve")
        with tl.step(0):
            with tl.phase("admit"):
                pass
        for _ in range(3):
            with tl.phase("idle"):
                pass
    finally:
        trace.disable()
    recs = [json.loads(line) for line in
            open(tmp_path / f"trace-idle-{os.getpid()}.jsonl") if line.strip()]
    assert sorted(r["name"] for r in recs if r.get("t") == "span") == \
        ["serve", "serve.admit"]
    assert [r.name for r in timeline.spans("serve")] == \
        ["serve.admit", "serve"] + ["serve.idle"] * 3


def test_train_step_gets_the_ring_with_no_edit_of_its_own():
    from paddle_tpu import nn
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.dist_step import DistributedTrainStep
    paddle.seed(3)
    net = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    step = DistributedTrainStep(
        net, lambda x, y: ((net(x) - y) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((8, 4), np.float32))
    y = paddle.to_tensor(np.zeros((8, 2), np.float32))
    t0 = time.perf_counter()
    try:
        for _ in range(2):
            step(x, y)
    finally:
        mesh_mod.set_mesh(None)     # the step made a default mesh
    rows = timeline.spans("train_step", since=t0)
    assert [r.name for r in rows] == [
        "train_step.h2d", "train_step.dispatch", "train_step.host",
        "train_step"] * 2
    assert [r.step for r in rows if r.name == "train_step"] == [0, 1]
