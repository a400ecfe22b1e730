"""ISSUE 5: unified observability — cross-process tracing, metric
histograms, Prometheus exposition, trace merging, and the telemetry
no-perturbation contract.

Coverage map (the ISSUE's test satellite):
- span nesting + trace/span-id propagation across a REAL
  PSClient <-> PSServer RPC (the server's apply span parents under the
  client's push span);
- fixed-bucket histogram quantiles vs numpy percentiles;
- Prometheus text exposition golden test + live /metrics endpoint;
- tools/trace_merge.py: clock-offset-corrected, parented, monotonic
  spans from two hand-skewed process sink files;
- the acceptance bar: a multi-process wide_deep-style run (trainer +
  PS primary subprocess + replica subprocess) merged into one Chrome
  trace where every client push/pull span parents its server-side
  apply span;
- bit-identical training math with telemetry on vs off (tracing and
  metrics may only ever READ clocks — any RNG/math perturbation is a
  bug this test catches).
"""
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.framework import monitor
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import trace
from paddle_tpu.observability.timeline import StepTimeline

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MERGE = os.path.join(_REPO, "tools", "trace_merge.py")


@pytest.fixture(autouse=True)
def _obs_clean():
    """Tracing state must never leak between tests (the run_tier1
    --trace pass runs the whole suite with PADDLE_TRACE=1 — sinks go
    where each test pointed them, then OFF again)."""
    yield
    trace.disable()
    monitor.enable_metrics(os.environ.get("PADDLE_METRICS", "0") == "1")


def _read_sink(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def _spans(recs, name=None):
    out = [r for r in recs if r.get("t") == "span"]
    if name is not None:
        out = [r for r in out if r["name"] == name]
    return out


# ---------------------------------------------------------------------------
# spans: nesting, ids, sampling
# ---------------------------------------------------------------------------

def test_span_nesting_parents_and_one_trace(tmp_path):
    trace.enable(dir=str(tmp_path), role="t")
    with trace.span("outer", cat="x", k=1):
        with trace.span("mid"):
            with trace.span("inner"):
                pass
    with trace.span("other_root"):
        pass
    trace.disable()
    recs = _read_sink(tmp_path / f"trace-t-{os.getpid()}.jsonl")
    outer, = _spans(recs, "outer")
    mid, = _spans(recs, "mid")
    inner, = _spans(recs, "inner")
    root2, = _spans(recs, "other_root")
    assert mid["parent"] == outer["span"]
    assert inner["parent"] == mid["span"]
    assert outer.get("parent") is None
    assert outer["trace"] == mid["trace"] == inner["trace"]
    # a fresh root = a fresh causal chain
    assert root2["trace"] != outer["trace"]
    assert outer["args"] == {"k": 1}


def test_disabled_tracing_is_nullspan_and_writes_nothing(tmp_path):
    assert not trace.enabled()
    sp = trace.span("nope")
    with sp:
        pass
    assert not list(tmp_path.iterdir())


def test_timeline_sampling_trace_every(tmp_path):
    trace.enable(dir=str(tmp_path), role="tl", every=2)
    tl = StepTimeline("train_step")
    for i in range(5):
        with tl.step(i):
            with tl.phase("dispatch"):
                pass
    trace.disable()
    recs = _read_sink(tmp_path / f"trace-tl-{os.getpid()}.jsonl")
    steps = sorted(s["args"]["step"] for s in _spans(recs, "train_step"))
    assert steps == [0, 2, 4]          # 1/2 sampling
    # phases only exist under sampled steps, parented to them
    phases = _spans(recs, "train_step.dispatch")
    assert len(phases) == 3
    step_ids = {s["span"] for s in _spans(recs, "train_step")}
    assert all(p["parent"] in step_ids for p in phases)


# ---------------------------------------------------------------------------
# propagation across a real PS RPC
# ---------------------------------------------------------------------------

def test_ps_rpc_spans_propagate_client_to_server(tmp_path):
    from paddle_tpu.distributed.fleet.ps import SparseTable
    from paddle_tpu.distributed.fleet.ps_service import PSClient, PSServer
    trace.enable(dir=str(tmp_path), role="inproc")
    srv = PSServer({"emb": SparseTable(4, optimizer="sgd", lr=0.5,
                                       seed=3)}, host="127.0.0.1")
    srv.start()
    cli = PSClient([f"127.0.0.1:{srv.port}"], worker_id="w0")
    ids = np.arange(8, dtype=np.int64)
    cli.pull("emb", ids)
    cli.push("emb", ids, np.ones((8, 4), np.float32))
    cli.close()
    srv.stop()
    # the server span closes AFTER the reply is on the wire: give the
    # serve thread its beat before freezing the sink
    sink = tmp_path / f"trace-inproc-{os.getpid()}.jsonl"
    deadline = time.monotonic() + 5.0
    while "ps.server.push" not in sink.read_text():
        assert time.monotonic() < deadline, "server spans never landed"
        time.sleep(0.01)
    trace.disable()
    recs = _read_sink(sink)
    for op in ("pull", "push"):
        c, = _spans(recs, f"ps.client.{op}")
        s, = _spans(recs, f"ps.server.{op}")
        assert s["parent"] == c["span"], op
        assert s["trace"] == c["trace"], op
        # the server handler ran inside the client's RPC window
        assert s["ts_us"] >= c["ts_us"] - 1000
        assert s["ts_us"] + s["dur_us"] <= c["ts_us"] + c["dur_us"] + 1000
    # the register round trip produced a clock sample naming the
    # server's sink (here: our own pid — in-process server)
    clocks = [r for r in recs if r.get("t") == "clock"]
    assert clocks and clocks[0]["peer"] == f"inproc-{os.getpid()}"
    assert abs(clocks[0]["offset_us"]) < 1e6   # same machine, same clock


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------

def test_histogram_bucket_counts_sum_and_overflow():
    h = monitor.Histogram(buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 3.0, 50.0, 1e9):
        h.observe(v)
    assert h.counts == [2, 1, 1, 1]    # le semantics: 1.0 lands in [<=1]
    assert h.count == 5
    assert h.sum == pytest.approx(0.5 + 1.0 + 3.0 + 50.0 + 1e9)
    snap = h.snapshot()
    assert snap["buckets"] == [[1.0, 2], [10.0, 3], [100.0, 4]]
    # overflow clamps to the last finite bound
    assert h.percentile(99.9) == 100.0


def test_histogram_percentiles_match_numpy():
    rng = np.random.RandomState(7)
    xs = rng.uniform(0.0, 100.0, 50000)
    h = monitor.Histogram(buckets=[float(b) for b in range(1, 101)])
    for x in xs:
        h.observe(x)
    for q in (10, 50, 90, 99):
        est = h.percentile(q)
        ref = float(np.percentile(xs, q))
        # within ~1.5 bucket widths (bucket width = 1.0)
        assert abs(est - ref) < 1.5, (q, est, ref)


def test_registry_gauges_and_hist_names():
    monitor.gauge_set("obs_test_gauge", 3.5)
    monitor.gauge_add("obs_test_gauge", 1.0)
    assert monitor.gauge_get("obs_test_gauge") == 4.5
    monitor.hist_observe("obs_test_hist_ms", 12.0)
    snap = monitor.metrics_snapshot()
    assert snap["gauges"]["obs_test_gauge"] == 4.5
    assert snap["histograms"]["obs_test_hist_ms"]["count"] == 1


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

def test_prometheus_text_golden():
    snap = {
        "counters": {"ps_client_retries": 3},
        "gauges": {"serve_queue_depth": 2.5},
        "histograms": {"rpc_ms": {
            "buckets": [[1.0, 1], [5.0, 3]], "sum": 7.5, "count": 4}},
    }
    bi = obs_metrics.build_info()
    expected = (
        "# TYPE paddle_build_info gauge\n"
        "paddle_build_info{"
        + ",".join(f'{k}="{bi[k]}"' for k in sorted(bi)) + "} 1\n"
        "# TYPE paddle_ps_client_retries counter\n"
        "paddle_ps_client_retries 3\n"
        "# TYPE paddle_serve_queue_depth gauge\n"
        "paddle_serve_queue_depth 2.5\n"
        "# TYPE paddle_rpc_ms histogram\n"
        'paddle_rpc_ms_bucket{le="1"} 1\n'
        'paddle_rpc_ms_bucket{le="5"} 3\n'
        'paddle_rpc_ms_bucket{le="+Inf"} 4\n'
        "paddle_rpc_ms_sum 7.5\n"
        "paddle_rpc_ms_count 4\n"
    )
    assert obs_metrics.prometheus_text(snap) == expected


def test_build_info_gauge_names_real_versions():
    bi = obs_metrics.build_info()
    assert set(bi) == {"version", "jax", "jaxlib"}
    import paddle_tpu
    assert bi["version"] == paddle_tpu.__version__
    # dist metadata, not an import: the PS server process must be able
    # to answer a scrape without pulling jax in
    import jax
    assert bi["jax"] == jax.__version__


def test_metrics_endpoint_serves_live_registry():
    monitor.stat_add("obs_endpoint_counter", 7)
    monitor.gauge_set("obs_endpoint_gauge", 1.25)
    srv = obs_metrics.MetricsServer(port=0, host="127.0.0.1").start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5) as r:
            assert r.status == 200
            assert "version=0.0.4" in r.headers["Content-Type"]
            body = r.read().decode()
        assert "paddle_obs_endpoint_counter 7" in body
        assert "paddle_obs_endpoint_gauge 1.25" in body
        assert "paddle_build_info{" in body
    finally:
        srv.stop()


def test_metrics_healthz_endpoint():
    import urllib.error
    srv = obs_metrics.MetricsServer(port=0, host="127.0.0.1").start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5) as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == "application/json"
            body = json.loads(r.read().decode())
        assert body["status"] == "ok"
        assert body["pid"] == os.getpid()
        assert body["uptime_s"] >= 0
        assert "role" in body and "version" in body
        # unknown paths still 404
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=5)
    finally:
        srv.stop()


def test_metrics_flusher_writes_snapshots(tmp_path):
    monitor.stat_add("obs_flush_counter", 2)
    fl = obs_metrics.MetricsFlusher(str(tmp_path / "m.jsonl"),
                                    interval_s=3600)
    fl.flush_once()
    fl.flush_once()
    recs = _read_sink(tmp_path / "m.jsonl")
    assert len(recs) == 2
    assert recs[0]["counters"]["obs_flush_counter"] >= 2
    assert "ts_us" in recs[0] and "gauges" in recs[0]


# ---------------------------------------------------------------------------
# trace_merge: clock correction + parenting from synthetic sinks
# ---------------------------------------------------------------------------

def _write_sink(path, recs):
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def test_trace_merge_corrects_skewed_clocks(tmp_path):
    """Two hand-written process sinks, the peer's clock 5 s ahead: after
    the merge the server span must sit INSIDE its parent client span on
    one monotonic timeline."""
    skew = 5_000_000           # peer clock ahead by 5 s
    t0 = 1_000_000
    trainer = tmp_path / "trace-trainer-1.jsonl"
    ps = tmp_path / "trace-ps0-2.jsonl"
    _write_sink(trainer, [
        {"t": "meta", "sink": "trainer-1", "role": "trainer", "pid": 1},
        {"t": "clock", "peer": "ps0-2", "offset_us": skew,
         "rtt_us": 120},
        {"t": "span", "name": "ps.client.push", "cat": "rpc",
         "ts_us": t0, "dur_us": 10_000, "pid": 1, "tid": 4,
         "trace": "tr1", "span": "c1"},
    ])
    _write_sink(ps, [
        {"t": "meta", "sink": "ps0-2", "role": "ps0", "pid": 2},
        {"t": "span", "name": "ps.server.push", "cat": "rpc",
         "ts_us": t0 + skew + 2_000, "dur_us": 3_000, "pid": 2,
         "tid": 9, "trace": "tr1", "span": "s1", "parent": "c1"},
    ])
    out = tmp_path / "merged.json"
    r = subprocess.run(
        [sys.executable, _MERGE, str(trainer), str(ps), "-o", str(out)],
        capture_output=True, text=True, cwd=_REPO)
    assert r.returncode == 0, r.stderr
    merged = json.load(open(out))
    evs = merged["traceEvents"]
    client = next(e for e in evs if e.get("name") == "ps.client.push")
    server = next(e for e in evs if e.get("name") == "ps.server.push")
    # the 5 s skew is gone: the server span is inside the client span
    assert client["ts"] == t0
    assert server["ts"] == t0 + 2_000
    assert server["ts"] >= client["ts"]
    assert server["ts"] + server["dur"] <= client["ts"] + client["dur"]
    # cross-process parent -> one flow arrow client -> server
    flows_s = [e for e in evs if e["ph"] == "s"]
    flows_f = [e for e in evs if e["ph"] == "f"]
    assert len(flows_s) == 1 and len(flows_f) == 1
    assert flows_s[0]["pid"] == client["pid"]
    assert flows_f[0]["pid"] == server["pid"]
    assert merged["metadata"]["clock_offsets_us"]["ps0-2"] == skew
    # distinct synthetic pids per sink; X events sorted monotonically
    assert client["pid"] != server["pid"]
    xs = [e["ts"] for e in evs if e["ph"] == "X"]
    assert xs == sorted(xs)


def test_trace_merge_degrades_on_sink_without_clock_edge(tmp_path):
    """A sink with NO clock-offset path to the root must degrade, not
    fail: the merge exits 0, warns on stderr, emits the island sink's
    spans on its own (uncorrected) timeline, and lists it under
    metadata.uncorrected."""
    trainer = tmp_path / "trace-trainer-1.jsonl"
    island = tmp_path / "trace-island-9.jsonl"
    _write_sink(trainer, [
        {"t": "meta", "sink": "trainer-1", "role": "trainer", "pid": 1},
        {"t": "span", "name": "step", "cat": "step", "ts_us": 1000,
         "dur_us": 500, "pid": 1, "tid": 1, "trace": "t1",
         "span": "a"},
        # a clock sample naming a peer that never wrote a sink must
        # not confuse the solver either
        {"t": "clock", "peer": "ghost-7", "offset_us": 42.0,
         "rtt_us": 10},
    ])
    _write_sink(island, [
        {"t": "meta", "sink": "island-9", "role": "serve", "pid": 9},
        {"t": "span", "name": "serve.batch", "cat": "serve",
         "ts_us": 77_000, "dur_us": 250, "pid": 9, "tid": 2,
         "trace": "t2", "span": "b"},
    ])
    out = tmp_path / "merged.json"
    r = subprocess.run(
        [sys.executable, _MERGE, str(trainer), str(island),
         "-o", str(out)],
        capture_output=True, text=True, cwd=_REPO)
    assert r.returncode == 0, r.stderr
    assert "no clock path" in r.stderr and "island-9" in r.stderr
    merged = json.load(open(out))
    assert merged["metadata"]["clock_offsets_us"]["island-9"] is None
    assert merged["metadata"]["uncorrected"] == ["island-9"]
    evs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    # both spans survived; the island span kept its own clock
    names = {e["name"] for e in evs}
    assert names == {"step", "serve.batch"}
    isl = next(e for e in evs if e["name"] == "serve.batch")
    assert isl["ts"] == 77_000


# ---------------------------------------------------------------------------
# acceptance: multi-process wide_deep run -> one merged, parented trace
# ---------------------------------------------------------------------------

_SERVER_SRC = r"""
import json, os, sys
sys.path.insert(0, sys.argv[1])
cfg = json.loads(sys.argv[2])
from paddle_tpu.distributed.fleet.ps import SparseTable
from paddle_tpu.distributed.fleet.ps_service import PSServer
tables = {n: SparseTable(**kw) for n, kw in cfg["tables"].items()}
srv = PSServer(tables, host="127.0.0.1",
               replica_of=cfg.get("replica_of"))
srv.start()
print(json.dumps({"port": srv.port, "pid": os.getpid()}), flush=True)
srv._stop.wait()
from paddle_tpu.observability import trace
trace.flush()
"""

_SPEC = {"emb": dict(dim=4, optimizer="adagrad", lr=0.1, seed=23)}


def _spawn_server(tmp_dir, role, replica_of=None, telemetry=True):
    env = dict(os.environ)
    env.pop("PADDLE_CHAOS", None)
    if telemetry:
        env.update(PADDLE_TRACE="1", PADDLE_TRACE_DIR=str(tmp_dir),
                   PADDLE_TRACE_ROLE=role, PADDLE_METRICS="1")
    else:
        env.pop("PADDLE_TRACE", None)
        env.pop("PADDLE_METRICS", None)
    cfg = {"tables": _SPEC, "replica_of": replica_of}
    proc = subprocess.Popen(
        [sys.executable, "-c", _SERVER_SRC, _REPO, json.dumps(cfg)],
        stdout=subprocess.PIPE, text=True, env=env)
    info = json.loads(proc.stdout.readline())
    return proc, f"127.0.0.1:{info['port']}", info["pid"]


def _train_rows(ep, steps=6):
    """The deterministic wide_deep-style loop of the PR 3 acceptance
    test: pull rows, push a step-derived gradient."""
    from paddle_tpu.distributed.fleet.ps_service import PSClient
    cli = PSClient([ep], mode="sync", worker_id="w0",
                   connect_timeout=5.0, rpc_timeout=5.0, max_retries=4,
                   backoff_base=0.02, rpc_deadline=30.0)
    ids = np.arange(16, dtype=np.int64)
    for step in range(steps):
        cli.pull("emb", ids)
        g = np.full((16, 4), 0.125 * ((step % 5) + 1), np.float32)
        cli.push("emb", ids, g)
    final = cli.pull("emb", ids).copy()
    cli.stop_server()
    cli.close()
    return final


def test_multiprocess_wide_deep_merged_trace(tmp_path):
    """Trainer + PS primary subprocess + hot-standby replica subprocess,
    all traced; tools/trace_merge.py fuses the three sinks and every
    client push/pull span contains its server-side apply span — with
    the replica's apply chained under the primary's forward."""
    prim, prim_ep, prim_pid = _spawn_server(tmp_path, "ps0")
    rep, rep_ep, rep_pid = _spawn_server(tmp_path, "ps0r",
                                         replica_of=prim_ep)
    trace.enable(dir=str(tmp_path), role="trainer")
    try:
        # wait for the replica to catch up (its sink then has the
        # replicate clock sample)
        # (the waits below are generous: under a loaded host a server
        # subprocess takes its time, and a wait that is too short shows
        # nothing about the trace)
        deadline = time.monotonic() + 90.0
        while not os.path.exists(
                tmp_path / f"trace-ps0r-{rep_pid}.jsonl"):
            assert time.monotonic() < deadline, "replica never attached"
            time.sleep(0.05)
        _train_rows(prim_ep, steps=6)
    finally:
        trace.disable()
        for p in (prim, rep):
            try:
                p.terminate()
            except OSError:
                pass
        for p in (prim, rep):
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=60)

    sinks = [str(tmp_path / f"trace-trainer-{os.getpid()}.jsonl"),
             str(tmp_path / f"trace-ps0-{prim_pid}.jsonl"),
             str(tmp_path / f"trace-ps0r-{rep_pid}.jsonl")]
    for s in sinks:
        assert os.path.exists(s), s
    out = tmp_path / "merged.json"
    r = subprocess.run([sys.executable, _MERGE] + sinks
                       + ["-o", str(out)],
                       capture_output=True, text=True, cwd=_REPO)
    assert r.returncode == 0, r.stderr
    # every sink found a clock path to the trainer's timeline
    merged = json.load(open(out))
    offs = merged["metadata"]["clock_offsets_us"]
    assert all(v is not None for v in offs.values()), offs
    # how far a corrected timestamp of each process can be off: half
    # the round trip its offset was estimated from (pid i+1 = sink i)
    errs = merged["metadata"]["clock_error_us"]
    assert list(errs) == list(offs) and errs[list(errs)[0]] == 0.0
    err_of_pid = {i + 1: e for i, e in enumerate(errs.values())}

    evs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    by_span = {e["args"]["span"]: e for e in evs}
    pids = {e["pid"] for e in evs}
    assert len(pids) == 3              # three process tracks survived

    # the serve thread of a connection takes one request at a time: the
    # request that followed each server span on its track, and the
    # client call that carried it
    next_call = {}
    tracks = {}
    for k in sorted(evs, key=lambda k: k["ts"]):
        if k["name"].startswith("ps.server."):
            tracks.setdefault((k["pid"], k["tid"]), []).append(k)
    for track in tracks.values():
        for k, nxt in zip(track, track[1:]):
            next_call[k["args"]["span"]] = by_span.get(
                nxt["args"].get("parent"))

    def end(ev):
        return ev["ts"] + ev["dur"]

    checked = 0
    for e in evs:
        if e["name"] not in ("ps.client.push", "ps.client.pull"):
            continue
        kids = [k for k in evs
                if k["args"].get("parent") == e["args"]["span"]
                and k["name"].startswith("ps.server.")]
        assert kids, f"client span {e['name']} has no server child"
        for k in kids:
            assert k["args"]["trace"] == e["args"]["trace"]
            assert k["pid"] != e["pid"]
            # clock-corrected containment: 1 ms of slack, plus what
            # the recorded round trip says the correction itself can
            # be off by (a loaded host widens that trip)
            slack = 1000 + err_of_pid[k["pid"]]
            assert e["ts"] - slack <= k["ts"] <= end(e) + slack
            checked += 1
            if end(k) <= end(e) + slack:
                continue
            # The server span closes AFTER its reply is on the wire, so
            # a serve thread that loses the CPU right there closes
            # late whatever the clocks say: with 16 busy processes on
            # 8 cores, 4 runs of 24 had one span end 517 to 3,516 us
            # past this slack, each 2.3 to 4.8 ms longer than the
            # client's whole call.  What the client recorded still
            # bounds it: the thread closed this span before it took
            # the connection's next request, so before the call that
            # carried that request returned.
            nxt = next_call.get(k["args"]["span"])
            assert nxt is not None and nxt["pid"] == e["pid"], k
            assert end(e) <= nxt["ts"] and end(k) <= end(nxt) + slack
    assert checked >= 12               # 6 pulls + 6 pushes at least

    # the replication chain: primary's forward span (child of its
    # server apply) parents the replica's apply span, cross-process
    fwd = [e for e in evs if e["name"] == "ps.replica.forward"]
    rep_applies = [e for e in evs if e["name"] == "ps.replica.apply"]
    assert fwd and rep_applies
    fwd_ids = {e["args"]["span"] for e in fwd}
    assert any(e["args"].get("parent") in fwd_ids for e in rep_applies)
    for e in fwd:
        par = by_span.get(e["args"].get("parent"))
        assert par is not None and par["name"] == "ps.server.push"


def test_wide_deep_telemetry_is_bit_identical(tmp_path):
    """Same seeds, telemetry off vs tracing+metrics on: the pulled rows
    after 6 deterministic steps must be np.array_equal — observability
    may read clocks, never touch math."""
    proc, ep, _pid = _spawn_server(tmp_path / "plain", "ps0",
                                   telemetry=False)
    try:
        ref = _train_rows(ep)
    finally:
        proc.wait(timeout=10)

    monitor.enable_metrics(True)
    trace.enable(dir=str(tmp_path), role="trainer2")
    proc, ep, _pid = _spawn_server(tmp_path, "ps0b", telemetry=True)
    try:
        got = _train_rows(ep)
    finally:
        proc.wait(timeout=10)
        trace.disable()
        monitor.enable_metrics(False)
    assert np.array_equal(got, ref)
    # telemetry actually ran: rpc latency histogram collected samples
    h = monitor.get_histogram("ps_rpc_ms")
    assert h is not None and h.count >= 12


def test_hapi_fit_telemetry_is_bit_identical(tmp_path):
    """Dense-path twin of the wide_deep check: a 4-step hapi fit with
    tracing+metrics on reaches bit-identical weights to the silent run
    (spans must not consume seeded RNG or reorder math)."""
    def run(telemetry):
        if telemetry:
            monitor.enable_metrics(True)
            trace.enable(dir=str(tmp_path), role="fit", every=1)
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                            nn.Linear(16, 3))
        model = paddle.Model(net)
        model.prepare(paddle.optimizer.Adam(
            learning_rate=0.01, parameters=net.parameters()),
            nn.CrossEntropyLoss())
        rng = np.random.RandomState(0)
        x = rng.randn(32, 8).astype("float32")
        y = rng.randint(0, 3, (32,)).astype("int64")
        # a generator of prebuilt (x, y) batches (fit's "any iterable
        # of batches" path — a list would be wrapped as a Dataset)
        model.fit((b for b in [(x, y)] * 4), epochs=1, verbose=0)
        out = [p.numpy().copy() for p in net.parameters()]
        if telemetry:
            trace.disable()
            monitor.enable_metrics(False)
        return out

    ref = run(False)
    got = run(True)
    for a, b in zip(ref, got):
        assert np.array_equal(a, b)
    # and the fit loop actually emitted its step timeline
    recs = _read_sink(tmp_path / f"trace-fit-{os.getpid()}.jsonl")
    assert _spans(recs, "fit")
    assert _spans(recs, "fit.data_wait")
    assert _spans(recs, "fit.dispatch")


# ---------------------------------------------------------------------------
# hapi guard surfacing + automatic batch blame (satellites 1 + 2)
# ---------------------------------------------------------------------------

def test_hapi_guard_counters_in_logs_and_auto_blame():
    """fit's default blame_fn finds the exact poisoned rows with no
    caller hook, and guard_skips/guard_rewinds/guard_blamed_rows ride
    the batch-end logs into every callback (ROADMAP open items)."""
    from paddle_tpu import nn
    from paddle_tpu.distributed.fleet import chaos
    from paddle_tpu.framework.monitor import stat_reset
    from paddle_tpu.hapi.callbacks import Callback
    from paddle_tpu.train_guard import GUARD_STAT_NAMES, TrainGuard
    import paddle_tpu.nn.functional as F

    for k in GUARD_STAT_NAMES:
        stat_reset(k)
    chaos.install(chaos.plan_from_spec("nan:batch:step=2:arg=2"))
    try:
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters())
        model = paddle.Model(net)
        guard = TrainGuard()
        model.prepare(opt, loss=lambda out, y: F.mse_loss(out, y),
                      guard=guard)

        seen = []

        class Grab(Callback):
            def on_train_batch_end(self, step, logs=None):
                seen.append(dict(logs or {}))

        rng = np.random.RandomState(1)
        batches = [(rng.randn(8, 4).astype("float32"),
                    rng.randn(8, 1).astype("float32"))
                   for _ in range(4)]
        model.fit((b for b in batches), epochs=1, verbose=0,
                  callbacks=[Grab()])
    finally:
        chaos.uninstall()

    assert guard.skips == 1
    # auto blame: chaos poisoned the 2 leading rows of batch #2
    assert guard.blamed_rows and guard.blamed_rows[-1][1] == [0, 1]
    assert seen[-1]["guard_skips"] == 1
    assert seen[-1]["guard_blamed_rows"] == 2
    assert seen[-1]["guard_rewinds"] == 0
    # weights stayed finite (the poisoned step was dropped)
    for p in net.parameters():
        assert np.isfinite(np.asarray(p.numpy())).all()


def test_guard_explicit_blame_fn_overrides_default():
    from paddle_tpu.distributed.fleet import chaos
    from paddle_tpu.train_guard import TrainGuard
    import paddle_tpu.nn.functional as F

    calls = []

    def my_blame(rows):
        calls.append(len(rows))
        return True            # claims everything healthy: no rows found

    chaos.install(chaos.plan_from_spec("nan:batch:step=1:arg=1"))
    try:
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters())
        model = paddle.Model(net)
        model.prepare(opt, loss=lambda out, y: F.mse_loss(out, y),
                      guard=TrainGuard(blame_fn=my_blame))
        rng = np.random.RandomState(1)
        x = rng.randn(8, 4).astype("float32")
        y = rng.randn(8, 1).astype("float32")
        model.train_batch([x], [y])
    finally:
        chaos.uninstall()
    assert model.last_guard_verdict == "skip"
    assert calls, "explicit blame_fn was not used"
    assert model._guard.blamed_rows == []   # override said all-healthy
