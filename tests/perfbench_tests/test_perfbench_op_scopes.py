"""Device time by model block and idle time by scheduler phase
(``perfbench/harness/op_scopes.py``) and the six readers of it, on the
recorded fixture and on hand-made events."""
import os

import pytest

from perfbench.harness import op_scopes as OS
from perfbench.harness import trace_reduce as TR
from perfbench.harness.manifest import BENCH_DIR, load_module

FIXTURE = os.path.join(BENCH_DIR, "fixtures", "small_1chip.xplane.pb")
D0, D1 = "/device:TPU:0", "/device:TPU:1"
HOST = "/host:CPU"
E = OS.Event
READERS = ("decode_attn_ms", "decode_mixer_ms", "decode_ffn_ms",
           "prefill_attn_ms_per_ktok", "prefill_ffn_ms_per_ktok",
           "device_unscoped_share")


def reader(name):
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                       "perfbench_metric_test_" + name)


@pytest.fixture(scope="module")
def recorded():
    return OS.events_from_xplane(FIXTURE)


# ---------------------------------------------------------------------
# (a) the recorded fixture
# ---------------------------------------------------------------------
def test_the_wire_read_gives_profiledatas_events_and_each_ops_tf_op(
        recorded):
    """The same events, times and order as ``ProfileData``'s; every
    fusion of the fixture's step carries the ``tf_op`` its jitted
    program wrote, the copies XLA added for a cross-program prefetch
    carry none."""
    assert [tuple(e[:5]) for e in recorded] == [
        tuple(e) for e in TR.events_from_xplane(FIXTURE)]
    ops = TR.device_ops(recorded)
    fusions = [e for e in ops if TR.op_name(e.name).startswith(
        "convolution")]
    assert len(ops) == 16 and len(fusions) == 8
    assert all(e.tf_op.startswith("jit(fixture_step)/") for e in fusions)
    assert all(e.tf_op == "" for e in ops if e not in fusions)
    assert not [e for e in recorded if not TR.is_device(e.plane)
                and e.tf_op]


def test_summarize_reads_the_same_off_the_wire_events(recorded):
    """Every key ``summarize()`` had keeps its value, pinned here from
    the reduction as it stood before the ``tf_op`` read."""
    s = TR.summarize(recorded)
    assert s == TR.summarize(TR.events_from_xplane(FIXTURE))
    assert s["busy_s"] == pytest.approx(2.52059999999929e-05, rel=1e-9)
    assert s["window_s"] == pytest.approx(0.009669929, rel=1e-9)
    assert [k for k, _ in s["device_ops"]] == [
        "convolution_tanh_fusion_bf16_512_512_", "copy-start_bf16_512_512_",
        "copy-done_bf16_512_512_"]
    assert [v for _, v in s["device_ops"]] == pytest.approx(
        [2.5144e-05, 5.2e-08, 1e-08], rel=1e-9)
    assert s["idle_gaps"] == [[
        "bench.stage_batch__PjitFunction_fixture_step_",
        pytest.approx(0.009644708, rel=1e-9)]]
    assert s["programs"] == {"fixture_step": pytest.approx(
        [6.307e-06, 6.329e-06, 6.308e-06, 6.308e-06], rel=1e-9)}
    assert (s["kernel_s"], s["mosaic_kernels"], s["custom_calls"],
            s["exposed_collective_s"], s["n_devices"]) == (
                0.0, [], [], 0.0, 1)


def test_the_fixtures_step_writes_no_scope(recorded):
    """The fixture's step names no block: its time is all under
    ``(none)``, with its largest ops beside it, and the self times add
    up to the device's busy time."""
    r = OS.reduce(recorded)
    assert list(r["scopes"]) == ["fixture_step"]
    assert list(r["scopes"]["fixture_step"]) == [OS.UNSCOPED]
    assert r["scopes"]["fixture_step"][OS.UNSCOPED] == pytest.approx(
        TR.summarize(recorded)["busy_s"], rel=1e-6)
    assert r["unscoped_ops"]["fixture_step"][0][0] == \
        "convolution_tanh_fusion_bf16_512_512_"
    assert r["idle_by_phase"][0][0] == OS.NO_PHASE


def test_a_ref_valued_tf_op_is_read_through_the_stat_table(tmp_path):
    """The profiler may intern a string stat: ``ref_value`` names the
    stat metadata whose name is the string."""
    space = OS._xspace_class()()
    plane = space.planes.add(name=D0)
    plane.stat_metadata[7].id, plane.stat_metadata[7].name = 7, "tf_op"
    plane.stat_metadata[9].id = 9
    plane.stat_metadata[9].name = "jit(decode_fn)/moe/dot_general"
    for k, name in ((1, "%fusion.1 = f32[2]"), (2, "%copy.3 = f32[2]")):
        plane.event_metadata[k].id, plane.event_metadata[k].name = k, name
    plane.event_metadata[1].stats.add(metadata_id=7, ref_value=9)
    line = plane.lines.add(name=TR.OPS_LINE, timestamp_ns=1000)
    line.events.add(metadata_id=1, offset_ps=2500, duration_ps=4999)
    line.events.add(metadata_id=2, offset_ps=9000, duration_ps=1000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    got = OS.events_from_xplane(str(path))
    assert got == [E(D0, TR.OPS_LINE, "%fusion.1 = f32[2]", 1002e-9, 4e-9,
                     "jit(decode_fn)/moe/dot_general"),
                   E(D0, TR.OPS_LINE, "%copy.3 = f32[2]", 1009e-9, 1e-9)]


# ---------------------------------------------------------------------
# (b) hand-made nested events
# ---------------------------------------------------------------------
def _op(start, dur, tf_op="", name="%fusion.1 = bf16[8,128]{1,0} fusion()",
        plane=D0):
    return E(plane, TR.OPS_LINE, name, start, dur, tf_op)


def _run(start, dur, prog, plane=D0):
    return E(plane, TR.MODULES_LINE, f"jit_{prog}(3)", start, dur)


def _nested_step():
    """Two runs of ``prefill_fn``: a ``while`` of the expert blocks over
    a ``conditional`` over a fusion (``moe``), then an attention fusion
    (``attn``) and a copy that no scope names; one op outside every
    run.  Times in seconds, each run 10 long."""
    ops, runs = [], []
    for t0 in (0.0, 20.0):
        runs.append(_run(t0, 10.0, "prefill_fn"))
        ops += [
            _op(t0 + 0.0, 6.0, "jit(prefill_fn)/moe/while",
                "%while.2 = s32[] while()"),
            _op(t0 + 1.0, 4.0, "jit(prefill_fn)/moe/while/body/cond",
                "%conditional.1 = bf16[1,256,2048] conditional()"),
            _op(t0 + 1.5, 2.0, "jit(prefill_fn)/moe/while/body/dot_general:"),
            _op(t0 + 6.0, 3.0, "jit(prefill_fn)/attn/dot_general"),
            _op(t0 + 9.0, 0.5, "",
                "%copy.7 = bf16[4096,4096]{0,1} copy()")]
    ops.append(_op(15.0, 1.0, "jit(prefill_fn)/attn/dot_general"))
    return ops, runs


def test_self_times_of_nested_ops_sum_to_their_union():
    ops, _ = _nested_step()
    self_t = OS.self_times(ops)
    assert self_t[:5] == [2.0, 2.0, 2.0, 3.0, 0.5]
    assert sum(self_t) == pytest.approx(
        TR.total(TR.union([(e.start, e.start + e.dur) for e in ops])))
    # an op that outlasts its parent is clipped to it
    assert OS.self_times([_op(0.0, 2.0), _op(1.0, 2.0)]) == [1.0, 2.0]


def test_scope_times_split_each_program_by_innermost_scope():
    ops, runs = _nested_step()
    table, loose = OS.scope_times(ops + runs)
    assert table == {"prefill_fn": {"moe": pytest.approx(12.0),
                                    "attn": pytest.approx(6.0),
                                    OS.UNSCOPED: pytest.approx(1.0)}}
    assert loose == {"prefill_fn": [["copy_bf16_4096_4096_",
                                     pytest.approx(1.0)]]}
    # the ops of the second device are left to the first's account
    more = [e._replace(plane=D1) for e in ops + runs]
    assert OS.scope_times(ops + runs + more) == (table, loose)


def test_scope_of_takes_the_innermost_vocabulary_name():
    assert OS.scope_of("jit(decode_fn)/mtp/moe/jit(_take)/gather") == "moe"
    assert OS.scope_of("jit(decode_fn)/attn/dot_general:") == "attn"
    assert OS.scope_of("jit(decode_fn)/attn") is None     # the op itself
    assert OS.scope_of("jit(decode_fn)/lfm2.attn/dot_general") is None
    assert OS.scope_of("") is None


def test_the_vocabulary_is_the_programs():
    from paddle_tpu.observability import scopes
    assert OS.SCOPES == scopes.VOCABULARY


def test_a_program_that_writes_no_tf_op_is_left_out():
    table, _ = OS.scope_times([_op(0.0, 1.0), _run(0.0, 2.0, "decode_fn")])
    assert table == {}
    assert OS.scope_times([]) == ({}, {})


def test_idle_by_phase_takes_the_innermost_span_that_covers_most():
    """Gaps between device ops go to the innermost ``serve`` span that
    covers most of each; ``serve.idle`` counts; a gap under no such span
    (the client's ``bench.*`` spans do not count) is ``no_serve_span``;
    a gap under 20 us is no gap."""
    ev = [_op(0.0, 1.0), _op(2.0, 1.0), _op(5.0, 1.0), _op(8.0, 1.0),
          _op(9.00001, 0.99999), _op(12.0, 1.0), _op(13.5, 1.0),
          E(HOST, "serve-thread", "serve#step_num=4#", 0.5, 7.0),
          E(HOST, "serve-thread", "serve.decode.fetch", 1.2, 0.5),
          E(HOST, "serve-thread", "serve.decode.emit", 1.7, 0.1),
          E(HOST, "serve-thread", "serve.prefill.stage#batch=2#", 3.2, 1.0),
          E(HOST, "serve-thread", "serve.idle", 10.0, 1.5),
          E(HOST, "client", "bench.wait_streams", 0.0, 15.0)]
    got = dict(OS.idle_by_phase(ev))
    assert got == {"serve.decode.fetch": pytest.approx(1.0),
                   "serve.prefill.stage": pytest.approx(2.0),
                   "serve": pytest.approx(2.0),
                   "serve.idle": pytest.approx(2.0),
                   OS.NO_PHASE: pytest.approx(0.5)}
    assert OS.idle_by_phase([]) == []


# ---------------------------------------------------------------------
# (c) the six readers
# ---------------------------------------------------------------------
def _ctx(scopes, decode_runs=4, prefill_tokens=2000, platform="tpu"):
    """A traced run's context; ``scopes`` None: a summary from a
    reduction that reads no ``tf_op``."""
    trace = {"busy_s": 1.0, "window_s": 1.1,
             "programs": {"decode_fn": [0.01] * decode_runs,
                          "prefill_fn": [0.1, 0.1], "feed_fn": [1e-5]}}
    if scopes is not None:
        trace.update(scopes=scopes, unscoped_ops={}, idle_by_phase=[])
    return {"trace": trace, "device": {"platform": platform},
            "trace_counters": {"prefill_tokens": prefill_tokens}}


SCOPED = {"decode_fn": {"attn": 0.012, "mixer": 0.004, "moe": 0.02,
                        "ffn": 0.002, "embed": 0.0001, "head": 0.0009,
                        "sample": 0.0002, OS.UNSCOPED: 0.0008},
          "prefill_fn": {"attn": 0.06, "moe": 0.1, "ffn": 0.01,
                         OS.UNSCOPED: 0.03},
          "feed_fn": {OS.UNSCOPED: 1e-5}}


def test_the_six_readers_on_a_synthetic_run(capsys):
    ctx = _ctx(SCOPED)
    got = {n: reader(n).read(ctx) for n in READERS}
    assert got == {
        "decode_attn_ms": pytest.approx(3.0),
        "decode_mixer_ms": pytest.approx(1.0),
        "decode_ffn_ms": pytest.approx(5.5),
        "prefill_attn_ms_per_ktok": pytest.approx(30.0),
        "prefill_ffn_ms_per_ktok": pytest.approx(55.0),
        "device_unscoped_share": pytest.approx(
            100 * 0.0308 / (0.04 + 0.2))}
    assert "device seconds by scope" in capsys.readouterr().err


def test_a_block_the_model_lacks_reads_zero_where_it_writes_scopes():
    ctx = _ctx({"decode_fn": {"attn": 0.01, "ffn": 0.01}})
    assert reader("decode_mixer_ms").read(ctx) == 0.0
    assert reader("prefill_attn_ms_per_ktok").read(ctx) is None


@pytest.mark.parametrize("ctx", [
    {"trace": None},
    {"trace": {"busy_s": 0.0, "programs": {}}},
    _ctx({}),
    _ctx({"decode_fn": {OS.UNSCOPED: 0.03},
          "prefill_fn": {OS.UNSCOPED: 0.2}}),
    _ctx(None, platform="cpu"),
], ids=["no_trace", "cpu_trace", "no_tf_op", "no_scope_written",
        "summary_without_the_key"])
def test_the_readers_read_nothing_where_there_is_nothing(ctx):
    ctx.setdefault("trace_counters", {"prefill_tokens": 100})
    assert {n: reader(n).read(ctx) for n in READERS} == dict.fromkeys(
        READERS)


def test_no_decode_run_or_no_prefill_token_reads_nothing():
    assert reader("decode_attn_ms").read(_ctx(SCOPED, decode_runs=0)) \
        is None
    assert reader("prefill_ffn_ms_per_ktok").read(
        _ctx(SCOPED, prefill_tokens=0)) is None
