import os

import pytest

from perfbench.harness import trace_reduce as TR
from perfbench.harness.manifest import BENCH_DIR

FIXTURE = os.path.join(BENCH_DIR, "fixtures", "small_1chip.xplane.pb")
E = TR.Event
D0, D1 = "/device:TPU:0", "/device:TPU:1"


@pytest.fixture(scope="module")
def recorded():
    return TR.events_from_xplane(FIXTURE)


def test_recorded_trace_planes_and_programs(recorded):
    s = TR.summarize(recorded)
    assert s["n_devices"] == 1
    # four runs of the jitted fixture step, by its program name
    assert list(s["programs"]) == ["fixture_step"]
    assert len(s["programs"]["fixture_step"]) == 4
    ops = TR.device_ops(recorded)
    assert len(ops) == 16 and all(TR.is_device(e.plane) for e in ops)


def test_recorded_trace_busy_idle_and_per_op(recorded):
    s = TR.summarize(recorded)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["busy_s"] == pytest.approx(25.2e-6, rel=0.02)
    assert s["window_s"] == pytest.approx(9.67e-3, rel=0.02)
    # per-op time adds up to the busy time (ops do not overlap here)
    assert sum(t for _, t in s["device_ops"]) == pytest.approx(
        s["busy_s"], rel=1e-6)
    assert s["device_ops"][0][0] == "convolution_tanh_fusion_bf16_512_512_"
    assert s["kernel_s"] == 0.0 and s["exposed_collective_s"] == 0.0


def test_recorded_trace_gap_attribution(recorded):
    s = TR.summarize(recorded)
    # the device idled while the host slept under bench.stage_batch
    name, secs = s["idle_gaps"][0]
    assert name.startswith("bench.stage_batch")
    assert secs == pytest.approx(s["window_s"] - s["busy_s"], rel=0.01)


def test_union_and_subtract():
    u = TR.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert u == [(0, 3), (5, 6)] and TR.total(u) == 4
    assert TR.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert TR.subtract([(0, 1), (4, 6)], [(0, 5)]) == [(5, 6)]


def test_op_names():
    full = ("%fusion.4 = bf16[8192,16,8,128]{3,2,1,0:T(8,128)(2,1)} "
            "fusion(bf16[8193,16,8,128]{3,2,1,0} %custom-call.52), "
            "kind=kCustom, calls=%fused_computation")
    assert TR.op_name(full) == "fusion"
    assert TR.op_key(full) == "fusion_bf16_8192_16_8_128_"
    assert not TR.is_mosaic_kernel(full)     # an operand is no target
    kern = ('%_fwd_kernel.7 = bf16[4,32,1024,128]{3,2,1,0} custom-call('
            'bf16[4,32,1024,128] %a), custom_call_target="tpu_custom_call"')
    assert TR.is_mosaic_kernel(kern)
    assert TR.op_key("%sort.6 = (f32[128,32768]{0,1}, s32[128,32768]{0,1})"
                     " sort(...)") == "sort_f32_128_32768_"
    assert TR.is_collective("%all-gather-start.3 = (f32[8]) all-gather-start")
    assert not TR.is_collective("%fusion.1 = f32[8] fusion(%all-reduce.2)")


def test_busy_union_and_exposed_collectives_across_chips():
    ev = [
        # chip 0: compute 0-4, an all-gather 3-6 (3-4 hidden, 4-6
        # exposed), compute 6-8
        E(D0, TR.OPS_LINE, "%fusion.1 = f32[8] fusion()", 0.0, 4.0),
        E(D0, TR.OPS_LINE, "%all-gather.1 = f32[8] all-gather()", 3.0, 3.0),
        E(D0, TR.OPS_LINE, "%fusion.2 = f32[8] fusion()", 6.0, 2.0),
        # chip 1: compute 0-2, a reduce-scatter 2-3 fully exposed, idle
        # 3-8
        E(D1, TR.OPS_LINE, "%fusion.1 = f32[8] fusion()", 0.0, 2.0),
        E(D1, TR.OPS_LINE, "%reduce-scatter.1 = f32[2] reduce-scatter()",
          2.0, 1.0),
        E(D0, TR.MODULES_LINE, "jit_step(123)", 0.0, 8.0),
        E("/host:CPU", "python3", "bench.wait_loss", 3.0, 5.0),
    ]
    busy = TR.busy_by_device(ev)
    assert busy == {D0: 8.0, D1: 3.0}
    assert TR.busy_and_window(ev) == (5.5, 8.0)
    assert TR.exposed_collective_time(ev) == pytest.approx((2.0 + 1.0) / 2)
    assert TR.time_per_program(ev) == {"step": [8.0]}
    rows = dict(TR.time_per_op(ev))
    assert rows["fusion_f32_8_"] == pytest.approx((4 + 2 + 2) / 2)


def test_gap_attribution_by_span_and_call():
    ev = [E(D0, TR.OPS_LINE, "%a.1 = f32[1] a()", 0.0, 1.0),
          E(D0, TR.OPS_LINE, "%a.2 = f32[1] a()", 3.0, 1.0),
          E(D0, TR.OPS_LINE, "%a.3 = f32[1] a()", 4.5, 1.0),
          E("/host:CPU", "t", "bench.stage_batch", 0.9, 2.0),
          E("/host:CPU", "t", "PjitFunction(step)", 1.0, 1.5)]
    gaps = dict(TR.idle_gaps(ev))
    assert gaps == {"bench.stage_batch__PjitFunction_step_": 2.0,
                    "no_bench_span": 0.5}


def test_no_device_plane_reads_nothing():
    ev = [E("/host:CPU", "t", "bench.submit", 0.0, 1.0)]
    s = TR.summarize(ev)
    assert s["busy_s"] == 0.0 and s["n_devices"] == 0
    assert s["idle_gaps"] == []
