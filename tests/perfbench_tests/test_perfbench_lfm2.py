"""The lfm2-8b-a1b configuration and its cell in BENCHMARK.json: its
file against the catalog row, the counting functions behind its
per-layer metrics against hand counts, each new reader on a synthetic
``ctx``, and the whole traced run of its cell at toy size on the CPU
(toy files of its own under ``toy/``, a manifest of its own).
Membership checks only: no position in a list is pinned."""
import json
import math
import os
import time

import pytest

from conftest import ROOT, TOY

CELL = "lfm2-8b-a1b-serve-decode"
CONFIG = "lfm2-8b-a1b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("lfm2_serve_mfu", "lfm2_serve_hbm_bw_share",
       "lfm2_paged_attn_roofline", "moe_rows_per_pick",
       "ttft_p95_lfm2_decode_cell_ms")
# the ten generic readers the Kimi cell lists, and its imbalance
SHARED = ("serve_compiles_in_window", "sched_batch_occupancy",
          "sched_prefill_share", "sched_host_share", "serve_peak_hbm_gib",
          "decode_step_ms", "prefill_ms_per_ktok",
          "serve_mosaic_kernel_share", "serve_device_idle_share",
          "itl_p95_decode_cell_ms", "moe_expert_load_imbalance")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def lfm2_manifest():
    with open(os.path.join(TOY, "manifest_lfm2.json")) as f:
        return json.load(f)


def _reader(name):
    from perfbench.harness import manifest as M
    return M.load_module(os.path.join(ROOT, "perfbench", "metrics",
                                      name + ".py"), "reader_" + name)


def test_only_the_depth_differs_from_the_catalog_row(cfg, real_manifest):
    """Every key of the catalog's copy of the published config.json at
    its published value but ``num_hidden_layers``; ``layer_types`` is
    kept whole, and what runs is its first 14 entries."""
    entry = [c for c in real_manifest["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == ["num_hidden_layers"] == cfg["reduced"]
    assert cfg["num_hidden_layers"] == 14
    assert cfg["published"] == {"num_hidden_layers": 24}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [json.loads(line) for line in f
                   if '"LFM2-8B-A1B"' in line][0]
        assert entry["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differ == {"num_hidden_layers"}
    run = cfg["layer_types"][:14]
    assert run == ["conv", "conv"] + ["full_attention", "conv", "conv",
                                      "conv"] * 3
    a = cfg["assumed"]
    assert a["tie_word_embeddings"] and a["held_experts"] == [0, 32]
    assert a["router_dtype"] == "float32" and a["norm_topk_eps"] == 1e-6
    assert "two-stage pipeline on two v5e chips" in cfg["deployment"]
    # every width, all 32 experts, the whole vocabulary
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["vocab_size"]) == (
        2048, 7168, 1792, 32, 4, 65536)


def test_the_cell_and_its_entries_are_listed(real_manifest):
    from perfbench.harness import manifest as M
    assert M.lint(real_manifest) == []
    cell = [w for w in real_manifest["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "closed-agent-decode", 1)
    mine = {m["name"]: m for m in real_manifest["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) | set(SHARED) == set(mine)
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "serve_out_tokens_per_s"
    assert mine["moe_rows_per_pick"]["better"] == "lower"
    e2e = {m["name"]: m for m in real_manifest["end_to_end"]}
    assert CELL in e2e["serve_out_tokens_per_s"]["workloads"]
    # the two tails carry no bound in this cell
    assert CELL not in e2e["ttft_p95_ms"]["workloads"]
    assert CELL not in e2e["itl_p95_ms"]["workloads"]
    with open(os.path.join(ROOT, "perfbench", "cells", CELL + ".json")) as f:
        spec = json.load(f)
    assert spec["server"] == {
        "num_slots": 256, "block_size": 16, "max_model_len": 1792,
        "prompt_buckets": [128, 256, 512, 768], "max_prefill_batch": 4}
    assert spec["correct"]["sample"] == 6
    assert spec["correct"]["limits"]["requests_unanswered"] == 0
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "closed-agent-decode.json")) as f:
        tr = json.load(f)
    assert (tr["kind"], tr["clients"], tr["sampling"], tr["warmup_s"]) == (
        "serve_closed_loop", 256, "greedy", 3)
    assert tr["prompt_len"] == {"dist": "loguniform", "lo": 64, "hi": 768}
    assert tr["output_len"] == {"dist": "loguniform", "lo": 256,
                                "hi": 1024}
    from perfbench.harness.traffic import ServeTraffic
    p, o = ServeTraffic(tr, 65536, 1).mean_lengths()
    assert p == pytest.approx(283, abs=2) and o == pytest.approx(554, abs=2)


def test_parameter_counts_are_the_issues_arithmetic(cfg):
    from perfbench.harness import flops_lfm2 as L
    assert L.conv_params(cfg) == 16_783_360
    assert L.attn_params(cfg) == 10_485_888
    assert L.dense_ffn_params(cfg) == 44_040_192
    assert L.expert_params(cfg) == 11_010_048
    assert L.router_params(cfg) == 65_568
    assert L.embed_params(cfg) == 134_217_728
    kinds = L.layer_kinds(cfg)
    assert [a for a, _ in kinds] == [False, False] + [True, False, False,
                                                      False] * 3
    assert [e for _, e in kinds] == [False] * 2 + [True] * 12
    # 11 conv, 3 attention, 2 dense and 12 expert layers, the embedding
    # and 29 norm vectors: 4,667M parameters = 9.33 GB
    n = L.held_weight_params(cfg)
    assert n == (11 * 16_783_360 + 3 * 10_485_888 + 2 * 44_040_192
                 + 12 * (32 * 11_010_048 + 65_568) + 134_217_728
                 + 29 * 2048)
    assert n / 1e6 == pytest.approx(4667, abs=0.5)
    assert 2 * n / 1e9 == pytest.approx(9.33, abs=0.005)
    # and they are the reference's own leaves
    from perfbench.harness import manifest as M
    ref = M.load_module(os.path.join(
        ROOT, "perfbench", "configs", CONFIG + ".reference.py"),
        "lfm2_reference_for_counts")
    assert n == sum(math.prod(s) for s, _ in ref.param_specs(cfg).values())


def test_decode_step_bytes_and_serve_flops(cfg):
    from perfbench.harness import flops_lfm2 as L
    # K and V of a token: 3 layers x 2 x 8 x 64 x 2 B
    assert L.kv_bytes_per_token(cfg) == 6144
    assert L.conv_tail_bytes(cfg) == 8192
    assert L.kv_read_bytes(cfg, 1000) == 6_144_000
    w = 2 * L.held_weight_params(cfg)
    assert L.decode_step_bytes(cfg, 0, 0) == w
    # 256 live rows at a mean context of 560: 0.88 GB of K/V, and the
    # tails of 11 conv layers read and written, 46 MB
    b = L.decode_step_bytes(cfg, 256, 256 * 560)
    assert b - w == 256 * 560 * 6144 + 2 * 256 * 11 * 8192
    assert (b - w) / 1e9 == pytest.approx(0.881 + 0.046, abs=0.001)
    # a token through the layers with four experts each: 1.67 GFLOP,
    # the tied head 0.27 more
    per = L.token_flops(cfg)
    assert per == (11 * (2 * (3 * 2048 * 2048 + 2048 * 2048) + 2 * 5 * 2048)
                   + 3 * 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
                   + 2 * 2 * 44_040_192
                   + 12 * 2 * (2048 * 32 + 4 * 11_010_048))
    assert per / 1e9 == pytest.approx(1.667, abs=0.001)
    assert L.attn_pair_flops(cfg) == 4 * 32 * 64
    f = L.serve_flops(cfg, 1000, 2, 100, 5000, 7000)
    assert f == 1100 * per + 2 * 102 * 134_217_728 + 3 * 8192 * 12000


def _ctx(cfg, **over):
    ctx = {"cfg": cfg, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "seconds": 10.0, "t0": 0.0, "t1": 10.0,
           "server": {"num_slots": 256},
           "window": {"decode_steps": 4, "tokens_generated": 1024,
                      "prefill_rows": 0},
           "requests": [{"prompt": [0] * 999,
                         "token_times": [-1.0, 1.0, 2.0, 3.0, 4.0]}],
           "stats_end": {"decode_steps": 10, "moe_picks_here": 1000,
                         "moe_max_expert_load": 50,
                         "moe_rows_multiplied": 8000},
           "ttft": [0.1, 0.2, 0.3],
           "trace": {"busy_s": 1.0, "kernel_s": 1e-5,
                     "mosaic_kernels": ["paged_attention_bf16_256_32_64_"],
                     "programs": {"jit_decode_fn": [0.02, 0.02]},
                     "device_ops": [["fusion_f32_", 0.5],
                                    ["paged_attention_bf16_256_32_64_",
                                     2e-5]]}}
    ctx.update(over)
    return ctx


def test_each_new_reader_on_a_synthetic_run(cfg):
    from perfbench.harness import flops_lfm2 as L
    ctx = _ctx(cfg)
    # four decoded tokens at contexts 1000..1003, nothing prefilled
    f = L.serve_flops(cfg, 0, 0, 4, 0, 4006)
    assert _reader("lfm2_serve_mfu").read(ctx) == pytest.approx(
        100 * f / 10.0 / 197e12)
    nbytes = L.decode_step_bytes(cfg, 256, 4006 / 4)
    assert _reader("lfm2_serve_hbm_bw_share").read(ctx) == pytest.approx(
        100 * nbytes / 0.02 / 819e9)
    # two traced decode steps, the kernel 10 us in all: it is the
    # trace's only Mosaic kernel, so the whole kernel time is its own
    want = 100 * 2 * 1001.5 * 6144 / 1e-5 / 819e9
    roof = _reader("lfm2_paged_attn_roofline")
    assert roof.read(ctx) == pytest.approx(want, rel=1e-6)
    # beside another kernel: the op's own time among the ten largest
    ctx["trace"]["mosaic_kernels"].append("gmm_bf16_")
    assert roof.read(ctx) == pytest.approx(want / 2, rel=1e-6)
    ctx["trace"]["device_ops"].pop()
    assert roof.read(ctx) is None
    assert _reader("moe_rows_per_pick").read(ctx) == 8.0
    assert _reader("ttft_p95_lfm2_decode_cell_ms").read(ctx) == \
        pytest.approx(290.0)
    assert _reader("moe_expert_load_imbalance").read(ctx) == \
        pytest.approx(50 * 32 / 1000)


def test_readers_return_nothing_where_there_is_nothing_to_read(cfg):
    """Another configuration's run, a program without the counters (the
    parent under these benchmark files), a CPU run: the new readers
    find nothing and say so, and never raise."""
    other = _ctx({"num_experts": 64, "hidden_size": 8})
    bare = _ctx(cfg, stats_end={"decode_steps": 10}, trace=None,
                window={"decode_steps": 0, "tokens_generated": 0,
                        "prefill_rows": 0})
    cpu = _ctx(cfg, device={"platform": "cpu", "kind": "cpu"})
    for name in NEW[:3]:
        # (the served operations come from the client's records alone:
        # they need no counter and no trace)
        for ctx in (other, cpu) + ((bare,) if "mfu" not in name else ()):
            assert _reader(name).read(ctx) is None, name
    assert _reader("moe_rows_per_pick").read(bare) is None
    assert _reader("ttft_p95_lfm2_decode_cell_ms").read(
        _ctx(cfg, ttft=[])) is None


def test_toy_manifest_is_clean_and_mirrors_the_real_entries(
        lfm2_manifest, real_manifest):
    from perfbench.harness import manifest as M
    assert M.lint(lfm2_manifest, bench_dir=TOY) == []
    real = {m["name"] for m in real_manifest["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {m["name"] for m in lfm2_manifest["per_layer"]} == real


def test_lfm2_rehearsal_end_to_end(lfm2_manifest):
    """The whole traced run of the toy cell on the CPU: correct, the
    fp8 control refused, every listed metric that a CPU run can write
    printed, and no device metric."""
    from perfbench import run as R
    from perfbench.harness.common import result_line
    res = R.run_cell(CELL, 2 ** 31 + 77, 1.0, True,
                     t_proc0=time.perf_counter(), require_chip=False,
                     manifest=lfm2_manifest, bench_dir=TOY,
                     controls=("fp8",))
    line = json.loads(result_line(
        res["correct"], res["attempted"], res["failed"], res["metrics"],
        res["device"], res["breakdown"], res["compared"]))
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    got = set(line["metrics"])
    assert {"moe_rows_per_pick", "moe_expert_load_imbalance",
            "ttft_p95_lfm2_decode_cell_ms", "itl_p95_decode_cell_ms",
            "sched_batch_occupancy", "sched_prefill_share",
            "sched_host_share", "serve_compiles_in_window"} <= got
    # a CPU run writes counts, never a device metric
    assert not got & {"lfm2_serve_mfu", "lfm2_serve_hbm_bw_share",
                      "lfm2_paged_attn_roofline", "decode_step_ms",
                      "serve_peak_hbm_gib"}
    assert got <= {m["name"] for m in lfm2_manifest["per_layer"]}
    assert line["metrics"]["serve_compiles_in_window"]["value"] == 0
    # 2 of 8 experts a token: the masked pass multiplies 4 rows a pick
    assert line["metrics"]["moe_rows_per_pick"]["value"] == 4.0
    assert 1.0 <= line["metrics"]["moe_expert_load_imbalance"]["value"] <= 4
    assert res["verdicts"]["fp8"]["correct"] is False


def _experts_off_by_one(server):
    for lyr in server._model.model.layers:
        if lyr.is_moe:       # every pick lands on its neighbour's weights
            lyr.feed_forward.held_experts = (1, 8)


def _tails_not_carried(server):
    import jax.numpy as jnp
    for lyr in server._model.model.layers:
        if not lyr.is_attention:
            lyr.conv.conv._value = lyr.conv.conv._value.at[:2].set(
                jnp.zeros_like(lyr.conv.conv._value[:2]))


@pytest.mark.parametrize("sabotage", [_experts_off_by_one,
                                      _tails_not_carried],
                         ids=lambda f: f.__name__)
def test_lfm2_fault_is_seen(lfm2_manifest, sabotage):
    from perfbench import run as R
    res = R.run_cell(CELL, 2 ** 31 + 77, 1.0, False,
                     t_proc0=time.perf_counter(), require_chip=False,
                     manifest=lfm2_manifest, bench_dir=TOY,
                     sabotage=sabotage)
    assert res["correct"] is False, res["compared"]
