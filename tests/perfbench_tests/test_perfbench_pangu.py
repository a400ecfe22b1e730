"""The openpangu-ultra-moe-718b configuration and its cell in
BENCHMARK.json: its file against the catalog row, the counting
functions behind its per-layer metrics against hand counts, each new
reader on a synthetic ``ctx``, and the whole traced run of its cell at
toy size on the CPU (toy files of its own under ``toy/``, a manifest of
its own).  Membership checks only: no position in a list is pinned."""
import json
import math
import os
import time

import pytest

from conftest import ROOT, TOY

CELL = "openpangu-ultra-moe-serve-decode"
CONFIG = "openpangu-ultra-moe-718b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (61, 5), "first_k_dense_replace": (3, 1),
           "n_routed_experts": (256, 16), "vocab_size": (153600, 19200),
           "num_nextn_predict_layers": (1, 0)}
NEW = ("pangu_serve_mfu", "pangu_serve_hbm_bw_share",
       "pangu_latent_attn_roofline", "ttft_p95_pangu_decode_cell_ms")
# The cell reports the rate, with its window opened after the start-up
# burst of 128 prefills (PERF.md section 2), and so it is listed in
# every entry that moves the rate and reads something here: the twelve
# generic ones (the ten the Kimi and LFM2 cells list, and the expert
# layers' two), the scheduler's six span entries that move the rate and
# its overlap share, and ``itl_p50_ms``, the decode step as the client
# sees it.  Its own four move the rate too.
GENERIC = ("serve_compiles_in_window", "sched_batch_occupancy",
           "sched_prefill_share", "sched_host_share", "serve_peak_hbm_gib",
           "decode_step_ms", "prefill_ms_per_ktok",
           "serve_mosaic_kernel_share", "serve_device_idle_share",
           "itl_p95_decode_cell_ms", "moe_expert_load_imbalance",
           "moe_rows_per_pick")
SPANS = ("sched_decode_stage_ms", "sched_decode_dispatch_ms",
         "sched_decode_fetch_ms", "sched_decode_emit_ms",
         "sched_prefill_pad_share", "sched_longest_span_ms",
         "sched_decode_overlap_share")
LISTED = ("itl_p50_ms",) + GENERIC + SPANS


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pangu_manifest():
    with open(os.path.join(TOY, "manifest_pangu.json")) as f:
        return json.load(f)


def _reader(name):
    from perfbench.harness import manifest as M
    return M.load_module(os.path.join(ROOT, "perfbench", "metrics",
                                      name + ".py"), "reader_" + name)


def test_only_the_five_reduced_keys_differ_from_the_catalog_row(
        cfg, real_manifest):
    """Every key of the catalog's copy of the published config.json at
    its published value but the five under ``reduced``, whose published
    values ``published`` states; what the row does not have
    (``num_experts``, ``published``, ``assumed``, ``deployment``) is an
    addition."""
    entry = [c for c in real_manifest["configs"] if c["name"] == CONFIG][0]
    assert set(entry["reduced"]) == set(REDUCED)
    assert cfg["published"] == {k: v[0] for k, v in REDUCED.items()}
    assert {k: cfg[k] for k in REDUCED} == {k: v[1]
                                            for k, v in REDUCED.items()}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [json.loads(line) for line in f
                   if '"openPangu-Ultra-MoE-718B"' in line][0]
        assert entry["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differ == set(REDUCED)
        assert cfg["published"] == {k: row["config"][k] for k in REDUCED}
        assert set(cfg) - set(row["config"]) == {
            "num_experts", "published", "assumed", "deployment"}
    # every published width
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["rope_theta"], cfg["sandwich_norm"]) == (
        7680, 128, 128, 64, 128, 1536, 512, 18432, 2048, 8, 2.5, 25600000,
        True)
    a = cfg["assumed"]
    assert a["held_experts"] == [0, 16] and a["scoring_func"] == "sigmoid"
    assert a["rope"] == "rotate_half" and a["torch_dtype"] == "bfloat16"
    # the benchmark reader's alias of the held count
    assert cfg["num_experts"] == cfg["n_routed_experts"] == 16
    assert "num_experts is the benchmark reader's alias" in a["note"]
    for said in ("16 chips share each layer", "4 tokens a decode step",
                 "16 times its deployed share", "5 of 61 layers"):
        assert said in cfg["deployment"], said


def test_the_cell_and_its_entries_are_listed(real_manifest):
    from perfbench.harness import manifest as M
    assert M.lint(real_manifest) == []
    cell = [w for w in real_manifest["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "closed-context-decode", 1)
    mine = {m["name"]: m for m in real_manifest["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(NEW) | set(LISTED) <= set(mine)
    for name in NEW + LISTED:
        assert mine[name]["moves"] == "serve_out_tokens_per_s", name
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
    assert mine["pangu_latent_attn_roofline"]["layer"] == "kernels"
    e2e = {m["name"]: m for m in real_manifest["end_to_end"]}
    # the rate carries the cell's bound; the two tails are quantised by
    # the prefill calls a stream waits out, and stand as per-layer
    # metrics (PERF.md section 2)
    assert CELL in e2e["serve_out_tokens_per_s"]["workloads"]
    assert CELL not in e2e["itl_p95_ms"]["workloads"]
    assert CELL not in e2e["ttft_p95_ms"]["workloads"]
    with open(os.path.join(ROOT, "perfbench", "cells", CELL + ".json")) as f:
        spec = json.load(f)
    assert spec["server"] == {
        "num_slots": 128, "block_size": 16, "max_model_len": 4096,
        "prompt_buckets": [1024, 1536, 2048, 3072], "max_prefill_batch": 2}
    assert spec["correct"]["sample"] == 6
    assert spec["correct"]["limits"]["requests_unanswered"] == 0
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "closed-context-decode.json")) as f:
        tr = json.load(f)
    assert (tr["kind"], tr["clients"], tr["sampling"], tr["warmup_s"]) == (
        "serve_closed_loop", 128, "greedy", 20)
    assert tr["prompt_len"] == {"dist": "loguniform", "lo": 768,
                                "hi": 3072}
    assert tr["output_len"] == {"dist": "loguniform", "lo": 256,
                                "hi": 1024}
    from perfbench.harness.traffic import ServeTraffic, quantile_lengths
    p, o = ServeTraffic(tr, 19200, 1).mean_lengths()
    assert p == pytest.approx(1662, abs=3) and o == pytest.approx(554, abs=2)
    # the longest prompt with the longest answer fits the server
    assert (quantile_lengths(tr["prompt_len"], 128).max()
            + quantile_lengths(tr["output_len"], 128).max()) <= 4096


def test_parameter_counts_are_the_issues_arithmetic(cfg):
    from perfbench.harness import flops_pangu as P
    assert P.attn_params(cfg) == (11_796_480 + 37_748_736 + 4_423_680
                                  + 16_777_216 + 125_829_120)
    assert P.attn_params(cfg) / 1e6 == pytest.approx(196.6, abs=0.05)
    assert P.dense_ffn_params(cfg) == 424_673_280
    assert P.expert_params(cfg) == 47_185_920
    assert P.moe_fixed_params(cfg) == 1_966_080 + 47_185_920
    assert P.embed_params(cfg) == 147_456_000
    assert (P.n_layers(cfg), P.n_moe_layers(cfg)) == (5, 4)
    # one dense layer, four expert layers of 16 held experts, embedding
    # and head, and the norm vectors: 4,919M parameters = 9.84 GB
    n = P.held_weight_params(cfg)
    assert n == (5 * (196_575_232 + 4 * 7680 + 1536 + 512) + 424_673_280
                 + 4 * (16 * 47_185_920 + 49_152_000)
                 + 2 * 147_456_000 + 7680)
    assert n / 1e6 == pytest.approx(4919, abs=0.5)
    assert 2 * n / 1e9 == pytest.approx(9.84, abs=0.005)
    # and they are the reference's own leaves, less the selection bias
    # this architecture lacks (a leaf of zeros in the program)
    from perfbench.harness import manifest as M
    ref = M.load_module(os.path.join(
        ROOT, "perfbench", "configs", CONFIG + ".reference.py"),
        "pangu_reference_for_counts")
    specs = ref.param_specs(cfg)
    assert n == sum(math.prod(s) for s, _ in specs.values()) - 4 * 256
    assert not any("mtp" in k for k in specs)       # the cut holds none


def test_decode_step_bytes_and_serve_flops(cfg):
    from perfbench.harness import flops_pangu as P
    # a token leaves [c | k_pe] = 576 numbers a layer, whatever the
    # head count
    assert P.latent_bytes_per_token(cfg) == 1152
    assert P.latent_read_bytes(cfg, 1000) == 5 * 1_152_000
    # 9.54 GB a step before the cache: everything but the embedding
    w = P.decode_step_bytes(cfg, 0, 0)
    assert w == 2 * (P.held_weight_params(cfg) - 147_456_000)
    assert w / 1e9 == pytest.approx(9.54, abs=0.005)
    # 128 live rows at a mean context of 2,000: 1.47 GB of latent rows
    b = P.decode_step_bytes(cfg, 128, 128 * 2000)
    assert b - w == 128 * 2000 * 5 * 1152 + 2 * 128 * 7680
    # a cached position: 128 heads x (576 + 512) x 2 absorbed, 128 x
    # 320 x 2 expanded
    assert P.absorbed_pair_flops(cfg) == 278_528
    assert P.expanded_pair_flops(cfg) == 81_920
    # a token through the layers with its share of 8 picks here
    per = P.token_flops(cfg, 1 / 16)
    assert per == 2 * (5 * 196_575_232 + 424_673_280
                       + 4 * (49_152_000 + 0.5 * 47_185_920))
    assert per / 1e9 == pytest.approx(3.397, abs=0.001)
    f = P.serve_flops(cfg, 1000, 2, 100, 5000, 7000, 1 / 16)
    assert f == 1100 * per + 2 * 102 * 147_456_000 \
        + 5 * (81_920 * 5000 + 278_528 * 7000)


def test_the_roofline_takes_the_larger_of_the_two_sides(cfg):
    """At 128 heads the MXU's time for a position (1.414 ns) and the
    HBM's (1.407 ns) meet; the count follows the shapes: FLOPs dominate
    at the published head count, bytes at a quarter of it, and a kernel
    at either peak reads 100, never more."""
    from perfbench.harness import flops_pangu as P
    s, side = P.latent_attn_seconds(cfg, 1000, 197e12, 819e9)
    assert side == "mxu"
    assert s == pytest.approx(5 * 1000 * 278_528 / 197e12)
    assert s / (5 * 1000 * 1152 / 819e9) == pytest.approx(1.005, abs=1e-3)
    few = dict(cfg, num_attention_heads=32)
    s, side = P.latent_attn_seconds(few, 1000, 197e12, 819e9)
    assert side == "hbm" and s == pytest.approx(5 * 1000 * 1152 / 819e9)
    roof = _reader("pangu_latent_attn_roofline")
    for c, least in ((cfg, 4006 / 4 * 5 * 278_528 / 197e12),
                     (few, 4006 / 4 * 5 * 1152 / 819e9)):
        # two traced steps; the kernel takes exactly the least time
        ctx = _ctx(c)
        ctx["trace"]["kernel_s"] = 2 * least
        assert roof.read(ctx) == pytest.approx(100.0)
        ctx["trace"]["kernel_s"] = 8 * least
        assert roof.read(ctx) == pytest.approx(25.0)


def _ctx(cfg, **over):
    ctx = {"cfg": cfg, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "seconds": 10.0, "t0": 0.0, "t1": 10.0,
           "server": {"num_slots": 128},
           "window": {"decode_steps": 4, "tokens_generated": 512,
                      "prefill_rows": 0},
           "requests": [{"prompt": [0] * 999,
                         "token_times": [-1.0, 1.0, 2.0, 3.0, 4.0]}],
           "stats_end": {"decode_steps": 10, "moe_picks_here": 2560,
                         "moe_max_expert_load": 480,
                         "moe_rows_multiplied": 81920},
           "ttft": [0.1, 0.2, 0.3],
           "trace": {"busy_s": 1.0, "kernel_s": 1e-4,
                     "mosaic_kernels": ["paged_attention_bf16_128_128_640_"],
                     "programs": {"jit_decode_fn": [0.03, 0.03]},
                     "device_ops": [["fusion_f32_", 0.5],
                                    ["paged_attention_bf16_128_128_640_",
                                     2e-4]]}}
    ctx.update(over)
    return ctx


def test_each_new_reader_on_a_synthetic_run(cfg):
    from perfbench.harness import flops_pangu as P
    ctx = _ctx(cfg)
    # 2,560 picks in 10 steps of 128 rows, 4 expert layers, 8 a token:
    # a sixteenth landed here; four decoded tokens at contexts
    # 1000..1003, nothing prefilled
    f = P.serve_flops(cfg, 0, 0, 4, 0, 4006, 1 / 16)
    assert _reader("pangu_serve_mfu").read(ctx) == pytest.approx(
        100 * f / 10.0 / 197e12)
    nbytes = P.decode_step_bytes(cfg, 128, 4006 / 4)
    assert _reader("pangu_serve_hbm_bw_share").read(ctx) == pytest.approx(
        100 * nbytes / 0.03 / 819e9)
    # two traced decode steps, the kernel 100 us in all: it is the
    # trace's only Mosaic kernel, so the whole kernel time is its own
    want = 100 * 2 * 1001.5 * 5 * 278_528 / 197e12 / 1e-4
    roof = _reader("pangu_latent_attn_roofline")
    assert roof.read(ctx) == pytest.approx(want, rel=1e-6)
    assert 0 < want < 100
    # beside another kernel: the op's own time among the ten largest
    ctx["trace"]["mosaic_kernels"].append("gmm_bf16_")
    assert roof.read(ctx) == pytest.approx(want / 2, rel=1e-6)
    ctx["trace"]["device_ops"].pop()
    assert roof.read(ctx) is None
    assert _reader("ttft_p95_pangu_decode_cell_ms").read(ctx) == \
        pytest.approx(290.0)
    # the two generic readers of the expert layers take this cell: the
    # masked pass multiplies 128 rows x 16 experts for 64 picks a layer
    assert _reader("moe_rows_per_pick").read(ctx) == 32.0
    assert _reader("moe_expert_load_imbalance").read(ctx) == \
        pytest.approx(480 * 16 / 2560)


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
        for ctx in (other, cpu, bare):
            assert _reader(name).read(ctx) is None, name
    assert _reader("ttft_p95_pangu_decode_cell_ms").read(
        _ctx(cfg, ttft=[])) is None


def test_toy_manifest_is_clean_and_holds_the_real_entries(
        pangu_manifest, real_manifest):
    from perfbench.harness import manifest as M
    assert M.lint(pangu_manifest, bench_dir=TOY) == []
    real = {m["name"]: m for m in real_manifest["per_layer"]}
    toy = {m["name"]: m for m in pangu_manifest["per_layer"]}
    assert set(toy) == set(NEW) | set(LISTED)
    for name, m in toy.items():      # each entry is the real one's
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            k: v for k, v in real[name].items() if k != "workloads"}


def test_pangu_rehearsal_end_to_end(pangu_manifest):
    """The whole traced run of the toy cell on the CPU: correct, the
    fp8 control refused, every listed metric that a CPU run can write
    printed, and no device metric."""
    from perfbench import run as R
    from perfbench.harness.common import result_line
    res = R.run_cell(CELL, 2 ** 31 + 77, 1.0, True,
                     t_proc0=time.perf_counter(), require_chip=False,
                     manifest=pangu_manifest, bench_dir=TOY,
                     controls=("fp8",))
    line = json.loads(result_line(
        res["correct"], res["attempted"], res["failed"], res["metrics"],
        res["device"], res["breakdown"], res["compared"]))
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    got = set(line["metrics"])
    assert {"moe_expert_load_imbalance", "moe_rows_per_pick", "itl_p50_ms",
            "ttft_p95_pangu_decode_cell_ms", "itl_p95_decode_cell_ms",
            "sched_batch_occupancy", "sched_prefill_share",
            "sched_host_share", "serve_compiles_in_window"} | set(SPANS) \
        <= got
    # a CPU run writes counts, never a device metric
    assert not got & {"pangu_serve_mfu", "pangu_serve_hbm_bw_share",
                      "pangu_latent_attn_roofline", "decode_step_ms",
                      "serve_peak_hbm_gib"}
    assert got <= {m["name"] for m in pangu_manifest["per_layer"]}
    assert line["metrics"]["serve_compiles_in_window"]["value"] == 0
    assert 1.0 <= line["metrics"]["moe_expert_load_imbalance"]["value"] <= 8
    # the share of mismatched tokens decides, as in the real cell (a
    # flipped expert moves one token's logit by a deviation, so the
    # widest gap is printed, not compared): bf16 reads 0.02-0.06 over
    # seeds here, the fp8 control 0.29-0.37, the limit 0.15
    lim = line["compared"]["served_mismatch_share"]
    assert lim[0] < lim[1] == 0.15 < \
        line["compared"]["control_fp8_mismatch_share"][0]
    assert line["compared"]["served_logit_gap"][1] is None
    assert res["verdicts"]["fp8"]["correct"] is False


def test_pangu_untraced_rehearsal_reports_the_rate(pangu_manifest, capsys):
    """An untraced run prints the rate and the set-up time, nothing
    else, and its run log sets the server's count of the window's
    tokens beside the client's."""
    from perfbench import run as R
    res = R.run_cell(CELL, 2 ** 31 + 78, 1.0, False,
                     t_proc0=time.perf_counter(), require_chip=False,
                     manifest=pangu_manifest, bench_dir=TOY)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
    assert res["metrics"]["serve_out_tokens_per_s"]["value"] > 0
    (log,) = [json.loads(l.split("perfbench: run ", 1)[1])
              for l in capsys.readouterr().err.splitlines()
              if l.startswith("perfbench: run ")]
    server = log["server_tokens_in_window"]
    assert server == log["window"]["tokens_generated"] > 0
    assert log["server_to_client_tokens"] == pytest.approx(
        server / log["out_tokens_in_window"])


def _experts_off_by_one(server):
    for lyr in server._model.model.layers:
        if lyr.is_moe:       # every pick lands on its neighbour's weights
            lyr.mlp.held_experts = (1, 8)


def _post_norms_left_out(server):
    import dataclasses
    for lyr in server._model.model.layers:
        lyr.config = dataclasses.replace(lyr.config, sandwich_norm=False)


# (a rotation left out is NOT among them: at the toy's widths the
# attention logits of seeded weights are ~0.01, so bf16 serving cannot
# tell; the float32 tests of tests/test_pangu_ultra_moe.py miss by 0.1)
@pytest.mark.parametrize("sabotage", [_experts_off_by_one,
                                      _post_norms_left_out],
                         ids=lambda f: f.__name__)
def test_pangu_fault_is_seen(pangu_manifest, sabotage):
    from perfbench import run as R
    res = R.run_cell(CELL, 2 ** 31 + 77, 1.0, False,
                     t_proc0=time.perf_counter(), require_chip=False,
                     manifest=pangu_manifest, bench_dir=TOY,
                     sabotage=sabotage)
    assert res["correct"] is False, res["compared"]
