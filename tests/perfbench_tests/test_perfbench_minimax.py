"""The minimax-m3 configuration and its cell in BENCHMARK.json: its file
against the catalog row, the counting functions behind its per-layer
metrics against hand counts, each new reader on a synthetic ``ctx``,
and the whole traced run of its cell at toy size on the CPU (toy files
of its own under ``toy/``, a manifest of its own), which has to fail
``correct`` under a planted fault.  Membership checks only: no
position in a list is pinned."""
import dataclasses
import json
import os
import time

import pytest

from conftest import ROOT, TOY

CELL = "minimax-m3-serve-longctx"
CONFIG = "minimax-m3"
# the model catalog (JSON lines, a row per architecture), where one is at hand
CATALOG = os.environ.get("MODEL_CATALOG", "")
REDUCED = {"num_hidden_layers": (60, 5),
           "moe_layer_freq": ([0, 0, 0] + [1] * 57, [0, 1, 1, 1, 1]),
           "num_local_experts": (128, 8), "vocab_size": (200064, 25008),
           "num_mtp_modules": (7, 0), "num_nextn_predict_layers": (1, 0)}
NEW = ("minimax_sparse_attn_roofline", "msa_read_share",
       "minimax_serve_mfu", "minimax_serve_hbm_bw_share")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def minimax_manifest():
    with open(os.path.join(TOY, "manifest_minimax.json")) as f:
        return json.load(f)


def _reader(name):
    from perfbench.harness import manifest as M
    return M.load_module(os.path.join(ROOT, "perfbench", "metrics",
                                      name + ".py"), "reader_" + name)


def test_only_the_reduced_keys_differ_from_the_catalog_row(
        cfg, real_manifest):
    """Every key of the catalog's copy of the published config.json at
    its published value but the six under ``reduced``, whose published
    values ``published`` states; what the row does not have is an
    addition."""
    entry = [c for c in real_manifest["configs"] if c["name"] == CONFIG][0]
    assert set(entry["reduced"]) == set(REDUCED)
    assert cfg["published"] == {k: v[0] for k, v in REDUCED.items()}
    assert {k: cfg[k] for k in REDUCED} == {k: v[1]
                                            for k, v in REDUCED.items()}
    if CATALOG and os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = [json.loads(line) for line in f
                   if '"MiniMax-M3"' in line][0]
        assert entry["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differ == set(REDUCED)
        assert set(cfg) - set(row["config"]) == {
            "num_experts", "published", "assumed", "deployment"}
    # every published width
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["rotary_dim"],
            cfg["intermediate_size"], cfg["dense_intermediate_size"],
            cfg["shared_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"], cfg["rope_theta"],
            cfg["swiglu_alpha"], cfg["swiglu_limit"]) == (
        6144, 64, 4, 128, 64, 3072, 12288, 3072, 4, 2, 5000000, 1.702, 7)
    a = cfg["assumed"]
    assert a["held_experts"] == [0, 8]
    assert (a["msa_block"], a["msa_top_k"], a["msa_local"],
            a["msa_index_heads"], a["msa_index_dim"]) == (128, 16, 2, 4, 128)
    assert cfg["num_experts"] == cfg["num_local_experts"] == 8
    for said in ("16 chips share each layer", "3,173M parameters",
                 "5.33 GB", "1/16", "5 of 60 layers"):
        assert said in cfg["deployment"], said


def test_the_cell_and_its_entries_are_listed(real_manifest):
    from perfbench.harness import manifest as M
    assert M.lint(real_manifest) == []
    cell = [w for w in real_manifest["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "closed-longctx-mixed", 1)
    mine = {m["name"]: m for m in real_manifest["per_layer"]
            if CELL in m.get("workloads", ())}
    # the cell is judged on the tail between tokens, a decode step of 10
    # ms in every run, not on the rate, which swings with where the
    # 4-6 s prefill calls fall in the window (PERF.md section 2): its
    # own four entries move that tail, and no entry that moves the rate
    # lists it
    assert set(NEW) == set(mine)
    for name in NEW:
        assert mine[name]["moves"] == "itl_p95_ms", name
        assert mine[name]["workloads"] == [CELL]
    assert mine["minimax_sparse_attn_roofline"]["layer"] == "kernels"
    e2e = {m["name"]: m for m in real_manifest["end_to_end"]}
    assert CELL in e2e["itl_p95_ms"]["workloads"]
    assert CELL not in e2e["serve_out_tokens_per_s"]["workloads"]
    assert CELL not in e2e["ttft_p95_ms"]["workloads"]
    with open(os.path.join(ROOT, "perfbench", "cells", CELL + ".json")) as f:
        spec = json.load(f)
    assert spec["server"] == {
        "num_slots": 12, "block_size": 128, "max_model_len": 43008,
        "prompt_buckets": [32768, 36864, 40960], "max_prefill_batch": 1}
    # three sampled requests: the harness keeps each one's 43,008
    # positions of float32 residual stream twice over a layer (PERF.md)
    assert spec["correct"]["sample"] == 3
    assert spec["correct"]["limits"]["requests_unanswered"] == 0
    # the geometric middle of the program's largest reading and the fp8
    # control's least (0.207 and 0.783 on the chip: PERF.md section 6)
    assert spec["correct"]["limits"]["served_mismatch_share"] == 0.4
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "closed-longctx-mixed.json")) as f:
        tr = json.load(f)
    assert (tr["kind"], tr["clients"], tr["sampling"], tr["warmup_s"]) == (
        "serve_closed_loop", 12, "greedy", 30)
    assert tr["prompt_len"] == {"dist": "loguniform", "lo": 28000,
                                "hi": 40000}
    assert tr["output_len"] == {"dist": "loguniform", "lo": 1024,
                                "hi": 2048}
    from perfbench.harness.traffic import quantile_lengths
    # the longest prompt with the longest answer fits the server, and
    # every prompt a bucket
    p, o = (quantile_lengths(tr["prompt_len"], 12),
            quantile_lengths(tr["output_len"], 12))
    assert p.max() + o.max() <= 43008 and p.max() <= 40960


def test_parameter_counts_match_the_hand_arithmetic(cfg):
    import math
    from perfbench.harness import flops_minimax as F
    from perfbench.harness import manifest as M
    assert F.attn_params(cfg) / 1e6 == pytest.approx(119.5, abs=0.05)
    assert F.dense_ffn_params(cfg) / 1e6 == pytest.approx(226.5, abs=0.05)
    assert F.expert_params(cfg) / 1e6 == pytest.approx(56.6, abs=0.05)
    assert F.embed_params(cfg) / 1e6 == pytest.approx(153.6, abs=0.05)
    assert (F.n_layers(cfg), F.n_moe_layers(cfg)) == (5, 4)
    moe_layer = (F.attn_params(cfg) + F.moe_fixed_params(cfg)
                 + 8 * F.expert_params(cfg))
    assert moe_layer / 1e6 == pytest.approx(629.9, abs=0.1)
    n = F.held_weight_params(cfg)
    assert n / 1e6 == pytest.approx(3173, abs=0.5)
    assert 2 * n / 1e9 == pytest.approx(6.35, abs=0.005)
    # and they are the reference's own leaves
    ref = M.load_module(os.path.join(
        ROOT, "perfbench", "configs", CONFIG + ".reference.py"),
        "minimax_reference_for_counts")
    assert n == sum(math.prod(s) for s, _ in ref.param_specs(cfg).values())
    # the cache: 10,240 B a token in five layers, 5.33 GB at 12 x 336
    # pages of 128, with 2 KB of float32 pooled keys a page and layer
    assert 5 * F.kv_bytes_per_token(cfg) == 10_240
    blocks = 12 * 336 + 1
    assert (blocks * 128 * 5 * F.kv_bytes_per_token(cfg)
            + blocks * 5 * 2048) / 1e9 == pytest.approx(5.33, abs=0.005)


def test_the_kernels_roofline_and_a_decode_steps_bytes(cfg):
    """A selected tile is 64 KB of K and V and 1 MFLOP at 16 query
    heads: bytes-bound; a decode step's bytes are the held weights but
    the embedding, the tiles and the live pages' pooled keys."""
    from perfbench.harness import flops_minimax as F
    assert F.tile_bytes(cfg) == 65_536 and F.tile_flops(cfg) == 1_048_576
    s, side = F.sparse_attn_seconds(cfg, 1000, 197e12, 819e9)
    assert side == "hbm" and s == pytest.approx(1000 * 65_536 / 819e9)
    # a step: 12 rows, 19 tiles a (row, kv head, layer), 270 live pages
    w = F.decode_step_bytes(cfg, 0, 0, 0)
    assert w == 2 * (F.held_weight_params(cfg) - F.embed_params(cfg))
    b = F.decode_step_bytes(cfg, 12, 12 * 4 * 5 * 19, 12 * 4 * 270)
    assert b - w == 2 * 12 * 6144 + 4560 * 65_536 + 5 * 12 * 4 * 270 * 512


def _ctx(cfg, **over):
    ctx = {"cfg": cfg, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "seconds": 10.0, "t0": 0.0, "t1": 10.0,
           "server": {"num_slots": 12},
           "window": {"decode_steps": 4, "tokens_generated": 48,
                      "prefill_rows": 0},
           "requests": [{"prompt": [0] * 30000,
                         "token_times": [-1.0, 1.0, 2.0, 3.0, 4.0]}],
           "stats_end": {"decode_steps": 10, "moe_picks_here": 120,
                         "moe_max_expert_load": 30,
                         "moe_rows_multiplied": 3840,
                         "msa_pages_read": 10 * 4560,
                         "msa_pages_live": 10 * 12 * 4 * 5 * 250},
           "ttft": [0.1, 0.2, 0.3],
           "trace": {"busy_s": 1.0, "kernel_s": 1e-3,
                     "mosaic_kernels": ["paged_sparse_attention"],
                     "programs": {"jit_decode_fn": [0.012, 0.012]},
                     "device_ops": [["fusion_f32_", 0.5],
                                    ["paged_sparse_attention", 2e-3]]}}
    ctx.update(over)
    return ctx


def test_each_new_reader_on_a_synthetic_run(cfg):
    from perfbench.harness import flops_minimax as F
    ctx = _ctx(cfg)
    # 19 of 250 pages a (row, kv head, layer)
    assert _reader("msa_read_share").read(ctx) == pytest.approx(
        100 * 19 / 250)
    # two traced steps, the kernel 1 ms in all: the trace's only
    # Mosaic kernel, so the whole kernel time is its own
    least = 4560 * 65_536 / 819e9
    roof = _reader("minimax_sparse_attn_roofline")
    assert roof.read(ctx) == pytest.approx(100 * 2 * least / 1e-3)
    assert 0 < roof.read(ctx) < 100
    # beside another kernel: the op's own time among the ten largest
    ctx["trace"]["mosaic_kernels"].append("flash_attention")
    assert roof.read(ctx) == pytest.approx(100 * 2 * least / 2e-3)
    ctx["trace"]["device_ops"].pop()
    assert roof.read(ctx) is None
    ctx = _ctx(cfg)
    # 120 picks in 10 steps of 12 rows, 4 expert layers, 4 a token: a
    # sixteenth landed here; 12 rows a step, 4,560 tiles and 12,000
    # pooled keys scored a step, the decode program's median 12 ms
    f = F.decode_step_flops(cfg, 12, 4560, 12 * 4 * 5 * 250, 1 / 16)
    assert f == 12 * (F.token_flops(cfg, 1 / 16) + 2 * 6144 * 25008) \
        + 4560 * 1_048_576 + 2 * 128 * 60_000
    assert _reader("minimax_serve_mfu").read(ctx) == pytest.approx(
        100 * f / 0.012 / 197e12)
    nbytes = F.decode_step_bytes(cfg, 12, 4560, 12 * 4 * 250)
    assert _reader("minimax_serve_hbm_bw_share").read(ctx) == \
        pytest.approx(100 * nbytes / 0.012 / 819e9)
    # the generic readers of the expert layers take this cell
    assert _reader("moe_rows_per_pick").read(ctx) == 32.0
    assert _reader("moe_expert_load_imbalance").read(ctx) == \
        pytest.approx(30 * 8 / 120)


def test_readers_return_nothing_where_there_is_nothing_to_read(cfg):
    """Another configuration's run, a program without the counters (the
    parent under these benchmark files), a CPU run: the new readers
    find nothing and say so, and never raise."""
    other = _ctx({"num_experts": 64, "hidden_size": 8, "assumed": {}})
    bare = _ctx(cfg, stats_end={"decode_steps": 10}, trace=None,
                window={"decode_steps": 0, "tokens_generated": 0,
                        "prefill_rows": 0})
    cpu = _ctx(cfg, device={"platform": "cpu", "kind": "cpu"})
    for name in ("minimax_sparse_attn_roofline", "minimax_serve_mfu",
                 "minimax_serve_hbm_bw_share"):
        for ctx in (other, cpu, bare):
            assert _reader(name).read(ctx) is None, name
    for ctx in (other, bare):
        assert _reader("msa_read_share").read(ctx) is None


def test_toy_manifest_is_clean_and_holds_the_real_entries(
        minimax_manifest, real_manifest):
    from perfbench.harness import manifest as M
    assert M.lint(minimax_manifest, bench_dir=TOY) == []
    real = {m["name"]: m for m in real_manifest["per_layer"]}
    toy = {m["name"]: m for m in minimax_manifest["per_layer"]}
    assert set(toy) == set(NEW)
    for name, m in toy.items():      # each entry is the real one's
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            k: v for k, v in real[name].items() if k != "workloads"}


def _run(manifest, seed, trace, **kw):
    from perfbench import run as R
    return R.run_cell(CELL, seed, 1.0, trace, t_proc0=time.perf_counter(),
                      require_chip=False, manifest=manifest, bench_dir=TOY,
                      **kw)


def test_minimax_rehearsal_end_to_end(minimax_manifest):
    """The whole traced run of the toy cell on the CPU: correct, the
    fp8 control over the limit, the one listed metric a CPU run can
    write (the share of pages read) printed, and no device metric; an
    untraced run prints the tail between tokens and the set-up time."""
    from perfbench.harness.common import result_line
    res = _run(minimax_manifest, 2 ** 31 + 77, True, controls=("fp8",))
    line = json.loads(result_line(
        res["correct"], res["attempted"], res["failed"], res["metrics"],
        res["device"], res["breakdown"], res["compared"]))
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    got = set(line["metrics"])
    # a CPU run writes the share of pages read, never a device metric
    assert got == {"msa_read_share"}
    # prompts of 40-90 tokens on pages of 8, 5 pages a query
    assert 20 < line["metrics"]["msa_read_share"]["value"] < 100
    # the share of mismatched tokens decides, as in the real cell:
    # bf16 reads ~0.03 here, the fp8 control ~0.22, the limit 0.15
    lim = line["compared"]["served_mismatch_share"]
    assert lim[0] < lim[1] == 0.15 < \
        line["compared"]["control_fp8_mismatch_share"][0]
    assert line["compared"]["served_logit_gap"][1] is None
    assert res["verdicts"]["fp8"]["correct"] is False
    res = _run(minimax_manifest, 2 ** 31 + 78, False)
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}


def _experts_off_by_one(server):
    for lyr in server._model.model.layers:
        if lyr.is_moe:       # every pick lands on its neighbour's weights
            lyr.mlp.held_experts = (1, 8)


def _one_page_a_query(server):
    for lyr in server._model.model.layers:
        lyr.self_attn.config = dataclasses.replace(
            lyr.self_attn.config, msa_top_k=0, msa_local=1)


def _indexer_reversed(server):
    for lyr in server._model.model.layers:
        # every query reads the pages its indexer scores lowest; the
        # programs are the sound ones (weights are arguments)
        w = lyr.self_attn.index_proj
        w._value = -w._value


@pytest.mark.parametrize("sabotage", [_experts_off_by_one,
                                      _one_page_a_query,
                                      _indexer_reversed],
                         ids=lambda f: f.__name__)
def test_minimax_fault_is_seen(minimax_manifest, sabotage):
    res = _run(minimax_manifest, 2 ** 31 + 77, False, sabotage=sabotage)
    assert res["correct"] is False, res["compared"]
