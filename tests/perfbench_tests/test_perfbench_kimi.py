"""The kimi-linear-48b-a3b configuration and its cell in
BENCHMARK.json: its file against the published config, the counting
functions behind its per-layer metrics, and the whole run of its cell
at toy size on the CPU (toy files of its own under ``toy/``, a manifest
of its own), with planted faults that have to come out as not
correct."""
import json
import os
import time

import numpy as np
import pytest

from conftest import ROOT, TOY

CELL = "kimi-linear-serve-decode"
# moonshotai/Kimi-Linear-48B-A3B-Instruct config.json, the keys that
# say something about the model's shape (the catalog's copy)
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                       19, 21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256,
    "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
    "num_shared_experts": 1, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = {"num_hidden_layers": 8, "num_experts": 64, "vocab_size": 40960}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        return json.load(f)


def test_the_cell_stands_last_and_the_accepted_entries_keep_their_place(
        real_manifest):
    """What PR 27 added stands at the end of each list; the entries of
    PR 24 to 26 keep their place and order, and a ``workloads`` list
    that gained the cell gained it at its end."""
    assert [w["name"] for w in real_manifest["workloads"]] == [
        "mistral7b-serve-decode", "mistral7b-serve-prefill", CELL]
    assert [c["name"] for c in real_manifest["configs"]] == [
        "mistral-7b-v0.3", "kimi-linear-48b-a3b"]
    assert real_manifest["run_seconds"] == 50
    names = [m["name"] for m in real_manifest["per_layer"]]
    assert names[-5:] == [
        "kimi_serve_mfu", "kimi_serve_hbm_bw_share",
        "moe_expert_load_imbalance", "ttft_p95_kimi_decode_cell_ms",
        "kimi_latent_attn_roofline"]
    for m in real_manifest["per_layer"] + real_manifest["end_to_end"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]
    # the tail carries no bound in this cell (PERF.md section 2)
    ttft = [m for m in real_manifest["end_to_end"]
            if m["name"] == "ttft_p95_ms"][0]
    assert CELL not in ttft["workloads"]


def test_only_the_three_reduced_keys_differ_from_the_source(
        cfg, real_manifest):
    entry = [c for c in real_manifest["configs"]
             if c["name"] == "kimi-linear-48b-a3b"][0]
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    for k, v in PUBLISHED.items():
        assert cfg[k] == REDUCED.get(k, v), k
    assert cfg["published"] == {k: PUBLISHED[k] for k in REDUCED}
    a = cfg["assumed"]
    assert a["held_experts"] == [0, 64] and a["gate_low_rank"] == 128
    assert a["state_dtype"] == "float32" and len(a["layer_kinds"]) == 8
    assert "16-chip" in cfg["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f if "Kimi-Linear" in line]
        assert rows[0]["config"] == PUBLISHED
        assert entry["source"] == rows[0]["source_url"]


def test_parameter_counts_are_the_issues_arithmetic(cfg):
    from perfbench.harness import flops_kimi as K
    m = 1e6
    assert K.kda_matmul_params(cfg) / m == pytest.approx(39.4, abs=0.1)
    assert K.mla_matmul_params(cfg) / m == pytest.approx(29.1, abs=0.1)
    assert K.moe_fixed_params(cfg) / m == pytest.approx(7.7, abs=0.1)
    assert K.expert_params(cfg) / m == pytest.approx(7.08, abs=0.01)
    assert K.dense_ffn_params(cfg) / m == pytest.approx(63.7, abs=0.1)
    assert K.head_params(cfg) / m == pytest.approx(94.4, abs=0.1)
    kinds = K.layer_kinds(cfg)
    assert [m_ for m_, _ in kinds] == [False, False, False, True] * 2
    assert [e for _, e in kinds] == [False] + [True] * 7
    # the whole cut, one of embedding and head apart: 3.77B less 94.4M
    assert K.held_weight_params(cfg) / 1e9 == pytest.approx(3.68, abs=0.01)
    # and they are the reference's own leaves
    from perfbench.harness import manifest as M
    ref = M.load_module(os.path.join(
        ROOT, "perfbench", "configs", "kimi-linear-48b-a3b.reference.py"),
        "kimi_reference_for_counts")
    import math
    n = sum(math.prod(s) for k, (s, _) in ref.param_specs(cfg).items()
            if len(s) > 1 and k != "embed"
            and k.split(".")[-1] not in ("cq", "ck", "cv"))
    assert n == K.held_weight_params(cfg)


def test_decode_step_bytes_and_serve_flops(cfg):
    from perfbench.harness import flops_kimi as K
    # 128 live rows, mean context 1,040: weights 7.36 GB, state read
    # and written 3.33 GB (2 MiB + 72 KiB a row and layer, 6 layers),
    # latent 0.31 GB
    b = K.decode_step_bytes(cfg, 128, 128 * 1040)
    w = 2 * K.held_weight_params(cfg)
    assert w / 1e9 == pytest.approx(7.36, abs=0.01)
    assert (b - w) / 1e9 == pytest.approx(3.334 + 0.307, abs=0.005)
    assert K.decode_step_bytes(cfg, 0, 0) == w
    assert K.kda_state_bytes(cfg) == 2 * 2 ** 20 + 72 * 2 ** 10
    # a token through the layers at a quarter of its picks here:
    # about 1.04 GFLOP, the head 0.19 more (the issue's "about 1.2")
    per = K.token_flops(cfg, 0.25)
    assert per / 1e9 == pytest.approx(1.04, abs=0.01)
    assert per - K.token_flops(cfg, 0.0) == pytest.approx(
        7 * 2 * 2 * K.expert_params(cfg))
    f = K.serve_flops(cfg, 1000, 2, 100, 5000, 7000, 0.25)
    assert f == pytest.approx(
        1100 * per + 2 * 102 * K.head_params(cfg)
        + 2 * K.mla_pair_flops(cfg) * 12000)
    assert K.mla_pair_flops(cfg) == 2 * 32 * (128 + 64 + 128)
    # two latent layers, 576 bf16 values a live token each
    assert K.latent_read_bytes(cfg, 1000) == 2 * 1000 * 1152
    assert b - K.decode_step_bytes(cfg, 128, 0) == K.latent_read_bytes(
        cfg, 128 * 1040)


@pytest.fixture(scope="module")
def kimi_manifest():
    with open(os.path.join(TOY, "manifest_kimi.json")) as f:
        return json.load(f)


def _run(manifest, **kw):
    from perfbench import run as R
    return R.run_cell(CELL, 2 ** 31 + 77, 1.0, kw.pop("trace", False),
                      t_proc0=time.perf_counter(), require_chip=False,
                      manifest=manifest, bench_dir=TOY, **kw)


def test_toy_manifest_is_clean_and_mirrors_the_real_entries(
        kimi_manifest, real_manifest):
    from perfbench.harness import manifest as M
    assert M.lint(kimi_manifest, bench_dir=TOY) == []
    assert M.lint(real_manifest) == []
    real = {m["name"]: m for m in real_manifest["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {m["name"] for m in kimi_manifest["per_layer"]} == set(real)
    assert {"kimi_serve_mfu", "kimi_serve_hbm_bw_share",
            "moe_expert_load_imbalance",
            "kimi_latent_attn_roofline"} <= set(real)
    cell = [w for w in real_manifest["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-a3b", "closed-reason-decode", 1)
    assert "4x" in cell["why"]


def test_kimi_rehearsal_end_to_end(kimi_manifest):
    from perfbench.harness.common import result_line
    res = _run(kimi_manifest, trace=True, controls=("fp8",))
    line = json.loads(result_line(
        res["correct"], res["attempted"], res["failed"], res["metrics"],
        res["device"], res["breakdown"], res["compared"]))
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # a CPU run writes counts, never a device metric
    got = set(line["metrics"])
    assert {"moe_expert_load_imbalance", "sched_batch_occupancy",
            "serve_compiles_in_window"} <= got
    assert not got & {"kimi_serve_mfu", "kimi_serve_hbm_bw_share",
                      "kimi_latent_attn_roofline", "decode_step_ms",
                      "serve_peak_hbm_gib"}
    assert line["metrics"]["serve_compiles_in_window"]["value"] == 0
    assert 1.0 <= line["metrics"]["moe_expert_load_imbalance"]["value"] <= 4
    assert res["verdicts"]["fp8"]["correct"] is False


def _no_shared_expert(server):
    for lyr in server._model.model.layers:
        if lyr.is_moe:
            lyr.mlp.shared = False


def _held_range_shifted(server):
    for lyr in server._model.model.layers:
        if lyr.is_moe:
            first, count = lyr.mlp.held_experts
            lyr.mlp.held_experts = (first + 1, count)


@pytest.mark.parametrize("sabotage", [_no_shared_expert,
                                      _held_range_shifted],
                         ids=lambda f: f.__name__)
def test_kimi_fault_is_seen(kimi_manifest, sabotage):
    res = _run(kimi_manifest, sabotage=sabotage)
    assert res["correct"] is False, res["compared"]
    val, lim = res["compared"]["served_logit_gap"]
    assert val > lim


def test_readers_return_nothing_without_the_programs_counters():
    """On a program that lacks this PR's counters (the parent under
    these benchmark files) the new readers find nothing and say so."""
    from perfbench.harness import manifest as M
    ctx = {"stats_end": {"decode_steps": 10}, "cfg": {"num_experts": 64},
           "server": {"num_slots": 8}, "device": {"platform": "tpu",
                                                   "kind": "TPU v5e"},
           "window": {"decode_steps": 0}, "trace": None}
    for name in ("kimi_serve_mfu", "kimi_serve_hbm_bw_share",
                 "moe_expert_load_imbalance", "kimi_latent_attn_roofline"):
        mod = M.load_module(os.path.join(ROOT, "perfbench", "metrics",
                                         name + ".py"), "reader_" + name)
        assert mod.read(ctx) is None


def test_latent_attn_roofline_reads_the_kernels_own_time(cfg):
    """Two traced decode steps over 1,000 live positions each, the
    kernel 10 us in all: 2 x 2 layers x 1,000 x 1,152 B over 10 us of
    819 GB/s; nothing when the kernel is not among the ten largest;
    the trace's whole kernel time when it is the only Mosaic kernel."""
    from perfbench.harness import manifest as M
    mod = M.load_module(os.path.join(
        ROOT, "perfbench", "metrics", "kimi_latent_attn_roofline.py"),
        "reader_latent_roofline")
    ctx = {"cfg": cfg, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "window": {"decode_steps": 4}, "t0": 0.0, "t1": 10.0,
           "requests": [{"prompt": [0] * 999,
                         "token_times": [-1.0, 1.0, 2.0, 3.0, 4.0]}],
           "trace": {"busy_s": 1.0,
                     "programs": {"jit_decode_fn": [0.03, 0.03]},
                     "device_ops": [["sort_f32_", 0.5],
                                    ["paged_attention_bf16_128_32_640_",
                                     1e-5]]}}
    # contexts 1000..1003 over four steps
    want = 100 * 2 * 2 * 1001.5 * 1152 / 1e-5 / 819e9
    assert mod.read(ctx) == pytest.approx(want, rel=1e-6)
    ctx["trace"]["device_ops"].pop()
    assert mod.read(ctx) is None
    # beside another kernel the whole kernel time is not this one's
    ctx["trace"].update(kernel_s=2e-5, mosaic_kernels=[
        "paged_attention_bf16_128_32_640_", "gmm_bf16_"])
    assert mod.read(ctx) is None
    ctx["trace"]["mosaic_kernels"].pop()
    assert mod.read(ctx) == pytest.approx(want / 2, rel=1e-6)


def test_gap_study_reads_nought_for_a_float32_program(kimi_manifest):
    """perfbench/tools/kimi_gap_study.py at the toy size: the program
    in float32 arithmetic chooses the reference's experts at every
    position and serves the reference's best token."""
    from perfbench.harness import manifest as M
    study = M.load_module(os.path.join(
        ROOT, "perfbench", "tools", "kimi_gap_study.py"), "kimi_gap_study")
    cell = M.Cell(kimi_manifest, CELL, bench_dir=TOY)
    cfg, seed = cell.config, 2 ** 31 + 5
    r = np.random.RandomState(1)
    requests = [(r.randint(1, cfg["vocab_size"], size=n).astype(np.int32),
                 None, 6) for n in (9, 14)]
    prog = study.program_routes(cfg, cell, seed, "float32", requests)
    seqs = [(rq[0], fed) for rq, (fed, _, _) in zip(requests, prog)]
    res = study.summarize(prog, study.reference_numbers(cfg, cell, seed,
                                                        seqs), seqs)
    assert res["all"]["tokens"] == 12
    assert res["all"]["gap_max"] < 1e-4
    assert res["all"]["mismatch_share"] == 0
    assert res["flipped_token_layers_share"] == 0
    assert res["routing_flipped_at_the_token"] == {"tokens": 0}
    # the replay of given tokens feeds them and reports its own
    again = study.program_routes(
        cfg, cell, seed, "float32",
        [(rq[0], fed, len(fed)) for rq, (fed, _, _) in zip(requests, prog)])
    assert [own for _, own, _ in again] == [fed for fed, _, _ in prog]
