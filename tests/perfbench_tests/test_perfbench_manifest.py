import json
import os

import pytest

from perfbench.harness import manifest as M


def test_manifest_lints_clean(real_manifest):
    assert M.lint(real_manifest) == []


def test_toy_manifest_lints_clean(toy_manifest):
    from conftest import TOY
    assert M.lint(toy_manifest, bench_dir=TOY) == []


def test_names_units_and_limits(real_manifest):
    m = real_manifest
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in m[sec]]
        assert len(names) == len(set(names))
        assert all(M.NAME_RE.match(n) for n in names)
    for x in m["end_to_end"] + m["per_layer"]:
        assert M.UNIT_RE.match(x["unit"]), x
        assert x["better"] in ("lower", "higher")
    assert len(json.dumps(m)) < 64 * 1024
    assert m["command"] == ["python3", "perfbench/run.py"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= 1
    setup = [x for x in m["end_to_end"] if x["name"] == "setup_s"][0]
    assert setup["bound"] <= 0.1 and "workloads" not in setup


def test_every_per_layer_metric_moves_a_metric_its_cells_report(
        real_manifest):
    m = real_manifest
    cells = {w["name"] for w in m["workloads"]}
    e2e = {x["name"]: set(x.get("workloads", cells))
           for x in m["end_to_end"]}
    for x in m["per_layer"]:
        assert set(x.get("workloads", cells)) <= e2e[x["moves"]], x["name"]
        assert os.path.exists(os.path.join(
            M.BENCH_DIR, "metrics", x["name"] + ".py"))
    for c in cells:
        assert any(c in set(x.get("workloads", cells))
                   for x in m["per_layer"])


def test_kernel_and_mfu_names(real_manifest):
    names = [x["name"] for x in real_manifest["per_layer"]]
    assert any("mfu" in n.split("_") for n in names)
    for x in real_manifest["per_layer"]:
        if x["name"].endswith("_roofline"):
            assert x["unit"] == "%"


def test_a_bad_manifest_is_caught(real_manifest):
    bad = json.loads(json.dumps(real_manifest))
    bad["per_layer"][0]["moves"] = "nope"
    bad["end_to_end"][0]["bound"] = 0.5
    bad["workloads"][0]["chips"] = 2
    bad["per_layer"][1]["why"] = "not allowed"
    faults = M.lint(bad)
    assert len(faults) >= 4


def test_configs_hold_published_widths(real_manifest):
    by = {c["name"]: c for c in real_manifest["configs"]}
    mis = json.load(open(os.path.join(M.ROOT, by["mistral-7b-v0.3"]["file"])))
    want = dict(hidden_size=4096, intermediate_size=14336,
                num_attention_heads=32, num_key_value_heads=8, head_dim=128,
                vocab_size=32768, rope_theta=1e6, rms_norm_eps=1e-5,
                tie_word_embeddings=False)
    assert {k: mis[k] for k in want} == want
    assert by["mistral-7b-v0.3"]["reduced"] == ["num_hidden_layers"]
    assert mis["num_hidden_layers"] < mis["published"]["num_hidden_layers"]
    # nothing but depth differs from the source
    changed = {k for k, v in mis["published"].items() if mis[k] != v}
    assert changed == {"num_hidden_layers"}


def test_cell_finds_its_files_by_name(real_manifest):
    for w in real_manifest["workloads"]:
        cell = M.Cell(real_manifest, w["name"])
        assert cell.spec["runner"] in ("serve", "train")
        assert cell.traffic["kind"]
        assert "limits" in cell.spec["correct"]
        assert hasattr(cell.reference(), "param_specs")
        assert hasattr(cell.binding(), "name_map")
        for mtr in cell.per_layer:
            assert callable(cell.metric_reader(mtr["name"]).read)
    with pytest.raises(KeyError):
        M.Cell(real_manifest, "no-such-cell")
