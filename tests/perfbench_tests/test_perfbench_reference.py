"""Each plain reference against the system at a small size on the CPU:
logits through both serving paths (whole-sequence forward; paged
prefill then decode), and -- through the rehearsal's own comparison --
loss and gradients of both training paths.  And the control: the
reference in fp8 put in the program's place goes through the harness's
own comparison with the cell's limits and comes out as not correct."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TOY
from perfbench.harness import correct as K
from perfbench.harness import manifest as M
from perfbench.harness import weights as W
from perfbench.harness.program import install_weights


@pytest.fixture(scope="module")
def mistral(toy_manifest):
    cell = M.Cell(toy_manifest, "mistral7b-serve-decode", bench_dir=TOY)
    ref, b, cfg = cell.reference(), cell.binding(), cell.config
    from paddle_tpu.distributed import mesh as mesh_mod
    mesh_mod.set_mesh(None)
    model = b.build_serving(cfg, 64)
    install_weights(model, b.name_map(cfg, model), ref.param_specs(cfg),
                    5, jnp.float32)
    params = jax.jit(lambda k: W.make_tree(ref.param_specs(cfg), k,
                                           jnp.float32))(W.seed_key(5))
    return cell, ref, cfg, model, params


def _ids(n, vocab, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, n).astype(np.int32)


def test_decoder_forward_matches_reference(mistral):
    cell, ref, cfg, model, params = mistral
    import paddle_tpu as paddle
    ids = _ids(40, cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward_logits(cfg, params, jnp.asarray(ids)))
        ctl = np.asarray(ref.forward_logits(cfg, params, jnp.asarray(ids),
                                            q="fp8"))
    got = np.asarray(model(paddle.to_tensor(ids[None]))._value,
                     np.float32)[0]
    sd = want.std()
    err = np.abs(got - want).max() / sd
    cerr = np.abs(ctl - want).max() / sd
    # the program computes in bf16: within a few bf16 roundings of the
    # float32 reference; the fp8 control is several times further
    assert err < 0.08, err
    assert cerr > 3 * err, (err, cerr)


def test_paged_prefill_then_decode_matches_reference(mistral):
    cell, ref, cfg, model, params = mistral
    from paddle_tpu.framework.core import Tensor, no_grad
    block, L = 4, 13
    ids = _ids(L + 3, cfg["vocab_size"], seed=2)
    M_ = -(-(L + 4) // block)
    tbl = jnp.arange(1, M_ + 1, dtype=jnp.int32)[None, :]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward_logits(cfg, params, jnp.asarray(ids)))
    sd = want.std()
    with no_grad():
        pools = model.init_paged_cache(M_ + 1, block)
        lg, pools = model.forward_paged(
            Tensor(jnp.asarray(ids[None, :L])),
            Tensor(jnp.arange(L, dtype=jnp.int32)[None, :]), pools, tbl,
            jnp.ones((1, L), bool),
            gather_at=jnp.asarray([L - 1], jnp.int32))
        got = [np.asarray(lg._value, np.float32)[0, -1]]
        for j in range(L, L + 3):      # three decode steps through the cache
            lg, pools = model.forward_paged(
                Tensor(jnp.asarray([[ids[j]]], jnp.int32)),
                Tensor(jnp.asarray([[j]], jnp.int32)), pools, tbl,
                jnp.ones((1, 1), bool))
            got.append(np.asarray(lg._value, np.float32)[0, -1])
    for j, g in zip(range(L - 1, L + 3), got):
        assert np.abs(g - want[j]).max() / sd < 0.08, j


def test_serve_gaps_zero_for_reference_tokens_and_control_reads_wide(mistral):
    cell, ref, cfg, model, params = mistral
    prompt = _ids(12, cfg["vocab_size"], seed=3)
    toks, seq = [], list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(10):            # the reference's own greedy tokens
            lg = ref.forward_logits(cfg, params, jnp.asarray(seq, jnp.int32))
            toks.append(int(jnp.argmax(lg[-1])))
            seq.append(toks[-1])
    sample = [{"prompt": prompt, "tokens": toks}]
    out = K.serve_gaps(ref, cfg, 5, jnp.float32, sample, controls=("fp8",))
    assert out["program"]["gap"] == 0.0
    assert out["program"]["tokens"] == 10
    # an altered token reads a wide gap
    bad = [{"prompt": prompt,
            "tokens": toks[:4] + [(toks[4] + 7) % cfg["vocab_size"]]
            + toks[5:]}]
    assert K.serve_gaps(ref, cfg, 5, jnp.float32, bad)["program"]["gap"] > 0.5


def test_weights_depend_on_seed_and_leaf_only(mistral):
    cell, ref, cfg, model, params = mistral
    specs = ref.param_specs(cfg)
    one = W.make_tree(specs, W.seed_key(5), jnp.float32,
                      names=ref.layer_names(1))
    for k, v in one.items():
        assert (np.asarray(v) == np.asarray(params[k])).all()
    other = W.make_tree(specs, W.seed_key(6), jnp.float32, names=["norm"])
    assert (np.asarray(other["norm"]) != np.asarray(params["norm"])).any()
    big = W.make_tree(specs, W.seed_key(2 ** 31 + 5), jnp.float32,
                      names=["norm"])
    assert np.isfinite(np.asarray(big["norm"])).all()
    # what the program holds is what the reference makes
    got = dict(model.named_parameters())
    assert (np.asarray(got["lm_head.weight"]._value)
            == np.asarray(params["head"])).all()


@pytest.mark.parametrize("workload,faults", [
    ("bert-base-train", ("half_batch", "state_unchanged")),
    ("mistral7b-train-fsdp4", ("half_batch", "no_exchange",
                               "state_unchanged"))])
def test_training_control_and_faults_come_out_not_correct(
        run_toy, workload, faults):
    """Loss and gradients of the program within the toy cell's limits
    of the reference.  The fp8 control and each planted fault, their
    numbers put in the program's place, go through the harness's own
    comparison with the cell's limits and come out as not correct."""
    res = run_toy(workload, seed=5, seconds=0.5, controls=("fp8",),
                  faults=faults)
    assert res["correct"] is True, res["compared"]
    assert set(res["verdicts"]) == {"fp8", *faults}
    for what, v in res["verdicts"].items():
        assert v["correct"] is False and v["failed"], (what, res["compared"])


@pytest.mark.parametrize("workload", ["mistral7b-serve-decode",
                                      "mistral7b-serve-prefill"])
def test_serving_control_comes_out_not_correct(run_toy, workload):
    """The fp8 control's token at every served position, put in the
    program's place and judged by the cell's own limit."""
    res = run_toy(workload, seed=5, seconds=0.5, controls=("fp8",))
    assert res["correct"] is True, res["compared"]
    v = res["verdicts"]["fp8"]
    assert v == {"correct": False, "failed": ["served_logit_gap"]}, \
        res["compared"]
