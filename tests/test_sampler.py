"""The serving programs' sampler (ISSUE 30): ``_sample_tokens`` sorts
only when a row of the step samples, and then once (``_sample_rows``,
its sampling branch, is also the whole sampler of a program whose model
already loops on the device).

- bit identity: the formula the server ran before (two sorts, no
  ``cond``) is kept HERE as the reference; every mix of greedy and
  sampling rows, every top-k / top-p / temperature corner, logits with
  ties at the k-th value and with ``-inf`` entries, both key widths:
  the same tokens, exactly;
- structure: each jitted program holds one ``sort``, inside a ``cond``
  branch (outside one where the model says its program loops on the
  device); the server compiles the programs it compiled before and
  none when a sampling request joins a greedy batch and leaves again;
- ``stats()["sampled_steps"]`` counts the decode, verify and prefill
  dispatches that held a sampling row, and reads 0 under greedy traffic.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import GenerationServer
from paddle_tpu.inference.generation_server import (_sample_rows,
                                                    _sample_tokens)
from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny

B, V = 8, 128


def _two_sort_reference(lg, kd, rng_steps, temp, top_k, top_p, do_sample):
    """``sample()`` of generation_server.py as it stood before ISSUE 30,
    line for line."""
    V = lg.shape[-1]
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    x = lg / jnp.maximum(temp, 1e-6)[:, None]
    srt = jnp.sort(x, axis=-1)[:, ::-1]
    kk = jnp.clip(top_k, 1, V).astype(jnp.int32)
    kth = jnp.take_along_axis(srt, (kk - 1)[:, None], axis=-1)
    use_k = ((top_k > 0) & (top_k < V))[:, None]
    x = jnp.where(use_k & (x < kth), -jnp.inf, x)
    srt2 = jnp.sort(x, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(srt2, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = jnp.maximum((cum < top_p[:, None]).sum(-1) + 1, 1)
    kth2 = jnp.take_along_axis(srt2, (keep - 1)[:, None], axis=-1)
    use_p = (top_p < 1.0)[:, None]
    x = jnp.where(use_p & (x < kth2), -jnp.inf, x)
    impl = {2: "threefry2x32", 4: "rbg"}.get(
        int(kd.shape[-1]), "threefry2x32")
    base = jax.random.wrap_key_data(kd, impl=impl)
    keys = jax.vmap(jax.random.fold_in)(base, rng_steps)
    sampled = jax.vmap(jax.random.categorical)(keys, x)
    return jnp.where(do_sample, sampled.astype(jnp.int32), greedy)


_new = jax.jit(_sample_tokens)
_new_no_cond = jax.jit(_sample_rows)
_old = jax.jit(_two_sort_reference)

MIXES = {
    "all_greedy": np.zeros(B, bool),
    "all_sampling": np.ones(B, bool),
    "mixed": np.arange(B) % 2 == 1,
    "one_sampling_row": np.arange(B) == 5,
}
TEMPS = (1e-9, 0.7, 1.5)


def _logits(kind, r):
    lg = r.randn(B, V).astype(np.float32) * 3.0
    if kind == "ties":
        # few distinct values: the k-th largest is shared by many
        # columns for every k, and the largest is too
        lg = np.round(lg)
    elif kind == "neg_inf":
        lg[r.rand(B, V) < 0.4] = -np.inf
        lg[:, 0] = 1.0          # no row without a finite entry
    return lg


def _draw_args(r, width):
    kd = r.randint(0, 2 ** 31, size=(B, width)).astype(np.uint32)
    steps = r.randint(0, 500, size=(B,)).astype(np.int32)
    return kd, steps


@pytest.mark.parametrize("width", [2, 4])
@pytest.mark.parametrize("top_p", [1.0, 0.9, 1e-6])
@pytest.mark.parametrize("top_k", [0, 1, 50, V - 1, V])
@pytest.mark.parametrize("mix", list(MIXES))
def test_one_sort_sampler_equals_the_two_sort_formula(mix, top_k, top_p,
                                                      width):
    r = np.random.RandomState(
        1000 * list(MIXES).index(mix) + 10 * top_k + width)
    do_sample = MIXES[mix]
    for kind, t in itertools.product(("normal", "ties", "neg_inf"), TEMPS):
        lg = _logits(kind, r)
        kd, steps = _draw_args(r, width)
        args = (lg, kd, steps, np.full(B, t, np.float32),
                np.full(B, top_k, np.int32),
                np.full(B, top_p, np.float32), do_sample)
        got, ref = np.asarray(_new(*args)), np.asarray(_old(*args))
        assert got.dtype == np.int32 and got.shape == (B,)
        np.testing.assert_array_equal(got, ref, err_msg=f"{kind} T={t}")
        # the same work outside a cond
        np.testing.assert_array_equal(np.asarray(_new_no_cond(*args)), ref)
        greedy = np.argmax(lg, -1)
        np.testing.assert_array_equal(got[~do_sample], greedy[~do_sample])


@pytest.mark.parametrize("width", [2, 4])
def test_rows_with_settings_of_their_own(width):
    """Every row its own temperature, top-k and top-p, in one call."""
    r = np.random.RandomState(77 + width)
    sampled_differs = False
    for kind in ("normal", "ties", "neg_inf"):
        for _ in range(4):
            lg = _logits(kind, r)
            kd, steps = _draw_args(r, width)
            args = (lg, kd, steps,
                    r.choice(TEMPS, B).astype(np.float32),
                    r.choice([0, 1, 5, 50, V - 1, V], B).astype(np.int32),
                    r.choice([1.0, 0.9, 0.5, 1e-6], B).astype(np.float32),
                    r.rand(B) < 0.6)
            got, ref = np.asarray(_new(*args)), np.asarray(_old(*args))
            np.testing.assert_array_equal(got, ref)
            np.testing.assert_array_equal(np.asarray(_new_no_cond(*args)),
                                          ref)
            sampled_differs |= bool((got != np.argmax(lg, -1)).any())
    assert sampled_differs      # the draws are draws, not the argmax


# ---------------------------------------------------------------------
# structure of the programs the server compiles
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def lm():
    paddle.seed(0)
    cfg = llama_tiny(vocab_size=64, hidden_size=32, intermediate_size=64,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=2, max_position_embeddings=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _server(lm, **kw):
    d = dict(num_slots=4, block_size=4, max_model_len=48,
             prompt_buckets=[8, 16], max_prefill_batch=2,
             check_replay=True, request_timeout_s=120.0)
    d.update(kw)
    return GenerationServer(lm, **d)


def _sub_jaxprs(v):
    if hasattr(v, "eqns"):
        yield v
    elif hasattr(v, "jaxpr"):
        yield from _sub_jaxprs(v.jaxpr)
    elif isinstance(v, (tuple, list)):
        for e in v:
            yield from _sub_jaxprs(e)


def _sorts(jaxpr, in_cond=False):
    """One entry per ``sort`` of the program: is it in a cond branch?"""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            found.append(in_cond)
        inside = in_cond or eqn.primitive.name == "cond"
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                found += _sorts(sub, inside)
    return found


def _program_args(srv, which):
    """What ``_prewarm`` hands each program."""
    Bs, M, S = srv._num_slots, srv._M, srv._k + 1
    z = lambda shape, dt: np.zeros(shape, dt)
    if which == "prefill":
        pb, b = srv._pbatches[-1], srv._buckets[-1]
        return (z((pb, b), np.int32), z((pb,), np.int32),
                z((pb,), np.int32), z((pb, M), np.int32),
                z((pb, 2), np.uint32), np.ones((pb,), np.float32),
                z((pb,), np.int32), np.ones((pb,), np.float32),
                z((pb,), bool))
    s = (Bs, S) if which == "verify" else (Bs, 1)
    steps = s if which == "verify" else (Bs,)
    # the target's decode program takes the step before's result first
    prev = (srv._prev,) if which == "decode" else ()
    return prev + (
        z(s, np.int32), z(s, np.int32), z((Bs, M), np.int32),
        z(s, bool), z((Bs, 2), np.uint32), z(steps, np.int32),
        np.ones((Bs,), np.float32), z((Bs,), np.int32),
        np.ones((Bs,), np.float32), z((Bs,), bool))


@pytest.mark.parametrize("which", ["decode", "prefill", "verify",
                                   "draft_decode"])
def test_each_program_holds_one_sort_and_where(lm, which):
    srv = _server(lm, draft_model=lm, spec_k=3)
    srv._build_programs()
    fn, vals, pools = {
        "decode": (srv._decode_fn, srv._pvals, srv._pools),
        "prefill": (srv._prefill_fn, srv._pvals, srv._pools),
        "verify": (srv._verify_fn, srv._pvals, srv._pools),
        "draft_decode": (srv._draft_decode_fn, srv._dvals, srv._dpools),
    }[which]
    traced = fn.trace(vals, pools, *_program_args(srv, which))
    assert _sorts(traced.jaxpr.jaxpr) == [True]    # one, in a branch
    assert traced.lower().as_text().count("stablehlo.sort") == 1


def test_no_cond_behind_a_model_that_loops_on_the_device(lm, monkeypatch):
    """A model may say which of its programs hold a device loop whose
    steps branch (``loops_on_device(n_tokens)``): those sort outside
    any ``cond``, the others inside one."""
    asked = []

    def loops_on_device(n_tokens):
        asked.append(n_tokens)
        return n_tokens >= 16
    monkeypatch.setattr(lm, "loops_on_device", loops_on_device,
                        raising=False)
    srv = _server(lm, draft_model=lm, spec_k=3)
    srv._build_programs()
    # (slots, 1), (slots, spec_k + 1) and (rows, bucket) tokens
    for which, fn, vals, pools, tokens in [
            ("decode", srv._decode_fn, srv._pvals, srv._pools, 4),
            ("draft_decode", srv._draft_decode_fn, srv._dvals,
             srv._dpools, 4),
            ("verify", srv._verify_fn, srv._pvals, srv._pools, 4 * 4),
            ("prefill", srv._prefill_fn, srv._pvals, srv._pools, 2 * 48)]:
        traced = fn.trace(vals, pools, *_program_args(srv, which))
        assert asked[-1] == tokens
        assert _sorts(traced.jaxpr.jaxpr) == [tokens < 16]


# what ISSUE 30's parent commit counted for the same arguments (read
# there): 3 buckets x the prefill batches, + decode; with speculation
# the draft's prefills, draft decode and verify too; + the fork with
# prefix sharing.  Since ISSUE 34 a plain server adds the scatter that
# hands a prefill call's first tokens to the decode program, traced
# once for each prefill batch width (a call's result has that width);
# a speculative server reads every call at once and has none.
@pytest.mark.parametrize("kw, parent_count, feeds", [
    ({}, 7, 2),
    ({"max_prefill_batch": 1}, 4, 1),
    ({"draft": True, "spec_k": 3}, 15, 0),
    ({"prefix_cache": True}, 8, 2),
])
def test_start_compiles_what_the_parent_compiled(lm, kw, parent_count,
                                                 feeds):
    kw = dict(kw)
    if kw.pop("draft", False):
        kw["draft_model"] = lm
    with _server(lm, **kw) as srv:
        assert srv.num_compiles() == parent_count + feeds
        st = srv.stats()
    assert st["prewarm_compiles"] == parent_count + feeds
    assert sum(k.startswith("feed:") for k in st["bucket_compiles"]) \
        == feeds
    assert st["traffic_compiles"] == 0 and st["sampled_steps"] == 0


# ---------------------------------------------------------------------
# the counter, and no compile when a sampling row comes and goes
# ---------------------------------------------------------------------
def _prompt(seed, n):
    return np.random.RandomState(seed).randint(1, 64, (n,)).astype("int32")


def test_a_sampling_row_joins_a_greedy_batch_and_leaves(lm):
    long_p, short_p = _prompt(1, 5), _prompt(2, 6)
    sampling = dict(max_new_tokens=4, do_sample=True, temperature=0.7,
                    top_p=0.9, seed=11)
    with _server(lm) as srv:
        n0 = srv.num_compiles()
        # greedy traffic: no dispatch counts
        alone = srv.submit(long_p, max_new_tokens=40).result(timeout=120)
        st = srv.stats()
        assert st["sampled_steps"] == 0 and st["decode_steps"] == 39
        # a sampling request alone: its prefill and its 3 decode steps
        drawn = srv.submit(short_p, **sampling).result(timeout=120)
        st = srv.stats()
        assert st["sampled_steps"] == 4
        assert st["decode_steps"] == 39 + 3 and st["prefill_batches"] == 2
        # both at once: the sampling row leaves 36 steps before the
        # greedy one ends; only the dispatches that held it count
        g = srv.submit(long_p, max_new_tokens=40)
        s = srv.submit(short_p, **sampling)
        assert s.result(timeout=120) == drawn
        assert g.result(timeout=120) == alone
        st = srv.stats()
        assert st["sampled_steps"] == 8
        assert st["decode_steps"] >= 42 + 39
        # and greedy again afterwards
        assert srv.submit(long_p, max_new_tokens=40).result(120) == alone
        st = srv.stats()
        assert st["sampled_steps"] == 8
        assert srv.num_compiles() == n0 and st["traffic_compiles"] == 0


def test_verify_dispatches_count_when_they_hold_a_sampling_row(lm):
    p = _prompt(3, 7)
    with _server(lm, draft_model=lm, spec_k=3,
                 max_prefill_batch=1) as srv:
        greedy = srv.submit(p, max_new_tokens=9).result(timeout=120)
        st0 = srv.stats()
        assert len(greedy) == 9 and st0["sampled_steps"] == 0
        assert st0["spec_verify_steps"] > 0
        out = srv.submit(p, max_new_tokens=9, do_sample=True,
                         temperature=0.9, top_k=8,
                         seed=5).result(timeout=120)
        st = srv.stats()
        assert len(out) == 9
        verifies = st["spec_verify_steps"] - st0["spec_verify_steps"]
        assert verifies > 0
        # one prefill, then every verify dispatch of the request
        assert st["sampled_steps"] == 1 + verifies
        assert st["traffic_compiles"] == 0
