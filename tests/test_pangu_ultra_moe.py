"""openPangu-Ultra-MoE on the serving path, against the benchmark's ONE
plain reference (``perfbench/configs/openpangu-ultra-moe-718b.
reference.py``, loaded by path) at a toy size: the whole forward pass,
prefill through a bucket then decode through the latent pages (alone
and through ``GenerationServer``: padded rows, batched prefill, slot
reuse, eviction and replay), absorbed against expanded latent
attention at rotated positions, the sandwich norms, the sixteen expert
shares that add up to the uncut layer, the MTP module, migration, and
the typed refusals.

Tolerances.  Program and reference run the same float32 arithmetic in
another order (absorbed products, an online softmax's re-association,
experts summed in another order): logits of size ~1 agree to a few
float32 ulps a layer, 5e-6 absolute over five layers.  The same model
in bfloat16 misses by four orders (its own test)."""
import dataclasses
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference import GenerationServer, migration
from paddle_tpu.inference.recurrent_state import MidSequenceStepUnsupported
from paddle_tpu.nn.layer import moe as MOE
from paddle_tpu.nn.layer.moe import dropless_moe
from paddle_tpu.text.models.kimi_linear import _rms
from paddle_tpu.text.models import (KimiLinearForCausalLM,
                                    PanguUltraMoEForCausalLM,
                                    kimi_linear_tiny, pangu_ultra_moe_tiny)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(ROOT, "tests", "perfbench_tests", "toy", "configs",
                   "openpangu-toy.json")
SEED = 2468
ATOL = 5e-6          # module doc


@pytest.fixture(scope="module")
def bench():
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench.harness import manifest as M
    d = os.path.join(ROOT, "perfbench", "configs")
    return {"ref": M.load_module(
                os.path.join(d, "openpangu-ultra-moe-718b.reference.py"),
                "pangu_reference_for_tests"),
            "bind": M.load_module(
                os.path.join(d, "openpangu-ultra-moe-718b.program.py"),
                "pangu_binding_for_tests")}


def toy_cfg():
    with open(TOY) as f:
        return json.load(f)


def build(bench, seed=SEED, dtype="float32", **replace):
    """(model with the seed's weights, computing in ``dtype``; the
    reference's flat float32 tree of the same weights)."""
    from perfbench.harness import weights as W
    from perfbench.harness.program import install_weights
    cfg = toy_cfg()
    ref, bind = bench["ref"], bench["bind"]
    mc = dataclasses.replace(bind.model_config(cfg, 128),
                             compute_dtype=dtype, **replace)
    model = PanguUltraMoEForCausalLM(mc)
    model.eval()
    specs = ref.param_specs(cfg)
    install_weights(model, bind.name_map(cfg, model), specs, seed, jnp.dtype(dtype))
    return model, W.make_tree(specs, W.seed_key(seed), jnp.float32)


@pytest.fixture(scope="module")
def built(bench):
    return build(bench)


def test_the_toy_is_the_tiny_preset(bench):
    """>= 1 dense and >= 3 expert layers, >= 4 heads whose nope, rope
    and v widths all differ, a low-rank query, 16 experts of which a
    share is held; latent layers alone, so no per-slot state."""
    mc = bench["bind"].model_config(toy_cfg(), 128)
    tiny = pangu_ultra_moe_tiny(compute_dtype="bfloat16")
    assert mc == tiny
    assert [mc.is_moe(l) for l in range(5)] == [False] + [True] * 4
    assert len({mc.qk_nope_head_dim, mc.qk_rope_head_dim,
                mc.v_head_dim}) == 3 and mc.num_attention_heads >= 4
    assert mc.q_lora_rank and mc.held_experts == (0, 8) \
        and mc.n_routed_experts == 16
    model = PanguUltraMoEForCausalLM(tiny)
    assert model.supports_kv_cache() and not model.has_recurrent_state()
    assert model.prefill_starts_sequences_only()
    names = {n for n, _ in model.named_parameters()}
    assert {"model.layers.1.self_attn.q_a_proj",
            "model.layers.1.self_attn.q_a_norm",
            "model.layers.1.self_attn.q_b_proj",
            "model.layers.1.post_attention_layernorm",
            "model.layers.1.pre_mlp_layernorm",
            "model.layers.1.post_mlp_layernorm", "lm_head",
            "mtp.0.eh_proj"} <= names
    assert "model.layers.1.self_attn.q_proj" not in names
    # the served cut builds no MTP module
    served = PanguUltraMoEForCausalLM(dataclasses.replace(
        tiny, num_nextn_predict_layers=0))
    assert not any(n.startswith("mtp")
                   for n, _ in served.named_parameters())
    with pytest.raises(ValueError, match="MTP"):
        served.mtp_logits(jnp.zeros((1, 4, 64)), jnp.zeros((1, 4), int),
                          jnp.arange(4)[None])
    pools = model.init_paged_cache(9, 4)
    assert [set(d) for d in pools] == [{"latent"}] * 5
    assert pools[0]["latent"].shape == (9, 4, 1, 128)


# ---------------------------------------------------------------------
# the model through its latent pages, against the reference
# ---------------------------------------------------------------------
def _whole(model, ids):
    """The sequence as one fresh block: logits at every position."""
    T = len(ids)
    pools = model.init_paged_cache(33, 4)
    lg, _, _ = model.forward_paged(
        jnp.asarray(ids)[None], jnp.arange(T, dtype=jnp.int32)[None],
        pools, jnp.arange(1, 33, dtype=jnp.int32)[None],
        jnp.ones((1, T), bool))
    return lg._value[0]


def _ids(n=43, seed=0):
    return np.random.RandomState(seed).randint(1, 256, size=n).astype(
        np.int32)


def test_forward_logits_agree_with_the_reference(bench, built):
    model, params = built
    ids = _ids()
    want = bench["ref"].forward_logits(toy_cfg(), params, jnp.asarray(ids))
    np.testing.assert_allclose(_whole(model, ids), want, atol=ATOL)


def test_bfloat16_arithmetic_fails_the_tolerance(bench, built):
    """The limit is tight enough that the next precision down does not
    pass it: the same weights (rounded to bfloat16, as served) in
    bfloat16 arithmetic miss it by orders."""
    _, params = built
    model, _ = build(bench, dtype="bfloat16")
    ids = _ids()
    want = bench["ref"].forward_logits(toy_cfg(), params, jnp.asarray(ids))
    off = float(jnp.abs(_whole(model, ids).astype(jnp.float32) - want).max())
    assert off > 100 * ATOL


def test_a_sandwich_layer_is_not_the_layer_without_its_post_norms(built):
    """The two post-norms change the result (so the tests above can
    tell whether they ran), and switched off they leave the plain
    pre-norm block."""
    model, _ = built
    lyr = model.model.layers[1]
    h = jnp.asarray(np.random.RandomState(1).randn(1, 9, 64), jnp.float32)
    pos = jnp.arange(9, dtype=jnp.int32)[None]
    with_ = lyr.forward_block(h, pos)
    c = lyr.config
    try:
        lyr.config = dataclasses.replace(c, sandwich_norm=False)
        without = lyr.forward_block(h, pos)
    finally:
        lyr.config = c
    assert float(jnp.abs(with_ - without).max()) > 0.1
    x = lambda t, w: _rms(t, w._value, c.rms_norm_eps)
    a = lyr.self_attn.forward_block(x(h, lyr.input_layernorm), pos)
    h1 = h + a
    y, *_ = lyr.mlp.apply_values(x(h1, lyr.pre_mlp_layernorm))
    np.testing.assert_allclose(without, h1 + y, atol=1e-6)
    h1 = h + x(a, lyr.post_attention_layernorm)
    y, *_ = lyr.mlp.apply_values(x(h1, lyr.pre_mlp_layernorm))
    np.testing.assert_allclose(with_, h1 + x(y, lyr.post_mlp_layernorm),
                               atol=1e-6)


def _prefill_then_decode(model, ids, L, slot=2, N=4, bs=4, Mx=32, Lb=48):
    """Logits at positions L-1 .. len(ids)-1: one batched prefill (the
    sequence in a bucket of ``Lb`` beside an empty row) and then one
    decode step a token, the sequence in ``slot`` among idle slots."""
    pools = model.init_paged_cache(N * Mx + 1, bs)
    prompt = np.zeros((2, Lb), np.int32)
    prompt[0, :L] = ids[:L]
    pos = np.broadcast_to(np.arange(Lb, dtype=np.int32), (2, Lb))
    wm = np.arange(Lb)[None] < np.asarray([L, 0])[:, None]
    tbl = np.zeros((2, Mx), np.int32)
    tbl[0] = np.arange(1, Mx + 1)
    lg, pools, counts = model.forward_paged(
        jnp.asarray(prompt), jnp.asarray(pos), pools,
        jnp.asarray(tbl), jnp.asarray(wm),
        gather_at=jnp.asarray([L - 1, 0]))
    assert counts.shape == (len(model.step_counters()),)
    got = [lg._value[0, 0]]
    tbl = np.zeros((N, Mx), np.int32)
    tbl[slot] = np.arange(1, Mx + 1)
    for t in range(L, len(ids)):
        tok, p = np.zeros((N, 1), np.int32), np.zeros((N, 1), np.int32)
        w = np.zeros((N, 1), bool)
        tok[slot, 0], p[slot, 0], w[slot, 0] = ids[t], t, True
        lg, pools, _ = model.forward_paged(
            jnp.asarray(tok), jnp.asarray(p), pools, jnp.asarray(tbl),
            jnp.asarray(w))
        got.append(lg._value[slot, 0])
    return jnp.stack(got)


@pytest.mark.parametrize("form", ["masked", "grouped"])
def test_prefill_then_decode_agree_with_the_reference(
        bench, built, monkeypatch, form):
    """Prefill through a padded bucket, then decode through the latent
    pages, is the reference's ONE full forward pass; with the experts
    in either form.  A prompt of 37 and decode to position 79: with
    theta 1e4 over 8 dims the first pair turns by a radian a position,
    so a rotation left out, applied twice or taken at the wrong
    position misses by orders."""
    monkeypatch.setattr(MOE, "masked_pass_pays",
                        lambda T, k, E: form == "masked")
    model, params = built
    ids = _ids(80)
    want = bench["ref"].forward_logits(toy_cfg(), params, jnp.asarray(ids))
    got = _prefill_then_decode(model, ids, L=37)
    np.testing.assert_allclose(got, want[36:], atol=ATOL)


def test_absorbed_latent_attention_equals_expanded(built):
    """The same tokens through the latent layer as one fresh block
    (expanded K and V) and one by one (absorbed, over the pages), at
    the same rotated positions."""
    model, _ = built
    attn = model.model.layers[2].self_attn
    assert attn.rope_theta == 10000.0 and attn.q_lora_rank == 24
    x = jnp.asarray(np.random.RandomState(5).randn(1, 27, 64), jnp.float32)
    tbl = jnp.arange(1, 9, dtype=jnp.int32)[None]
    pos = jnp.arange(27, dtype=jnp.int32)[None]
    cache = attn.init_cache(9, 4, jnp.float32)
    want, filled = attn.forward_paged(x, pos, cache, tbl,
                                      jnp.ones((1, 27), bool))
    np.testing.assert_allclose(attn.forward_block(x, pos), want, atol=0)
    got = []
    for t in range(27):
        o, cache = attn.forward_paged(x[:, t:t + 1], pos[:, t:t + 1],
                                      cache, tbl, jnp.ones((1, 1), bool))
        got.append(o[:, 0])
    np.testing.assert_allclose(jnp.stack(got, 1), want, atol=2e-6)
    # both left the same rows in the pages: [c | ROTATED k_pe | 0]
    np.testing.assert_allclose(cache["latent"], filled["latent"],
                               atol=1e-6)
    row = filled["latent"][1 + 26 // 4, 26 % 4, 0]
    _, lat, kpe = attn._project(x, pos)
    np.testing.assert_allclose(row[:32], jnp.concatenate(
        [lat[0, 26], kpe[0, 26]]), atol=0)
    assert not bool(row[32:].any())
    # a token's page row depends on its position through k_pe alone
    _, lat0, kpe0 = attn._project(x, pos * 0)
    np.testing.assert_allclose(lat0, lat, atol=0)
    assert float(jnp.abs(kpe0[0, 26] - kpe[0, 26]).max()) > 0.05


@pytest.mark.parametrize("chunk,limit", [(16, None), (32, None), (48, None),
                                         (None, 4 * 2 * 3 * 32 * 96),
                                         (None, 4 * 2 * 3 * 16 * 96)])
def test_query_chunks_attend_like_one_block(chunk, limit, monkeypatch):
    """``_attend`` in query chunks (stated, or derived from the score
    block's limit: at this model's 128 heads the 512 queries of the Kimi
    cut would hold 1.6 GB of float32 scores) against the whole square
    in one block."""
    from paddle_tpu.text.models import kimi_linear
    r = np.random.RandomState(3)
    q = jnp.asarray(r.randn(2, 96, 3, 24), jnp.float32)
    k = jnp.asarray(r.randn(2, 96, 3, 24), jnp.float32)
    v = jnp.asarray(r.randn(2, 96, 3, 16), jnp.float32)
    whole = kimi_linear._attend(q, k, v, 0.2, chunk=96)
    if limit is not None:
        monkeypatch.setattr(kimi_linear, "_SCORE_BLOCK_BYTES", limit)
        # the derived chunk is the largest halving of 512 under the limit
        jaxpr = str(jax.make_jaxpr(
            lambda *a: kimi_linear._attend(*a, 0.2))(q, k, v))
        assert f"f32[2,3,{limit // (4 * 2 * 3 * 96)},96]" in jaxpr
    np.testing.assert_allclose(
        kimi_linear._attend(q, k, v, 0.2, chunk=chunk), whole, atol=2e-6)


def _qkv(S, dtype=jnp.float32, B=2, H=3, dv=16, seed=3):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(B, S, H, 24), dtype),
            jnp.asarray(r.randn(B, S, H, 24), dtype),
            jnp.asarray(r.randn(B, S, H, dv), dtype))


def _one_block(q, k, v, scale):
    """The whole square in one block, float32 throughout: the plain
    form ``_attend`` is held to."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    S = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _dots(jaxpr, times=1):
    """Every ``dot_general`` of a jaxpr and of the loops in it:
    (shape of its result, how often it runs)."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "dot_general":
            out.append((e.outvars[0].aval.shape, times))
        for name, sub in e.params.items():
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                out += _dots(inner, times * e.params.get("length", 1)
                             if name == "jaxpr" else times)
    return out


# n chunks -> groups: the largest divisor of n up to twelve
GROUPS = {1: 1, 2: 2, 3: 3, 4: 4, 6: 6, 8: 8, 12: 12, 16: 8, 32: 8}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n", [2, 3, 4, 6, 8, 12, 16, 32])
def test_grouped_chunks_attend_like_one_block(n, dtype):
    """Every number of chunks the serving buckets give, at the rule's
    groups, against one block over the whole square: float32 to
    rounding, bfloat16 to one ulp of the output's scale."""
    from paddle_tpu.text.models import kimi_linear
    assert kimi_linear._chunk_groups(n) == GROUPS[n]
    q, k, v = _qkv(16 * n, jnp.dtype(dtype), H=2)
    assert kimi_linear.attend_plan(2, 16 * n, 2, 16)[:2] == (16, GROUPS[n])
    got = kimi_linear._attend(q, k, v, 0.2, chunk=16)
    want = _one_block(q, k, v, 0.2)
    assert got.dtype == q.dtype
    atol = 2e-6 if dtype == "float32" else float(jnp.abs(want).max()) * 2 ** -7
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=atol,
                               rtol=0)


@pytest.mark.parametrize("n", [2, 3, 4, 6, 8, 12, 16, 32])
def test_keys_no_query_of_a_group_may_see_are_never_multiplied(n):
    """Not merely masked: no ``dot_general`` of group j takes more
    than the keys up to the group's last query, and what the program
    multiplies is what the plan counts."""
    from paddle_tpu.text.models import kimi_linear
    B, H, S, G = 2, 3, 16 * n, GROUPS[n]
    q, k, v = _qkv(S, dv=20)     # no count of keys is 20
    dots = _dots(jax.make_jaxpr(
        lambda *a: kimi_linear._attend(*a, 0.2, chunk=16))(q, k, v).jaxpr)
    scores = [(s, t) for s, t in dots if 20 not in s]
    assert all(s[:3] == (B, H, 16) for s, _ in scores)
    ends = [S // G * (j + 1) for j in range(G)]
    # a prefix a group, its chunks one after another, no device loop
    assert sorted(s[3] for s, _ in scores) == sorted(ends * (n // G))
    assert len(dots) == 2 * n and all(t == 1 for _, t in dots)
    chunk, groups, done, square = kimi_linear.attend_plan(B, S, H, 16)
    assert (chunk, groups, square) == (16, G, B * H * S * S)
    assert done == sum(B * H * 16 * s[3] * t for s, t in scores)
    assert done * 2 * G == square * (G + 1)


@pytest.mark.parametrize("S,chunk", [(96, None), (96, 96), (16, 16),
                                     (96, 40), (50, 16), (7, None)])
def test_one_chunk_or_no_whole_chunks_is_one_block(S, chunk):
    """``S <= chunk`` is one group of one chunk through the same body,
    and a length that does not divide into chunks falls back to it:
    one pair of ``dot_general`` over all keys, no loop."""
    from paddle_tpu.text.models import kimi_linear
    q, k, v = _qkv(S, dv=20)
    f = lambda *a: kimi_linear._attend(*a, 0.2, chunk=chunk)
    jaxpr = jax.make_jaxpr(f)(q, k, v)
    scores, summed = _dots(jaxpr.jaxpr)
    assert scores == ((2, 3, S, S), 1) and summed[1] == 1
    assert sorted(summed[0]) == sorted((2, 3, S, 20))
    assert "scan" not in str(jaxpr) and "while" not in str(jaxpr)
    assert kimi_linear.attend_plan(2, S, 3, chunk) == (
        S, 1, 2 * 3 * S * S, 2 * 3 * S * S)
    np.testing.assert_allclose(f(q, k, v), _one_block(q, k, v, 0.2),
                               atol=2e-6, rtol=0)


@pytest.mark.parametrize("B,S,H,chunk", [
    (1, 64, 2, 16), (2, 96, 3, 16), (1, 96, 1, 32), (2, 384, 1, 32),
    (1, 512, 2, 16), (3, 50, 2, 16), (1, 2048, 128, None),
    (2, 3072, 128, None), (4, 2048, 32, None), (4, 512, 32, None)])
def test_the_plan_counts_what_a_brute_force_count_finds(B, S, H, chunk):
    """The plan against a count over the square: a pair is multiplied
    when its key lies under the end of its query's group; every pair
    the mask lets through is among them."""
    from paddle_tpu.text.models import kimi_linear
    c, G, done, square = kimi_linear.attend_plan(B, S, H, chunk)
    assert S % c == 0 and (S // c) % G == 0
    per = S // G
    qs, ks = np.arange(S)[:, None], np.arange(S)[None, :]
    multiplied = ks < (qs // per + 1) * per
    assert multiplied[ks <= qs].all()
    assert done == B * H * int(multiplied.sum())
    assert square == B * H * S * S
    if chunk is None:        # the serving shapes: the chunk is derived
        assert 4 * B * H * c * S <= kimi_linear._SCORE_BLOCK_BYTES \
            or c == 16


def test_a_latent_server_counts_the_pairs_its_prefill_skips(
        built, monkeypatch):
    """``stats()`` adds up the plan of every prefill call, and the
    call's share rides its ``serve.prefill.stage`` span: 1.0 where a
    bucket is one block, under 1 where it is several chunks (the score
    block's limit is lowered so that a toy bucket holds four)."""
    from paddle_tpu.observability import timeline
    from paddle_tpu.text.models import kimi_linear
    model, _ = built
    short, long = _prompts(2, seed=11, lo=9, hi=14), \
        _prompts(2, seed=12, lo=40, hi=60)
    want, _ = _serve(model, short + long, max_new=5,
                     prompt_buckets=[16, 64], max_model_len=96)
    monkeypatch.setattr(kimi_linear, "_SCORE_BLOCK_BYTES", 1)
    assert model.prefill_attn_pairs(2, 16) == (5 * 2 * 4 * 16 * 16,) * 2
    assert model.prefill_attn_pairs(1, 64) == (
        5 * 4 * 64 * 64 * 5 // 8, 5 * 4 * 64 * 64)
    with GenerationServer(model, num_slots=4, block_size=4,
                          max_model_len=96, prompt_buckets=[16, 64],
                          max_prefill_batch=1, check_replay=True) as srv:
        t0 = time.perf_counter()     # prewarm traffic is not the session
        got = [srv.submit(p, max_new_tokens=5).result(timeout=300)
               for p in short]
        st = srv.stats()
        assert st["prefill_attn_pairs_multiplied"] \
            == st["prefill_attn_pairs_square"] == 2 * 5 * 4 * 16 * 16
        got += [srv.submit(p, max_new_tokens=5).result(timeout=300)
                for p in long]
    end = srv.stats()
    assert got == want               # the same tokens, chunked or whole
    done = end["prefill_attn_pairs_multiplied"] \
        - st["prefill_attn_pairs_multiplied"]
    square = end["prefill_attn_pairs_square"] \
        - st["prefill_attn_pairs_square"]
    assert square == 2 * 5 * 4 * 64 * 64 and done * 8 == square * 5
    shares = [r.args["attn_pairs_share"]
              for r in timeline.spans("serve", since=t0)
              if r.name == "serve.prefill.stage"]
    assert shares == [1.0, 1.0, 0.625, 0.625]


def test_a_latent_block_has_to_start_its_sequence(built):
    model, _ = built
    attn = model.model.layers[1].self_attn
    x = jnp.asarray(np.random.RandomState(6).randn(2, 5, 64), jnp.float32)
    tbl = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)
    pos = jnp.asarray([[0, 1, 2, 3, 4], [4, 5, 6, 7, 8]], jnp.int32)
    args = (attn.init_cache(9, 4, jnp.float32), tbl, jnp.ones((2, 5), bool))
    with pytest.raises(ValueError, match="from position 0"):
        attn.forward_paged(x, pos, *args)
    o, _ = jax.jit(attn.forward_paged)(x, pos, *args)
    assert bool(jnp.isfinite(o[0]).all()) and bool(jnp.isnan(o[1]).all())


# ---------------------------------------------------------------------
# the expert layer and the MTP module
# ---------------------------------------------------------------------
def test_sixteen_shares_add_up_to_the_uncut_layer(bench, monkeypatch):
    """The sixteen shares of 2 of the 32 experts, each from the router
    at its full width, add up to the uncut layer with the shared
    expert counted ONCE: for the reference's shares and for the
    program's, whose shares take both forms."""
    ref = bench["ref"]
    r = np.random.RandomState(2)
    d, f, E, per = 32, 16, 32, 2
    n = lambda *s: jnp.asarray(r.randn(*s) * 0.3, jnp.float32)
    lp = {"router": n(d, E), "rbias": jnp.zeros((E,)), "eg": n(E, d, f),
          "eu": n(E, d, f), "ed": n(E, f, d), "sg": n(d, f),
          "su": n(d, f), "sd": n(f, d)}
    whole = {"n_routed_experts": E, "num_experts_per_tok": 4,
             "routed_scaling_factor": 2.5}
    x = n(19, d)
    want = ref.moe(whole, lp, x)
    assert float(jnp.abs(want - ref.moe(whole, lp, x, shared=False)
                         ).max()) > 0.01
    got_ref = got_prog = ref.swiglu(x, lp["sg"], lp["su"], lp["sd"])
    for first in range(0, E, per):
        part = dict(whole, n_routed_experts=per,
                    published={"n_routed_experts": E},
                    assumed={"held_experts": [first, per]})
        lp_i = dict(lp, **{k: lp[k][first:first + per]
                           for k in ("eg", "eu", "ed")})
        got_ref = got_ref + ref.moe(part, lp_i, x, shared=False)
        # half the shares by the masked pass, half by the grouped form
        monkeypatch.setattr(MOE, "masked_pass_pays",
                            lambda T, k, E_, masked=first < 16: masked)
        y, _, _ = dropless_moe(
            x, lp["router"], lp["rbias"], lp_i["eg"], lp_i["eu"],
            lp_i["ed"], top_k=4, scale=2.5, held=(first, per))
        got_prog = got_prog + y
    np.testing.assert_allclose(got_ref, want, atol=2e-6)
    np.testing.assert_allclose(got_prog, want, atol=2e-6)


def test_mtp_logits_agree_with_the_reference(bench, built):
    """The MTP module over a fresh block: from the main model's
    residual stream at i and token i + 1, logits for token i + 2."""
    model, params = built
    ref, cfg = bench["ref"], toy_cfg()
    ids = _ids(30, seed=3)
    pos = jnp.arange(29, dtype=jnp.int32)[None]
    hid = model.hidden_block(jnp.asarray(ids[:-1])[None], pos)
    want_h = ref.hidden_states(cfg, params, jnp.asarray(ids[:-1]))
    np.testing.assert_allclose(hid[0], want_h, atol=2e-5)
    got = model.mtp_logits(hid, jnp.asarray(ids[1:])[None], pos)
    want = ref.mtp_logits(cfg, params, want_h, jnp.asarray(ids[1:]))
    np.testing.assert_allclose(got._value[0], want, atol=ATOL)
    # and it is not the main head over the same stream
    assert float(jnp.abs(want - ref.logits(cfg, params, want_h)).max()) > .01


# ---------------------------------------------------------------------
# through GenerationServer
# ---------------------------------------------------------------------
def _serve(model, prompts, max_new=10, **kw):
    opts = dict(num_slots=4, block_size=4, max_model_len=64,
                prompt_buckets=[16, 32], max_prefill_batch=2,
                check_replay=True)
    opts.update(kw)
    with GenerationServer(model, **opts) as srv:
        streams = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
        outs = [s.result(timeout=300) for s in streams]
    return outs, srv.stats()      # after stop(): the last step is read


def _prompts(n, seed=0, lo=5, hi=30):
    r = np.random.RandomState(seed)
    return [r.randint(1, 256, size=r.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


def test_served_tokens_are_the_references_best(bench, built):
    """Through submit(): more requests than slots, batched prefill in
    two buckets with padded rows, slots reused.  Every served token is
    the reference's best at its position, up to float32 rounding of
    the logits (the reference's one full forward pass over prompt +
    served tokens; logits, not tokens, decide)."""
    model, params = built
    prompts = _prompts(7)
    outs, st = _serve(model, prompts, max_new=24)
    assert (st["state_slots"], st["state_bytes"], st["kv_pool_bytes"],
            st["state_resets"]) == (0, 0, 0, 0)
    # five layers of latent pages, 128 float32 lanes a row
    assert st["latent_pool_bytes"] == 5 * (st["total_blocks"] + 1) \
        * 4 * 128 * 4
    assert st["traffic_compiles"] == 0
    assert st["prefills_overlapped"] > 0 and st["decode_steps_overlapped"] > 0
    for p, out in zip(prompts, outs):
        ids = np.concatenate([p, np.asarray(out[:-1], np.int32)])
        lg = bench["ref"].forward_logits(toy_cfg(), params,
                                         jnp.asarray(ids))
        at = lg[len(p) - 1:]
        gap = at.max(-1) - jnp.take_along_axis(
            at, jnp.asarray(out)[:, None], -1)[:, 0]
        assert float(gap.max()) < 2 * ATOL


def test_the_three_step_counters_are_summed_under_their_names(built):
    """``stats()`` adds up what the decode program counts: 4 expert
    layers, 2 picks a row of which those on experts 0-7 land here, and
    the masked pass's 8 row-products a row of 4 slots."""
    model, _ = built
    assert model.step_counters() == (
        "moe_picks_here", "moe_max_expert_load", "moe_rows_multiplied")
    # 8 of 256 at the published router: the 128-row decode step takes
    # the masked pass, every prefill program the grouped blocks
    wide = PanguUltraMoEForCausalLM(pangu_ultra_moe_tiny(
        n_routed_experts=256, num_experts_per_tok=8, held_experts=(0, 16),
        moe_intermediate_size=8))
    assert [wide.loops_on_device(n) for n in (128, 1024, 6144)] == [
        False, True, True]
    _, st = _serve(model, _prompts(3, seed=5), max_new=6)
    steps = st["decode_steps"]
    assert steps >= 5
    assert st["moe_rows_multiplied"] == steps * 4 * 4 * 8
    assert 0 < st["moe_picks_here"] < steps * 4 * 4 * 2
    assert 0 < st["moe_max_expert_load"] <= st["moe_picks_here"]


def test_a_reused_slot_is_served_as_if_alone(built):
    """One slot: a short request takes the slot and the pages a longer
    owner left, and is served as if alone."""
    model, _ = built
    long_, short = _prompts(1, seed=7, lo=25, hi=30)[0], \
        _prompts(1, seed=8, lo=5, hi=9)[0]
    (_, second), _ = _serve(model, [long_, short], num_slots=1)
    (alone,), _ = _serve(model, [short], num_slots=1)
    assert second == alone


def test_evict_and_replay_gives_the_same_tokens(built):
    model, _ = built
    prompts = _prompts(4, seed=9, lo=20, hi=30)
    calm, _ = _serve(model, prompts, max_new=20)
    # 4 sequences of up to 50 positions need ~50 blocks of 4: 24 force
    # evictions, re-prefill from position 0 and replay (check_replay
    # asserts every replayed token)
    tight, st = _serve(model, prompts, max_new=20, num_blocks=25)
    assert st["evicted"] > 0 and st["replay_steps"] > 0
    assert tight == calm


def _pure_latent_kimi():
    model = KimiLinearForCausalLM(kimi_linear_tiny(
        full_attn_layers=(1, 2, 3, 4)))
    model.eval()
    return model


@pytest.mark.parametrize("make", [
    lambda built: built[0], lambda built: _pure_latent_kimi()],
    ids=["pangu", "kimi-latent-only"])
def test_what_runs_a_step_from_mid_sequence_is_refused(built, make):
    """A latent-only model keeps no per-slot state, so nothing was
    refused on that ground, and a prefix-sharing server would have
    served NaN (the suffix prefill cannot raise under a trace): typed
    refusals at construction."""
    model = make(built)
    assert not model.has_recurrent_state()
    assert model.prefill_starts_sequences_only()
    with pytest.raises(MidSequenceStepUnsupported, match="prefix_cache"):
        GenerationServer(model, prefix_cache=True)
    with pytest.raises(MidSequenceStepUnsupported, match="speculative"):
        GenerationServer(model, draft_model=model)
    pools = model.init_paged_cache(9, 4)
    with pytest.raises(NotImplementedError):
        model.forward_paged(jnp.zeros((2, 3), jnp.int32),
                            jnp.zeros((2, 3), jnp.int32), pools,
                            jnp.zeros((2, 8), jnp.int32),
                            jnp.ones((2, 3), bool), verify_mode=True)


def test_a_latent_model_as_a_draft_is_refused_too(built):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny
    paddle.seed(0)
    target = LlamaForCausalLM(llama_tiny())
    target.eval()
    with pytest.raises(MidSequenceStepUnsupported, match="speculative"):
        GenerationServer(target, draft_model=built[0])


def test_migration_ships_the_latent_pages(built):
    """No per-slot state: a live sequence's latent pages are exported
    and imported like K/V blocks, and the stream goes on with the
    tokens it would have had."""
    model, _ = built
    prompt = _prompts(1, seed=11, lo=20, hi=25)[0]
    opts = dict(num_slots=2, block_size=4, max_model_len=64,
                prompt_buckets=[32])
    (want,), _ = _serve(model, [prompt], max_new=16, **opts)
    with GenerationServer(model, **opts) as a, \
            GenerationServer(model, **opts) as b:
        s = a.submit(prompt, max_new_tokens=16)
        head = [next(s) for _ in range(5)]
        blob = migration.export_sequence(a, 1)
        assert blob["kv"] is not None and set(blob["kv"][0]) == {"latent"}
        head += list(s)           # what a had generated by then
        tail = migration.import_sequence(b, blob).result(timeout=120)
    assert 5 <= len(head) < 16 and head + list(tail) == want
