"""MiniMax-M3 on the serving path, against the benchmark's ONE plain
reference (``perfbench/configs/minimax-m3.reference.py``, loaded by
path) at a toy size with pages of 8 tokens and the top 2 pages (a query
reads at most 5): the whole forward pass, prefill through a bucket then
decode through the head-major pages with the block-sparse selection
(through the XLA reference of the kernel and through the kernel under
the interpreter, alone and through ``GenerationServer``), the
selection's invariants, the pooled keys after every write, the expert
shares that add up to the uncut layer, and the clamped SwiGLU beside
the default form the other expert models keep.

Tolerances.  Program and reference run the same float32 arithmetic in
another order (a head's norm and rotary fused otherwise, the softmax
normalised behind the weighted sum, experts summed in another order):
logits of size ~5 agree to a few float32 ulps a layer, 1e-5 absolute
over five layers.  The same model in bfloat16 misses by orders (its
own test), and a selection that drops or adds one page a query misses
by more than 1e-2 (its own test)."""
import dataclasses
import re
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference import GenerationServer
from paddle_tpu.inference.recurrent_state import MidSequenceStepUnsupported
from paddle_tpu.nn.layer import moe as MOE
from paddle_tpu.nn.layer.moe import dropless_moe
from paddle_tpu.ops.pallas import registry as kreg
from paddle_tpu.text.models import MiniMaxM3ForCausalLM, minimax_m3_tiny
from paddle_tpu.text.models import minimax_m3 as MM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(ROOT, "tests", "perfbench_tests", "toy", "configs",
                   "minimax-toy.json")
SEED = 1357
ATOL = 1e-5          # module doc
BS = 8               # the toy's page


@pytest.fixture(scope="module")
def bench():
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench.harness import manifest as M
    d = os.path.join(ROOT, "perfbench", "configs")
    return {"ref": M.load_module(os.path.join(d, "minimax-m3.reference.py"),
                                 "minimax_reference_for_tests"),
            "bind": M.load_module(os.path.join(d, "minimax-m3.program.py"),
                                  "minimax_binding_for_tests")}


@pytest.fixture(autouse=True)
def _clean_registry():
    yield
    kreg.set_mode("paged_sparse_attention", None)


def toy_cfg(**assumed):
    with open(TOY) as f:
        cfg = json.load(f)
    cfg["assumed"].update(assumed)
    return cfg


def build(bench, seed=SEED, dtype="float32", cfg=None):
    """(model with the seed's weights, computing in ``dtype``; the
    reference's flat float32 tree of the same weights)."""
    from perfbench.harness import weights as W
    from perfbench.harness.program import install_weights
    cfg = cfg or toy_cfg()
    ref, bind = bench["ref"], bench["bind"]
    mc = dataclasses.replace(bind.model_config(cfg, 256),
                             compute_dtype=dtype)
    model = MiniMaxM3ForCausalLM(mc)
    model.eval()
    specs = ref.param_specs(cfg)
    install_weights(model, bind.name_map(cfg, model), specs, seed,
                    jnp.dtype(dtype))
    return model, W.make_tree(specs, W.seed_key(seed), jnp.float32)


@pytest.fixture(scope="module")
def built(bench):
    return build(bench)


def _ids(n=120, seed=0):
    return np.random.RandomState(seed).randint(1, 256, size=n).astype(
        np.int32)


def test_the_toy_is_the_tiny_preset(bench):
    """One dense and four expert layers, 8 query heads on 2 kv heads
    with a partial rotary, pages of 8, 16 experts of which 8 are held;
    attention alone, so no per-slot state; head-major pages beside the
    pooled keys."""
    mc = bench["bind"].model_config(toy_cfg(), 256)
    tiny = minimax_m3_tiny(compute_dtype="bfloat16")
    assert mc == tiny
    assert [mc.is_moe(l) for l in range(5)] == [False] + [True] * 4
    assert mc.msa_pages == 5 and mc.rotary_dim < mc.head_dim
    model = MiniMaxM3ForCausalLM(tiny)
    assert model.supports_kv_cache() and not model.has_recurrent_state()
    assert model.prefill_starts_sequences_only()
    pools = model.init_paged_cache(9, BS)
    assert [set(d) for d in pools] == [{"k", "v", "kmax"}] * 5
    assert pools[0]["k"].shape == (9, 2, BS, 32)
    assert pools[0]["kmax"].shape == (9, 2, 32)
    # the pages in the compute type, the pooled keys in float32
    assert pools[0]["k"].dtype == jnp.bfloat16
    assert pools[0]["kmax"].dtype == jnp.float32
    with pytest.raises(ValueError, match="MSA page"):
        model.init_paged_cache(9, 4)


# ---------------------------------------------------------------------
# the model through its pages, against the reference
# ---------------------------------------------------------------------
def _step(model):
    """``model.forward_paged`` as one jitted program: (logits, pools,
    counters).  Traced anew for each call of this helper, so that what
    a test patches (the expert form, the kernel's mode) is what runs."""
    def f(ids, pos, pools, tbl, wm, gather_at=None):
        lg, pools, counts = model.forward_paged(ids, pos, pools, tbl, wm,
                                                gather_at=gather_at)
        return lg._value, pools, counts
    return jax.jit(f)


def _whole(model, ids, bucket=128, jit=True):
    """The sequence as one fresh block: logits at every position (op by
    op with ``jit=False``: the CPU backend has no compiled bfloat16
    product into float32)."""
    T = len(ids)
    pools = model.init_paged_cache(40, BS)
    run = _step(model) if jit else (
        lambda *a: (lambda o: (o[0]._value,) + o[1:])(
            model.forward_paged(*a)))
    lg, _, _ = run(
        jnp.asarray(np.pad(ids, (0, bucket - T)))[None],
        jnp.arange(bucket, dtype=jnp.int32)[None], pools,
        jnp.arange(1, 33, dtype=jnp.int32)[None],
        (jnp.arange(bucket) < T)[None])
    return lg[0, :T]


def test_forward_logits_agree_with_the_reference(bench, built):
    model, params = built
    ids = _ids()
    want = bench["ref"].forward_logits(toy_cfg(), params, jnp.asarray(ids))
    np.testing.assert_allclose(_whole(model, ids), want, atol=ATOL)


def test_the_indexer_multiplies_its_float32_input_at_16_bits():
    """``_dot_f32``: a float32 input by bfloat16 weights (what the
    indexer's queries and the pooled keys are made of) lands within
    2**-15 of the exact product's scale, where one bfloat16 pass over
    the rounded input misses by about 2**-9, the rounding that flipped
    the selection."""
    rng = np.random.default_rng(0)
    xf = jnp.asarray(rng.standard_normal((2, 3, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 64)) / 16, jnp.bfloat16)
    exact = np.asarray(xf, np.float64) @ np.asarray(w.astype(jnp.float32),
                                                    np.float64)
    scale = np.abs(exact).max()
    got = np.asarray(MM._dot_f32(xf, w))
    one = np.asarray(jnp.dot(xf.astype(jnp.bfloat16), w,
                             preferred_element_type=jnp.float32))
    assert got.dtype == np.float32
    assert np.abs(got - exact).max() < 2.0 ** -15 * scale
    assert np.abs(one - exact).max() > 2.0 ** -11 * scale
    # float32 weights: the product itself
    w32 = w.astype(jnp.float32)
    np.testing.assert_allclose(MM._dot_f32(xf, w32), exact, rtol=0,
                               atol=1e-5 * scale)


def test_bfloat16_arithmetic_fails_the_tolerance(bench, built):
    """The limit is tight enough that the next precision down does not
    pass it: the same weights in bfloat16 arithmetic miss by orders."""
    _, params = built
    model, _ = build(bench, dtype="bfloat16")
    ids = _ids()
    want = bench["ref"].forward_logits(toy_cfg(), params, jnp.asarray(ids))
    off = float(jnp.abs(_whole(model, ids, jit=False).astype(jnp.float32)
                        - want).max())
    assert off > 100 * ATOL


def _prefill_then_decode(model, ids, L, slot=1, N=3, Mx=32, Lb=48):
    """Logits at positions L-1 .. len(ids)-1 and the last step's
    counters: one batched prefill (the sequence in a bucket of ``Lb``
    beside an empty row), then one decode step a token, the sequence in
    ``slot`` among idle slots, on pages scattered over the pool."""
    pools = model.init_paged_cache(N * Mx + 1, BS)
    phys = np.random.RandomState(5).permutation(np.arange(1, N * Mx + 1))
    prompt = np.zeros((2, Lb), np.int32)
    prompt[0, :L] = ids[:L]
    pos = np.broadcast_to(np.arange(Lb, dtype=np.int32), (2, Lb))
    wm = np.arange(Lb)[None] < np.asarray([L, 0])[:, None]
    tbl = np.zeros((2, Mx), np.int32)
    tbl[0] = phys[:Mx]
    step = _step(model)
    lg, pools, counts = step(
        jnp.asarray(prompt), jnp.asarray(pos), pools,
        jnp.asarray(tbl), jnp.asarray(wm),
        gather_at=jnp.asarray([L - 1, 0]))
    assert counts.shape == (len(model.step_counters()),)
    got = [lg[0, 0]]
    tbl = np.zeros((N, Mx), np.int32)
    tbl[slot] = phys[:Mx]
    for t in range(L, len(ids)):
        tok, p = np.zeros((N, 1), np.int32), np.zeros((N, 1), np.int32)
        w = np.zeros((N, 1), bool)
        tok[slot, 0], p[slot, 0], w[slot, 0] = ids[t], t, True
        lg, pools, counts = step(
            jnp.asarray(tok), jnp.asarray(p), pools, jnp.asarray(tbl),
            jnp.asarray(w))
        got.append(lg[slot, 0])
    return jnp.stack(got), np.asarray(counts), pools, tbl[slot]


@pytest.mark.parametrize("form,kernel,chunk", [
    ("masked", "xla_ref", 4096), ("grouped", "xla_ref", 4096),
    ("grouped", "xla_ref", 16), ("masked", "interpret", 4096)])
def test_prefill_then_decode_agree_with_the_reference(
        bench, built, monkeypatch, form, kernel, chunk):
    """Prefill of 37 tokens through a padded bucket, then decode to
    position 119 (15 pages: each query of the last ten pages reads 5 of
    them) is the reference's ONE full forward pass; with the experts in
    either form, the prefill's FFN whole or 16 tokens at a time, and
    the decode's attention through the XLA reference of the kernel or
    through the kernel itself."""
    monkeypatch.setattr(MOE, "masked_pass_pays",
                        lambda T, k, E: form == "masked")
    kreg.set_mode("paged_sparse_attention", kernel)
    model, params = built
    monkeypatch.setattr(MM, "FFN_CHUNK", chunk)
    ids = _ids()
    want = bench["ref"].forward_logits(toy_cfg(), params, jnp.asarray(ids))
    got, counts, _, _ = _prefill_then_decode(model, ids, L=37)
    np.testing.assert_allclose(got, want[36:], atol=ATOL)
    # the last step: 5 of 15 pages a kv head, 2 kv heads, 5 layers
    assert counts[3:].tolist() == [5 * 2 * 5, 15 * 2 * 5]
    assert kreg.dispatch_counts("paged_sparse_attention").get(kernel)


@pytest.mark.parametrize("score_bytes,chunk,groups", [
    (2 ** 17, 32, 4), (2 ** 16, 16, 8)])
def test_query_chunks_in_groups_attend_like_one_block(
        bench, built, monkeypatch, score_bytes, chunk, groups):
    """A small score block puts a prefill call's 128 queries in chunks
    and groups of chunks, each group projected at once and over the
    keys up to its last query: the reference's logits, as with one
    block."""
    model, params = built
    monkeypatch.setattr(MM, "_SCORE_BLOCK_BYTES", score_bytes)
    plan = MM.msa_plan(1, 128, 8)
    assert plan[:2] == (chunk, groups) and plan[2] < plan[3]
    ids = _ids()
    want = bench["ref"].forward_logits(toy_cfg(), params, jnp.asarray(ids))
    np.testing.assert_allclose(_whole(model, ids), want, atol=ATOL)


def test_the_selection_matters(bench, built):
    """At 15 pages a query reads 5: the same weights with every page
    read (full attention) give other logits, so the tests above can
    tell whether the selection ran; a page more or less a query moves
    them by more than the tolerance."""
    _, params = built
    ids = jnp.asarray(_ids())
    ref = bench["ref"]
    sparse = ref.forward_logits(toy_cfg(), params, ids)
    for k in (1, 3, 1000):
        other = ref.forward_logits(toy_cfg(msa_top_k=k), params, ids)
        assert float(jnp.abs(other - sparse)[24:].max()) > 1e-2, k


def test_with_19_or_fewer_pages_the_layer_is_dense_gqa(bench, built):
    """With no more pages than a query may read (here 5: page 0, the
    top 2, the two local ones) every page is selected: the program
    equals the reference with the selection switched off (top_k past
    the page count), and so does a sequence of exactly 5 pages."""
    model, params = built
    ids = _ids(40, seed=3)
    dense = bench["ref"].forward_logits(toy_cfg(msa_top_k=1000), params,
                                        jnp.asarray(ids))
    np.testing.assert_allclose(_whole(model, ids), dense, atol=ATOL)
    got, counts, _, _ = _prefill_then_decode(model, ids, L=20)
    np.testing.assert_allclose(got, dense[19:], atol=ATOL)
    assert counts[3] == counts[4] == 5 * 2 * 5


@pytest.mark.parametrize("local,top_k", [(2, 2), (2, 16), (1, 3), (3, 0)])
def test_page_0_and_the_local_pages_are_always_selected(local, top_k):
    """For every query page: page 0 and the ``local`` pages up to the
    query's own are in, no page past the query's is, exactly
    ``min(top_k, candidates)`` others are, and those are the best
    scored (ties to the lower page)."""
    rng = np.random.RandomState(7)
    P = 40
    scores = jnp.asarray(rng.randn(P, P).astype(np.float32))
    scores = scores.at[:, 5].set(scores[:, 6])          # a tie
    bt = jnp.arange(P)
    sel = np.asarray(MM.select_pages(scores, bt, top_k, local))
    s = np.asarray(scores)
    for b in range(P):
        row = sel[b]
        assert row[0] and row[max(b - local + 1, 0):b + 1].all()
        assert not row[b + 1:].any()
        cand = np.arange(1, max(b - local + 1, 1))
        want = cand[np.argsort(-s[b, cand], kind="stable")][:top_k]
        got = [p for p in cand if row[p]]
        assert sorted(got) == sorted(want.tolist()), b
    # the list the kernel reads: ascending, padded with 0, counted
    logical, count = MM.selected_list(jnp.asarray(sel), 1 + local + top_k)
    for b in range(P):
        n = int(count[b])
        assert n == sel[b].sum() <= 1 + local + top_k
        assert np.asarray(logical[b, :n]).tolist() == np.flatnonzero(
            sel[b]).tolist()
        assert not np.asarray(logical[b, n:]).any()


def test_the_pooled_key_of_a_partial_page_is_the_max_after_every_write(
        built):
    """After the prefill and after every decode write, each written
    page's pooled key is the elementwise max of the keys its page holds
    so far, in every layer and kv head; the trash block and pages not
    yet written are not read."""
    model, _ = built
    ids = _ids(60, seed=4)
    pools = model.init_paged_cache(40, BS)
    tbl = np.zeros((1, 16), np.int32)
    tbl[0] = np.arange(17, 33)[::-1]

    def check(pools, n):
        for d in pools:
            for page in range(-(-n // BS)):
                phys = tbl[0, page]
                held = min(BS, n - page * BS)
                np.testing.assert_array_equal(
                    d["kmax"][phys], d["k"][phys, :, :held].max(1))
    L = 13
    prompt = np.zeros((1, 16), np.int32)
    prompt[0, :L] = ids[:L]
    step = _step(model)
    _, pools, _ = step(
        jnp.asarray(prompt), jnp.arange(16, dtype=jnp.int32)[None], pools,
        jnp.asarray(tbl), jnp.asarray(np.arange(16)[None] < L))
    check(pools, L)
    for t in range(L, 30):
        _, pools, _ = step(
            jnp.asarray([[ids[t]]]), jnp.asarray([[t]], jnp.int32), pools,
            jnp.asarray(tbl), jnp.ones((1, 1), bool))
        check(pools, t + 1)


# ---------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------
def test_sixteen_shares_add_up_to_the_uncut_layer(bench, monkeypatch):
    """The sixteen shares of 2 of the 32 clamped-SwiGLU experts, each
    from the router at its full width (score + bias), add up to the
    uncut layer with the shared expert counted ONCE: for the
    reference's shares and for the program's, whose shares take both
    forms."""
    ref = bench["ref"]
    r = np.random.RandomState(2)
    d, f, E, per = 32, 16, 32, 2
    n = lambda *s: jnp.asarray(r.randn(*s) * 0.5, jnp.float32)
    lp = {"router": n(d, E), "rbias": n(E) * 0.2, "eg": n(E, d, f),
          "eu": n(E, d, f), "ed": n(E, f, d), "sg": n(d, f),
          "su": n(d, f), "sd": n(f, d)}
    whole = {"num_local_experts": E, "num_experts_per_tok": 4,
             "routed_scaling_factor": 2.0, "swiglu_alpha": 1.702,
             "swiglu_limit": 7}
    x = n(19, d) * 4          # the clamps engage
    want = ref.moe(whole, lp, x)
    assert float(jnp.abs(want - ref.moe(whole, lp, x, shared=False)
                         ).max()) > 0.01
    got_ref = got_prog = ref.glu(x, lp["sg"], lp["su"], lp["sd"], whole)
    for first in range(0, E, per):
        part = dict(whole, num_local_experts=per,
                    published={"num_local_experts": E},
                    assumed={"held_experts": [first, per]})
        lp_i = dict(lp, **{k: lp[k][first:first + per]
                           for k in ("eg", "eu", "ed")})
        got_ref = got_ref + ref.moe(part, lp_i, x, shared=False)
        monkeypatch.setattr(MOE, "masked_pass_pays",
                            lambda T, k, E_, masked=first < 16: masked)
        y, _, _ = dropless_moe(
            x, lp["router"], lp["rbias"], lp_i["eg"], lp_i["eu"],
            lp_i["ed"], top_k=4, scale=2.0, held=(first, per),
            act="swigluoai")
        got_prog = got_prog + y
    np.testing.assert_allclose(got_ref, want, atol=1e-5)
    np.testing.assert_allclose(got_prog, want, atol=1e-5)


def test_swigluoai_is_the_clamped_form():
    g = jnp.asarray([-20.0, -1.0, 0.0, 0.5, 6.9, 7.0, 30.0])
    u = jnp.asarray([-30.0, -7.5, 0.0, 1.0, 3.0, 7.5, 30.0])
    gc, uc = jnp.minimum(g, 7.0), jnp.clip(u, -7.0, 7.0)
    want = (uc + 1.0) * gc * jax.nn.sigmoid(1.702 * gc)
    np.testing.assert_allclose(MOE._glu(g, u, "swigluoai"), want,
                               rtol=1e-6)
    np.testing.assert_array_equal(MOE._glu(g, u),
                                  jax.nn.silu(g) * u)
    with pytest.raises(ValueError, match="activation"):
        MOE.DroplessMoELayer(8, 4, 4, top_k=2, act="gelu")


def _parent_form(x, router_w, router_b, wg, wu, wd, top_k, scale, held,
                 norm_eps, masked):
    """The expert layer as it stood before the clamped form existed
    (silu(x W_g) * x W_u, written out here), masked or grouped."""
    def swiglu(x, wg, wu, wd):
        h = jax.nn.silu(jnp.dot(x, wg, preferred_element_type=jnp.float32)) \
            * jnp.dot(x, wu, preferred_element_type=jnp.float32)
        return jnp.dot(h.astype(x.dtype), wd,
                       preferred_element_type=jnp.float32)
    local, w, here = MOE._route(x, router_w, router_b, top_k, scale, held,
                                norm_eps)
    if masked:
        count = wg.shape[0]
        onehot = jax.nn.one_hot(local, count, dtype=jnp.float32)
        wt = (onehot * w[..., None]).sum(1)
        h = jax.nn.silu(jnp.einsum("td,edf->etf", x, wg,
                                   preferred_element_type=jnp.float32)) \
            * jnp.einsum("td,edf->etf", x, wu,
                         preferred_element_type=jnp.float32)
        out = jnp.einsum("etf,efd->etd", h.astype(x.dtype), wd,
                         preferred_element_type=jnp.float32)
        return jnp.einsum("etd,te->td", out, wt)
    T, d = x.shape
    y = jnp.zeros((T, d), jnp.float32)
    for e in range(wg.shape[0]):
        on = (here & (local == e))
        we = jnp.where(on, w, 0.0).sum(1)
        y = y + we[:, None] * swiglu(x, wg[e], wu[e], wd[e])
    return y


@pytest.mark.parametrize("model", ["kimi", "lfm2", "pangu"])
def test_the_default_expert_path_is_the_parents(model):
    """The Kimi, LFM2 and openPangu expert layers keep the default
    activation, and their masked pass is BIT-identical to the form it
    had before the clamped one was added (the same operations, traced
    the same way); the grouped form sums the same products (its rows
    are gathered in blocks, so it is compared to float32 rounding)."""
    from paddle_tpu.text.models import (KimiLinearForCausalLM,
                                        Lfm2MoeForCausalLM,
                                        PanguUltraMoEForCausalLM,
                                        kimi_linear_tiny, lfm2_moe_tiny,
                                        pangu_ultra_moe_tiny)
    make = {"kimi": (KimiLinearForCausalLM, kimi_linear_tiny),
            "lfm2": (Lfm2MoeForCausalLM, lfm2_moe_tiny),
            "pangu": (PanguUltraMoEForCausalLM, pangu_ultra_moe_tiny)}
    cls, tiny = make[model]
    m = cls(tiny())
    layer = [l for l in m.sublayers()
             if isinstance(l, MOE.DroplessMoELayer)][0]
    assert layer.act == "swiglu"
    x = jnp.asarray(np.random.RandomState(3).randn(24, layer.d_model),
                    jnp.float32)
    vals = [p._value for p in (layer.router, layer.router_bias,
                               layer.gate_w, layer.up_w, layer.down_w)]
    kw = dict(top_k=layer.top_k, scale=layer.routed_scaling_factor,
              held=layer.held_experts, norm_eps=layer.norm_eps)
    for masked in (True, False):
        MOE_pays = MOE.masked_pass_pays
        try:
            MOE.masked_pass_pays = lambda T, k, E, m_=masked: m_
            new = jax.jit(lambda x, *v: dropless_moe(x, *v, **kw)[0])(
                x, *vals)
        finally:
            MOE.masked_pass_pays = MOE_pays
        old = jax.jit(lambda x, *v: _parent_form(x, *v, masked=masked,
                                                 **kw))(x, *vals)
        if masked:
            np.testing.assert_array_equal(new, old)
        else:
            np.testing.assert_allclose(new, old, atol=1e-5)


# ---------------------------------------------------------------------
# through GenerationServer
# ---------------------------------------------------------------------
def _serve(model, prompts, max_new=10, **kw):
    opts = dict(num_slots=3, block_size=BS, max_model_len=128,
                prompt_buckets=[96], max_prefill_batch=2,
                check_replay=True)
    opts.update(kw)
    with GenerationServer(model, **opts) as srv:
        streams = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
        outs = [s.result(timeout=300) for s in streams]
    return outs, srv.stats()      # after stop(): the last step is read


def _prompts(n, seed=0, lo=30, hi=90):
    r = np.random.RandomState(seed)
    return [r.randint(1, 256, size=r.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


def test_served_tokens_are_the_references_best(bench, built):
    """Through submit(): more requests than slots, batched prefill with
    padded rows, slots reused, contexts of up to 15
    pages.  Every served token is the reference's best at its position
    up to float32 rounding of the logits; the decode program counts the
    pages it read and those a dense read would have."""
    model, params = built
    prompts = _prompts(5)
    outs, st = _serve(model, prompts, max_new=24)
    assert st["traffic_compiles"] == 0
    assert 0 < st["msa_pages_read"] < st["msa_pages_live"]
    for p, out in zip(prompts, outs):
        ids = np.concatenate([p, np.asarray(out[:-1], np.int32)])
        lg = bench["ref"].forward_logits(toy_cfg(), params,
                                         jnp.asarray(ids))
        at = lg[len(p) - 1:]
        gap = at.max(-1) - jnp.take_along_axis(
            at, jnp.asarray(out)[:, None], -1)[:, 0]
        assert float(gap.max()) < 2 * ATOL


def test_the_five_step_counters_are_summed_under_their_names(built):
    model, _ = built
    assert model.step_counters() == (
        "moe_picks_here", "moe_max_expert_load", "moe_rows_multiplied",
        "msa_pages_read", "msa_pages_live")
    _, st = _serve(model, _prompts(3, seed=5, lo=5, hi=12), max_new=6)
    steps = st["decode_steps"]
    assert steps >= 5
    # the masked pass: 3 slots x 8 held experts a layer
    assert st["moe_rows_multiplied"] == steps * 4 * 3 * 8
    # short rows read every page they hold
    assert st["msa_pages_read"] == st["msa_pages_live"] > 0


def test_evict_and_replay_gives_the_same_tokens(built):
    """A pool too small for three sequences evicts; the re-prefill
    from position 0 and the replayed decode rebuild the pages' pooled
    keys, and the tokens are the unhurried server's."""
    model, _ = built
    prompts = _prompts(3, seed=9, lo=40, hi=60)
    calm, _ = _serve(model, prompts, max_new=20)
    tight, st = _serve(model, prompts, max_new=20, num_blocks=21)
    assert st["evicted"] > 0 and st["replay_steps"] > 0
    assert tight == calm


def test_what_runs_a_step_from_mid_sequence_is_refused(built):
    model, _ = built
    with pytest.raises(MidSequenceStepUnsupported, match="prefix_cache"):
        GenerationServer(model, prefix_cache=True, block_size=BS)
    with pytest.raises(MidSequenceStepUnsupported, match="speculative"):
        GenerationServer(model, draft_model=model, block_size=BS)
    pools = model.init_paged_cache(9, BS)
    with pytest.raises(NotImplementedError):
        model.forward_paged(jnp.zeros((2, 3), jnp.int32),
                            jnp.zeros((2, 3), jnp.int32), pools,
                            jnp.zeros((2, 8), jnp.int32),
                            jnp.ones((2, 3), bool), verify_mode=True)


def test_the_indexer_is_filed_under_attn(built):
    """The indexer's ops carry ``msa_index`` nested in ``attn``: a name
    outside the block vocabulary, so a reader of device time by block
    counts them as attention."""
    from paddle_tpu.observability.scopes import VOCABULARY
    model, _ = built
    assert MM.MSA_INDEX not in VOCABULARY
    pools = model.init_paged_cache(40, BS)
    args = (jnp.zeros((3, 1), jnp.int32), jnp.full((3, 1), 50, jnp.int32),
            pools, jnp.tile(jnp.arange(1, 17, dtype=jnp.int32), (3, 1)),
            jnp.ones((3, 1), bool))
    hlo = _step(model).lower(*args).compiler_ir("hlo").as_hlo_module(
    ).to_string()
    paths = set(re.findall(r'op_name="([^"]*msa_index[^"]*)"', hlo))
    assert paths and all("/attn/" in p for p in paths)
