"""LFM2-MoE on the serving path, against the benchmark's ONE plain
reference (``perfbench/configs/lfm2-8b-a1b.reference.py``, loaded by
path) at ``lfm2_moe_tiny()``: all three layer kinds (conv + dense,
conv + experts, attention + experts), two periods, GQA with 64-wide
normalised heads.  The whole forward pass, prefill through a bucket
then decode through the pools and the tails, ``GenerationServer`` with
padded rows, batched prefill, slot reuse, eviction and replay, the
expert shares that add up to the uncut layer, every expert form
against the masked pass, the kernel's wide page through the model, and
the typed errors.

Tolerances.  Program and reference compute the same float32 sums in
another order (the program batches, fuses ``silu(g) * u`` and reads K/V
back from pages; the reference loops over experts): logits of std 0.34
built from sums of a few hundred terms differ by a few float32 ulps of
the largest partial sum, under 5e-6 here (1.3e-6 measured).  bfloat16
in place of float32 moves them by 1e-2, three orders over the limit
(:func:`test_bfloat16_arithmetic_fails_the_tolerance`).
"""
import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference import GenerationServer
from paddle_tpu.inference.recurrent_state import RecurrentStateUnsupported
from paddle_tpu.nn.layer import moe as MOE
from paddle_tpu.nn.layer.moe import dropless_moe
from paddle_tpu.ops.pallas import registry as kreg
from paddle_tpu.text.models import Lfm2MoeForCausalLM, lfm2_moe_tiny

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(ROOT, "tests", "perfbench_tests", "toy", "configs",
                   "lfm2-toy.json")
SEED = 4321
ATOL = 5e-6          # module doc


@pytest.fixture(scope="module")
def bench():
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench.harness import manifest as M
    d = os.path.join(ROOT, "perfbench", "configs")
    return {"ref": M.load_module(
                os.path.join(d, "lfm2-8b-a1b.reference.py"),
                "lfm2_reference_for_tests"),
            "bind": M.load_module(
                os.path.join(d, "lfm2-8b-a1b.program.py"),
                "lfm2_binding_for_tests")}


def toy_cfg():
    with open(TOY) as f:
        return json.load(f)


def build(bench, cfg=None, seed=SEED, dtype="float32"):
    """(model with the seed's weights, computing in ``dtype``; the
    reference's flat float32 tree of the same weights)."""
    from perfbench.harness import weights as W
    from perfbench.harness.program import install_weights
    cfg = cfg or toy_cfg()
    ref, bind = bench["ref"], bench["bind"]
    mc = dataclasses.replace(bind.model_config(cfg, 128),
                             compute_dtype=dtype)
    model = Lfm2MoeForCausalLM(mc)
    model.eval()
    specs = ref.param_specs(cfg)
    install_weights(model, bind.name_map(cfg, model), specs, seed,
                    jnp.dtype(dtype))
    return model, W.make_tree(specs, W.seed_key(seed), jnp.float32)


@pytest.fixture(scope="module")
def built(bench):
    return build(bench)


def test_the_toy_is_the_tiny_preset_with_every_layer_kind(bench):
    mc = bench["bind"].model_config(toy_cfg(), 128)
    tiny = lfm2_moe_tiny(held_experts=(0, 8), compute_dtype="bfloat16")
    assert mc == tiny
    kinds = [(mc.is_attention(l), mc.is_moe(l)) for l in range(10)]
    assert kinds[:3] == [(False, False), (False, False), (True, True)]
    assert sum(a for a, _ in kinds) == 2 and mc.head_dim == 64
    assert {(False, False), (False, True), (True, True)} == set(kinds)
    model = Lfm2MoeForCausalLM(tiny)
    assert model.supports_kv_cache() and model.has_recurrent_state()
    assert not any(n.endswith("lm_head")
                   for n, _ in model.named_parameters())      # tied


# ---------------------------------------------------------------------
# the model through its paged caches, against the reference
# ---------------------------------------------------------------------
def _whole(model, ids):
    """The sequence as one fresh block: logits at every position."""
    T = len(ids)
    pools = model.init_paged_cache(33, 4, 2)
    lg, _, _ = model.forward_paged(
        jnp.asarray(ids)[None], jnp.arange(T, dtype=jnp.int32)[None],
        pools, jnp.arange(1, 33, dtype=jnp.int32)[None],
        jnp.ones((1, T), bool), slots=jnp.asarray([0], jnp.int32))
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


def _prefill_then_decode(model, ids, L, slot=2, N=4, bs=4, Mx=32, Lb=48):
    """Logits at positions L-1 .. len(ids)-1: one batched prefill (the
    sequence, padded to the bucket, beside an empty row) and then one
    decode step a token, the sequence in ``slot`` among idle slots."""
    pools = model.init_paged_cache(N * Mx + 1, bs, N)
    prompt = np.zeros((2, Lb), np.int32)
    prompt[0, :L] = ids[:L]
    pos = np.broadcast_to(np.arange(Lb, dtype=np.int32), (2, Lb))
    wm = np.arange(Lb)[None] < np.asarray([L, 0])[:, None]
    tbl = np.zeros((2, Mx), np.int32)
    tbl[0] = np.arange(1, Mx + 1)
    lg, pools, counts = model.forward_paged(
        jnp.asarray(prompt), jnp.asarray(pos), pools,
        jnp.asarray(tbl), jnp.asarray(wm),
        gather_at=jnp.asarray([L - 1, 0]),
        slots=jnp.asarray([slot, N], jnp.int32))
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
    """Prefill through a bucket (padded rows, an empty row beside it),
    then decode through the K/V pages and the conv tails, is the
    reference's one full forward pass -- with the expert layers in
    either form."""
    monkeypatch.setattr(MOE, "masked_pass_pays",
                        lambda T, k, E: form == "masked")
    model, params = built
    ids = _ids()
    want = bench["ref"].forward_logits(toy_cfg(), params, jnp.asarray(ids))
    got = _prefill_then_decode(model, ids, L=37)
    np.testing.assert_allclose(got, want[36:], atol=ATOL)


def test_a_decode_step_through_the_kernels_wide_page(built):
    """The attention layers' decode step through ``paged_attention``
    (Pallas interpreter; 2 K/V heads x 64 = one lane tile: the wide
    page) is the XLA reference's, to the kernel's stated tolerance."""
    model, _ = built
    ids = _ids(30, seed=2)
    try:
        kreg.set_mode("paged_attention", "xla_ref")
        want = _prefill_then_decode(model, ids, L=25)
        kreg.set_mode("paged_attention", "interpret")
        kreg.reset_dispatch_counts()
        got = _prefill_then_decode(model, ids, L=25)
        assert kreg.dispatch_counts("paged_attention").get("interpret")
    finally:
        kreg.set_mode("paged_attention", None)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------
def test_four_shares_of_eight_experts_add_up_to_the_uncut_layer(
        bench, monkeypatch):
    """The four shares of 8 of the 32 experts, each from the router at
    its full width, add up to the uncut layer: nothing is counted
    twice (no shared expert), for the reference's shares and for the
    program's, whose shares take both forms."""
    ref = bench["ref"]
    r = np.random.RandomState(2)
    d, f, E = 32, 16, 32
    n = lambda *s: jnp.asarray(r.randn(*s) * 0.3, jnp.float32)
    lp = {"router": n(d, E), "rbias": n(E) * 0.1, "eg": n(E, d, f),
          "eu": n(E, d, f), "ed": n(E, f, d)}
    whole = {"num_experts": E, "num_experts_per_tok": 4,
             "routed_scaling_factor": 1.0}
    x = n(19, d)
    want = ref.moe(whole, lp, x)
    got_ref = got_prog = 0.0
    for first in (0, 8, 16, 24):
        part = dict(whole, assumed={"held_experts": [first, 8]})
        lp_i = dict(lp, **{k: lp[k][first:first + 8]
                           for k in ("eg", "eu", "ed")})
        got_ref = got_ref + ref.moe(part, lp_i, x)
        # two shares by the masked pass, two by the grouped dispatch
        monkeypatch.setattr(MOE, "masked_pass_pays",
                            lambda T, k, E_, masked=first < 16: masked)
        y, _, _ = dropless_moe(
            x, lp["router"], lp["rbias"], lp_i["eg"], lp_i["eu"],
            lp_i["ed"], top_k=4, scale=1.0, held=(first, 8),
            norm_eps=1e-6)
        got_prog = got_prog + y
    np.testing.assert_allclose(got_ref, want, atol=1e-6)
    np.testing.assert_allclose(got_prog, want, atol=1e-6)


@pytest.mark.parametrize("routing", ["even", "skewed"])
@pytest.mark.parametrize("T", [128, 256, 512])
def test_every_expert_form_is_the_masked_pass(T, routing):
    """The sorted/grouped dispatch against the masked dense pass at
    128, 256 and 512 rows of 4-of-32 experts, under even routing and
    under a bias that sends half of all picks to two experts.  One sum
    in two orders: float32 rounding of sums of 16 terms, atol 1e-6."""
    r = np.random.RandomState(T)
    d, f, E, K = 32, 16, 32, 4
    n = lambda *s: jnp.asarray(r.randn(*s) * 0.3, jnp.float32)
    x, router, wg, wu, wd = n(T, d), n(d, E), n(E, d, f), n(E, d, f), \
        n(E, f, d)
    rb = jnp.zeros((E,)).at[:2].set(10.0 if routing == "skewed" else 0.0)
    local, w, here = MOE._route(x, router, rb, K, 1.0, (0, E), 1e-6)
    want, picks, load, rows = MOE._experts_masked(x, local, w, wg, wu, wd)
    assert (int(picks), rows) == (T * K, T * E)
    if routing == "skewed":
        assert int(load) == T               # every token picks 0 and 1
    y, p2, l2, (used, blk) = MOE._experts_grouped(x, local, w, here, wg,
                                                  wu, wd)
    np.testing.assert_allclose(y, want, atol=1e-6)
    assert (int(p2), int(l2)) == (int(picks), int(load))
    # whole blocks of the picks each expert got, no more
    assert T * K <= int(used) * blk <= T * K + E * MOE._GROUP_BLOCK
    # and through the layer's own line: the shape decides, no knob
    for T_, form in ((128, True), (256, True), (512, False)):
        assert MOE.masked_pass_pays(T_, K, E) is form


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
    outs, st = _serve(model, prompts)
    assert st["state_slots"] == 4 and st["state_resets"] == 7
    # 8 conv layers x 4 slots x [2, 256] float32; 2 attention layers'
    # K and V pools; no latent pages
    assert st["state_bytes"] == 8 * 4 * 2 * 256 * 4
    assert st["kv_pool_bytes"] == (
        2 * 2 * (st["total_blocks"] + 1) * 4 * 128 * 4)
    assert st["latent_pool_bytes"] == 0
    assert st["traffic_compiles"] == 0
    for p, out in zip(prompts, outs):
        ids = np.concatenate([p, np.asarray(out[:-1], np.int32)])
        lg = bench["ref"].forward_logits(toy_cfg(), params,
                                         jnp.asarray(ids))
        at = lg[len(p) - 1:]
        gap = at.max(-1) - jnp.take_along_axis(
            at, jnp.asarray(out)[:, None], -1)[:, 0]
        assert float(gap.max()) < 2 * ATOL


def test_the_three_step_counters_are_summed_under_their_names(built):
    """``stats()`` adds up what the decode program counts: 8 expert
    layers, 2 picks a live row, and the masked pass's 8 row-products a
    row of 4 slots whether live or not."""
    model, _ = built
    assert model.step_counters() == (
        "moe_picks_here", "moe_max_expert_load", "moe_rows_multiplied")
    assert not model.loops_on_device(256) and model.loops_on_device(4096)
    _, st = _serve(model, _prompts(3, seed=5), max_new=6)
    steps = st["decode_steps"]
    assert steps >= 5
    assert st["moe_rows_multiplied"] == steps * 8 * 4 * 8
    assert st["moe_picks_here"] == steps * 8 * 4 * 2
    assert 0 < st["moe_max_expert_load"] <= st["moe_picks_here"]


def test_a_reused_slot_starts_from_zero_tails(built):
    """One slot: a short request takes the slot a longer owner left,
    tails and pages still in it, and is served as if alone."""
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
    # evictions, re-prefill from zero tails and replay (check_replay
    # asserts every replayed token)
    tight, st = _serve(model, prompts, max_new=20, num_blocks=25)
    assert st["evicted"] > 0 and st["replay_steps"] > 0
    assert tight == calm


def test_what_knows_only_kv_blocks_is_refused(built):
    model, _ = built
    from paddle_tpu.inference import migration
    with pytest.raises(RecurrentStateUnsupported, match="prefix_cache"):
        GenerationServer(model, prefix_cache=True)
    with pytest.raises(RecurrentStateUnsupported, match="speculative"):
        GenerationServer(model, draft_model=model)
    with GenerationServer(model, num_slots=2, block_size=4,
                          max_model_len=32, prompt_buckets=[16]) as srv:
        s = srv.submit(_prompts(1)[0][:8], max_new_tokens=4)
        with pytest.raises(RecurrentStateUnsupported, match="migration"):
            migration.export_sequence(srv, 1)
        s.result(timeout=120)
    pools = model.init_paged_cache(9, 4, 2)
    with pytest.raises(RecurrentStateUnsupported):
        model.forward_paged(jnp.zeros((2, 3), jnp.int32),
                            jnp.zeros((2, 3), jnp.int32), pools,
                            jnp.zeros((2, 8), jnp.int32),
                            jnp.ones((2, 3), bool), verify_mode=True)
    with pytest.raises(ValueError, match="num_slots"):
        model.init_paged_cache(9, 4)
