"""Kimi-Linear on the serving path, against the benchmark's ONE plain
reference (``perfbench/configs/kimi-linear-48b-a3b.reference.py``,
loaded by path) at a toy size: KDA's chunked scan against the token by
token recurrence, absorbed against expanded latent attention, prefill
then decode through the paged caches and through ``GenerationServer``,
slot reuse, eviction and replay, the expert shares that add up to the
uncut layer, the vocabulary slice, and the typed errors."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference import GenerationServer
from paddle_tpu.inference.recurrent_state import RecurrentStateUnsupported
from paddle_tpu.nn.layer import moe as MOE
from paddle_tpu.nn.layer.moe import DroplessMoELayer, dropless_moe
from paddle_tpu.text.models import KimiLinearForCausalLM
from paddle_tpu.text.models import kimi_linear as KL

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(ROOT, "tests", "perfbench_tests", "toy", "configs",
                   "kimi-linear-toy.json")
SEED = 12345


@pytest.fixture(scope="module")
def bench():
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench.harness import manifest as M
    d = os.path.join(ROOT, "perfbench", "configs")
    return {"ref": M.load_module(
                os.path.join(d, "kimi-linear-48b-a3b.reference.py"),
                "kimi_reference_for_tests"),
            "bind": M.load_module(
                os.path.join(d, "kimi-linear-48b-a3b.program.py"),
                "kimi_binding_for_tests")}


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    """The toy's blocks are 16 to 48 tokens: a scan chunk of 16 puts
    chunk boundaries inside them."""
    monkeypatch.setattr(KL, "KDA_CHUNK", 16)


def toy_cfg(**assumed):
    with open(TOY) as f:
        cfg = json.load(f)
    cfg["assumed"] = dict(cfg["assumed"], **assumed)
    return cfg


def build(bench, cfg, dt_bias=0.0, seed=SEED):
    """(model in float32 with the seed's weights, the reference's flat
    tree of the same weights)."""
    from perfbench.harness import weights as W
    from perfbench.harness.program import install_weights
    ref, bind = bench["ref"], bench["bind"]
    mc = bind.model_config(cfg, 128)
    mc.compute_dtype = "float32"
    model = KimiLinearForCausalLM(mc)
    model.eval()
    specs = ref.param_specs(cfg)
    install_weights(model, bind.name_map(cfg, model), specs, seed,
                    jnp.float32)
    params = W.make_tree(specs, W.seed_key(seed), jnp.float32)
    if dt_bias:
        # the public model starts near -4 (decay about 0.95 a token):
        # a fault in state handling then lasts long enough to be seen
        for n, p in model.named_parameters():
            if n.endswith("dt_bias"):
                p._value = p._value + dt_bias
        params = {k: v + dt_bias if k.endswith(".dtb") else v
                  for k, v in params.items()}
    return model, params


# ---------------------------------------------------------------------
# KDA: chunked scan = recurrence, under padding and strong decay
# ---------------------------------------------------------------------
def _kda_inputs(B, L, H, d, decay, seed=0):
    r = np.random.RandomState(seed)
    n = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)
    q = KL._l2(n(B, L, H, d)) * d ** -0.5
    k = KL._l2(n(B, L, H, d))
    v = n(B, L, H, d)
    g = -decay * jax.nn.softplus(n(B, L, H, d))
    beta = jax.nn.sigmoid(n(B, L, H))
    S0 = n(B, H, d, d) * 0.3
    return S0, q, k, v, g, beta


def _recurrent(S, q, k, v, g, beta):
    def step(S, t):
        return KL.kda_step(S, *t)
    mv = lambda x: jnp.moveaxis(x, 1, 0)
    S, o = jax.lax.scan(step, S, tuple(mv(x) for x in (q, k, v, g, beta)))
    return S, jnp.moveaxis(o, 0, 1)


@pytest.mark.parametrize("chunk", [16, 32, 64])
@pytest.mark.parametrize("decay", [0.05, 1.0, 12.0],
                         ids=["slow", "fast", "overflowing"])
def test_chunked_kda_equals_recurrent(chunk, decay):
    # 75 tokens: crosses chunk and sub-block boundaries and ends in a
    # padded chunk; "overflowing" decays e^-500 over a chunk, which a
    # factorised exp(+G) could not hold in float32
    S0, q, k, v, g, beta = _kda_inputs(2, 75, 2, 16, decay)
    S1, o1 = KL.kda_chunked(S0, q, k, v, g, beta, chunk)
    S2, o2 = _recurrent(S0, q, k, v, g, beta)
    assert np.isfinite(np.asarray(o1)).all()
    np.testing.assert_allclose(o1, o2, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(S1, S2, atol=2e-5, rtol=1e-4)


def test_padded_positions_leave_the_state_alone():
    S0, q, k, v, g, beta = _kda_inputs(2, 40, 2, 16, 1.0, seed=3)
    live = (jnp.arange(40)[None, :] < jnp.asarray([23, 0])[:, None])
    lf = live.astype(jnp.float32)
    gm, bm = g * lf[..., None, None], beta * lf[..., None]
    S1, _ = KL.kda_chunked(S0, q, k, v, gm, bm, 16)
    S2, _ = KL.kda_chunked(S0[:1], q[:1, :23], k[:1, :23], v[:1, :23],
                           g[:1, :23], beta[:1, :23], 16)
    np.testing.assert_allclose(S1[0], S2[0], atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(S1[1], S0[1])     # an empty row
    # and one decode step of a masked row: bit for bit
    z = jnp.zeros_like(g[:, 0])
    S3, _ = KL.kda_step(S0, q[:, 0], k[:, 0], v[:, 0], z, z[..., 0])
    np.testing.assert_array_equal(S3, S0)


def test_short_conv_carries_its_tail_through_padding():
    r = np.random.RandomState(1)
    x = jnp.asarray(r.randn(2, 3, 10, 8), jnp.float32)
    w = jnp.asarray(r.randn(3, 4, 8), jnp.float32)
    tail0 = jnp.zeros((2, 3, 3, 8), jnp.float32)
    y, _ = KL.short_conv(x, tail0, w)
    # in two pieces, the first padded beyond its 6 real positions
    ya, ta = KL.short_conv(x[:, :, :8], tail0, w,
                           length=jnp.asarray([6, 6]))
    yb, tb = KL.short_conv(x[:, :, 6:], ta, w)
    np.testing.assert_allclose(ya[:, :, :6], y[:, :, :6], atol=1e-6)
    np.testing.assert_allclose(yb, y[:, :, 6:], atol=1e-6)
    np.testing.assert_array_equal(tb, x[:, :, 7:])


# ---------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------
def _dense_moe(x, m):
    """Every held expert over every token, masked: the oracle."""
    first, count = m.held_experts
    s = jax.nn.sigmoid(x @ m.router._value)
    _, idx = jax.lax.top_k(s + m.router_bias._value, m.top_k)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * m.routed_scaling_factor
    W = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], idx].set(w)
    y = 0
    for e in range(count):
        h = jax.nn.silu(x @ m.gate_w._value[e]) * (x @ m.up_w._value[e])
        y = y + W[:, first + e, None] * (h @ m.down_w._value[e])
    return y


def _masked_below(monkeypatch, n):
    """Move the line between the two expert forms for a test: the
    masked pass under ``n`` tokens, whatever the router's shape."""
    monkeypatch.setattr(MOE, "masked_pass_pays", lambda T, k, E: T < n)


@pytest.mark.parametrize("block,dense_below", [(4, 0), (16, 0),
                                               (256, 0), (256, 256)])
def test_dropless_dispatch_equals_masked_dense(monkeypatch, block,
                                               dense_below):
    """Grouped dispatch in blocks of the sorted picks (prefill; a
    group of 11 picks over three blocks of 4, inside one of 16, and
    the whole batch smaller than a block) and the masked dense pass (a
    decode step's few tokens): one sum."""
    import paddle_tpu as paddle
    monkeypatch.setattr(MOE, "_GROUP_BLOCK", block)
    _masked_below(monkeypatch, dense_below)
    paddle.seed(0)
    m = DroplessMoELayer(32, 16, 16, top_k=4, held_experts=(4, 8),
                         routed_scaling_factor=2.0)
    x = jnp.asarray(np.random.RandomState(0).randn(21, 32), jnp.float32)
    y, n_here, load = dropless_moe(
        x, m.router._value, m.router_bias._value, m.gate_w._value,
        m.up_w._value, m.down_w._value, top_k=4, scale=2.0,
        held=(4, 8))
    np.testing.assert_allclose(y, _dense_moe(x, m), atol=1e-6)
    assert (int(n_here), int(load)) == (33, 11)


def test_four_shares_add_up_to_the_uncut_layer(bench, monkeypatch):
    """The parts all shares give, the shared expert counted once, are
    the uncut reference's layer output -- for the reference's shares
    and for the program's."""
    ref = bench["ref"]
    from perfbench.harness import weights as W
    whole = toy_cfg(held_experts=[0, 8])
    whole["num_experts"] = 8
    specs = ref.param_specs(whole)
    params = W.make_tree(specs, W.seed_key(SEED), jnp.float32)
    lp = ref.layer_params(params, 1)
    x = jnp.asarray(np.random.RandomState(2).randn(19, 64), jnp.float32)
    want = ref.moe(whole, lp, x)
    shared = ref.swiglu(x, lp["sg"], lp["su"], lp["sd"])
    got_ref, got_prog = shared, shared
    for first in (0, 2, 4, 6):
        # two shares by the grouped dispatch, two by the dense pass
        _masked_below(monkeypatch, 0 if first < 4 else 256)
        part = dict(whole, num_experts=2,
                    assumed=dict(whole["assumed"],
                                 held_experts=[first, 2]))
        lp_i = dict(lp, **{k: lp[k][first:first + 2]
                           for k in ("eg", "eu", "ed")})
        got_ref = got_ref + ref.moe(part, lp_i, x, shared=False)
        y, _, _ = dropless_moe(
            x, lp["router"], lp["rbias"], lp_i["eg"], lp_i["eu"],
            lp_i["ed"], top_k=2, scale=whole["routed_scaling_factor"],
            held=(first, 2))
        got_prog = got_prog + y
    np.testing.assert_allclose(got_ref, want, atol=1e-6)
    np.testing.assert_allclose(got_prog, want, atol=1e-6)


# ---------------------------------------------------------------------
# the model through its paged caches, against the reference
# ---------------------------------------------------------------------
def _prefill_then_decode(model, ids, L, slot=2, N=4, bs=4, Mx=32, Lb=48):
    """Logits at positions L-1 .. len(ids)-1: one batched prefill (the
    sequence beside an empty row) and then one decode step a token,
    the sequence in ``slot`` among idle slots."""
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


@pytest.mark.parametrize("dt_bias,chunk,dense_below",
                         [(0.0, 16, 256), (-4.0, 32, 0)])
def test_prefill_then_decode_agree_with_the_reference(
        bench, monkeypatch, dt_bias, chunk, dense_below):
    cfg = toy_cfg()
    monkeypatch.setattr(KL, "KDA_CHUNK", chunk)
    _masked_below(monkeypatch, dense_below)   # 0: grouped
    model, params = build(bench, cfg, dt_bias)
    ids = np.random.RandomState(0).randint(1, 256, size=43).astype(np.int32)
    want = bench["ref"].forward_logits(cfg, params, jnp.asarray(ids))
    got = _prefill_then_decode(model, ids, L=37)
    np.testing.assert_allclose(got, want[36:], atol=5e-6)


def test_absorbed_latent_attention_equals_expanded(bench):
    """The same tokens through the MLA layer as one fresh block
    (expanded K and V) and one by one (absorbed, over the pages)."""
    model, _ = build(bench, toy_cfg())
    attn = model.model.layers[3].self_attn
    assert model.model.layers[3].is_mla
    r = np.random.RandomState(5)
    x = jnp.asarray(r.randn(1, 11, 64), jnp.float32)
    tbl = jnp.arange(1, 9, dtype=jnp.int32)[None]
    pos = jnp.arange(11, dtype=jnp.int32)[None]
    cache = attn.init_cache(9, 4, jnp.float32)
    want, _ = attn.forward_paged(x, pos, cache, tbl,
                                 jnp.ones((1, 11), bool))
    got = []
    for t in range(11):
        o, cache = attn.forward_paged(x[:, t:t + 1], pos[:, t:t + 1],
                                      cache, tbl, jnp.ones((1, 1), bool))
        got.append(o[:, 0])
    np.testing.assert_allclose(jnp.stack(got, 1), want, atol=2e-6)


def test_a_latent_block_has_to_start_its_sequence(bench):
    """Expanded latent attention sees the fresh block only: a block
    that starts mid-sequence raises where the positions can be read,
    and reads NaN under a trace, where nothing can raise."""
    model, _ = build(bench, toy_cfg())
    attn = model.model.layers[3].self_attn
    x = jnp.asarray(np.random.RandomState(6).randn(2, 5, 64), jnp.float32)
    tbl = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)
    pos = jnp.asarray([[0, 1, 2, 3, 4], [4, 5, 6, 7, 8]], jnp.int32)
    cache = attn.init_cache(9, 4, jnp.float32)
    args = (cache, tbl, jnp.ones((2, 5), bool))
    with pytest.raises(ValueError, match="from position 0"):
        attn.forward_paged(x, pos, *args)
    o, _ = jax.jit(attn.forward_paged)(x, pos, *args)
    assert bool(jnp.isfinite(o[0]).all()) and bool(jnp.isnan(o[1]).all())


def test_a_vocabulary_slice_is_a_smaller_vocabulary(bench):
    cfg = toy_cfg()
    model, params = build(bench, cfg)
    small = dict(cfg, vocab_size=64)
    model_s, _ = build(bench, small)
    for (n, p), (_, ps) in zip(model.named_parameters(),
                               model_s.named_parameters()):
        if n == "model.embed_tokens":
            ps._value = p._value[:64]
        elif n == "lm_head":
            ps._value = p._value[:, :64]
        else:
            ps._value = p._value
    ids = np.random.RandomState(4).randint(1, 64, size=30).astype(np.int32)
    whole = _prefill_then_decode(model, ids, L=25)
    part = _prefill_then_decode(model_s, ids, L=25)
    np.testing.assert_allclose(part, whole[:, :64], atol=1e-6)


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
        return outs, srv.stats()


def _prompts(n, seed=0, lo=5, hi=30):
    r = np.random.RandomState(seed)
    return [r.randint(1, 256, size=r.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


@pytest.mark.parametrize("dt_bias", [0.0, -4.0])
def test_served_tokens_are_the_references_best(bench, dt_bias):
    """Through submit(): more requests than slots, batched prefill in
    two buckets, slots reused.  Every served token is the reference's
    best at its position, up to float32 rounding of the logits."""
    cfg = toy_cfg()
    model, params = build(bench, cfg, dt_bias)
    prompts = _prompts(7)
    outs, st = _serve(model, prompts)
    assert st["state_slots"] == 4 and st["state_bytes"] > 0
    assert st["latent_pool_bytes"] > 0 and st["state_resets"] == 7
    assert st["moe_picks_here"] > 0
    assert 0 < st["moe_max_expert_load"] <= st["moe_picks_here"]
    assert st["traffic_compiles"] == 0
    for p, out in zip(prompts, outs):
        ids = np.concatenate([p, np.asarray(out[:-1], np.int32)])
        lg = bench["ref"].forward_logits(cfg, params, jnp.asarray(ids))
        at = lg[len(p) - 1:]
        gap = at.max(-1) - jnp.take_along_axis(
            at, jnp.asarray(out)[:, None], -1)[:, 0]
        assert float(gap.max()) < 1e-5


def test_a_sampling_request_costs_no_program_and_is_counted(bench):
    """ISSUE 30: the sampler's work sits in a ``cond`` inside the
    programs, so this server compiles the 7 programs the commit before
    compiled for these arguments (6 prefill shapes + decode; since
    ISSUE 34 the first tokens' scatter too, once a prefill batch
    width), none when a request samples, and counts the dispatches
    that held it."""
    model, _ = build(bench, toy_cfg())
    with GenerationServer(model, num_slots=4, block_size=4,
                          max_model_len=64, prompt_buckets=[16, 32],
                          max_prefill_batch=2, check_replay=True) as srv:
        assert srv.num_compiles() == 7 + 2
        a, b = _prompts(2, seed=3)
        greedy = srv.submit(a, max_new_tokens=6).result(timeout=300)
        assert srv.stats()["sampled_steps"] == 0
        kw = dict(max_new_tokens=5, do_sample=True, temperature=0.7,
                  top_p=0.9, seed=3)
        drawn = srv.submit(b, **kw).result(timeout=300)
        assert srv.submit(b, **kw).result(timeout=300) == drawn
        st = srv.stats()
        assert len(greedy) == 6 and len(drawn) == 5
        assert st["sampled_steps"] == 2 * 5     # prefill + 4 decodes
        assert st["num_compiles"] == 9 and st["traffic_compiles"] == 0


@pytest.mark.parametrize("dense_below, in_cond", [
    (256, {"decode": True, "prefill": True}),    # nothing is grouped
    (8, {"decode": True, "prefill": False}),     # prefill 2 x 16 is
    (4, {"decode": False, "prefill": False}),    # and 4 decode rows are
])
def test_the_sampler_branches_only_behind_a_program_without_the_expert_loop(
        bench, monkeypatch, dense_below, in_cond):
    """The grouped expert product is a device loop whose steps branch;
    a ``conditional`` behind it stopped a v5e (PERF.md section 7, X), so
    a program that holds it sorts outside any ``cond``."""
    from test_sampler import _program_args, _sorts
    _masked_below(monkeypatch, dense_below)
    model, _ = build(bench, toy_cfg())
    srv = GenerationServer(model, num_slots=4, block_size=4,
                           max_model_len=64, prompt_buckets=[16],
                           max_prefill_batch=2)
    srv._build_programs()
    for which, fn, rows in [
            ("decode", srv._decode_fn, {}),
            ("prefill", srv._prefill_fn, srv._row_slots([], 2))]:
        args = _program_args(srv, which)
        traced = fn.trace(srv._pvals, srv._pools, *args, **rows)
        assert model.loops_on_device(args[0].size) != in_cond[which]
        # the grouped dispatch sorts its picks too; the sampler's sort
        # is the program's last
        *experts, sampler = _sorts(traced.jaxpr.jaxpr)
        assert sampler == in_cond[which] and not any(experts)
        assert bool(experts) != in_cond[which]


def test_what_must_not_move_for_the_kimi_cell():
    """PR 33 drew the line between the expert forms by the router's
    shape: at the published router (8 of 256) the cell's programs are
    what they were.  Its 128-row decode step holds no device loop (the
    masked pass), every prefill shape from 256 tokens holds the expert
    blocks, the router's constant is 1e-20 and the model names its two
    counters, not the layer's third."""
    from paddle_tpu.text.models import kimi_linear_tiny
    model = KimiLinearForCausalLM(kimi_linear_tiny(
        num_experts=256, num_experts_per_token=8, held_experts=(0, 64),
        moe_intermediate_size=8))
    assert [model.loops_on_device(n) for n in (128, 256, 2048)] == [
        False, True, True]
    assert model.step_counters() == ("moe_picks_here",
                                     "moe_max_expert_load")
    moe_layers = [lyr.mlp for lyr in model.model.layers if lyr.is_moe]
    assert moe_layers and all(m.norm_eps == 1e-20 for m in moe_layers)
    pools = model.init_paged_cache(9, 4, 2)
    _, _, counts = model.forward_paged(
        jnp.zeros((2, 1), jnp.int32), jnp.zeros((2, 1), jnp.int32), pools,
        jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 1), bool))
    assert counts.shape == (2,)


def test_a_reused_slot_starts_from_zero_state(bench):
    model, _ = build(bench, toy_cfg(), dt_bias=-4.0)
    a, b = _prompts(2, seed=7)
    # one slot: b takes the slot a left, state and tail still in it
    (_, second), _ = _serve(model, [a, b], num_slots=1)
    (alone,), _ = _serve(model, [b], num_slots=1)
    assert second == alone


def test_evict_and_replay_gives_the_same_tokens(bench):
    model, _ = build(bench, toy_cfg(), dt_bias=-4.0)
    prompts = _prompts(4, seed=9, lo=20, hi=30)
    calm, _ = _serve(model, prompts, max_new=20)
    # 4 sequences of up to 50 positions need ~50 blocks of 4: 24 force
    # evictions, re-prefill from zero state and replay (check_replay
    # asserts every replayed token)
    tight, st = _serve(model, prompts, max_new=20, num_blocks=25)
    assert st["evicted"] > 0 and st["replay_steps"] > 0
    assert tight == calm


def test_what_knows_only_kv_blocks_is_refused(bench):
    model, _ = build(bench, toy_cfg())
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


def test_a_kv_model_is_served_as_before():
    """No recurrent state: the server keeps the jitted programs it
    built, stages no slots and reports empty state."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny())
    model.eval()
    with GenerationServer(model, num_slots=2, block_size=4,
                          max_model_len=32, prompt_buckets=[16]) as srv:
        assert srv._stateful is False
        assert hasattr(srv._prefill_fn, "lower")       # the jit itself
        assert hasattr(srv._decode_fn, "lower")
        out = srv.submit(np.arange(1, 9), max_new_tokens=4).result(120)
        st = srv.stats()
    assert len(out) == 4
    assert (st["state_slots"], st["state_bytes"], st["latent_pool_bytes"],
            st["state_resets"], st["moe_picks_here"]) == (0, 0, 0, 0, 0)


def test_chunked_queries_attend_like_one_block():
    r = np.random.RandomState(3)
    q = jnp.asarray(r.randn(2, 64, 2, 24), jnp.float32)
    k = jnp.asarray(r.randn(2, 64, 2, 24), jnp.float32)
    v = jnp.asarray(r.randn(2, 64, 2, 16), jnp.float32)
    np.testing.assert_allclose(KL._attend(q, k, v, 0.2, chunk=16),
                               KL._attend(q, k, v, 0.2, chunk=64),
                               atol=1e-6)


def test_a_hybrid_server_counts_the_pairs_its_latent_layers_skip(
        bench, monkeypatch):
    """``stats()`` adds up ``attend_plan`` over the model's latent
    layers (the KDA layers take no square): a one-block bucket reads a
    share of 1.0, a bucket of four chunks 5/8 (the score block's limit
    is lowered so that a toy bucket holds four), and the tokens are
    those of the whole square."""
    model, _ = build(bench, toy_cfg())
    mla = sum(lyr.is_mla for lyr in model.model.layers)
    heads = model.config.num_attention_heads
    assert 0 < mla < len(model.model.layers)
    short, long = _prompts(2, seed=11, lo=9, hi=14), \
        _prompts(2, seed=12, lo=40, hi=60)
    opts = dict(prompt_buckets=[16, 64], max_model_len=96, max_new=5)
    want, whole = _serve(model, short + long, **opts)
    assert whole["prefill_attn_pairs_multiplied"] \
        == whole["prefill_attn_pairs_square"] > 0
    monkeypatch.setattr(KL, "_SCORE_BLOCK_BYTES", 1)
    assert model.prefill_attn_pairs(2, 16) == (mla * 2 * heads * 256,) * 2
    got, st = _serve(model, short, **opts)
    assert st["prefill_attn_pairs_multiplied"] \
        == st["prefill_attn_pairs_square"] > 0
    more, st = _serve(model, long, max_prefill_batch=1, **opts)
    assert got + more == want
    assert st["prefill_attn_pairs_square"] == 2 * mla * heads * 64 * 64
    assert st["prefill_attn_pairs_multiplied"] * 8 \
        == st["prefill_attn_pairs_square"] * 5


# ---------------------------------------------------------------------
# the latent module is shared with Pangu Ultra MoE since PR 35: what
# must not move for the Kimi cell
# ---------------------------------------------------------------------
class _ParentLatentAttention(KL._Params):
    """``KimiLatentAttention`` as the parent of PR 35 had it, kept here
    verbatim: what the shared module must equal."""

    def __init__(self, c):
        super().__init__()
        self.config = c
        self._std = c.initializer_range
        h, nh = c.hidden_size, c.num_attention_heads
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        self.q_proj = self._mk(h, nh * (dn + dr))
        self.kv_a_proj = self._mk(h, (c.kv_lora_rank + c.qk_rope_head_dim))
        self.kv_a_norm = self._mk(c.kv_lora_rank, one=True)
        self.kv_b_proj = self._mk(c.kv_lora_rank, nh * (dn + dv))
        self.o_proj = self._mk(nh * dv, h)

    def init_cache(self, num_blocks: int, block_size: int, dtype):
        return {"latent": jnp.zeros(
            (num_blocks, block_size, 1, 640 if self.config.kv_lora_rank == 512 else 128),
            dtype)}

    def forward_paged(self, x, positions, cache, block_tables, write_mask):
        from paddle_tpu.ops.pallas import registry as _kreg
        c = self.config
        nh, kvr = c.num_attention_heads, c.kv_lora_rank
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        B, S = x.shape[:2]
        v_ = lambda p: p._value
        pool = cache["latent"]
        bs, wp = pool.shape[1], pool.shape[-1]
        q = jnp.dot(x, v_(self.q_proj)).reshape(B, S, nh, dn + dr)
        kva = jnp.dot(x, v_(self.kv_a_proj))
        lat = KL._rms(kva[..., :kvr], v_(self.kv_a_norm),
                   c.rms_norm_eps).astype(x.dtype)
        row = jnp.concatenate(
            [lat, kva[..., kvr:],
             jnp.zeros((B, S, wp - (c.kv_lora_rank + c.qk_rope_head_dim)), x.dtype)], -1)
        # one latent row per token into its page; masked writes divert
        # to the trash block (0, 0), as the K/V pools' do
        blk = jnp.take_along_axis(block_tables,
                                  (positions // bs).astype(jnp.int32), 1)
        blk = jnp.where(write_mask, blk, 0).reshape(-1)
        off = jnp.where(write_mask, positions % bs, 0).reshape(-1)
        pool = pool.at[blk, off, 0].set(
            row.reshape(B * S, wp).astype(pool.dtype))
        scale = (dn + dr) ** -0.5
        kvb = v_(self.kv_b_proj).reshape(kvr, nh, dn + dv)
        if S > 1:
            # a fresh block, expanded and causal: it attends over
            # itself only, so it has to start its sequence
            fresh = positions[:, 0] == 0
            if not isinstance(fresh, jax.core.Tracer) \
                    and not bool(fresh.all()):
                raise ValueError(
                    "latent attention over a block of tokens takes the "
                    "block from position 0 (no suffix prefill)")
            kv = jnp.einsum("bsc,chd->bshd", lat, kvb).astype(x.dtype)
            k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
                kva[:, :, None, kvr:], (B, S, nh, dr))], -1)
            v = kv[..., dn:]
            # not the flash kernel: it takes ONE head size of 64, 128
            # or 256 for q, k and v, and padded to 256 it hung a v5e
            # once in ~100k calls (PERF.md, PR 27)
            o = KL._attend(q, k, v, scale)
            # under a trace nothing can raise: a row that starts
            # mid-sequence reads NaN, not a plausible wrong answer
            o = jnp.where(fresh[:, None, None, None], o, jnp.nan)
        else:
            # one query per row, absorbed: q~_h = [W_uk_h^T q_nope_h |
            # q_pe_h], all heads over the one latent row per token
            # (K = V = the latent pool; the kernel's own 1/sqrt(width)
            # is undone in the query), o_h = W_uv_h sum p c
            qa = jnp.einsum("bhd,chd->bhc", q[:, 0, :, :dn],
                            kvb[..., :dn], preferred_element_type=jnp.float32)
            qt = jnp.concatenate(
                [qa, q[:, 0, :, dn:].astype(jnp.float32),
                 jnp.zeros((B, nh, wp - (c.kv_lora_rank + c.qk_rope_head_dim)), jnp.float32)], -1)
            qt = (qt * (scale * wp ** 0.5)).astype(pool.dtype)
            ol = _kreg.dispatch("paged_attention", qt[:, None], pool, pool,
                                None, None, block_tables, positions, 1)
            ol = ol.reshape(B, nh, wp)[..., :kvr]
            o = jnp.einsum("bhc,chd->bhd", ol, kvb[..., dn:],
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)[:, None]
        return (jnp.dot(o.reshape(B, S, nh * dv), v_(self.o_proj)),
                {"latent": pool})


def test_the_shared_latent_module_is_the_parents(bench):
    """``LatentAttention(c, None, None)``: the parent's parameters
    under the parent's names, and for a fresh block, a padded batch
    and a run of decode steps the parent's pages bit for bit (a write
    moves data) and the parent's outputs to 1e-5 of their largest value
    in float32 (eagerly and under ``jit``): a few roundings, so another
    order of the softmax's sums passes and bfloat16 in place of float32
    (4e-3 of it) does not.  Today the outputs are bit-equal too."""
    model, _ = build(bench, toy_cfg())
    attn = model.model.layers[3].self_attn
    assert type(attn) is KL.LatentAttention
    assert (attn.q_lora_rank, attn.rope_theta) == (None, None)
    old = _ParentLatentAttention(model.config)
    names = ("q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj")
    assert [n for n, _ in attn.named_parameters()] == list(names) \
        == [n for n, _ in old.named_parameters()]
    for n in names:
        assert getattr(old, n)._value.shape == getattr(attn, n)._value.shape
        getattr(old, n)._value = getattr(attn, n)._value
    r = np.random.RandomState(8)
    x = jnp.asarray(r.randn(2, 11, 64), jnp.float32)
    tbl = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)
    pos = jnp.broadcast_to(jnp.arange(11, dtype=jnp.int32), (2, 11))
    wm = jnp.asarray(np.arange(11)[None] < np.asarray([11, 7])[:, None])
    same = lambda a, b: jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda u, v: bool((u == v).all()), a, b))

    def close(got, want):
        top = float(jnp.abs(want[0]).max())
        np.testing.assert_allclose(got[0], want[0], rtol=0, atol=1e-5 * top)
        return top > 0.01 and same(got[1], want[1])
    for run in (lambda f: f, jax.jit):
        cache = attn.init_cache(9, 4, jnp.float32)
        assert same(cache, old.init_cache(9, 4, jnp.float32))
        got = run(attn.forward_paged)(x, pos, cache, tbl, wm)
        want = run(old.forward_paged)(x, pos, cache, tbl, wm)
        assert close(got, want)
        cache = got[1]
        for t in (11, 12):
            step = (x[:, t - 11:t - 10], jnp.full((2, 1), t, jnp.int32),
                    cache, tbl, jnp.ones((2, 1), bool))
            got = run(attn.forward_paged)(*step)
            assert close(got, run(old.forward_paged)(*step))
            cache = got[1]


def test_the_latent_layers_shapes_at_the_published_widths():
    """The Kimi cell's latent layers keep their parameter names and
    shapes, their pages keep 640 lanes, and a hybrid still says it
    keeps per-slot state (so the server refuses on that ground first);
    a model of latent layers alone says it does not, and that its
    prefill starts sequences only."""
    from paddle_tpu.framework.core import abstract_init
    from paddle_tpu.text.models import KimiLinearConfig, kimi_linear_tiny
    with abstract_init():
        attn = KL.LatentAttention(KimiLinearConfig(), None, None)
    assert {n: tuple(p._value.shape)
            for n, p in attn.named_parameters()} == {
        "q_proj": (2304, 32 * 192), "kv_a_proj": (2304, 576),
        "kv_a_norm": (512,), "kv_b_proj": (512, 32 * 256),
        "o_proj": (32 * 128, 2304)}
    assert (attn.latent_width, attn.page_width) == (576, 640)
    shape = jax.eval_shape(
        lambda: attn.init_cache(5, 16, jnp.bfloat16))["latent"]
    assert (shape.shape, shape.dtype) == ((5, 16, 1, 640), jnp.bfloat16)
    hybrid = KimiLinearForCausalLM(kimi_linear_tiny())
    assert hybrid.has_recurrent_state()
    latent = KimiLinearForCausalLM(kimi_linear_tiny(
        full_attn_layers=(1, 2, 3, 4)))
    assert not latent.has_recurrent_state()
    assert latent.prefill_starts_sequences_only()
    assert [set(d) for d in latent.init_paged_cache(9, 4)] == \
        [{"latent"}] * 4
