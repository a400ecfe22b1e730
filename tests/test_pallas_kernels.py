"""Pallas kernel tier (ISSUE 13): registry dispatch + interpret-mode
parity suite.

Every kernel's parity test runs the Pallas INTERPRETER against the
registered XLA reference — the tolerance asserted here is the one
documented on the registration (and in the README table):

- ``opt_apply``          bit-exact (np.array_equal), plus bit-exact
                         shard/world invariance (the PR 9 contract)
- ``int8_matmul``        dynamic path bit-exact; weight-only within
                         rtol 2e-2 @ bf16 / 1e-5 @ f32
- ``int8_kv_attention``  atol 2e-5 / rtol 1e-4 (online softmax)
- ``paged_attention``    atol 2e-5 / rtol 1e-4 on f32 (online softmax);
  bit-identical across physical block ids and batch neighbours
- ``paged_latent_attention``  the same, over one latent pool
- ``segment_sum``        bit-exact for integer-valued grads, atol 1e-6
                         for arbitrary floats
- ``flash_attention``    compat re-export + dispatch counters (numeric
                         parity lives in test_flash_attention.py)

Plus: dispatch counters prove which path ran and appear on /metrics,
jitted dispatch never retraces in steady state, and the int8-KV llama
path keeps its default (xla_ref) route on CPU so PR 11's replay /
prefix-sharing bit contracts are untouched.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.pallas import registry as kreg
from paddle_tpu.ops.pallas.opt_apply import (SLOTS, opt_apply_pallas,
                                             opt_apply_ref, pack_hyper)


@pytest.fixture(autouse=True)
def _clean_registry():
    """Mode overrides and counters must never leak across tests (the
    suite runs in shuffled order in tier-1)."""
    yield
    for name in kreg.kernels():
        kreg.set_mode(name, None)
    kreg.reset_dispatch_counts()


def _rand(rng, *shape):
    return rng.standard_normal(shape).astype(np.float32)


# ---------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------

def test_registry_lists_every_kernel_with_tolerance():
    ks = kreg.kernels()
    for name in ("flash_attention", "opt_apply", "int8_matmul",
                 "int8_kv_attention", "paged_attention",
                 "paged_latent_attention", "paged_sparse_attention",
                 "segment_sum",
                 "pull_dequant"):
        assert name in ks, sorted(ks)
        assert ks[name].tolerance, name
        assert callable(ks[name].xla_ref_fn)
        assert callable(ks[name].pallas_fn)


def test_mode_resolution_order(monkeypatch):
    # default off-TPU: xla_ref
    assert kreg.resolve("opt_apply") == "xla_ref"
    # global escape hatch
    monkeypatch.setenv("PADDLE_PALLAS", "0")
    assert kreg.resolve("opt_apply") == "xla_ref"
    # per-kernel env beats the global hatch
    monkeypatch.setenv("PADDLE_PALLAS_OPT_APPLY", "interpret")
    assert kreg.resolve("opt_apply") == "interpret"
    # process-local override beats env
    kreg.set_mode("opt_apply", "xla_ref")
    assert kreg.resolve("opt_apply") == "xla_ref"
    kreg.set_mode("opt_apply", None)
    assert kreg.resolve("opt_apply") == "interpret"
    # junk env value is a typed error, not a silent fallback
    monkeypatch.setenv("PADDLE_PALLAS_OPT_APPLY", "fast")
    with pytest.raises(ValueError):
        kreg.resolve("opt_apply")
    with pytest.raises(ValueError):
        kreg.set_mode("opt_apply", "mosaic")


def test_dispatch_counters_and_unknown_kernel():
    kreg.reset_dispatch_counts()
    rng = np.random.default_rng(0)
    p, g = _rand(rng, 100), _rand(rng, 100)
    hy = pack_hyper("sgd", lr=0.1)
    kreg.dispatch("opt_apply", "sgd", p, g, (), hy)
    kreg.set_mode("opt_apply", "interpret")
    kreg.dispatch("opt_apply", "sgd", p, g, (), hy)
    c = kreg.dispatch_counts("opt_apply")
    assert c == {"xla_ref": 1, "interpret": 1}, c
    with pytest.raises(KeyError):
        kreg.dispatch("warp_drive", p)


def test_dispatch_counters_on_metrics_endpoint():
    """The trace pass contract: kernel-dispatch counters surface as
    the labeled ``pallas_dispatch{kernel=,path=}`` family in the
    Prometheus exposition (always-on, like every rare-event counter)."""
    from paddle_tpu.observability.metrics import prometheus_text
    rng = np.random.default_rng(0)
    hy = pack_hyper("sgd", lr=0.1)
    kreg.dispatch("opt_apply", "sgd", _rand(rng, 64), _rand(rng, 64),
                  (), hy)
    text = prometheus_text()
    assert "pallas_dispatch{" in text
    line = [ln for ln in text.splitlines()
            if "pallas_dispatch{" in ln
            and 'kernel="opt_apply"' in ln and 'path="xla_ref"' in ln]
    assert line, text[:2000]


def test_no_steady_state_retrace_through_dispatch():
    """num_compiles-style assertion: a jitted caller that routes
    through the registry compiles ONCE for a shape and never again —
    and the python-side dispatch counter (which ticks per trace under
    jit) stays flat across steady-state calls."""
    kreg.set_mode("segment_sum", "interpret")
    kreg.reset_dispatch_counts()
    traces = []

    @jax.jit
    def step(g, inv):
        traces.append(1)
        return kreg.dispatch("segment_sum", g, inv, num_segments=8)

    rng = np.random.default_rng(0)
    g = jnp.asarray(_rand(rng, 32, 4))
    inv = jnp.asarray(rng.integers(0, 8, 32), jnp.int32)
    outs = [np.asarray(step(g, inv)) for _ in range(5)]
    assert len(traces) == 1
    assert kreg.dispatch_counts("segment_sum") == {"interpret": 1}
    for o in outs[1:]:
        assert np.array_equal(o, outs[0])


# ---------------------------------------------------------------------
# kernel 1: fused optimizer-apply (bit-exact contract)
# ---------------------------------------------------------------------

def _assert_adam_within_one_rounding(p, g, slots, hy, ref, ker):
    """Adam's moments are sums of two products (``b1*m + (1-b1)*g``),
    and XLA:CPU contracts ONE product of such a sum into the add, which
    one being the code generator's choice: it chose differently for
    jit(ref) and for the interpreted kernel's loop body under JAX 0.9.
    Either way a moment is off the exact sum by at most one rounding of
    its operands, eps * (|b1*m| + |(1-b1)*g|): under cancellation that
    is many ulps of the RESULT, so the bound is on the operands.  ``p``
    is held to each route's own moments."""
    eps = float(np.finfo(np.float32).eps)
    lr, b1, b2, e, c1, c2, _mu, omb1, omb2 = hy[0].astype(np.float64)
    p, g, m, v = (np.asarray(a, np.float64) for a in (p, g) + slots)
    ref, ker = ([np.asarray(a, np.float64) for a in r] for r in (ref, ker))
    tol_m = 2 * eps * (np.abs(b1 * m) + np.abs(omb1 * g))
    tol_v = 2 * eps * (np.abs(b2 * v) + np.abs(omb2 * g * g))
    assert (np.abs(ref[1] - ker[1]) <= tol_m).all()
    assert (np.abs(ref[2] - ker[2]) <= tol_v).all()
    for p_n, m_n, v_n in (ref, ker):
        u = lr * (m_n / c1) / (np.sqrt(v_n / c2) + e)
        assert (np.abs(p_n - (p - u))
                <= 4 * eps * (np.abs(p) + np.abs(u))).all()


@pytest.mark.parametrize("kind", ["sgd", "momentum", "adam"])
def test_opt_apply_interpret_bit_exact_vs_ref(kind):
    """Parity is pinned between the two COMPILED routes — jit(ref) vs
    jit(kernel) — the discipline every real caller uses
    (fused_optimizer_apply jits its dispatch).  Comparing an eager
    op-by-op run against a compiled one would instead measure XLA
    CPU's FMA contraction (see the opt_apply module docstring).
    ``sgd`` and ``momentum`` are BIT-EXACT: each of their sums holds one
    product, so there is one way to contract it.  ``adam`` is held to
    one rounding of its sums' operands (the helper above says why)."""
    rng = np.random.default_rng(3)
    n = 4097                       # deliberately not tile-aligned
    p, g = _rand(rng, n), _rand(rng, n)
    # second-moment-style slots stay nonnegative (sqrt domain)
    slots = tuple(np.abs(_rand(rng, n)) * 0.1 for _ in SLOTS[kind])
    hy = pack_hyper(kind, lr=0.01, t=7)
    ref = jax.jit(lambda *a: opt_apply_ref(kind, *a))(
        jnp.asarray(p), jnp.asarray(g), tuple(map(jnp.asarray, slots)),
        jnp.asarray(hy))
    ker = jax.jit(lambda *a: opt_apply_pallas(kind, *a,
                                              interpret=True))(
        jnp.asarray(p), jnp.asarray(g), tuple(map(jnp.asarray, slots)),
        jnp.asarray(hy))
    assert len(ref) == len(ker) == 1 + len(SLOTS[kind])
    for k in ker:
        assert np.isfinite(np.asarray(k)).all()
    if kind == "adam":
        _assert_adam_within_one_rounding(p, g, slots, hy, ref, ker)
        return
    for r, k in zip(ref, ker):
        assert np.array_equal(np.asarray(r), np.asarray(k))


@pytest.mark.parametrize("kind", ["sgd", "adam"])
def test_opt_apply_shard_invariance_bit_exact(kind):
    """The PR 9 world-invariance contract on the kernel itself: the
    update of a shard equals the same slice of the full update, for
    arbitrary (offset, length) — zero-padding can never leak in."""
    rng = np.random.default_rng(4)
    n = 10001
    p, g = _rand(rng, n), _rand(rng, n)
    slots = tuple(np.abs(_rand(rng, n)) * 0.01 for _ in SLOTS[kind])
    hy = pack_hyper(kind, lr=0.003, t=5)
    full = opt_apply_pallas(kind, jnp.asarray(p), jnp.asarray(g),
                            tuple(map(jnp.asarray, slots)), hy,
                            interpret=True)
    for lo, hi in ((0, n), (1, 128), (1003, 9001), (n - 257, n)):
        shard = opt_apply_pallas(
            kind, jnp.asarray(p[lo:hi]), jnp.asarray(g[lo:hi]),
            tuple(jnp.asarray(s[lo:hi]) for s in slots), hy,
            interpret=True)
        for f, s in zip(full, shard):
            assert np.array_equal(np.asarray(f)[lo:hi], np.asarray(s)), \
                (kind, lo, hi)


def test_fused_elastic_engine_world_invariant_and_near_host():
    """``_FlatAdam(fused=True)`` (the dist_step.fused_optimizer_apply
    route): a 2-shard world's updates concat bit-exactly to the
    1-world update across steps (the reshard contract WITHIN the fused
    engine), and the fused trajectory tracks the host-numpy engine
    within the documented FMA-contraction envelope."""
    from paddle_tpu.distributed.fleet.elastic import _FlatAdam

    rng = np.random.default_rng(5)
    n = 6000
    cut = 2471
    p0 = _rand(rng, n)
    grads = [_rand(rng, n) for _ in range(3)]

    def mk(sz):
        o = _FlatAdam(0.01, fused=True)
        o.m = np.zeros(sz, np.float32)
        o.v = np.zeros(sz, np.float32)
        return o

    full, pf = mk(n), p0.copy()
    a, pa = mk(cut), p0[:cut].copy()
    b, pb = mk(n - cut), p0[cut:].copy()
    for g in grads:
        pf = full.update(pf, g)
        pa = a.update(pa, g[:cut])
        pb = b.update(pb, g[cut:])
    assert np.array_equal(pf, np.concatenate([pa, pb]))
    assert np.array_equal(full.m, np.concatenate([a.m, b.m]))

    host, ph = _FlatAdam(0.01, fused=False), p0.copy()
    host.m = np.zeros(n, np.float32)
    host.v = np.zeros(n, np.float32)
    for g in grads:
        ph = host.update(ph, g)
    # engines agree up to XLA-CPU FMA contraction (~1 ulp per mul+add,
    # amplified through adam's rsqrt) — documented in ops/pallas/
    # opt_apply.py; bit-contracts hold WITHIN an engine, never across
    np.testing.assert_allclose(ph, pf, atol=5e-6, rtol=5e-3)


def test_fused_optimizer_apply_jit_cache_is_step_invariant():
    """t changes every step but c1/c2 ride in the hyper ARGUMENT — the
    jit cache must not grow across steps (no steady-state retrace)."""
    from paddle_tpu.distributed.fleet.dist_step import (
        _FUSED_APPLY_CACHE, fused_optimizer_apply)

    rng = np.random.default_rng(6)
    n = 512
    p, g = _rand(rng, n), _rand(rng, n)
    slots = {"m": np.zeros(n, np.float32), "v": np.zeros(n, np.float32)}
    fused_optimizer_apply("adam", p, g, slots, t=1, lr=0.01)
    entries = len(_FUSED_APPLY_CACHE)
    for t in range(2, 6):
        p, slots = fused_optimizer_apply("adam", p, g, slots, t=t,
                                         lr=0.01)
    assert len(_FUSED_APPLY_CACHE) == entries
    assert np.isfinite(p).all()


# ---------------------------------------------------------------------
# kernel 2: fused int8 dequant-matmul
# ---------------------------------------------------------------------

def _quantize_w(rng, k, n):
    w = _rand(rng, k, n)
    sc = np.maximum(np.abs(w).max(0) / 127.0, 1e-9).astype(np.float32)
    qw = np.clip(np.round(w / sc), -127, 127).astype(np.int8)
    return w, qw, sc


@pytest.mark.parametrize("cdt", [jnp.float32, jnp.bfloat16])
def test_int8_matmul_dynamic_bit_exact(cdt):
    from paddle_tpu.ops.pallas.int8_matmul import (int8_matmul_pallas,
                                                   int8_matmul_ref)
    rng = np.random.default_rng(7)
    _, qw, sc = _quantize_w(rng, 70, 33)
    xq = rng.integers(-127, 128, (5, 70)).astype(np.int8)
    xs = np.float32(0.013)
    ref = int8_matmul_ref(jnp.asarray(xq), jnp.asarray(qw),
                          jnp.asarray(sc), x_scale=xs,
                          compute_dtype=cdt)
    ker = int8_matmul_pallas(jnp.asarray(xq), jnp.asarray(qw),
                             jnp.asarray(sc), x_scale=xs,
                             compute_dtype=cdt, interpret=True)
    assert ref.dtype == ker.dtype == cdt
    assert np.array_equal(np.asarray(ref, np.float32),
                          np.asarray(ker, np.float32))


def test_int8_matmul_weight_only_tolerance_and_batch_dims():
    from paddle_tpu.ops.pallas.int8_matmul import (int8_matmul_pallas,
                                                   int8_matmul_ref)
    rng = np.random.default_rng(8)
    _, qw, sc = _quantize_w(rng, 96, 40)
    x = _rand(rng, 2, 3, 96)
    ref = int8_matmul_ref(jnp.asarray(x), jnp.asarray(qw),
                          jnp.asarray(sc), compute_dtype=jnp.float32)
    ker = int8_matmul_pallas(jnp.asarray(x), jnp.asarray(qw),
                             jnp.asarray(sc),
                             compute_dtype=jnp.float32, interpret=True)
    assert ker.shape == (2, 3, 40)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               atol=1e-5, rtol=1e-5)
    refb = int8_matmul_ref(jnp.asarray(x), jnp.asarray(qw),
                           jnp.asarray(sc), compute_dtype=jnp.bfloat16)
    kerb = int8_matmul_pallas(jnp.asarray(x), jnp.asarray(qw),
                              jnp.asarray(sc),
                              compute_dtype=jnp.bfloat16,
                              interpret=True)
    # bf16 compute: the documented rtol 2e-2, with an atol floor for
    # near-zero outputs (one boundary element observed at 0.031 abs
    # on a 0.42 value — 2 bf16 output-rounding steps)
    np.testing.assert_allclose(np.asarray(refb, np.float32),
                               np.asarray(kerb, np.float32),
                               rtol=2e-2, atol=5e-2)


def test_int8_linear_layer_interpret_matches_ref_bit_exact():
    from paddle_tpu.nn import Linear
    from paddle_tpu.quantization import Int8InferenceLinear

    paddle.seed(0)
    lin = Linear(24, 12)
    lay = Int8InferenceLinear(lin, compute_dtype=jnp.float32)
    x = np.random.default_rng(9).standard_normal((6, 24)) \
        .astype(np.float32)
    kreg.set_mode("int8_matmul", "xla_ref")
    ref = np.asarray(lay(paddle.to_tensor(x))._value)
    kreg.set_mode("int8_matmul", "interpret")
    got = np.asarray(lay(paddle.to_tensor(x))._value)
    # dynamic path: int32 accumulation — identical bits either route
    assert np.array_equal(ref, got)
    c = kreg.dispatch_counts("int8_matmul")
    assert c.get("xla_ref", 0) >= 1 and c.get("interpret", 0) >= 1, c


# ---------------------------------------------------------------------
# Int8InferenceConv2D promotion (satellite 1)
# ---------------------------------------------------------------------

def _conv_pair(rng, fmt="NCHW", bias=True, stride=1, padding=1):
    from paddle_tpu.nn import Conv2D
    conv = Conv2D(3, 5, 3, stride=stride, padding=padding,
                  data_format=fmt, bias_attr=bias)
    x = rng.standard_normal(
        (2, 3, 8, 8) if fmt == "NCHW" else (2, 8, 8, 3)
    ).astype(np.float32)
    return conv, x


@pytest.mark.parametrize("fmt", ["NCHW", "NHWC"])
def test_int8_conv_fused_bit_exact_vs_xla_int8(fmt):
    """The fused patches->int8-matmul path is BIT-EXACT vs the XLA
    int8 conv (same integer sums, same f32 rescale)."""
    from paddle_tpu.quantization import Int8InferenceConv2D

    paddle.seed(1)
    rng = np.random.default_rng(10)
    conv, x = _conv_pair(rng, fmt=fmt, stride=2)
    lay = Int8InferenceConv2D(conv, compute_dtype=jnp.float32)
    kreg.set_mode("int8_matmul", "xla_ref")
    ref = np.asarray(lay(paddle.to_tensor(x))._value)
    kreg.set_mode("int8_matmul", "interpret")
    got = np.asarray(lay(paddle.to_tensor(x))._value)
    assert ref.shape == got.shape
    assert np.array_equal(ref, got), np.abs(ref - got).max()


def test_int8_conv_quantization_error_bound():
    """Typed error-bound contract on the fused path: against the f32
    convolution, the int8 result's error is bounded by the rounding
    model |err| <= 0.5*xs*sum|w| + 0.5*|sc|*sum|x_patch| + K/4*xs*sc
    per output element (x = xs*xq + ex with |ex| <= xs/2, likewise w)."""
    from paddle_tpu.nn import Conv2D
    import paddle_tpu.nn.functional as F
    from paddle_tpu.quantization import Int8InferenceConv2D

    paddle.seed(2)
    rng = np.random.default_rng(11)
    conv, x = _conv_pair(rng, bias=False)
    w = np.asarray(conv.weight._value)
    ref = np.asarray(F.conv2d(paddle.to_tensor(x), conv.weight, None,
                              1, 1, 1, 1, "NCHW")._value)
    lay = Int8InferenceConv2D(conv, compute_dtype=jnp.float32)
    kreg.set_mode("int8_matmul", "interpret")
    got = np.asarray(lay(paddle.to_tensor(x))._value)
    xs = max(np.abs(x).max() / 127.0, 1e-9 / 127.0)
    sc = np.asarray(lay.w_scale._value)                   # [out]
    k_el = w[0].size                                      # in*kh*kw
    # conservative per-channel bound: patch magnitudes <= max|x|
    bound = (0.5 * xs * np.abs(w).sum(axis=(1, 2, 3))
             + 0.5 * sc * k_el * np.abs(x).max()
             + 0.25 * k_el * xs * sc)
    err = np.abs(got - ref).max(axis=(0, 2, 3))           # per channel
    assert (err <= bound * 1.01 + 1e-6).all(), (err, bound)
    # and the bound is TIGHT enough to be meaningful: well under the
    # signal scale
    assert err.max() < 0.15 * np.abs(ref).max()


def test_int8_conv_typed_config_validation():
    from paddle_tpu.nn import Conv2D, Linear
    from paddle_tpu.quantization import Int8InferenceConv2D

    paddle.seed(3)
    with pytest.raises(TypeError):
        Int8InferenceConv2D(Linear(4, 4))
    conv = Conv2D(2, 2, 3)
    with pytest.raises(TypeError):
        Int8InferenceConv2D(conv, compute_dtype=jnp.int8)
    with pytest.raises(ValueError):
        Int8InferenceConv2D(conv, act_quant="static")
    # promoted: the docstring no longer carries the EXPERIMENTAL flag
    assert "EXPERIMENTAL —" not in Int8InferenceConv2D.__doc__
    assert "promoted out of EXPERIMENTAL" in Int8InferenceConv2D.__doc__


# ---------------------------------------------------------------------
# kernel 3: fused int8-KV dequant-attention
# ---------------------------------------------------------------------

def _kv_case(rng, B=2, S=1, G=2, R=2, D=16, bs=8, M=4, nb=9):
    qh = _rand(rng, B, S, G * R, D)
    kpool = rng.integers(-127, 128, (nb, bs, G, D)).astype(np.int8)
    vpool = rng.integers(-127, 128, (nb, bs, G, D)).astype(np.int8)
    ks = (rng.random((nb, bs)) * 0.01 + 1e-3).astype(np.float32)
    vs = (rng.random((nb, bs)) * 0.01 + 1e-3).astype(np.float32)
    tbl = rng.integers(1, nb, (B, M)).astype(np.int32)
    pos = rng.integers(0, bs * M, (B, S)).astype(np.int32)
    pos.sort(axis=1)
    return [jnp.asarray(a) for a in
            (qh, kpool, vpool, ks, vs, tbl, pos)], G


@pytest.mark.parametrize("shape", [
    dict(),                                   # decode S=1, GQA
    dict(S=4, M=6),                           # verify block S>1
    dict(G=4, R=1, D=8, bs=4),                # MHA, tiny head
])
def test_kv_attention_interpret_parity(shape):
    from paddle_tpu.ops.pallas.kv_attention import (int8_paged_attention,
                                                    paged_attention_ref)
    rng = np.random.default_rng(12)
    args, G = _kv_case(rng, **shape)
    ref = paged_attention_ref(*args, G)
    ker = int8_paged_attention(*args, G, interpret=True)
    assert ref.shape == ker.shape
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               atol=2e-5, rtol=1e-4)


def test_kv_attention_trash_blocks_and_low_positions():
    """Table entries pointing at the trash block (0) and positions
    inside the first block: every beyond-position slot must contribute
    exactly nothing (the fully-masked-block pitfall)."""
    from paddle_tpu.ops.pallas.kv_attention import (int8_paged_attention,
                                                    paged_attention_ref)
    rng = np.random.default_rng(13)
    args, G = _kv_case(rng, B=2, S=1, M=4, bs=8)
    qh, kp, vp, ks, vs, tbl, _ = args
    tbl = jnp.asarray(np.array([[3, 0, 0, 0], [5, 6, 0, 0]],
                               np.int32))
    pos = jnp.asarray(np.array([[2], [11]], np.int32))
    ref = paged_attention_ref(qh, kp, vp, ks, vs, tbl, pos, G)
    ker = int8_paged_attention(qh, kp, vp, ks, vs, tbl, pos, G,
                               interpret=True)
    assert np.isfinite(np.asarray(ker)).all()
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               atol=2e-5, rtol=1e-4)


def _tiny_int8_llama():
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny
    paddle.seed(4)
    cfg = llama_tiny(vocab_size=64, hidden_size=32,
                     intermediate_size=64, num_hidden_layers=2,
                     num_attention_heads=4, num_key_value_heads=2,
                     max_position_embeddings=64,
                     kv_cache_dtype="int8")
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _paged_decode(m, mode):
    from paddle_tpu.framework.core import Tensor, no_grad
    kreg.set_mode("int8_kv_attention", mode)
    try:
        pools = m.init_paged_cache(16, 4)
        tbl = np.arange(1, 9, dtype=np.int32)[None, :]
        rng = np.random.RandomState(0)
        p = rng.randint(1, 64, (7,)).astype(np.int32)
        ids = np.zeros((1, 8), np.int32)
        ids[0, :7] = p
        pos = np.arange(8, dtype=np.int32)[None, :]
        wm = np.zeros((1, 8), bool)
        wm[0, :7] = True
        with no_grad():
            lg, pools = m.forward_paged(
                Tensor(ids), Tensor(pos), pools, tbl, wm,
                gather_at=np.asarray([6], np.int32))
        outs = [np.asarray(lg._value if isinstance(lg, Tensor) else lg)]
        tok = int(np.argmax(outs[0][0, 0]))
        for j in range(3):
            with no_grad():
                lg, pools = m.forward_paged(
                    Tensor(np.asarray([[tok]], np.int32)),
                    Tensor(np.asarray([[7 + j]], np.int32)),
                    pools, tbl, np.ones((1, 1), bool))
            outs.append(np.asarray(
                lg._value if isinstance(lg, Tensor) else lg))
            tok = int(np.argmax(outs[-1][0, 0]))
        return outs
    finally:
        kreg.set_mode("int8_kv_attention", None)


def test_llama_int8_paged_decode_kernel_parity():
    """End-to-end through ``LlamaAttention.forward_paged``: decode
    logits with the fused kernel (interpret) track the xla_ref path
    within the documented tolerance, and the dispatch counters name
    the routes taken."""
    m = _tiny_int8_llama()
    kreg.reset_dispatch_counts()
    ref = _paged_decode(m, "xla_ref")
    got = _paged_decode(m, "interpret")
    for r, g in zip(ref, got):
        np.testing.assert_allclose(g, r, atol=5e-4, rtol=1e-3)
    c = kreg.dispatch_counts("int8_kv_attention")
    assert c.get("xla_ref", 0) >= 1 and c.get("interpret", 0) >= 1, c


def test_llama_int8_default_route_is_xla_ref_on_cpu():
    """PR 11's replay/prefix-sharing bit contracts are pinned on the
    NON-pallas path: on the CPU backend the default route must be the
    byte-identical XLA reference (pallas only via explicit opt-in)."""
    assert jax.default_backend() != "tpu"
    assert kreg.resolve("int8_kv_attention") == "xla_ref"
    m = _tiny_int8_llama()
    kreg.reset_dispatch_counts()
    outs = _paged_decode(m, "xla_ref")
    c = kreg.dispatch_counts("int8_kv_attention")
    assert set(c) == {"xla_ref"} and c["xla_ref"] >= 1, c
    assert all(np.isfinite(o).all() for o in outs)


# ---------------------------------------------------------------------
# kernel 3b: bf16 paged decode attention through the block table
# ---------------------------------------------------------------------

def _paged_case(rng, lengths, G=2, R=2, D=16, bs=8, M=5, dtype=np.float32,
                tables=None, flat_pool=False):
    """A decode batch: row i holds ``lengths[i]`` positions on its own
    physical blocks, its table padded with the trash block (0); the
    trash block and every unused block hold noise a correct kernel
    never weighs."""
    B = len(lengths)
    nb = 1 + B * M
    qh = _rand(rng, B, 1, G * R, D)
    kpool = _rand(rng, nb, bs, G, D)
    vpool = _rand(rng, nb, bs, G, D)
    if flat_pool:           # a narrow head's pool as its model keeps it
        kpool, vpool = (x.reshape(nb, bs, G * D) for x in (kpool, vpool))
    tbl = np.zeros((B, M), np.int32)
    for i, n in enumerate(lengths):
        used = -(-n // bs)
        tbl[i, :used] = 1 + i * M + rng.permutation(M)[:used]
    if tables == "trash_last":      # the last row an inactive slot's
        tbl[-1] = 0
    elif tables is not None:
        tbl = np.asarray(tables, np.int32)
    pos = np.asarray(lengths, np.int32)[:, None] - 1
    return [jnp.asarray(qh, dtype), jnp.asarray(kpool, dtype),
            jnp.asarray(vpool, dtype), None, None, jnp.asarray(tbl),
            jnp.asarray(pos)], G


_PAGED_CASES = {
    # lengths 1, bs-1, bs, bs+1 and the full table, small shape
    "small_ragged": dict(lengths=[1, 7, 8, 9, 40]),
    # GQA 32/8 x 128, block 16, bf16 pools as served (the hi+lo p.v)
    "gqa32x8x128_block16_bf16": dict(
        lengths=[1, 15, 16, 17, 64], G=8, R=4, D=128, bs=16, M=4,
        dtype=jnp.bfloat16),
    "gqa32x8x128_block16_f32": dict(
        lengths=[33, 64, 2], G=8, R=4, D=128, bs=16, M=4),
    # more pages than one grid-step block holds, the last block partial
    "three_blocks_of_pages": dict(lengths=[160, 67, 129, 1], bs=4, M=40),
    "mha_tiny_head": dict(lengths=[5, 12], G=4, R=1, D=8, bs=4, M=3),
    # GQA 32/8 x 64 (PR 33): the wide page, (16, 512), pool kept 3-D
    "gqa32x8x64_block16_bf16": dict(
        lengths=[1, 15, 16, 17, 64], G=8, R=4, D=64, bs=16, M=4,
        dtype=jnp.bfloat16, flat_pool=True),
    "gqa32x8x64_block16_f32": dict(
        lengths=[33, 64, 2], G=8, R=4, D=64, bs=16, M=4),
    # wide pages, more of them than one grid-step block holds
    "wide_three_blocks_of_pages": dict(
        lengths=[160, 67, 129, 1], G=2, R=2, D=64, bs=4, M=40,
        flat_pool=True),
    # 128 query heads over ONE latent "kv head" of 640 lanes (PR 35:
    # the Pangu Ultra MoE cell's absorbed decode, K = V = the pool)
    "latent128x1x640_block16_bf16": dict(
        lengths=[1, 16, 17, 300], G=1, R=128, D=640, bs=16, M=20,
        dtype=jnp.bfloat16),
    "latent128x1x640_block16_f32": dict(
        lengths=[33, 2], G=1, R=128, D=640, bs=16, M=4),
    # the latent kernel: one pool, K its 640 lanes and V the
    # first 512, at the two latent cells' head counts; lengths 1, a page
    # boundary (16, 32), one past it, more than one grid-step block, and
    # a row of the trash block (position 0, an all-zero table)
    "latent_kernel128x640v512_block16_bf16": dict(
        lengths=[1, 16, 17, 300, 1], G=1, R=128, D=640, bs=16, M=20,
        dtype=jnp.bfloat16, value_width=512,
        tables="trash_last"),
    "latent_kernel128x640v512_block16_f32": dict(
        lengths=[33, 32, 1, 2], G=1, R=128, D=640, bs=16, M=4,
        value_width=512, tables="trash_last"),
    "latent_kernel32x640v512_block16_bf16": dict(
        lengths=[1, 32, 260, 15, 1], G=1, R=32, D=640, bs=16, M=20,
        dtype=jnp.bfloat16, value_width=512, tables="trash_last"),
    "latent_kernel32x640v512_block16_f32": dict(
        lengths=[48, 1, 17, 1], G=1, R=32, D=640, bs=16, M=4,
        value_width=512, tables="trash_last"),
    # slots with the write mask off: position 0, an all-zero table
    "inactive_rows_zero_table": dict(
        lengths=[1, 11, 1], bs=8, M=2,
        tables=[[0, 0], [3, 4], [0, 0]]),
}


_LATENT_SCALE = (128 + 64) ** -0.5


def _paged_decode_pair(args, G, value_width=None, interpret=True):
    """(kernel, reference) of one decode batch: ``paged_attention``, or
    with ``value_width`` the latent kernel over ``args``' K pool."""
    from paddle_tpu.ops.pallas import kv_attention as kv
    if value_width is None:
        return (kv.paged_attention(*args, G, interpret=interpret),
                kv.paged_attention_ref(*args, G))
    qh, pool, _, _, _, tbl, pos = args
    call = (qh, pool, tbl, pos, value_width, _LATENT_SCALE)
    return (kv.paged_latent_attention(*call, interpret=interpret),
            kv.paged_latent_attention_ref(*call))


@pytest.mark.parametrize("case", sorted(_PAGED_CASES))
def test_paged_attention_interpret_parity(case):
    """The decode kernels against their references (the latent kernel's
    is ``paged_attention_ref`` over its one pool): atol 2e-5 / rtol
    1e-4 on f32 inputs (online softmax re-associates the f32 sums, as
    for ``int8_kv_attention``); a bf16 case rounds both results to bf16
    last, so they may sit one bf16 ulp apart."""
    kw = dict(_PAGED_CASES[case])
    vw = kw.pop("value_width", None)
    args, G = _paged_case(np.random.default_rng(21), **kw)
    ker, ref = _paged_decode_pair(args, G, vw)
    ref = np.asarray(ref.astype(jnp.float32))
    assert ker.shape == ref.shape and ker.dtype == args[0].dtype
    ker = np.asarray(ker.astype(jnp.float32))
    assert np.isfinite(ker).all()
    if args[0].dtype == jnp.bfloat16:
        np.testing.assert_allclose(ker, ref, atol=2 ** -9, rtol=2 ** -7)
    else:
        np.testing.assert_allclose(ker, ref, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype,D,R", [
    pytest.param("float32", 16, 2, id="float32-16"),
    pytest.param("bfloat16", 16, 2, id="bfloat16-16"),
    pytest.param("bfloat16", 64, 2, id="bfloat16-64"),
    # the latent kernel: 128 and 32 heads over one pool
    pytest.param("bfloat16", 640, 128, id="latent-bfloat16-128"),
    pytest.param("float32", 640, 32, id="latent-float32-32")])
def test_paged_attention_ignores_block_ids_and_neighbours(dtype, D, R):
    """The eviction / replay contract: the same logical sequence gives
    BIT-identical output on permuted physical blocks, in another slot,
    beside other rows (blocks are reduced in logical order; what a
    buffer holds past a row's length weighs exactly zero)."""
    rng = np.random.default_rng(22)
    bs, M, n = 4, 40, 150                 # three blocks of pages
    G, vw = (1, 512) if D == 640 else (2, None)
    (qh, kp, vp, _, _, tbl, pos), G = _paged_case(
        rng, [n, 9, 160], G=G, R=R, bs=bs, M=M, D=D,
        dtype=jnp.dtype(dtype))

    def kernel(*args):
        return np.asarray(_paged_decode_pair(args, G, vw)[0]
                          .astype(jnp.float32))
    a = kernel(qh, kp, vp, None, None, tbl, pos)
    # row 0's sequence moves to permuted blocks of a fresh pool (the
    # rest of it other noise), into slot 2, beside two other rows
    used = -(-n // bs)
    nb = kp.shape[0]
    new_ids = 1 + rng.permutation(nb - 1)[:used]
    kp2 = np.array(_rand(rng, *kp.shape))
    vp2 = np.array(_rand(rng, *vp.shape))
    old_ids = np.asarray(tbl)[0, :used]
    kp2[new_ids] = np.asarray(kp.astype(jnp.float32))[old_ids]
    vp2[new_ids] = np.asarray(vp.astype(jnp.float32))[old_ids]
    tbl2 = np.zeros((3, M), np.int32)
    tbl2[2, :used] = new_ids
    tbl2[0, :1] = new_ids[:1]
    tbl2[1, :M] = rng.integers(1, nb, M)
    pos2 = np.asarray([[2], [M * bs - 1], [n - 1]], np.int32)
    qh2 = np.array(_rand(rng, *qh.shape))
    qh2[2] = np.asarray(qh.astype(jnp.float32))[0]
    b = kernel(
        jnp.asarray(qh2, qh.dtype), jnp.asarray(kp2, kp.dtype),
        jnp.asarray(vp2, vp.dtype), None, None, jnp.asarray(tbl2),
        jnp.asarray(pos2))
    assert np.array_equal(a[0], b[2])


def _sparse_case(rng, lengths, KH=2, R=4, D=16, bs=8, ns=5, nb=60,
                 dtype=np.float32):
    """A block-sparse decode batch: rows at ``lengths`` (position =
    length - 1), each kv head selecting page 0, its own partial page,
    the one before it and up to ``ns - 3`` others at random, in
    logical order, on scattered physical pages; the lists padded with
    the trash page and a zero count."""
    B = len(lengths)
    kp = jnp.asarray(_rand(rng, nb, KH, bs, D), dtype)
    vp = jnp.asarray(_rand(rng, nb, KH, bs, D), dtype)
    q = jnp.asarray(_rand(rng, B, 1, KH * R, D), dtype)
    pages = np.zeros((B, KH, ns), np.int32)
    logical = np.zeros((B, KH, ns), np.int32)
    counts = np.zeros((B, KH), np.int32)
    phys = rng.permutation(np.arange(1, nb))
    for b, n in enumerate(lengths):
        bt = (n - 1) // bs
        table = phys[b * 7:b * 7 + bt + 1] if bt < 7 else \
            rng.choice(np.arange(1, nb), bt + 1, replace=False)
        for g in range(KH):
            far = np.arange(1, max(bt - 1, 1))
            pick = rng.permutation(far)[:ns - 3] if len(far) else far
            sel = sorted({0, max(bt - 1, 0), bt} | set(pick.tolist()))
            counts[b, g] = len(sel)
            logical[b, g, :len(sel)] = sel
            pages[b, g, :len(sel)] = table[sel]
    pos = np.asarray(lengths, np.int32)[:, None] - 1
    return (q, kp, vp, jnp.asarray(pages), jnp.asarray(logical),
            jnp.asarray(counts), jnp.asarray(pos))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_sparse_attention_interpret_parity(dtype):
    """The block-sparse decode kernel against its reference: ragged
    lengths (a row inside page 0, rows on and one past a page
    boundary, long rows whose lists are full), lists padded with the
    trash page, each row reading its own partial page.  atol 2e-5 /
    rtol 1e-4 on f32 inputs (the f32 softmax sums in another order;
    p . v in bf16 hi + lo halves drops under 2**-17 of p); a bf16 case
    rounds both results to bf16 last, one bf16 ulp apart at most."""
    from paddle_tpu.ops.pallas import kv_attention as kv
    args = _sparse_case(np.random.default_rng(31), [3, 8, 9, 17, 120, 60],
                        dtype=jnp.dtype(dtype))
    assert int(args[5].min()) >= 1 and int(args[5].max()) == 5
    ker = kv.paged_sparse_attention(*args, interpret=True)
    ref = kv.paged_sparse_attention_ref(*args)
    assert ker.shape == ref.shape == args[0].shape
    assert ker.dtype == args[0].dtype
    ker = np.asarray(ker.astype(jnp.float32))
    ref = np.asarray(ref.astype(jnp.float32))
    assert np.isfinite(ker).all()
    if dtype == "bfloat16":
        np.testing.assert_allclose(ker, ref, atol=2 ** -9, rtol=2 ** -7)
    else:
        np.testing.assert_allclose(ker, ref, atol=2e-5, rtol=1e-4)


def test_paged_sparse_attention_reads_only_the_listed_pages():
    """What the list leaves out is never read: pages not listed, the
    padding past the count and positions past the row's own may hold
    anything finite, and the result is BIT-identical; the same pages
    moved to other physical ids, or the row beside other rows, too."""
    from paddle_tpu.ops.pallas import kv_attention as kv
    rng = np.random.default_rng(32)
    q, kp, vp, pages, logical, counts, pos = _sparse_case(
        rng, [45, 77, 5])
    run = lambda *a: np.asarray(kv.paged_sparse_attention(
        *a, interpret=True))
    base = run(q, kp, vp, pages, logical, counts, pos)
    listed = np.zeros(kp.shape[:2], bool)
    for b in range(3):
        for g in range(2):
            listed[np.asarray(pages[b, g, :counts[b, g]]), g] = True
    noise = jnp.asarray(_rand(rng, *kp.shape)) * 100
    kp2 = jnp.where(listed[..., None, None], kp, noise)
    vp2 = jnp.where(listed[..., None, None], vp, -noise)
    # the row's own partial page past its position, too
    b, g = 0, 1
    own = int(pages[b, g, counts[b, g] - 1])
    off = int(pos[b, 0]) % 8
    kp2 = kp2.at[own, g, off + 1:].set(1e4)
    np.testing.assert_array_equal(
        run(q, kp2, vp2, pages, logical, counts, pos), base)
    # other physical ids for the same pages
    perm = np.concatenate([[0], rng.permutation(np.arange(1, 60))])
    inv = np.argsort(perm)
    np.testing.assert_array_equal(
        run(q, kp[perm], vp[perm], jnp.asarray(inv[np.asarray(pages)]),
            logical, counts, pos), base)
    # the first row alone
    np.testing.assert_array_equal(
        run(q[:1], kp, vp, pages[:1], logical[:1], counts[:1], pos[:1]),
        base[:1])


def _tiny_bf16_llama(kv_cache_dtype=None):
    """A decoder whose decode call the compiled kernel would take: bf16
    weights and pools, head_dim 128, a (16, 128)-tiled page."""
    from paddle_tpu.text.models import LlamaForCausalLM, llama_tiny
    paddle.seed(5)
    cfg = llama_tiny(vocab_size=64, hidden_size=256,
                     intermediate_size=64, num_hidden_layers=2,
                     num_attention_heads=2, num_key_value_heads=1,
                     max_position_embeddings=64,
                     kv_cache_dtype=kv_cache_dtype)
    m = LlamaForCausalLM(cfg)
    m.bfloat16()
    m.eval()
    return m


def _paged_call(m, ids, pos, pools, tbl, wm, **kw):
    from paddle_tpu.framework.core import Tensor, no_grad
    with no_grad():
        lg, pools = m.forward_paged(
            Tensor(np.asarray(ids, np.int32)),
            Tensor(np.asarray(pos, np.int32)), pools,
            np.asarray(tbl, np.int32), np.asarray(wm, bool), **kw)
    return np.asarray((lg._value if hasattr(lg, "_value") else lg)
                      .astype(jnp.float32)), pools


def _bf16_paged_decode(m, mode, steps=3):
    """Prefill 19 tokens over two blocks of 16, then ``steps`` decode
    calls, beside an inactive slot (mask off, table of zeros)."""
    kreg.set_mode("paged_attention", mode)
    try:
        pools = m.init_paged_cache(8, 16)
        tbl = np.array([[3, 5, 2, 0], [0, 0, 0, 0]], np.int32)
        rng = np.random.RandomState(1)
        n = 19
        ids = np.zeros((2, 32), np.int32)
        ids[0, :n] = rng.randint(1, 64, (n,))
        pos = np.broadcast_to(np.arange(32, dtype=np.int32), (2, 32))
        wm = np.zeros((2, 32), bool)
        wm[0, :n] = True
        lg, pools = _paged_call(m, ids, pos, pools, tbl, wm,
                                gather_at=np.asarray([n - 1, 0], np.int32))
        outs = [lg[0, 0]]
        for j in range(steps):
            tok = int(np.argmax(outs[-1]))
            lg, pools = _paged_call(m, [[tok], [0]], [[n + j], [0]],
                                    pools, tbl, [[True], [False]])
            assert np.isfinite(lg).all()
            outs.append(lg[0, 0])
        return outs
    finally:
        kreg.set_mode("paged_attention", None)


def test_llama_paged_decode_kernel_parity():
    """End-to-end through ``LlamaAttention.forward_paged``: decode
    logits with the kernel (interpret) track the reference path, and
    the counters name the routes (prefill takes neither)."""
    m = _tiny_bf16_llama()
    kreg.reset_dispatch_counts()
    ref = _bf16_paged_decode(m, "xla_ref")
    assert kreg.dispatch_counts("paged_attention") == {"xla_ref": 6}
    kreg.reset_dispatch_counts()
    got = _bf16_paged_decode(m, "interpret")
    assert kreg.dispatch_counts("paged_attention") == {"interpret": 6}
    for r, g in zip(ref, got):
        np.testing.assert_allclose(g, r, atol=2e-2, rtol=2e-2)
    assert [int(np.argmax(o)) for o in got] == \
        [int(np.argmax(o)) for o in ref]


def _trace_paged(m, S, target, verify_mode=False):
    """Trace (never compile) one ``forward_paged`` call as if programs
    were being compiled for ``target``; the counters of the route."""
    import paddle_tpu.distributed.mesh as mesh_mod
    from paddle_tpu.framework.core import Tensor, no_grad
    pools = m.init_paged_cache(8, 16)
    orig = mesh_mod.target_platform
    mesh_mod.target_platform = lambda: target
    kreg.reset_dispatch_counts()
    try:
        def f(ids, pos, pools, tbl, wm):
            with no_grad():
                lg, pools = m.forward_paged(
                    Tensor(ids), Tensor(pos), pools, tbl, wm,
                    verify_mode=verify_mode)
            return lg._value if hasattr(lg, "_value") else lg
        jax.eval_shape(f, np.zeros((2, S), np.int32),
                       np.zeros((2, S), np.int32), pools,
                       np.zeros((2, 4), np.int32), np.zeros((2, S), bool))
    finally:
        mesh_mod.target_platform = orig
    return kreg.dispatch_counts()


@pytest.mark.parametrize("what,expect", [
    ("decode_cpu", {"paged_attention": {"xla_ref": 2}}),
    ("decode_tpu", {"paged_attention": {"pallas": 2}}),
    ("verify_tpu", {"paged_attention": {"xla_ref": 2}}),
    ("verify_one_token_tpu", {"paged_attention": {"xla_ref": 2}}),
    ("int8_pools_tpu", {"int8_kv_attention": {"xla_ref": 2}}),
    ("f32_weights_tpu", {"paged_attention": {"fallback": 2}}),
])
def test_paged_decode_route_is_chosen_from_the_call(what, expect):
    """No knob: a decode step over bf16 pools takes the kernel where
    programs are compiled for a TPU and the reference on the CPU;
    verify (S > 1, or ``verify_mode``), int8 pools and shapes the
    kernel does not tile keep the reference, each counted."""
    m = _tiny_bf16_llama("int8" if what == "int8_pools_tpu" else None)
    if what == "f32_weights_tpu":
        m.float()
    target = "cpu" if what.endswith("_cpu") else "tpu"
    S = 3 if what == "verify_tpu" else 1
    counts = _trace_paged(m, S, target,
                          verify_mode=what.startswith("verify"))
    assert {k: v for k, v in counts.items() if v} == expect


def _tiny_bf16_latent(kind, **kw):
    """A latent-attention decoder whose decode call the latent kernel
    would take: a bf16 pool of 256-lane rows, V its first 128 lanes."""
    from paddle_tpu.text.models import kimi_linear as KL
    from paddle_tpu.text.models import pangu_ultra_moe as PU
    paddle.seed(5)
    kw = dict(dict(kv_lora_rank=128, compute_dtype="bfloat16"), **kw)
    if kind == "kimi":
        return KL.KimiLinearForCausalLM(KL.kimi_linear_tiny(**kw))
    return PU.PanguUltraMoEForCausalLM(PU.pangu_ultra_moe_tiny(**kw))


@pytest.mark.parametrize("kind", ["kimi", "pangu"])
@pytest.mark.parametrize("what", ["decode_cpu", "decode_tpu",
                                  "f32_pool_tpu", "narrow_latent_tpu"])
def test_latent_decode_route_is_chosen_from_the_call(kind, what):
    """The two latent models' decode step takes ``paged_latent_attention``
    (never ``paged_attention``) once a latent layer: the kernel where
    programs are compiled for a TPU and the reference on the CPU; a
    float32 pool or a latent width that is not whole lane tiles falls
    back, counted."""
    import paddle_tpu.distributed.mesh as mesh_mod
    from paddle_tpu.text.models.kimi_linear import LatentAttention
    kw = {"f32_pool_tpu": dict(compute_dtype="float32"),
          "narrow_latent_tpu": dict(kv_lora_rank=24)}.get(what, {})
    m = _tiny_bf16_latent(kind, **kw)
    n = sum(isinstance(lyr.self_attn, LatentAttention)
            for lyr in m.model.layers)
    pools = m.init_paged_cache(8, 16, num_slots=2)
    orig = mesh_mod.target_platform
    mesh_mod.target_platform = lambda: what.rsplit("_", 1)[1]
    kreg.reset_dispatch_counts()
    try:
        jax.eval_shape(
            lambda ids, pos, pools, tbl, wm: m.forward_paged(
                ids, pos, pools, tbl, wm)[0]._value,
            np.zeros((2, 1), np.int32), np.zeros((2, 1), np.int32), pools,
            np.zeros((2, 4), np.int32), np.zeros((2, 1), bool))
    finally:
        mesh_mod.target_platform = orig
    path = {"decode_cpu": "xla_ref", "decode_tpu": "pallas"}.get(
        what, "fallback")
    counts = {k: v for k, v in kreg.dispatch_counts().items() if v}
    assert n >= 1 and counts == {"paged_latent_attention": {path: n}}


@pytest.mark.parametrize("kind", ["kimi", "pangu"])
def test_latent_paged_decode_kernel_parity(kind):
    """End-to-end through ``LatentAttention.forward_paged``: a prefill
    of 19 tokens over two pages, then decode steps beside an inactive
    slot, with the latent kernel (interpret) track the reference path
    and pick the same tokens."""
    m = _tiny_bf16_latent(kind)

    def decode(mode):
        kreg.set_mode("paged_latent_attention", mode)
        try:
            pools = m.init_paged_cache(8, 16, num_slots=2)
            tbl = np.array([[3, 5, 2, 0], [0, 0, 0, 0]], np.int32)
            n = 19
            ids = np.zeros((2, 32), np.int32)
            ids[0, :n] = np.random.RandomState(1).randint(1, 64, (n,))
            pos = np.broadcast_to(np.arange(32, dtype=np.int32), (2, 32))
            pos = np.where(np.arange(2)[:, None] == 0, pos, 0)
            wm = np.zeros((2, 32), bool)
            wm[0, :n] = True
            lg, pools, _ = m.forward_paged(
                ids, pos, pools, tbl, wm,
                gather_at=np.asarray([n - 1, 0], np.int32))
            outs = [np.asarray(lg._value[0, 0], np.float32)]
            for j in range(3):
                tok = int(np.argmax(outs[-1]))
                lg, pools, _ = m.forward_paged(
                    np.asarray([[tok], [0]], np.int32),
                    np.asarray([[n + j], [0]], np.int32), pools, tbl,
                    np.asarray([[True], [False]]))
                outs.append(np.asarray(lg._value[0, 0], np.float32))
            return outs
        finally:
            kreg.set_mode("paged_latent_attention", None)
    ref = decode("xla_ref")
    kreg.reset_dispatch_counts()
    got = decode("interpret")
    from paddle_tpu.text.models.kimi_linear import LatentAttention
    n = sum(isinstance(lyr.self_attn, LatentAttention)
            for lyr in m.model.layers)
    assert kreg.dispatch_counts("paged_latent_attention") == \
        {"interpret": 3 * n}
    for r, g in zip(ref, got):
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, r, atol=2e-2, rtol=2e-2)
    assert [int(np.argmax(o)) for o in got] == \
        [int(np.argmax(o)) for o in ref]


def test_generation_server_decode_program_routes(monkeypatch):
    """The server's own ``decode_fn``, traced: ``xla_ref`` as built on
    the CPU, the kernel when the same server is built for a TPU
    target; prefill programs never reach the dispatch."""
    import paddle_tpu.distributed.mesh as mesh_mod
    from paddle_tpu.inference import GenerationServer
    m = _tiny_bf16_llama()

    def routes():
        srv = GenerationServer(m, num_slots=2, block_size=16,
                               max_model_len=64)
        srv._build_programs()
        kreg.reset_dispatch_counts()
        B, W = 2, int(np.asarray(srv._seq_key_data(0)).shape[-1])
        jax.eval_shape(
            srv._decode_fn, srv._pvals, srv._pools, srv._prev,
            np.zeros((B, 1), np.int32), np.zeros((B, 1), np.int32),
            np.zeros((B, srv._M), np.int32), np.zeros((B, 1), bool),
            np.zeros((B, W), np.uint32), np.zeros((B,), np.int32),
            np.ones((B,), np.float32), np.zeros((B,), np.int32),
            np.ones((B,), np.float32), np.zeros((B,), bool))
        return kreg.dispatch_counts("paged_attention")

    assert routes() == {"xla_ref": 2}
    monkeypatch.setattr(mesh_mod, "target_platform", lambda: "tpu")
    assert routes() == {"pallas": 2}


def test_generation_server_with_kernel_matches_reference_route():
    """A served greedy session with the kernel (interpret) in the
    decode program gives the reference route's tokens."""
    from paddle_tpu.inference import GenerationServer
    m = _tiny_bf16_llama()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 64, (l,)).astype("int32")
               for l in (5, 18, 9)]

    def serve(mode):
        kreg.set_mode("paged_attention", mode)
        srv = GenerationServer(m, num_slots=2, block_size=16,
                               max_model_len=64,
                               request_timeout_s=300.0)
        srv.start()
        try:
            streams = [srv.submit(p, max_new_tokens=5) for p in prompts]
            return [s.result(timeout=300) for s in streams]
        finally:
            srv.stop()
            kreg.set_mode("paged_attention", None)

    ref = serve("xla_ref")
    kreg.reset_dispatch_counts()
    got = serve("interpret")
    assert kreg.dispatch_counts("paged_attention").get("interpret")
    assert got == ref


# ---------------------------------------------------------------------
# kernel 4: segment-sum embedding grads
# ---------------------------------------------------------------------

def test_segment_sum_interpret_parity():
    from paddle_tpu.ops.pallas.segment_sum import (segment_sum_pallas,
                                                   segment_sum_ref)
    rng = np.random.default_rng(14)
    g = _rand(rng, 37, 9)
    inv = rng.integers(0, 13, 37).astype(np.int32)
    ref = segment_sum_ref(jnp.asarray(g), jnp.asarray(inv), 16)
    ker = segment_sum_pallas(jnp.asarray(g), jnp.asarray(inv), 16,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               atol=1e-6)
    # untouched segments are exact zeros
    assert np.array_equal(np.asarray(ker)[13:], np.zeros((3, 9)))


def test_segment_sum_integer_grads_bit_exact():
    from paddle_tpu.ops.pallas.segment_sum import (segment_sum_pallas,
                                                   segment_sum_ref)
    rng = np.random.default_rng(15)
    g = rng.integers(-50, 50, (64, 5)).astype(np.float32)
    inv = rng.integers(0, 7, 64).astype(np.int32)
    ref = segment_sum_ref(jnp.asarray(g), jnp.asarray(inv), 8)
    ker = segment_sum_pallas(jnp.asarray(g), jnp.asarray(inv), 8,
                             interpret=True)
    assert np.array_equal(np.asarray(ref), np.asarray(ker))


def test_segment_sum_feeds_device_cache_push():
    """heter.DeviceCachedTable's device-side push routes its merge
    through the registry: interpret mode reproduces the xla_ref rows
    bit-exactly for integer grads (duplicate ids segment-summed)."""
    from paddle_tpu.distributed.fleet.heter import DeviceCachedTable
    from paddle_tpu.distributed.fleet.ps import SparseTable

    def run(mode):
        kreg.set_mode("segment_sum", mode)
        try:
            t = SparseTable(dim=4, init_std=0.0)
            c = DeviceCachedTable(t, capacity=16, lr=1.0)
            ids = np.array([3, 9, 3, 5, 9, 3], np.int64)
            c.pull(ids, pin=True)
            grads = np.tile(
                np.arange(1, 7, dtype=np.float32)[:, None], (1, 4))
            c.push(ids, grads)
            c.flush()
            return t.pull(np.array([3, 5, 9], np.int64))
        finally:
            kreg.set_mode("segment_sum", None)
    ref = run("xla_ref")
    got = run("interpret")
    assert np.array_equal(np.asarray(ref), np.asarray(got))
    # id 3 saw rows 1+3+6, id 5 row 4, id 9 rows 2+5 (sgd lr=1 => -sum)
    assert np.allclose(np.asarray(ref)[:, 0], [-10.0, -4.0, -7.0])


# ---------------------------------------------------------------------
# kernel 4b: SORTED-segment variant for vocab-scale nseg (ISSUE 14
# satellite — PR 13's named follow-up)
# ---------------------------------------------------------------------

def test_segment_sum_sorted_registered_with_ref():
    ks = kreg.kernels()
    assert "segment_sum_sorted" in ks
    assert ks["segment_sum_sorted"].tolerance
    assert callable(ks["segment_sum_sorted"].xla_ref_fn)


def test_segment_sum_sorted_vocab_scale_parity():
    """The point of the variant: nseg far beyond what the sequential
    kernel's whole-output-in-VMEM budget allows, exact vs the XLA
    reference (per-segment accumulation order equals row order)."""
    from paddle_tpu.ops.pallas.segment_sum import (
        _eligible, segment_sum_sorted_pallas, segment_sum_sorted_ref)
    rng = np.random.default_rng(21)
    nseg, n, dim = 200_000, 256, 16
    assert not _eligible(np.zeros((n, dim), np.float32), None, nseg), \
        "vocab-scale nseg should NOT be sequential-kernel eligible"
    seg = np.sort(rng.integers(0, nseg, n)).astype(np.int64)
    g = _rand(rng, n, dim)
    ref = segment_sum_sorted_ref(jnp.asarray(g), jnp.asarray(seg), nseg)
    ker = segment_sum_sorted_pallas(jnp.asarray(g), jnp.asarray(seg),
                                    nseg, interpret=True)
    assert ker.shape == (nseg, dim)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               atol=1e-6)


def test_segment_sum_sorted_integer_grads_bit_exact():
    from paddle_tpu.ops.pallas.segment_sum import (
        segment_sum_sorted_pallas, segment_sum_sorted_ref)
    rng = np.random.default_rng(22)
    for nseg, n in ((6000, 64), (513, 9), (4096, 8)):
        seg = np.sort(rng.integers(0, nseg, n)).astype(np.int64)
        g = rng.integers(-50, 50, (n, 5)).astype(np.float32)
        ref = segment_sum_sorted_ref(jnp.asarray(g), jnp.asarray(seg),
                                     nseg)
        ker = segment_sum_sorted_pallas(jnp.asarray(g),
                                        jnp.asarray(seg), nseg,
                                        interpret=True)
        assert np.array_equal(np.asarray(ref), np.asarray(ker)), nseg


def test_merge_segments_picks_kernel_by_segment_count():
    """The streaming trainer's pre-merge dispatch: recsys-scale nseg
    takes the sequential kernel, vocab-scale the sorted one — and both
    produce the reference merge (stable sort preserves within-segment
    row order, so integer grads stay bit-exact)."""
    from paddle_tpu.ops.pallas.segment_sum import (SORTED_NSEG_MIN,
                                                   merge_segments)
    kreg.reset_dispatch_counts()
    rng = np.random.default_rng(23)
    # small: sequential kernel
    ids = rng.integers(0, 40, 128)
    uniq, inv = np.unique(ids, return_inverse=True)
    g = rng.integers(-8, 8, (128, 4)).astype(np.float32)
    out = np.asarray(merge_segments(g, inv, int(uniq.size)))
    want = np.zeros((uniq.size, 4), np.float32)
    np.add.at(want, inv, g)
    assert np.array_equal(out, want)
    assert kreg.dispatch_counts("segment_sum"), \
        kreg.dispatch_counts()
    assert not kreg.dispatch_counts("segment_sum_sorted")
    # vocab-scale: sorted kernel (UNSORTED inverse goes in — the
    # helper sorts)
    nseg = SORTED_NSEG_MIN + 1000
    inv2 = rng.integers(0, nseg, 128).astype(np.int64)
    g2 = rng.integers(-8, 8, (128, 4)).astype(np.float32)
    out2 = np.asarray(merge_segments(g2, inv2, nseg))
    want2 = np.zeros((nseg, 4), np.float32)
    np.add.at(want2, inv2, g2)
    assert np.array_equal(out2, want2)
    assert kreg.dispatch_counts("segment_sum_sorted"), \
        kreg.dispatch_counts()


def test_streaming_trainer_device_merge_matches_numpy():
    """StreamingTrainer(device_merge=True) pre-merges duplicate ids
    through the pallas tier; the pushed (ids, grads) must equal the
    numpy merge bit-for-bit (integer grads)."""
    from paddle_tpu.online.streaming import StreamingTrainer

    class _Sink:
        def __init__(self):
            self.calls = []

        def push_stamped(self, table, ids, grads, seq, src=None,
                         wm=None):
            self.calls.append((np.asarray(ids), np.asarray(grads)))
            return True

        def pull(self, table, ids):
            return np.zeros((np.asarray(ids).size, 4), np.float32)

    rng = np.random.default_rng(3)
    ids = rng.integers(0, 100, 8192).astype(np.int64)
    grads = rng.integers(-4, 4, (8192, 4)).astype(np.float32)

    def run(device_merge):
        sink = _Sink()
        tr = StreamingTrainer(
            [ {"ids": ids} ], sink, "emb",
            lambda b, pull: (b["ids"], grads),
            merge_duplicates=True, device_merge=device_merge)
        tr.run(max_batches=1)
        return sink.calls[0]

    i1, g1 = run(False)
    i2, g2 = run(True)
    assert np.array_equal(i1, i2)
    assert np.array_equal(g1, g2)


# ---------------------------------------------------------------------
# GraftLint: pallas custom calls are kernels, not host callbacks
# ---------------------------------------------------------------------

def test_jaxpr_audit_classifies_pallas_as_kernels():
    from paddle_tpu.analysis.jaxpr_audit import audit_fn
    from paddle_tpu.ops.pallas.opt_apply import (opt_apply_pallas,
                                                 pack_hyper)

    p = jnp.zeros(512, jnp.float32)
    hy = jnp.asarray(pack_hyper("adam", lr=0.01))
    rep = audit_fn(
        lambda p, g, m, v, h: opt_apply_pallas(
            "adam", p, g, (m, v), h, interpret=True),
        [p, p, p, p, hy], check_donation=False)
    # inventoried by kernel name, count 1 — and NOT flagged as a
    # jaxpr.host-callback error (pallas is device code)
    assert rep.kernels == {"_opt_apply_kernel": 1}, rep.kernels
    assert not [f for f in rep.findings
                if f.rule == "jaxpr.host-callback"], rep.summary()
    assert "kernels: _opt_apply_kernel x1" in rep.summary()
    assert rep.asdict()["kernels"] == {"_opt_apply_kernel": 1}


def test_hlo_kernel_inventory_parses_custom_call_targets():
    from paddle_tpu.analysis.jaxpr_audit import hlo_kernel_inventory
    hlo = "\n".join([
        '  %k = f32[128]{0} custom-call(f32[128]{0} %x), '
        'custom_call_target="tpu_custom_call"',
        '  %c = f32[8]{0} custom-call(f32[8]{0} %y), '
        'custom_call_target="Sharding"',
    ])
    assert hlo_kernel_inventory(hlo) == {"tpu_custom_call": 1}


# ---------------------------------------------------------------------
# flash attention: compat path + registry governance (satellite 6)
# ---------------------------------------------------------------------

def test_flash_attention_compat_import_path():
    import importlib
    compat = importlib.import_module("paddle_tpu.ops.flash_attention")
    impl = importlib.import_module(
        "paddle_tpu.ops.pallas.flash_attention")
    for name in ("flash_attention", "flash_attention_bhsd",
                 "flash_eligible", "chunked_attention", "dropout_seed",
                 "_resolve_blocks", "_ref_chunked"):
        assert getattr(compat, name) is getattr(impl, name), name
    # the package-level function export keeps working too (it shadows
    # the submodule attribute, as it always has)
    from paddle_tpu.ops import flash_attention as fa_fn
    assert callable(fa_fn)


def test_flash_attention_dispatch_counter_and_xla_ref_route():
    from paddle_tpu.ops.flash_attention import (_ref_chunked,
                                                flash_attention_bhsd)
    rng = np.random.default_rng(16)
    q = jnp.asarray(_rand(rng, 1, 2, 128, 16))
    k = jnp.asarray(_rand(rng, 1, 2, 128, 16))
    v = jnp.asarray(_rand(rng, 1, 2, 128, 16))
    kreg.reset_dispatch_counts()
    # CPU default resolves to xla_ref -> the chunked reference, bitwise
    out = flash_attention_bhsd(q, k, v, causal=True)
    ref = _ref_chunked(q, k, v, None, True, 1.0 / 4.0)
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    # explicit interpret=True forces the kernel (the parity-test hook)
    out_i = flash_attention_bhsd(q, k, v, causal=True, interpret=True,
                                 block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out_i), np.asarray(ref),
                               atol=2e-4, rtol=1e-3)
    c = kreg.dispatch_counts("flash_attention")
    assert c.get("xla_ref", 0) == 1 and c.get("interpret", 0) == 1, c


def test_pull_dequant_interpret_bit_exact_vs_ref():
    """int8 -> f32 conversion is exact and each output element is one
    f32 multiply of identical operands: kernel == xla_ref == the PS
    quantizer's own numpy dequant, bit for bit (tolerance 0.0)."""
    from paddle_tpu.distributed.fleet.ps import (dequantize_rows_q8,
                                                 quantize_rows_q8)
    from paddle_tpu.ops.pallas.pull_dequant import (pull_dequant_pallas,
                                                    pull_dequant_ref)
    rng = np.random.default_rng(16)
    rows = (rng.standard_normal((37, 24)) * 3).astype(np.float32)
    rows[5] = 0.0  # all-zero row ships scale 0
    codes, scales = quantize_rows_q8(rows)
    ref = np.asarray(pull_dequant_ref(jnp.asarray(codes),
                                      jnp.asarray(scales)))
    ker = np.asarray(pull_dequant_pallas(jnp.asarray(codes),
                                         jnp.asarray(scales),
                                         interpret=True))
    assert np.array_equal(ker, ref)
    assert np.array_equal(ref, dequantize_rows_q8(codes, scales))
    assert np.array_equal(ker[5], np.zeros(24, np.float32))
    # empty batch keeps its shape through the registry path
    kreg.set_mode("pull_dequant", "interpret")
    try:
        empty = kreg.dispatch("pull_dequant",
                              np.zeros((0, 24), np.int8),
                              np.zeros(0, np.float32))
        assert np.asarray(empty).shape == (0, 24)
    finally:
        kreg.set_mode("pull_dequant", None)
