"""Pallas flash-attention kernel vs XLA reference (interpret mode on CPU,
per pallas_guide debugging pattern)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.flash_attention import flash_attention_bhsd


def _ref(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = np.tril(np.ones((sq, sk), bool), sk - sq)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    rng = np.random.RandomState(0)
    q = rng.randn(2, 2, 256, 64).astype(np.float32)
    k = rng.randn(2, 2, 256, 64).astype(np.float32)
    v = rng.randn(2, 2, 256, 64).astype(np.float32)
    scale = 1.0 / np.sqrt(64)
    out = flash_attention_bhsd(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                               causal=causal, block_q=128, block_k=128,
                               interpret=True)  # interpret
    ref = _ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_gradients_match_reference():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 128, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, 128, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, 128, 64).astype(np.float32))

    def loss_flash(q, k, v):
        return flash_attention_bhsd(q, k, v, causal=True, block_q=64,
                                    block_k=64, interpret=True).sum()

    def loss_ref(q, k, v):
        return _ref(q, k, v, True, 1.0 / np.sqrt(64)).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-3)


def test_non_divisible_seq_falls_back():
    from paddle_tpu.ops.flash_attention import _fa_impl
    from paddle_tpu.ops.pallas import registry
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 1, 100, 32).astype(np.float32))
    registry.reset_dispatch_counts("flash_attention")
    out = flash_attention_bhsd(q, q, q, block_q=64, block_k=64,
                               interpret=True)
    ref = _ref(q, q, q, False, 1.0 / np.sqrt(32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)
    # the reference route is chosen BEFORE the dispatch is counted: a
    # shape the kernel does not take is a counted fallback, and a call
    # that reaches the kernel entry (what "pallas"/"interpret" counts)
    # runs the kernel or raises — it can no longer return the reference
    assert registry.dispatch_counts("flash_attention") == {"fallback": 1}
    with pytest.raises(ValueError, match="block-divisible"):
        _fa_impl(q, q, q, None, None, None, False, None, 64, 64, True, 0.0)
    with pytest.raises(ValueError, match="block-divisible"):
        jax.grad(lambda x: _fa_impl(x, q, q, None, None, None, False,
                                    None, 64, 64, True, 0.0).sum())(q)


def test_flash_runs_per_shard_under_a_mesh():
    """With a multi-device mesh installed the kernel call is made manual
    (``jax.shard_map``: batch over the data axes, heads over 'tp') —
    Mosaic calls cannot be SPMD-partitioned — including inside the
    partial-manual 'pp' region of the pipeline, and equals the
    unsharded result."""
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.planner.spec_layout import get_layout
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(4, 4, 128, 64).astype(np.float32))
               for _ in range(3))

    def loss(q_, k_, v_):
        return (flash_attention_bhsd(q_, k_, v_, causal=True, block_q=64,
                                     block_k=64, interpret=True) ** 2).sum()
    want = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    # (fresh lambdas: jax caches traces per function object, and the
    # installed mesh is not part of that key)
    assert "shard_map" not in str(
        jax.make_jaxpr(lambda *a: loss(*a))(q, k, v))

    mesh = mesh_mod.init_mesh({"fsdp": 2, "pp": 2, "tp": 2})
    assert "shard_map" in str(jax.make_jaxpr(lambda *a: loss(*a))(q, k, v))
    got = jax.jit(jax.grad(lambda *a: loss(*a),
                           argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    # nested in the pipeline's partial-manual region over 'pp'
    rep = get_layout().replicated()
    staged = jax.shard_map(lambda *a: loss(*a), mesh=mesh,
                           axis_names={"pp"},
                           in_specs=(rep, rep, rep), out_specs=rep,
                           check_vma=False)
    np.testing.assert_allclose(float(jax.jit(staged)(q, k, v)),
                               float(loss(q, k, v)), rtol=1e-5)


def test_flash_additive_bias_matches_reference():
    # r3: padding masks stream through the kernel as [B,1,1,S] rows
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 2, 128, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 2, 128, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 2, 128, 64).astype(np.float32))
    keep = rng.rand(2, 128) > 0.3
    bias = jnp.asarray(np.where(keep, 0.0, -1e30)
                       .astype(np.float32))[:, None, None, :]
    out = flash_attention_bhsd(q, k, v, bias=bias, block_q=64, block_k=64,
                               interpret=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q / np.sqrt(64), k) + bias
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    # grads flow through the masked path too
    def loss(q, k, v):
        return flash_attention_bhsd(q, k, v, bias=bias, block_q=64,
                                    block_k=64, interpret=True).sum()
    def loss_ref(q, k, v):
        sc = jnp.einsum("bhqd,bhkd->bhqk", q / np.sqrt(64), k) + bias
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(sc, axis=-1), v).sum()
    g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal,sq,sk", [(False, 256, 256),
                                          (True, 256, 256),
                                          (False, 128, 256)])
def test_pallas_backward_kernels_match_autodiff(causal, sq, sk):
    # r3: FlashAttention-2-style dKV/dQ kernels (interpret mode) vs
    # autodiff of the dense reference, rectangular blocks + multi-block
    # sequences on both axes
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 3, sq, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 3, sk, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 3, sk, 64).astype(np.float32))
    g = jnp.asarray(rng.randn(2, 3, sq, 64).astype(np.float32))

    def loss_flash(q, k, v):
        return (flash_attention_bhsd(q, k, v, causal=causal, block_q=64,
                                     block_k=128, interpret=True) * g).sum()

    def loss_ref(q, k, v):
        return (_ref(q, k, v, causal, 1.0 / np.sqrt(64)) * g).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_dropout_matches_masked_reference(causal):
    """In-kernel attention dropout (injected keep mask; the on-chip PRNG
    path reuses the identical masking math, validated by the bench's
    TPU-side parity check). Reference: dropout applied to the NORMALIZED
    softmax weights, inverted scaling — fwd and all three grads."""
    rng = np.random.RandomState(9)
    B, H, S, D, p_drop = 2, 2, 128, 64, 0.3
    q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    g = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    keep = jnp.asarray((rng.rand(B, H, S, S) > p_drop).astype(np.uint8))

    def flash(q, k, v):
        return flash_attention_bhsd(q, k, v, test_mask=keep,
                                    causal=causal, block_q=64,
                                    block_k=64, interpret=True,
                                    dropout_p=p_drop)

    def ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q / np.sqrt(D), k)
        if causal:
            m = np.tril(np.ones((S, S), bool))
            s = jnp.where(jnp.asarray(m), s, -1e30)
        probs = jax.nn.softmax(s, axis=-1)
        probs = probs * keep / (1.0 - p_drop)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               rtol=3e-3, atol=3e-3)
    g1 = jax.grad(lambda *a: (flash(*a) * g).sum(), argnums=(0, 1, 2))(
        q, k, v)
    g2 = jax.grad(lambda *a: (ref(*a) * g).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_resolve_blocks_defaults_vs_explicit():
    """Public block defaults are None and resolve internally (512,
    shrunk to 256 at seq >= 8192); an EXPLICIT 512 is honored verbatim
    — the old sentinel-on-512 scheme silently rewrote it (ISSUE 2
    satellite)."""
    from paddle_tpu.ops.flash_attention import _resolve_blocks

    assert _resolve_blocks(2048, 2048, None, None) == (512, 512)
    assert _resolve_blocks(8192, 8192, None, None) == (256, 256)
    # explicit 512 at long seq survives (caller opted in)
    assert _resolve_blocks(8192, 8192, 512, 512) == (512, 512)
    # per-side resolution: only the long side shrinks
    assert _resolve_blocks(8192, 2048, None, None) == (256, 512)
    assert _resolve_blocks(2048, 8192, None, None) == (512, 256)
    # explicit non-default blocks always pass through
    assert _resolve_blocks(1024, 1024, 128, 64) == (128, 64)


def test_default_blocks_flow_through_call():
    """flash_attention_bhsd with default (None) blocks runs the same
    program as explicit 512s at short seq (interpret-mode smoke)."""
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 1, 128, 64).astype(np.float32))
    a = flash_attention_bhsd(q, q, q, causal=True, interpret=True)
    b = flash_attention_bhsd(q, q, q, causal=True, block_q=512,
                             block_k=512, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
