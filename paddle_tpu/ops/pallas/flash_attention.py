"""Pallas flash attention for TPU.

Greenfield per SURVEY.md §5.7 — the 2021-era reference has no fused
attention (only the inference-side operators/fused/multihead_matmul_op.*);
long-context capability is a requirement of this framework, not a port.

Design: classic FlashAttention-style blockwise online softmax.
- grid = (batch, heads, Q blocks); the K/V loop runs inside the kernel via
  ``lax.fori_loop`` so K/V tiles stream HBM->VMEM block by block.
- running max / denominator live in VMEM scratch (f32) for stability even
  when inputs are bf16.
- causal masking skips fully-masked K blocks (upper-triangular work is
  never issued).
- backward is a custom VJP that recomputes attention blockwise per Q chunk
  (memory O(S·block) instead of O(S²)) in plain XLA — a fair trade for
  round 1; a fused Pallas bwd kernel can replace it without API change.

Layout convention here is (B, H, S, D); the public
``nn.functional.scaled_dot_product_attention`` converts from paddle's
(B, S, H, D).

ISSUE 13: this module moved under the Pallas kernel tier
(``ops/pallas/``) and dispatches through its registry — the public
entry points route ``pallas | xla_ref | interpret`` per the resolved
mode (``chunked_attention``'s ``_ref_chunked`` is the registered XLA
reference) and tick the ``flash_attention`` dispatch counters.  The
old ``paddle_tpu.ops.flash_attention`` import path keeps working via
a compat re-export.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

__all__ = ["flash_attention", "flash_attention_bhsd"]

NEG_INF = -1e30


def _keep_mask(seed_ref, mask_ref, b, h, qb, kb, block_q, block_k,
               dropout_p):
    """Dropout keep-mask for score block (qb, kb) — either regenerated
    from the on-chip PRNG seeded by (seed, b, h, qb, kb) so forward and
    backward agree bit-exactly, or (tests / interpret mode) read from an
    injected full [B, H, Sq, Sk] mask."""
    if mask_ref is not None:
        return mask_ref[0, 0, pl.dslice(qb * block_q, block_q),
                        pl.dslice(kb * block_k, block_k)] > 0
    # Mosaic accepts at most two seed words: pack the block coordinates
    # into one (8 bits each for h/qb/kb, the rest for b — ample for any
    # shape this kernel accepts)
    idx = ((b * 256 + h) * 256 + qb) * 256 + kb
    pltpu.prng_seed(seed_ref[0], idx)
    bits = pltpu.prng_random_bits((block_q, block_k))
    # dropout_p is a static python float; 4294967296.0 == 2 ** 32
    thresh = jnp.uint32(int(dropout_p * 4294967296.0) & 0xFFFFFFFF)
    return pltpu.bitcast(bits, jnp.uint32) >= thresh


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, block_k: int, causal: bool,
                scale: float, seq_k: int, block_q: int, has_bias: bool,
                with_lse: bool = False, dropout_p: float = 0.0,
                has_mask_in: bool = False):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    seed_ref = rest.pop(0) if dropout_p > 0.0 and not has_mask_in \
        else None
    mask_ref = rest.pop(0) if has_mask_in else None
    if with_lse:
        o_ref, lse_ref = rest
    else:
        (o_ref,) = rest
        lse_ref = None
    bi, hi, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    # dots run in the INPUT dtype (bf16 on the hot path) with f32
    # accumulation via preferred_element_type — upcasting q/k/v first
    # halves MXU throughput (measured ~2x on the fwd+bwd microbench)
    q = q_ref[0, 0]                              # (block_q, d)

    m0 = jnp.full((q.shape[0],), NEG_INF, jnp.float32)
    l0 = jnp.zeros((q.shape[0],), jnp.float32)
    acc0 = jnp.zeros((q.shape[0], v_ref.shape[-1]), jnp.float32)

    num_kb = seq_k // block_k
    if causal:
        # K blocks beyond the diagonal of this Q block contribute nothing
        num_kb_eff = jnp.minimum(num_kb,
                                 (qi * block_q + block_q + block_k - 1)
                                 // block_k)
    else:
        num_kb_eff = num_kb

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.dslice(kb * block_k, block_k)]
        v = v_ref[0, 0, pl.dslice(kb * block_k, block_k)]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if has_bias:
            # additive [B, 1, 1, S_k] bias (padding masks): one row per
            # batch, broadcast over heads and queries
            bv = bias_ref[0, 0, 0, pl.dslice(kb * block_k, block_k)]
            s = s + bv.astype(jnp.float32)[None, :]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        # the normalizer accumulates the UNdropped probabilities (the
        # reference applies dropout to the normalized softmax), only the
        # value accumulation sees the mask
        l_new = l * alpha + p.sum(axis=1)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref, mask_ref, bi, hi, qi, kb,
                              block_q, block_k, dropout_p)
            p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_kb_eff, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0, 0] = out.astype(o_ref.dtype)
    if with_lse:
        lse_ref[0, 0] = (m + jnp.log(jnp.maximum(l, 1e-30)))[:, None]


def _mask_specs_args(in_specs, args, seed, test_mask, sq, sk):
    """Thread the dropout seed (SMEM scalar) or an injected full keep
    mask into a pallas_call's inputs."""
    if test_mask is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, sq, sk), lambda b_, h_, i_: (b_, h_, 0, 0)))
        args.append(test_mask)
    elif seed is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)


def _pallas_forward(q, k, v, bias, causal, scale, block_q, block_k,
                    interpret, with_lse=False, dropout_p=0.0, seed=None,
                    test_mask=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    grid = (b, h, sq // block_q)

    kernel = functools.partial(_fwd_kernel, block_k=block_k, causal=causal,
                               scale=scale, seq_k=sk, block_q=block_q,
                               has_bias=bias is not None,
                               with_lse=with_lse, dropout_p=dropout_p,
                               has_mask_in=test_mask is not None)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda b_, h_, q_: (b_, h_, q_, 0)),
        pl.BlockSpec((1, 1, sk, d), lambda b_, h_, q_: (b_, h_, 0, 0)),
        pl.BlockSpec((1, 1, sk, d), lambda b_, h_, q_: (b_, h_, 0, 0)),
    ]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, 1, 1, sk),
                                     lambda b_, h_, q_: (b_, 0, 0, 0)))
        args.append(bias)
    if dropout_p > 0.0:
        _mask_specs_args(in_specs, args, seed, test_mask, sq, sk)
    out_specs = pl.BlockSpec((1, 1, block_q, d),
                             lambda b_, h_, q_: (b_, h_, q_, 0))
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if with_lse:
        # trailing singleton keeps the last-two-dims TPU tiling rule
        # satisfied ((block_q, 1): 8-divisible x equal-to-array)
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1, block_q, 1),
                                  lambda b_, h_, q_: (b_, h_, q_, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------------
# Pallas backward (FlashAttention-2 style): dKV and dQ kernels over the
# saved logsumexp; delta = rowsum(dO * O) precomputed in plain XLA.
# ---------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                    *rest, block_q: int, block_k: int,
                    causal: bool, scale: float, seq_q: int,
                    dropout_p: float = 0.0, has_mask_in: bool = False):
    rest = list(rest)
    seed_ref = rest.pop(0) if dropout_p > 0.0 and not has_mask_in \
        else None
    mask_ref = rest.pop(0) if has_mask_in else None
    dk_ref, dv_ref = rest
    bi, hi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    k = k_ref[0, 0]                              # (block_k, d)
    v = v_ref[0, 0]
    num_qb = seq_q // block_q
    qb0 = (ki * block_k) // block_q if causal else 0

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.dslice(qb * block_q, block_q)]
        do = do_ref[0, 0, pl.dslice(qb * block_q, block_q)]
        lse = lse_ref[0, 0, pl.dslice(qb * block_q, block_q), 0]
        delta = delta_ref[0, 0, pl.dslice(qb * block_q, block_q), 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])            # (block_q, block_k)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            # regenerate the forward's exact mask: same (seed,b,h,qb,kb)
            keep = _keep_mask(seed_ref, mask_ref, bi, hi, qb, ki,
                              block_q, block_k, dropout_p)
            p_drop = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
            dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
        else:
            p_drop = p
        dv = dv + jnp.dot(p_drop.astype(do.dtype).T, do,
                          preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        return dk, dv

    zeros = jnp.zeros((k.shape[0], k.shape[1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(qb0, num_qb, body, (zeros, zeros))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(k_ref, v_ref, do_ref, lse_ref, delta_ref, q_ref,
                   *rest, block_q: int, block_k: int, causal: bool,
                   scale: float, seq_k: int, dropout_p: float = 0.0,
                   has_mask_in: bool = False):
    rest = list(rest)
    seed_ref = rest.pop(0) if dropout_p > 0.0 and not has_mask_in \
        else None
    mask_ref = rest.pop(0) if has_mask_in else None
    (dq_ref,) = rest
    bi, hi, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q = q_ref[0, 0]                              # (block_q, d)
    do = do_ref[0, 0]
    lse = lse_ref[0, 0, :, 0]
    delta = delta_ref[0, 0, :, 0]
    num_kb = seq_k // block_k
    if causal:
        num_kb_eff = jnp.minimum(
            num_kb, (qi * block_q + block_q + block_k - 1) // block_k)
    else:
        num_kb_eff = num_kb

    def body(kb, dq):
        k = k_ref[0, 0, pl.dslice(kb * block_k, block_k)]
        v = v_ref[0, 0, pl.dslice(kb * block_k, block_k)]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref, mask_ref, bi, hi, qi, kb,
                              block_q, block_k, dropout_p)
            dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
        ds = (p * (dp - delta[:, None]) * scale).astype(k.dtype)
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(
        0, num_kb_eff, body,
        jnp.zeros((q.shape[0], q.shape[1]), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _pallas_backward(q, k, v, out, lse, do, causal, scale, block_q,
                     block_k, interpret, dropout_p=0.0, seed=None,
                     test_mask=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)      # [B,H,Sq,1]

    whole_seq = lambda b_, h_, i: (b_, h_, 0, 0)   # noqa: E731
    has_mask_in = test_mask is not None

    dkv_specs = [
        pl.BlockSpec((1, 1, sq, d), whole_seq),
        pl.BlockSpec((1, 1, sq, d), whole_seq),
        pl.BlockSpec((1, 1, sq, 1), whole_seq),
        pl.BlockSpec((1, 1, sq, 1), whole_seq),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b_, h_, i: (b_, h_, i, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b_, h_, i: (b_, h_, i, 0)),
    ]
    dkv_args = [q, do, lse, delta, k, v]
    if dropout_p > 0.0:
        _mask_specs_args(dkv_specs, dkv_args, seed, test_mask, sq, sk)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q,
                          block_k=block_k, causal=causal, scale=scale,
                          seq_q=sq, dropout_p=dropout_p,
                          has_mask_in=has_mask_in),
        grid=(b, h, sk // block_k),
        in_specs=dkv_specs,
        out_specs=[pl.BlockSpec((1, 1, block_k, d),
                                lambda b_, h_, i: (b_, h_, i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=interpret,
    )(*dkv_args)

    dq_specs = [
        pl.BlockSpec((1, 1, sk, d), whole_seq),
        pl.BlockSpec((1, 1, sk, d), whole_seq),
        pl.BlockSpec((1, 1, block_q, d),
                     lambda b_, h_, i: (b_, h_, i, 0)),
        pl.BlockSpec((1, 1, block_q, 1),
                     lambda b_, h_, i: (b_, h_, i, 0)),
        pl.BlockSpec((1, 1, block_q, 1),
                     lambda b_, h_, i: (b_, h_, i, 0)),
        pl.BlockSpec((1, 1, block_q, d),
                     lambda b_, h_, i: (b_, h_, i, 0)),
    ]
    dq_args = [k, v, do, lse, delta, q]
    if dropout_p > 0.0:
        _mask_specs_args(dq_specs, dq_args, seed, test_mask, sq, sk)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, causal=causal, scale=scale,
                          seq_k=sk, dropout_p=dropout_p,
                          has_mask_in=has_mask_in),
        grid=(b, h, sq // block_q),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, i: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(*dq_args)
    return dq, dk, dv


def _ref_chunked(q, k, v, bias, causal, scale, chunk=512):
    """Blockwise-RECOMPUTE attention in plain XLA: queries processed in
    chunks with ``jax.checkpoint`` per chunk, so neither forward nor
    backward ever holds more than one chunk's ``[B, H, chunk, S_k]``
    score block (without the checkpoint, AD would stash every chunk's
    softmax — same total memory as the naive composition).  The
    memory-efficient route wherever the Pallas kernel cannot run:
    shapes the kernel does not take (counted ``fallback``), and
    CPU-mesh dryruns of long-sequence models (the 7B geometry proof
    compiles through this path)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]

    @jax.checkpoint
    def one_chunk(qc, q0, kv):
        kk, vv = kv
        s = jnp.einsum("bhqd,bhkd->bhqk", qc * scale, kk)
        if bias is not None:
            s = s + bias.astype(s.dtype)
        if causal:
            q_pos = q0 + jnp.arange(qc.shape[2])[:, None]
            k_pos = jnp.arange(sk)[None, :]
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vv)

    # chunk must DIVIDE sq (the lax.map reshape is exact): largest
    # divisor <= the requested chunk; degenerate divisors (tiny chunks
    # on near-prime lengths) fall back to a single block
    c = min(chunk, sq)
    while c > 1 and sq % c:
        c -= 1
    chunk = c if c >= 128 else sq
    n = sq // chunk
    if n == 1:
        return one_chunk(q, jnp.asarray(0), (k, v))
    # lax.map (a scan) SERIALIZES the chunks: a python loop would hand
    # XLA n independent score blocks whose live ranges overlap, putting
    # peak memory right back at the naive composition's
    qs = jnp.moveaxis(q.reshape(b, h, n, chunk, d), 2, 0)
    q0s = jnp.arange(n) * chunk
    outs = jax.lax.map(lambda qc_q0: one_chunk(qc_q0[0], qc_q0[1],
                                               (k, v)), (qs, q0s))
    return jnp.moveaxis(outs, 0, 2).reshape(b, h, sq, d)


def chunked_attention(q, k, v, bias=None, causal=False, scale=None,
                      chunk=512):
    """Memory-efficient XLA attention on paddle-layout (B, S, H, D)
    tensors — the non-Pallas long-sequence fallback (see _ref_chunked)."""
    sc = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out = _ref_chunked(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                       jnp.swapaxes(v, 1, 2), bias, causal, sc,
                       chunk=chunk)
    return jnp.swapaxes(out, 1, 2)


def _blocks_ok(sq, sk, block_q, block_k):
    return (sq % min(block_q, sq) == 0 and sk % min(block_k, sk) == 0)


def _kernel_takes(q, k, bias, block_q, block_k):
    """Shapes the Pallas kernels accept: block-divisible sequence
    lengths and (when present) a ``[B, 1, 1, S_k]`` bias.  ONE
    predicate shared by the dispatcher (which routes everything else to
    the counted ``fallback``) and the kernel entry (which raises)."""
    sq, sk = q.shape[2], k.shape[2]
    if bias is not None and tuple(bias.shape) != (q.shape[0], 1, 1, sk):
        return False
    return _blocks_ok(sq, sk, block_q, block_k)


def _dropout_blocks_ok(sq, sk, block_q, block_k):
    """Shapes the kernel's dropout path can take: block-divisible seqs
    and <=256 blocks per side (the PRNG packs block coordinates into 8
    bits).  ONE predicate shared by flash_eligible (dispatch) and
    _check_dropout_args (kernel entry) so they cannot drift — dispatch
    saying yes while the kernel raises was advisor finding r4."""
    if not _blocks_ok(sq, sk, block_q, block_k):
        return False
    return max(sq // min(block_q, sq), sk // min(block_k, sk)) <= 256


def dropout_seed(key):
    """Kernel seed-format contract: first word of ``jax.random.key_data``
    bitcast to an int32 ``[1]`` array — the one definition every
    dropout-capable call site (sdpa dispatch, bert attention) shares."""
    import jax
    return jax.lax.bitcast_convert_type(
        jax.random.key_data(key).reshape(-1)[:1], jnp.int32)


def _check_dropout_args(dropout_p, seed, test_mask, sq, sk, block_q,
                        block_k, bias=None):
    if dropout_p > 0.0:
        if bias is not None:
            raise ValueError(
                "flash attention dropout does not compose with an "
                "additive bias (the fused backward has no dbias path "
                "and the fallback backward would silently ignore the "
                "dropout)")
        if seed is None and test_mask is None:
            raise ValueError(
                "flash attention dropout needs a seed (int32 [1] array) "
                "or an injected test mask")
        if not _dropout_blocks_ok(sq, sk, block_q, block_k):
            raise ValueError(
                "flash attention dropout requires block-divisible "
                "sequence lengths with <=256 blocks per side (PRNG "
                f"packs block coords into 8 bits), got sq={sq} sk={sk} "
                f"blocks=({block_q},{block_k})")


def _resolve_blocks(sq, sk, block_q, block_k):
    """Resolve the public ``block_q=block_k=None`` defaults: 512, shrunk
    to 256 at very long sequence lengths — the backward kernels'
    scoped-VMEM working set (dO/O/dQ tiles plus the K/V stream)
    overflows the 16 MB stack at seq 8192 with 512-wide blocks
    (measured: 316 KB over).  Any caller-specified block size — 512
    included — is honored verbatim; only ``None`` auto-resolves, so an
    explicit 512 at seq 8192 is distinguishable from the default (the
    old sentinel-on-512 scheme silently rewrote it)."""
    if block_q is None:
        block_q = 256 if sq >= 8192 else 512
    if block_k is None:
        block_k = 256 if sk >= 8192 else 512
    return block_q, block_k


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _fa_impl(q, k, v, bias=None, seed=None, test_mask=None,
             causal=False, scale=None, block_q=None,
             block_k=None, interpret=False, dropout_p=0.0):
    """Flash attention on (B, H, S, D) tensors (kernel entry — the
    public ``flash_attention_bhsd`` wrapper routes here per the
    registry mode).

    ``bias``: optional additive [B, 1, 1, S_k] tensor (padding masks as
    0/-inf rows), added to the scores before softmax — streamed into the
    Pallas kernel one batch-row at a time, so the [B, H, S, S] score
    tensor still never materializes.

    ``dropout_p`` applies dropout to the normalized attention weights
    INSIDE the kernel: the keep mask is regenerated from the on-chip
    PRNG seeded with (``seed``, batch, head, q-block, k-block), so no
    [B, H, S, S] mask tensor exists and forward/backward agree
    bit-exactly. ``test_mask`` (a full uint8 keep mask) replaces the
    PRNG for parity tests / interpret mode, where the TPU PRNG
    primitives don't lower."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    sq, sk = q.shape[2], k.shape[2]
    block_q, block_k = _resolve_blocks(sq, sk, block_q, block_k)
    _check_dropout_args(dropout_p, seed, test_mask, sq, sk, block_q,
                        block_k, bias)
    if not _kernel_takes(q, k, bias, block_q, block_k):
        # the dispatcher routes such shapes to the reference BEFORE
        # counting; reaching the kernel entry with one is a bug, never
        # a quiet downgrade of a call already counted ``pallas``
        raise ValueError(
            "flash attention kernel needs block-divisible sequence "
            f"lengths and a [B,1,1,S_k] bias, got sq={sq} sk={sk} "
            f"blocks=({block_q},{block_k}) bias="
            f"{None if bias is None else tuple(bias.shape)}")
    return _pallas_forward(q, k, v, bias, causal, scale, block_q,
                           block_k, interpret, dropout_p=dropout_p,
                           seed=seed, test_mask=test_mask)


def _fa_fwd(q, k, v, bias, seed, test_mask, causal, scale, block_q,
            block_k, interpret, dropout_p):
    sc = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    sq, sk = q.shape[2], k.shape[2]
    block_q, block_k = _resolve_blocks(sq, sk, block_q, block_k)
    # custom_vjp skips the primal under differentiation: validate here
    # too or dropout misuse surfaces as opaque unpack errors / silently
    # dropout-free gradients
    _check_dropout_args(dropout_p, seed, test_mask, sq, sk, block_q,
                        block_k, bias)
    if bias is None and _blocks_ok(sq, sk, block_q, block_k):
        # fused path: forward also emits the logsumexp rows the Pallas
        # backward kernels need (FlashAttention-2 recomputation scheme)
        out, lse = _pallas_forward(q, k, v, None, causal, sc, block_q,
                                   block_k, interpret, with_lse=True,
                                   dropout_p=dropout_p, seed=seed,
                                   test_mask=test_mask)
        return out, (q, k, v, bias, seed, test_mask, out, lse)
    # bias path (or a shape the kernel does not take: the entry raises)
    out = _fa_impl(q, k, v, bias, seed, test_mask, causal,
                   scale, block_q, block_k, interpret, dropout_p)
    return out, (q, k, v, bias, seed, test_mask, None, None)


def _fa_bwd(causal, scale, block_q, block_k, interpret, dropout_p, res,
            g):
    q, k, v, bias, seed, test_mask, out, lse = res
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    block_q, block_k = _resolve_blocks(q.shape[2], k.shape[2],
                                       block_q, block_k)
    if lse is not None:
        dq, dk, dv = _pallas_backward(q, k, v, out, lse, g, causal, s,
                                      block_q, block_k, interpret,
                                      dropout_p=dropout_p, seed=seed,
                                      test_mask=test_mask)
        return dq, dk, dv, None, None, None
    # bias path: the fused backward has no dbias kernel, so the
    # backward (and only the backward) differentiates the reference
    _, vjp = jax.vjp(
        lambda q_, k_, v_, b_: _ref_chunked(q_, k_, v_, b_, causal, s),
        q, k, v, bias)
    return (*vjp(g), None, None)


_fa_impl.defvjp(_fa_fwd, _fa_bwd)


def _under_mesh(kernel, q, k, v, bias, seed, test_mask):
    """Run the kernel entry per shard when a mesh of more than one
    device is installed: Mosaic custom calls cannot be partitioned by
    XLA's SPMD pass, so the call is made manual with ``jax.shard_map``
    — batch over the live data axes, heads over the attention-heads
    axis ('tp'), specs from the canonical SpecLayout.  A dim the axes
    do not divide stays replicated (correct, redundantly computed).

    Composes with an enclosing partial-manual region (the 'pp'
    shard_map in distributed/pipeline.py): only the still-automatic
    axes are made manual, against the context mesh.  Inside a fully
    manual region (ring / ulysses attention bodies) the arrays are
    already per-shard and the kernel is called directly.  With no mesh,
    or a one-device mesh, the program is exactly the unwrapped call."""
    from ...distributed import mesh as mesh_mod
    from ...distributed.planner.spec_layout import get_layout
    mesh = mesh_mod.get_mesh(create=False)
    if mesh is None or mesh.size == 1:
        return kernel(q, k, v, bias, seed, test_mask)
    ctx = jax.sharding.get_abstract_mesh()
    manual = set() if ctx.empty else set(ctx.manual_axes)
    auto = [a for a in mesh.axis_names if a not in manual]
    if not auto:
        return kernel(q, k, v, bias, seed, test_mask)
    lay = get_layout()

    b_axes = tuple(a for a in lay.act_axis("batch")
                   if a in auto and mesh.shape[a] > 1)
    if q.shape[0] % math.prod(mesh.shape[a] for a in b_axes):
        b_axes = ()
    h_ax = lay.act_axis("attn_heads")
    if not (h_ax in auto and mesh.shape[h_ax] > 1
            and q.shape[1] % mesh.shape[h_ax] == 0):
        h_ax = None
    qspec = lay.attention_bhsd(b_axes, h_ax)
    # optional operands ride along only when present
    extra = [(bias, lay.attention_bhsd(b_axes, None)),   # [B,1,1,S_k]
             (seed, lay.replicated()),
             (test_mask, qspec)]                          # [B,H,S_q,S_k]
    present = [(x, s) for x, s in extra if x is not None]
    sharded = b_axes + ((h_ax,) if h_ax else ())

    def body(q_, k_, v_, *rest):
        rest = iter(rest)
        bias_, seed_, mask_ = (next(rest) if x is not None else None
                               for x, _ in extra)
        if seed_ is not None and sharded:
            # the kernel seeds its PRNG with (seed, LOCAL b, h, blocks):
            # fold the shard's index in so two shards never draw the
            # same mask (only over axes that shard the call — a
            # replicated axis must keep identical draws)
            idx = jnp.int32(0)
            for ax in sharded:
                idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
            seed_ = seed_ + idx.astype(jnp.int32) * jnp.int32(40503)
        return kernel(q_, k_, v_, bias_, seed_, mask_)

    kw = {"mesh": mesh} if ctx.empty else {}
    return jax.shard_map(
        body, in_specs=(qspec,) * 3 + tuple(s for _, s in present),
        out_specs=qspec, axis_names=set(auto), check_vma=False,
        **kw)(q, k, v, *(x for x, _ in present))


def flash_attention_bhsd(q, k, v, bias=None, seed=None, test_mask=None,
                         causal=False, scale=None, block_q=None,
                         block_k=None, interpret=False, dropout_p=0.0):
    """Registry-dispatched flash attention on (B, H, S, D) tensors.

    Routing (see :mod:`paddle_tpu.ops.pallas.registry`), decided BEFORE
    the dispatch is counted: ``xla_ref`` mode runs the chunked-recompute
    XLA reference; shapes the kernel does not take (non-dividing
    blocks, a bias that is not ``[B,1,1,S_k]``) run it too, counted
    ``fallback``; everything else runs the Pallas kernel — compiled, or
    under the interpreter in ``interpret`` mode (an explicit
    ``interpret=True`` from the caller — the parity tests — forces
    this regardless of mode).  A call counted ``pallas`` /
    ``interpret`` therefore ran the kernel or raised.  A
    ``dropout_p > 0`` call always takes the kernel: the reference has
    no dropout path, exactly the constraint ``flash_eligible`` encodes
    for dispatch-level callers.  Under an installed multi-device mesh
    the kernel runs per shard (:func:`_under_mesh`).  See ``_fa_impl``
    for the kernel semantics (bias streaming, on-chip PRNG dropout, the
    custom-vjp backward).
    """
    mode = registry.resolve("flash_attention")
    if interpret:
        mode = "interpret"
    elif mode == "interpret":
        interpret = True
    if dropout_p == 0.0:
        path = None
        if mode == "xla_ref":
            path = "xla_ref"
        elif not _kernel_takes(q, k, bias, *_resolve_blocks(
                q.shape[2], k.shape[2], block_q, block_k)):
            path = "fallback"
        if path is not None:
            registry.note("flash_attention", path)
            sc = (scale if scale is not None
                  else 1.0 / math.sqrt(q.shape[-1]))
            return _ref_chunked(q, k, v, bias, causal, sc)
    registry.note("flash_attention", "pallas" if mode == "xla_ref"
                  else mode)

    def kernel(q_, k_, v_, bias_, seed_, mask_):
        return _fa_impl(q_, k_, v_, bias_, seed_, mask_, causal, scale,
                        block_q, block_k, interpret, dropout_p)
    return _under_mesh(kernel, q, k, v, bias, seed, test_mask)


def flash_eligible(seq_len: int, head_dim: int, *, has_mask: bool = False,
                   dropout: float = 0.0, mask_shape=None,
                   mask_dtype=None, kv_seq_len=None) -> bool:
    """Single source of truth for Pallas flash-attention dispatch: long
    sequences with MXU-friendly head dims on TPU. Additive [B,1,1,S]
    float masks stream through the kernel (pass mask_shape/mask_dtype to
    vet them). With dropout > 0 the kernel applies it to the normalized
    weights via the on-chip PRNG — long sequences only (measured on a
    v5e at seq 128/BERT-base geometry the fused kernel LOSES to XLA's
    composition, 112k vs 166k tok/s: tiny per-(batch,head) programs pay
    more in launch overhead than the mask/RNG traffic they save) and
    only without a mask (the fused backward has no dbias path).

    ``PADDLE_TPU_FLASH_MIN_SEQ`` overrides the sequence-length floor
    (default 1024) for A/B experiments in the short-seq regime."""
    import os

    from ...distributed.mesh import target_platform
    min_seq = int(os.environ.get("PADDLE_TPU_FLASH_MIN_SEQ", "1024"))
    if not (target_platform() == "tpu"
            and head_dim in (64, 128, 256) and seq_len >= min_seq):
        return False
    if dropout > 0.0:
        if has_mask or mask_shape is not None:
            return False
        # dropout runs ONLY in the fused kernel (the chunked reference
        # fallback has no dropout path), so the kernel's block
        # constraints gate dispatch here — shapes the kernel would
        # reject must fall back to the XLA composition, not raise
        sk = kv_seq_len if kv_seq_len is not None else seq_len
        return _dropout_blocks_ok(seq_len, sk,
                                  *_resolve_blocks(seq_len, sk, None,
                                                   None))
    if not has_mask and mask_shape is None:
        return True
    if mask_shape is None:      # mask present but un-vettable
        return False
    return (len(mask_shape) == 4 and mask_shape[1] == 1
            and mask_shape[2] == 1
            and (mask_dtype is None
                 or jnp.issubdtype(mask_dtype, jnp.floating)))


registry.register(
    "flash_attention",
    lambda q, k, v, bias=None, causal=False, scale=None,
    interpret=False: _fa_impl(q, k, v, bias, None, None, causal,
                              scale, None, None, interpret, 0.0),
    lambda q, k, v, bias=None, causal=False, scale=None: _ref_chunked(
        q, k, v, bias, causal,
        scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])),
    tolerance="atol ~1e-4 vs the chunked XLA reference (blockwise "
              "online softmax vs full softmax; measured in BENCH_r04); "
              "fwd+bwd self-parity pinned by tests/test_flash_attention",
    doc="blockwise flash attention (fwd + custom-vjp bwd); routes "
        "itself through the public flash_attention_bhsd wrapper — the "
        "registry entry carries the mode, counters and this table row",
)


def flash_attention(q, k, v, bias=None, causal=False, scale=None,
                    block_q=None, block_k=None, interpret=False,
                    dropout_p=0.0, seed=None):
    """Flash attention on paddle-layout (B, S, H, D) tensors."""
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhsd(qh, kh, vh, bias=bias, seed=seed,
                               causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret, dropout_p=dropout_p)
    return jnp.swapaxes(out, 1, 2)
