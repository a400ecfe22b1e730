"""Llama-family decoder-only LM — the flagship long-context model.

The 2021-era reference has no Llama; its largest NLP target is the ERNIE/
BERT encoder family trained with Fleet collective (reference:
python/paddle/distributed/fleet/, python/paddle/nn/layer/transformer.py).
This model is the greenfield long-context capability SURVEY.md §5.7 calls
for, designed TPU-first:

- every projection is a tensor-parallel layer (``ColumnParallelLinear`` /
  ``RowParallelLinear`` / ``VocabParallelEmbedding``) whose parameters
  carry PartitionSpecs over the 'tp' mesh axis — XLA SPMD derives the
  collectives, no ``c_allreduce`` ops;
- attention dispatches to the Pallas flash-attention kernel for long
  sequences (ops/flash_attention.py), and under a 'sp' mesh axis the
  sequence dimension is sharded (ring/all-to-all handled by XLA SPMD +
  sharding constraints, see distributed/sequence_parallel.py);
- bf16-first: matmul-heavy compute runs in ``bfloat16`` on the MXU while
  params/norms stay fp32 (the reference's AMP white/black lists,
  python/paddle/fluid/contrib/mixed_precision/fp16_lists.py, collapse into
  this dtype policy);
- rematerialisation boundaries per decoder layer via ``remat=True`` map to
  ``jax.checkpoint`` (reference: RecomputeOptimizer,
  python/paddle/fluid/backward.py:725).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

import paddle_tpu.nn.functional as F
from ...distributed import mesh as mesh_mod
from ...distributed.planner.spec_layout import get_layout as _layout
from ...distributed.meta_parallel import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)
from ...framework.core import Tensor, _apply
from ...nn.initializer import Constant, Normal
from ...nn.layer.layers import Layer, Parameter
from ...observability.scopes import ATTN, EMBED, FFN, HEAD

__all__ = [
    "KVCacheUnsupportedError",
    "LlamaConfig", "LlamaForCausalLM", "LlamaModel", "RMSNorm",
    "llama_tiny", "llama_7b", "llama_13b",
]


class KVCacheUnsupportedError(NotImplementedError):
    """Raised when incremental (KV-cache / paged) decode is requested on
    a model configuration that cannot serve it.  Subclasses
    NotImplementedError so pre-existing ``except NotImplementedError``
    and ``except RuntimeError`` callers keep working; the message always
    names the workaround (build with ``scan_layers=False``)."""


# tests pin this message: it must keep naming the scan_layers=False
# workaround verbatim
_SCAN_LAYERS_KV_MSG = (
    "KV-cache decoding is not supported with scan_layers=True (stacked "
    "decoder: lax.scan carries no per-layer cache); build the model "
    "with scan_layers=False for incremental generation")


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None  # GQA; None -> MHA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    remat: bool = True            # per-layer activation checkpointing
    compute_dtype: str = "bfloat16"
    sequence_parallel: bool = False  # shard activations' seq dim over 'sp'
    # context-parallel attention over 'sp': None -> XLA-derived from the
    # activation sharding; "ring" -> ring attention (ppermute KV rotation,
    # ops/ring_attention.py); "ulysses" -> all-to-all head scatter
    context_parallel: Optional[str] = None
    scan_layers: bool = False     # stack layer params, lax.scan the depth
    pp_num_microbatches: int = 1  # GPipe microbatches when mesh has pp>1
    # paged-KV pool dtype (ISSUE 11 satellite / ROADMAP item 2 hook):
    # None -> compute_dtype; "int8" -> quantized pools with a per-block
    # [num_blocks, block_size] f32 scale tensor per pool (symmetric
    # per-token scales, quantize on write / dequantize on read); any
    # other value is taken as a plain storage dtype for the pools
    kv_cache_dtype: Optional[str] = None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads


def llama_tiny(**kw) -> LlamaConfig:
    """Small config for tests / compile checks."""
    d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, max_position_embeddings=128,
             remat=False)
    d.update(kw)
    return LlamaConfig(**d)


def llama_7b(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def llama_13b(**kw) -> LlamaConfig:
    d = dict(hidden_size=5120, intermediate_size=13824,
             num_hidden_layers=40, num_attention_heads=40)
    d.update(kw)
    return LlamaConfig(**d)


class RMSNorm(Layer):
    """y = x / rms(x) * w — computed in fp32 regardless of input dtype."""

    def __init__(self, hidden_size: int, eps: float = 1e-5):
        super().__init__()
        self.eps = eps
        self.weight = Parameter(Constant(1.0)((hidden_size,)))

    def forward(self, x):
        eps = self.eps

        def f(v, w):
            h = v.astype(jnp.float32)
            var = jnp.mean(h * h, axis=-1, keepdims=True)
            h = h * jax.lax.rsqrt(var + eps)
            return (h * w).astype(v.dtype)
        return _apply(f, x, self.weight, op_name="rms_norm")


def _rope(x, positions, theta: float):
    """Rotary position embedding on (B, S, H, D)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, :, None].astype(jnp.float32) * freq  # B,S,D/2
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def paged_write_attend(qh, kh, vh, pos, wm, kpool, vpool, tbl,
                       kscale=None, vscale=None, *, kv_kernel, kv_mode,
                       verify_mode=False):
    """What every attention layer with block-paged K/V does once its
    queries and keys are ready (projected, normalised, rotated, as the
    model has it): write this call's K/V into the pools through the
    block table, then attend -- a fresh block over itself (flash or
    XLA), a decode step or a verify call over the pools.  ``qh``
    [B, S, H, D], ``kh``/``vh`` [B, S, KH, D]; pools
    ``[num_blocks, block, KH, D]`` (or with the last two dims as one,
    as a model with a head narrower than a lane tile keeps them);
    int8 pools come with ``kscale``/``vscale``.  ``kv_kernel`` and
    ``kv_mode`` are the registry's kernel and its mode, resolved by
    the caller outside any trace.  Returns ``(o [B, S, H*D], kpool,
    vpool[, kscale, vscale])``.  :meth:`LlamaAttention.forward_paged`
    documents the contracts (trash block, verify mode, int8 pools)."""
    from ...ops.pallas import registry as _kreg
    B, S, H, D = qh.shape
    KH = kh.shape[2]
    bs = kpool.shape[1]
    quant = kscale is not None
    # scatter this call's K/V into the pools: physical block =
    # table[logical block], offset = pos % block_size; masked
    # writes divert to the trash block (0, 0)
    blk_log = (pos // bs).astype(jnp.int32)
    blk_phys = jnp.take_along_axis(tbl, blk_log, axis=1)
    off = (pos % bs).astype(jnp.int32)
    blk_phys = jnp.where(wm, blk_phys, 0)
    off = jnp.where(wm, off, 0)
    fb = blk_phys.reshape(-1)
    fo = off.reshape(-1)
    kfl = kh.reshape(B * S, KH, D)
    vfl = vh.reshape(B * S, KH, D)
    page_row = kpool.shape[2:]          # (KH, D), or (KH*D,)
    if quant:
        # symmetric per-token int8: one f32 scale per written
        # (block, slot), stored beside the rows so dequant is a
        # gather of exactly what the write saw (replay-stable)
        ksc = jnp.maximum(jnp.max(jnp.abs(
            kfl.astype(jnp.float32)), axis=(1, 2)) / 127.0, 1e-8)
        vsc = jnp.maximum(jnp.max(jnp.abs(
            vfl.astype(jnp.float32)), axis=(1, 2)) / 127.0, 1e-8)
        kpool = kpool.at[fb, fo].set(jnp.clip(jnp.round(
            kfl.astype(jnp.float32) / ksc[:, None, None]),
            -127, 127).astype(jnp.int8))
        vpool = vpool.at[fb, fo].set(jnp.clip(jnp.round(
            vfl.astype(jnp.float32) / vsc[:, None, None]),
            -127, 127).astype(jnp.int8))
        kscale = kscale.at[fb, fo].set(ksc)
        vscale = vscale.at[fb, fo].set(vsc)
    else:
        kpool = kpool.at[fb, fo].set(
            kfl.astype(kpool.dtype).reshape((B * S,) + page_row))
        vpool = vpool.at[fb, fo].set(
            vfl.astype(vpool.dtype).reshape((B * S,) + page_row))

    def ret(o):
        out = (o.reshape(B, S, H * D), kpool, vpool)
        return out + (kscale, vscale) if quant else out

    if S > 1 and not verify_mode:
        # PREFILL: causal attention over the fresh block equals
        # attention against the just-written cache (contiguous
        # positions from 0) — use the flash/sdpa path; the
        # scattered K/V stay behind for decode.  Right-padding
        # is causal-safe: a real token never attends forward.
        kh2, vh2 = kh, vh
        if KH != H:
            rep = H // KH
            kh2 = jnp.repeat(kh, rep, axis=2)
            vh2 = jnp.repeat(vh, rep, axis=2)
        from ...nn.functional.attention import _sdpa_ref
        from ...ops.flash_attention import (flash_attention as
                                            _fa_t, flash_eligible)
        if flash_eligible(S, D):
            o = _fa_t(qh, kh2, vh2, causal=True)
        else:
            o = _sdpa_ref(qh, kh2, vh2, None, 0.0, True, None)
        return ret(o)
    # DECODE / VERIFY: gather the sequence's cache through its
    # block table — [B, M, bs, KH, D] -> [B, M*bs, KH, D] in
    # logical position order — then the same grouped-query
    # masked attention as :meth:`_forward_cached` (slot index
    # == absolute position, valid iff slot <= query position).
    # In verify mode the queries' own K/V were written above,
    # so slot <= pos is simultaneously the causal mask within
    # the block and the prefix mask against the cache.
    #
    # The gather/attend math lives in ops/pallas/kv_attention.
    # paged_attention_ref (lifted verbatim, so the non-pallas
    # serving contracts — replay, prefix sharing, eviction — are
    # pinned by the SAME ops); the registry picks, per traced
    # program and from what this call sees, a kernel that reads
    # the pools through the table instead: ``paged_attention``
    # for a decode step (S == 1, bf16 pools, TPU target),
    # ``int8_kv_attention`` for int8 pools (xla_ref on TPU
    # until it lowers).  Verify and suffix-prefill calls keep
    # the reference.
    mode = kv_mode
    if not quant and (S > 1 or verify_mode):
        mode = "xla_ref"
    o = _kreg.dispatch(kv_kernel, qh, kpool, vpool, kscale,
                       vscale, tbl, pos, KH, mode=mode)
    return ret(o)


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.config = c
        init = Normal(0.0, c.initializer_range)
        self.q_proj = ColumnParallelLinear(
            c.hidden_size, c.num_attention_heads * c.head_dim,
            weight_attr=init, has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(
            c.hidden_size, c.kv_heads * c.head_dim,
            weight_attr=init, has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(
            c.hidden_size, c.kv_heads * c.head_dim,
            weight_attr=init, has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(
            c.num_attention_heads * c.head_dim, c.hidden_size,
            weight_attr=init, has_bias=False, input_is_parallel=True)

    def forward(self, hidden, positions, cache=None):
        c = self.config
        q = self.q_proj(hidden)
        k = self.k_proj(hidden)
        v = self.v_proj(hidden)
        if cache is not None:
            return self._forward_cached(q, k, v, positions, cache)

        def attn(qv, kv, vv, pos):
            B, S = qv.shape[0], qv.shape[1]
            qh = qv.reshape(B, S, c.num_attention_heads, c.head_dim)
            kh = kv.reshape(B, S, c.kv_heads, c.head_dim)
            vh = vv.reshape(B, S, c.kv_heads, c.head_dim)
            qh = _rope(qh, pos, c.rope_theta)
            kh = _rope(kh, pos, c.rope_theta)
            # heads stay sharded over 'tp' through the attention
            qh = mesh_mod.constrain_dim(qh, 2, _layout().act_axis("attn_heads"))
            if c.kv_heads != c.num_attention_heads:
                rep = c.num_attention_heads // c.kv_heads
                kh = jnp.repeat(kh, rep, axis=2)
                vh = jnp.repeat(vh, rep, axis=2)
            from ...nn.functional.attention import _sdpa_ref
            from ...ops.flash_attention import flash_attention as _fa_t
            from ...ops.flash_attention import flash_eligible
            if c.context_parallel and mesh_mod.mesh_axis_size("sp") > 1:
                from ...ops.ring_attention import (ring_attention,
                                                   ulysses_attention)
                if c.context_parallel == "ring":
                    cp = ring_attention
                elif c.context_parallel == "ulysses":
                    cp = ulysses_attention
                else:
                    raise ValueError(
                        "context_parallel must be 'ring' or 'ulysses', "
                        "got %r" % (c.context_parallel,))
                o = cp(qh, kh, vh, causal=True)
            elif flash_eligible(S, c.head_dim):
                o = _fa_t(qh, kh, vh, causal=True)
            elif S >= 1024:
                # flash-ineligible long sequence (odd head dims, or a
                # CPU-mesh dryrun): query-chunked attention with
                # per-chunk remat bounds the score block to
                # [B, H, chunk, S] instead of [B, H, S, S]
                from ...ops.flash_attention import chunked_attention
                o = chunked_attention(qh, kh, vh, causal=True)
            else:
                o = _sdpa_ref(qh, kh, vh, None, 0.0, True, None)
            return o.reshape(B, S, c.num_attention_heads * c.head_dim)

        ctx = _apply(attn, q, k, v, positions, op_name="llama_attention")
        return self.o_proj(ctx)

    def _forward_cached(self, q, k, v, positions, cache):
        """Incremental decode: write this call's K/V into the cache
        buffers at ``positions`` and attend the (few) query tokens against
        the whole prefix. Cache = {"k": [B,Smax,KH,D], "v": ...}; slot
        index == absolute position, so the validity mask is simply
        key_slot <= query_position (RoPE is applied before caching, like
        every standard KV-cache implementation)."""
        c = self.config

        def attn_cached(qv, kv, vv, pos, kbuf, vbuf):
            B, S = qv.shape[0], qv.shape[1]
            Smax = kbuf.shape[1]
            qh = qv.reshape(B, S, c.num_attention_heads, c.head_dim)
            kh = kv.reshape(B, S, c.kv_heads, c.head_dim)
            vh = vv.reshape(B, S, c.kv_heads, c.head_dim)
            qh = _rope(qh, pos, c.rope_theta)
            kh = _rope(kh, pos, c.rope_theta)
            qh = mesh_mod.constrain_dim(qh, 2, _layout().act_axis("attn_heads"))  # heads stay sharded
            bidx = jnp.arange(B)[:, None]
            kbuf = kbuf.at[bidx, pos].set(kh.astype(kbuf.dtype))
            vbuf = vbuf.at[bidx, pos].set(vh.astype(vbuf.dtype))
            if S > 1:
                # PREFILL (empty cache, contiguous positions from 0):
                # causal attention over the block equals attention against
                # the cache — use the flash/sdpa path instead of the
                # [B,H,S,Smax] logits tensor (quadratic in the FULL
                # buffer), then keep the scattered K/V for decode
                kh2, vh2 = kh, vh
                if c.kv_heads != c.num_attention_heads:
                    rep = c.num_attention_heads // c.kv_heads
                    kh2 = jnp.repeat(kh, rep, axis=2)
                    vh2 = jnp.repeat(vh, rep, axis=2)
                from ...nn.functional.attention import _sdpa_ref
                from ...ops.flash_attention import (flash_attention as
                                                    _fa_t, flash_eligible)
                if flash_eligible(S, c.head_dim):
                    o = _fa_t(qh, kh2, vh2, causal=True)
                else:
                    o = _sdpa_ref(qh, kh2, vh2, None, 0.0, True, None)
                return (o.reshape(B, S,
                                  c.num_attention_heads * c.head_dim),
                        kbuf, vbuf)
            # GQA: group the query heads instead of materialising a
            # repeated [B,Smax,H,D] copy of the cache every step
            G = c.kv_heads
            R = c.num_attention_heads // G
            qg = qh.reshape(B, S, G, R, c.head_dim)
            scale = 1.0 / (c.head_dim ** 0.5)
            logits = jnp.einsum(
                "bsgrd,btgd->bgrst", qg.astype(jnp.float32),
                kbuf.astype(jnp.float32)) * scale      # [B,G,R,S,Smax]
            valid = (jnp.arange(Smax)[None, None, None, None, :]
                     <= pos[:, None, None, :, None])
            logits = jnp.where(valid, logits, -jnp.inf)
            w = jax.nn.softmax(logits, axis=-1)
            o = jnp.einsum("bgrst,btgd->bsgrd", w,
                           vbuf.astype(jnp.float32)).astype(qv.dtype)
            return (o.reshape(B, S, c.num_attention_heads * c.head_dim),
                    kbuf, vbuf)

        ctx, kbuf, vbuf = _apply(attn_cached, q, k, v, positions,
                                 cache["k"], cache["v"],
                                 op_name="llama_attention_cached")
        return self.o_proj(ctx), {"k": kbuf, "v": vbuf}

    def forward_paged(self, hidden, positions, cache, block_tables,
                      write_mask, verify_mode: bool = False):
        """Block-paged variant of :meth:`_forward_cached` (continuous-
        batching serving, ISSUE 8).  K/V live in fixed-shape pools
        ``[num_blocks, block_size, KH, D]`` shared by every sequence; a
        per-sequence ``block_tables`` row [max_blocks] maps logical
        block ``pos // block_size`` to its physical pool block, so a
        sequence's cache is a gather over its table instead of a
        dedicated ``[B, Smax]`` buffer.  Physical block ids never enter
        the math — the gathered tensor is in logical position order —
        which is what makes an evicted + re-admitted sequence's decode
        bit-identical regardless of which blocks it lands on.

        ``write_mask`` [B, S] routes masked-off positions' K/V writes
        (prompt padding, inactive decode slots) to physical block 0,
        which is reserved as a trash block and never allocated; the
        validity mask (slot <= query position) guarantees trash is
        never read.

        ``verify_mode`` (ISSUE 11): a multi-token call whose positions
        do NOT start at 0 — speculative-decode verification and
        prefix-cache suffix prefill both feed an S>1 block that must
        attend against the EXISTING cache plus itself.  The fresh-block
        flash path assumes an empty cache, so verify mode takes the
        gather path instead: writes land first, then every query
        attends the gathered table with the slot <= position mask
        (causal within the block AND against the prefix by the same
        inequality).

        Quantized pools (``kv_cache_dtype="int8"``): the cache dict
        additionally carries ``k_scale`` / ``v_scale``
        ``[num_blocks, block_size]`` f32 tensors; writes store a
        symmetric per-token scale next to the int8 rows and the gather
        path dequantizes with the gathered scales.
        """
        c = self.config
        q = self.q_proj(hidden)
        k = self.k_proj(hidden)
        v = self.v_proj(hidden)
        quant = "k_scale" in cache
        # ISSUE 13: kernel mode resolved OUTSIDE the traced closure and
        # bound into it, so any dispatch cache keys on the mode (a mode
        # switch must never replay the other path's program)
        from ...ops.pallas import registry as _kreg
        kv_kernel = "int8_kv_attention" if quant else "paged_attention"
        kv_mode = _kreg.resolve(kv_kernel)

        def attn_paged(qv, kv, vv, pos, wm, kpool, vpool, tbl,
                       kscale=None, vscale=None):
            B, S = qv.shape[0], qv.shape[1]
            qh = qv.reshape(B, S, c.num_attention_heads, c.head_dim)
            kh = kv.reshape(B, S, c.kv_heads, c.head_dim)
            vh = vv.reshape(B, S, c.kv_heads, c.head_dim)
            qh = _rope(qh, pos, c.rope_theta)
            kh = _rope(kh, pos, c.rope_theta)
            qh = mesh_mod.constrain_dim(qh, 2, _layout().act_axis("attn_heads"))  # heads stay sharded
            return paged_write_attend(
                qh, kh, vh, pos, wm, kpool, vpool, tbl, kscale, vscale,
                kv_kernel=kv_kernel, kv_mode=kv_mode,
                verify_mode=verify_mode)

        if quant:
            ctx, kpool, vpool, ksc, vsc = _apply(
                attn_paged, q, k, v, positions, write_mask,
                cache["k"], cache["v"], block_tables,
                cache["k_scale"], cache["v_scale"],
                op_name="llama_attention_paged")
            return self.o_proj(ctx), {"k": kpool, "v": vpool,
                                      "k_scale": ksc, "v_scale": vsc}
        ctx, kpool, vpool = _apply(attn_paged, q, k, v, positions,
                                   write_mask, cache["k"], cache["v"],
                                   block_tables,
                                   op_name="llama_attention_paged")
        return self.o_proj(ctx), {"k": kpool, "v": vpool}


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        init = Normal(0.0, c.initializer_range)
        self.gate_proj = ColumnParallelLinear(
            c.hidden_size, c.intermediate_size, weight_attr=init,
            has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(
            c.hidden_size, c.intermediate_size, weight_attr=init,
            has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(
            c.intermediate_size, c.hidden_size, weight_attr=init,
            has_bias=False, input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, hidden, positions, cache=None):
        if cache is None:
            h = hidden + self.self_attn(self.input_layernorm(hidden),
                                        positions)
            return h + self.mlp(self.post_attention_layernorm(h))
        attn_out, cache = self.self_attn(self.input_layernorm(hidden),
                                         positions, cache)
        h = hidden + attn_out
        return h + self.mlp(self.post_attention_layernorm(h)), cache

    def forward_paged(self, hidden, positions, cache, block_tables,
                      write_mask, verify_mode: bool = False):
        with jax.named_scope(ATTN):
            attn_out, cache = self.self_attn.forward_paged(
                self.input_layernorm(hidden), positions, cache,
                block_tables, write_mask, verify_mode=verify_mode)
            h = hidden + attn_out
        with jax.named_scope(FFN):
            return h + self.mlp(self.post_attention_layernorm(h)), cache


class StackedLlamaDecoder(Layer):
    """The decoder stack with layer-STACKED parameters.

    Every parameter has a leading layer dim scanned by ``lax.scan`` —
    the standard JAX LLM idiom (one compiled layer body instead of L
    inlined copies), and the exact layout pipeline parallelism needs: the
    leading dim carries ``P('pp', ...)`` so each pipeline stage owns a
    contiguous chunk of layers (distributed/pipeline.py).  The reference
    has no analog — its PipelineOptimizer cuts a flat Program per device
    (fluid/optimizer.py:3718); here the cut is a sharding annotation.
    """

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        L = config.num_hidden_layers
        layers = [LlamaDecoderLayer(config) for _ in range(L)]
        proto = layers[0]
        object.__setattr__(self, "_proto", proto)  # not a registered child
        self._names = [n for n, _ in proto.named_parameters()]
        from ...distributed.meta_parallel import mark_sharding
        for n in self._names:
            vals = [dict(l.named_parameters())[n]._value for l in layers]
            if isinstance(vals[0], jax.ShapeDtypeStruct):
                # meta-init construction (framework.core.abstract_init):
                # stack avals, not storage
                stacked = Parameter(jax.ShapeDtypeStruct(
                    (len(vals),) + tuple(vals[0].shape), vals[0].dtype))
            else:
                stacked = Parameter(jnp.stack(vals))
            ann = getattr(dict(proto.named_parameters())[n], "dist_spec",
                          None)
            spec = _layout().stack(ann, stacked._value.ndim)
            mark_sharding(stacked, spec)
            self.add_parameter(n.replace(".", "__"), stacked)

    def _stacked_values(self):
        return {n: getattr(self, n.replace(".", "__"))._value
                for n in self._names}

    def _apply_one_layer(self, per_layer_vals, h, positions):
        """Functionally run the proto layer with one layer's params."""
        proto = self._proto
        st = dict(proto.named_parameters())
        old = {k: t._value for k, t in st.items()}
        try:
            for k in self._names:
                st[k]._value = per_layer_vals[k]
            out = proto(Tensor(h), Tensor(positions))
        finally:
            for k, t in st.items():
                t._value = old[k]
        return out._value

    def forward(self, hidden, positions):
        from ...distributed.pipeline import num_stages, pipeline_apply
        cfg = self.config
        names = self._names
        remat = cfg.remat

        def body_fn(h, per_layer, pos):
            return self._apply_one_layer(per_layer, h, pos)
        if remat:
            body_fn = jax.checkpoint(body_fn)

        def stage_fn(local_stacked, h, pos):
            def body(hh, per_layer):
                out = body_fn(hh, per_layer, pos)
                # f32 params promote a bf16 carry (bf16 x f32 -> f32);
                # scan requires carry-in == carry-out, so fold the layer
                # output back to the compute dtype
                return out.astype(hh.dtype), None
            h2, _ = jax.lax.scan(body, h, local_stacked)
            return h2

        def f(hval, pval, *stacked_vals):
            stacked = dict(zip(names, stacked_vals))
            S = num_stages()
            if S > 1:
                return pipeline_apply(
                    stage_fn, stacked, hval, pval,
                    num_microbatches=max(cfg.pp_num_microbatches, 1))
            return stage_fn(stacked, hval, pval)

        tensors = [getattr(self, n.replace(".", "__")) for n in names]
        return _apply(f, hidden, positions, *tensors,
                      op_name="stacked_decoder")


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        from ...nn.layer.container import LayerList
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))
        if config.scan_layers:
            self.decoder = StackedLlamaDecoder(config)
            self.layers = LayerList([])
        else:
            self.decoder = None
            self.layers = LayerList(
                [LlamaDecoderLayer(config)
                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, positions=None, caches=None):
        c = self.config
        if positions is None:
            S = input_ids.shape[1]
            positions = _apply(
                lambda ids: jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32)[None, :], ids.shape),
                input_ids, op_name="positions")
        hidden = self.embed_tokens(input_ids)
        if c.compute_dtype:
            hidden = hidden.astype(c.compute_dtype)
        # re-anchor the batch sharding on the embedded activations
        # (ISSUE 15, found by the planner's verify phase): on non-pp
        # hybrid meshes XLA's propagation otherwise GUESSES from the
        # gather output and replicated the ENTIRE activation path —
        # full-batch scores/logits on every device (measured: a
        # 16-row proxy on fsdp8 spent 224 MiB/device of temps where
        # sharded accounting says 26).  pipeline.py's split() applies
        # the same cure after its microbatch reshape, for the same
        # documented reason.  No live data axis -> identity, so
        # single-device programs are bit-identical.
        hidden = _apply(lambda v: mesh_mod.constrain_dim(
            v, 0, _layout().act_axis("batch")), hidden)
        if c.sequence_parallel:
            hidden = _apply(lambda v: mesh_mod.constrain_dim(
                v, 1, _layout().act_axis("seq")),
                            hidden)
        if caches is not None:
            if self.decoder is not None:
                raise KVCacheUnsupportedError(_SCAN_LAYERS_KV_MSG)
            new_caches = []
            for layer, cache in zip(self.layers, caches):
                hidden, cache = layer(hidden, positions, cache)
                new_caches.append(cache)
            return self.norm(hidden), new_caches
        if self.decoder is not None:
            hidden = self.decoder(hidden, positions)
        else:
            for layer in self.layers:
                if c.remat:
                    hidden = _remat_layer(layer, hidden, positions)
                else:
                    hidden = layer(hidden, positions)
        return self.norm(hidden)

    def forward_paged(self, input_ids, positions, pools, block_tables,
                      write_mask, verify_mode: bool = False):
        """Paged-KV forward: ``pools`` is one {"k","v"} pool dict per
        layer (plus ``k_scale``/``v_scale`` for int8 pools),
        ``block_tables`` [B, max_blocks] int32, ``write_mask`` [B, S]
        bool.  ``verify_mode``: multi-token blocks whose positions
        start mid-sequence (spec-decode verify, suffix prefill) attend
        through the cache gather instead of the fresh-block prefill
        path.  Returns (hidden, new_pools)."""
        c = self.config
        if self.decoder is not None:
            raise KVCacheUnsupportedError(_SCAN_LAYERS_KV_MSG)
        with jax.named_scope(EMBED):
            hidden = self.embed_tokens(input_ids)
            if c.compute_dtype:
                hidden = hidden.astype(c.compute_dtype)
        new_pools = []
        for layer, pool in zip(self.layers, pools):
            hidden, pool = layer.forward_paged(hidden, positions, pool,
                                               block_tables, write_mask,
                                               verify_mode=verify_mode)
            new_pools.append(pool)
        with jax.named_scope(HEAD):
            return self.norm(hidden), new_pools


def _remat_layer(layer: LlamaDecoderLayer, hidden: Tensor, positions):
    """Run one decoder layer under jax.checkpoint via functional_call.

    The eager tape sees a single fused op whose vjp recomputes the layer
    forward — activation-checkpointing parity with the reference's
    RecomputeOptimizer (fluid/optimizer.py RecomputeOptimizer) done the
    XLA way.
    """
    names = [n for n, _ in layer.named_parameters()]
    params = dict(layer.named_parameters())

    @functools.partial(jax.checkpoint, static_argnums=())
    def run(pvals, h, pos):
        st = dict(layer.named_parameters())
        old = {k: t._value for k, t in st.items()}
        try:
            for k, t in st.items():
                t._value = pvals[k]
            out = layer(Tensor(h), Tensor(pos))
        finally:
            for k, t in st.items():
                t._value = old[k]
        return out._value

    tensors = [params[n] for n in names]

    def f(h, pos, *pv):
        return run(dict(zip(names, pv)), h, pos)
    return _apply(f, hidden, positions, *tensors, op_name="remat_layer")


class LlamaForCausalLM(Layer):
    """Causal LM head on LlamaModel.

    ``forward(input_ids, labels=None)`` returns logits, or (loss, logits)
    when labels are given (next-token shift done internally, label -100 =
    ignore, matching the common pretrain convention).
    """

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size,
                weight_attr=Normal(0.0, config.initializer_range),
                has_bias=False, gather_output=True)

    def _logits(self, hidden):
        if self.lm_head is not None:
            return self.lm_head(hidden.astype("float32"))
        emb = self.model.embed_tokens.weight

        def f(h, w):
            return h.astype(jnp.float32) @ w.T.astype(jnp.float32)
        return _apply(f, hidden, emb, op_name="tied_lm_head")

    def forward(self, input_ids, labels=None, positions=None):
        hidden = self.model(input_ids, positions)
        if labels is None:
            return self._logits(hidden)
        import jax as _jax
        traced = isinstance(hidden._value, _jax.core.Tracer)
        logits_bytes = (hidden.shape[0] * hidden.shape[1]
                        * self.config.vocab_size * 4)
        if (traced and hidden.shape[1] - 1 >= 2 * _LOSS_CHUNK
                and logits_bytes >= _CHUNK_BYTES_MIN):
            # long sequences under jit: CE computed chunked from hidden
            # + the projection weight, so the full [B,S,V] f32 logits
            # tensor never materializes (at 7B dims it is the single
            # largest loss-path temp — ~0.5 GiB per microbatch).  The
            # logits below still trace for API parity and are DCE'd
            # whenever the caller keeps only the loss; a traced caller
            # that CONSUMES the returned logits keeps the full
            # projection (and pays the chunked loss on top) — the
            # memory win targets training steps, which keep only the
            # loss.  Eager callers materialize the returned logits
            # regardless, so chunking would only add compute there —
            # they take the plain path.
            if self.lm_head is not None:
                w, transposed = self.lm_head.weight, False
            else:
                w, transposed = self.model.embed_tokens.weight, True

            def f(h, wv, lb):
                return _chunked_causal_lm_loss(h, wv, lb, transposed)
            loss = _apply(f, hidden, w, labels, op_name="lm_loss_chunked")
            logits = self._logits(hidden)
        else:
            logits = self._logits(hidden)
            loss = _apply(_causal_lm_loss, logits, labels,
                          op_name="lm_loss")
        return loss, logits

    def generate(self, input_ids, **kwargs):
        """Autoregressive decoding (greedy/sampling/beam) — see
        paddle_tpu.text.generation.generate."""
        from ..generation import generate
        return generate(self, input_ids, **kwargs)

    # -- KV-cache incremental decode API (generation fast path) --------
    def supports_kv_cache(self) -> bool:
        c = self.config
        # scan-stacked decoders and sequence/context-parallel configs
        # (ring/ulysses exchange, sp-sharded activations) must use the
        # full-recompute path — the cached attention has no CP dispatch
        return (self.model.decoder is None and not c.context_parallel
                and not c.sequence_parallel)

    def init_cache(self, batch_size: int, max_len: int):
        """Per-layer K/V buffers; slot index == absolute position. Under
        a tp mesh the kv-head dim is sharded so each device holds only
        its heads' cache (matching the projections' head sharding)."""
        c = self.config
        dt = jnp.dtype(c.compute_dtype) if c.compute_dtype else jnp.float32
        shape = (batch_size, max_len, c.kv_heads, c.head_dim)

        def make():
            buf = jnp.zeros(shape, dt)
            return mesh_mod.constrain_dim(
                buf, 2, _layout().act_axis("kv_heads"))

        return [{"k": make(), "v": make()}
                for _ in range(c.num_hidden_layers)]

    def forward_with_cache(self, input_ids, positions, caches,
                           last_logits_only: bool = False):
        """(logits, caches) for the given token block; caches advance.
        ``last_logits_only`` skips the vocab projection for all but the
        final position (prefill only needs the last-token logits — the
        full [B, S0, V] f32 tensor is the dominant prefill cost)."""
        hidden, caches = self.model(input_ids, positions, caches=caches)
        if last_logits_only:
            hidden = hidden[:, -1:]
        return self._logits(hidden), caches

    # -- block-paged KV cache API (continuous-batching serving) --------
    def init_paged_cache(self, num_blocks: int, block_size: int):
        """Per-layer K/V pools ``[num_blocks, block_size, KH, D]``
        shared across every concurrent sequence (physical block 0 is
        the conventional trash block — the scheduler must never hand it
        out).  Under a tp mesh the kv-head dim is sharded like
        :meth:`init_cache`.  ``config.kv_cache_dtype="int8"`` mints
        int8 pools plus per-(block, slot) f32 scale tensors
        ``k_scale``/``v_scale`` [num_blocks, block_size] — the ROADMAP
        item 2 hook: this method and :meth:`LlamaAttention.
        forward_paged` are the only two quantization sites."""
        if not self.supports_kv_cache():
            raise KVCacheUnsupportedError(_SCAN_LAYERS_KV_MSG)
        c = self.config
        kvdt = c.kv_cache_dtype
        quant = kvdt == "int8"
        if quant:
            dt = jnp.int8
        elif kvdt:
            dt = jnp.dtype(kvdt)
        else:
            dt = (jnp.dtype(c.compute_dtype) if c.compute_dtype
                  else jnp.float32)
        shape = (int(num_blocks), int(block_size), c.kv_heads, c.head_dim)

        def make():
            buf = jnp.zeros(shape, dt)
            return mesh_mod.constrain_dim(
                buf, 2, _layout().act_axis("kv_heads"))

        def make_scale():
            return jnp.zeros(shape[:2], jnp.float32)

        if quant:
            return [{"k": make(), "v": make(),
                     "k_scale": make_scale(), "v_scale": make_scale()}
                    for _ in range(c.num_hidden_layers)]
        return [{"k": make(), "v": make()}
                for _ in range(c.num_hidden_layers)]

    def forward_paged(self, input_ids, positions, pools, block_tables,
                      write_mask, gather_at=None,
                      verify_mode: bool = False):
        """(logits, pools) through the block-paged cache.  With
        ``gather_at`` [B] the hidden states are gathered at those
        positions BEFORE the vocab projection (prefill only pays the
        [B, 1, V] projection of its last real token, not [B, S, V]).
        ``verify_mode`` routes S>1 blocks with mid-sequence positions
        through the cache-gather attention (spec-decode verification,
        prefix-cache suffix prefill)."""
        hidden, pools = self.model.forward_paged(
            input_ids, positions, pools, block_tables, write_mask,
            verify_mode=verify_mode)
        with jax.named_scope(HEAD):
            if gather_at is not None:
                hv = hidden._value if isinstance(hidden, Tensor) else hidden
                ga = gather_at._value if isinstance(gather_at, Tensor) \
                    else gather_at
                hv = jnp.take_along_axis(
                    hv, ga[:, None, None].astype(jnp.int32), axis=1)
                hidden = Tensor(hv)
            return self._logits(hidden), pools


def _causal_lm_loss(logits, labels):
    lg = logits[:, :-1, :]
    lb = labels[:, 1:]
    valid = lb >= 0
    lb = jnp.where(valid, lb, 0)
    logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, lb[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)


_LOSS_CHUNK = 256    # sequence positions per loss chunk
# engage the chunked loss only when the full f32 [B,S,V] logits would
# be big enough to matter (the 7B fit's ~2.1 GB global-batch logits
# qualify): at bench-proxy sizes (~1 GB, HBM not tight) the chunk
# scan only serializes the lm_head matmuls — measured -4% tok/s
_CHUNK_BYTES_MIN = int(1.5 * 1024 ** 3)


@jax.custom_vjp
def _proj_chunk(hc, wm):
    """[B,C,H] @ [H,V] with f32 accumulation — forward numerics match
    ``_logits`` exactly (same input rounding, f32 accumulate).  The
    custom vjp keeps the BACKWARD transpose dots in the params' compute
    dtype: a plain f32-typed result would promote W to f32 in the
    backward and all-gather an f32 copy of the whole projection under
    ZeRO-3.  Rounding the cotangent to the compute dtype is the
    standard AMP gradient convention."""
    return jax.lax.dot_general(hc, wm, (((2,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _proj_chunk_fwd(hc, wm):
    return _proj_chunk(hc, wm), (hc, wm)


def _proj_chunk_bwd(res, g):
    hc, wm = res
    gl = g.astype(wm.dtype)
    dhc = jax.lax.dot_general(gl, wm, (((2,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dwm = jax.lax.dot_general(hc, gl, (((0, 1), (0, 1)), ((), ())),
                              preferred_element_type=jnp.float32)
    return dhc.astype(hc.dtype), dwm.astype(wm.dtype)


_proj_chunk.defvjp(_proj_chunk_fwd, _proj_chunk_bwd)


def _chunked_causal_lm_loss(hidden, w, labels, transposed):
    """Next-token CE streamed over sequence chunks: per-chunk f32
    logits [B, C, V] are the only vocab-sized temp (lax.scan reuses the
    buffer), vs the unchunked path's [B, S, V].  ``w`` is [H, V]
    (lm_head) or [V, H] with ``transposed`` (tied embedding).  Forward
    numerics match :func:`_causal_lm_loss` (same input rounding, f32
    accumulation, f32 log_softmax, same -100 masking and valid-count
    normalization); the projection's cotangents are rounded to the
    compute dtype (see :func:`_proj_chunk`)."""
    B, S, H = hidden.shape
    n = S - 1
    h = hidden[:, :-1, :]
    lb = labels[:, 1:]
    C = _LOSS_CHUNK
    n_chunks = -(-n // C)
    pad = n_chunks * C - n
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        lb = jnp.pad(lb, ((0, 0), (0, pad)), constant_values=-100)
    hs = jnp.swapaxes(h.reshape(B, n_chunks, C, H), 0, 1)
    ls = jnp.swapaxes(lb.reshape(B, n_chunks, C), 0, 1)
    wm = w.T if transposed else w          # [H, V], compute dtype

    # chunk body rematerialized: without it lax.scan SAVES each chunk's
    # [B, C, V] f32 logits for the backward and the chunking buys
    # nothing.
    @jax.checkpoint
    def body(carry, hc_lc):
        s_nll, s_cnt = carry
        hc, lc = hc_lc
        lg = _proj_chunk(hc, wm)
        valid = lc >= 0
        lcs = jnp.where(valid, lc, 0)
        logp = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(logp, lcs[..., None], axis=-1)[..., 0]
        nll = jnp.where(valid, nll, 0.0)
        return (s_nll + nll.sum(), s_cnt + valid.sum()), None

    (s_nll, s_cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (hs, ls))
    return s_nll / jnp.maximum(s_cnt, 1).astype(jnp.float32)
