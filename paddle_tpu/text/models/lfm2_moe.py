"""LFM2-MoE decoder-only LM (LiquidAI LFM2-8B-A1B, ``model_type``
``lfm2_moe``): gated short convolutions in three layers of four,
grouped-query attention with 64-wide heads in the fourth, a dense
SwiGLU FFN in the leading layers and sparse experts in the others,
served through ``GenerationServer`` like any other causal LM.

Per layer (pre-norm RMSNorm blocks, tied head), with
``x = RMSNorm(h; operator_norm)``:

- **conv**: ``[B | C | X] = x W_in``, ``u = B * X``, a causal depthwise
  convolution of width ``conv_L_cache`` = 3 over ``u`` (no bias, no
  activation), ``y = (C * conv(u)) W_out``.  Its cache is NOT keys and
  values: the last two ``u`` per SLOT, ``[slots, 2, hidden]``, whatever
  the context (:func:`~paddle_tpu.text.models.kimi_linear.short_conv`
  carries it).
- **full_attention**: 32 query heads on 8 K/V heads of 64; q and k are
  RMS-normalised over the 64 dims (one learned vector each, shared by
  the heads), THEN rotated (rotate-half, theta 1e6); causal softmax.
  K/V live in block-paged pools written and read by the same code as
  the Llama family's (:func:`~paddle_tpu.text.models.llama.
  paged_write_attend`), kept ``[num_blocks, block, 8 * 64]``: a page of
  whole lane tiles, the ``paged_attention`` kernel's wide page.
- ``h += y``; ``z = RMSNorm(h; ffn_norm)``; the FFN is a dense SwiGLU in
  the first ``num_dense_layers`` layers and
  :class:`~paddle_tpu.nn.layer.moe.DroplessMoELayer` after (sigmoid
  router in float32 over all experts, the top 4 by score + bias,
  weights the chosen scores over their sum + 1e-6, no shared expert).

Serving only.  The paged-cache protocol is the Kimi-Linear model's
(``init_paged_cache`` takes ``num_slots``, ``forward_paged`` takes
``slots=`` and returns the counters :meth:`Lfm2MoeForCausalLM.
step_counters` names, ``loops_on_device`` tells the server which of its
programs hold the expert layers' device loop).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...framework.core import Tensor
from ...nn.layer.moe import DroplessMoELayer, _swiglu
from .kimi_linear import _Params, _rms, _rope_half, short_conv
from .llama import paged_write_attend

__all__ = ["Lfm2MoeConfig", "Lfm2MoeForCausalLM", "lfm2_moe_tiny"]

F32 = jnp.float32
# the published pattern: two conv layers, then attention, conv, conv,
# conv (the last period is one conv short)
_LAYER_TYPES = ("conv", "conv") + ("full_attention", "conv", "conv",
                                   "conv") * 4 \
    + ("full_attention", "conv", "conv", "full_attention", "conv", "conv")


@dataclasses.dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168           # the dense leading layers' FFN
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = _LAYER_TYPES
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    moe_intermediate_size: int = 1792
    num_experts: int = 32                   # the router's width
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    # (first, count) of the routed experts this chip holds; None = all
    held_experts: Optional[Tuple[int, int]] = None
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    max_position_embeddings: int = 128000
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)[:self.num_hidden_layers]
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {"conv", "full_attention"}:
            raise ValueError(
                f"layer_types {self.layer_types} do not name "
                f"{self.num_hidden_layers} conv / full_attention layers")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def is_attention(self, l: int) -> bool:
        return self.layer_types[l] == "full_attention"

    def is_moe(self, l: int) -> bool:
        return l >= self.num_dense_layers


def lfm2_moe_tiny(**kw) -> Lfm2MoeConfig:
    """Small config for tests: ten layers (the two leading conv + dense
    layers, then two periods attention, conv, conv, conv over experts),
    4 query heads on 2 K/V heads of 64, 8 experts, top 2."""
    d = dict(vocab_size=256, hidden_size=256, intermediate_size=128,
             num_hidden_layers=10, num_attention_heads=4,
             num_key_value_heads=2, moe_intermediate_size=64,
             num_experts=8, num_experts_per_tok=2,
             max_position_embeddings=128, compute_dtype="float32")
    d.update(kw)
    return Lfm2MoeConfig(**d)


class Lfm2ShortConv(_Params):
    """The gated short convolution with its per-slot tail (module doc).
    With ``slots`` the rows are a block of tokens each, from the named
    slot's tail (zero where the block starts a sequence); without, row
    i is slot i and one token a row shifts the whole tail array."""

    def __init__(self, c: Lfm2MoeConfig):
        super().__init__()
        self.config = c
        self._std = c.initializer_range
        h = c.hidden_size
        self.in_proj = self._mk(h, 3 * h)
        # the public model draws it U(+-1/sqrt(3)) (fan-in 3)
        self.conv = self._mk(c.conv_L_cache, h, std=0.3)
        self.out_proj = self._mk(h, h)

    def init_cache(self, num_slots: int, dtype):
        c = self.config
        return {"conv": jnp.zeros(
            (num_slots, c.conv_L_cache - 1, c.hidden_size), dtype)}

    def forward_paged(self, x, positions, cache, write_mask, slots):
        B, S, h = x.shape
        conv = cache["conv"]
        N = conv.shape[0]
        bcx = jnp.dot(x, self.in_proj._value)
        u = (bcx[..., :h] * bcx[..., 2 * h:])[:, None]       # [B, 1, S, h]
        w = self.conv._value[None]                           # [1, W, h]
        if S == 1 and slots is None:
            if B != N:
                raise ValueError(
                    f"a conv decode step runs over every slot: got {B} "
                    f"rows for {N} slots (name the rows' slots)")
            tail0 = conv[:, None]
            v, tail = short_conv(u, tail0, w)
            tail = jnp.where(write_mask[:, :, None, None], tail,
                             tail0.astype(tail.dtype))
            conv = tail[:, 0].astype(conv.dtype)
        else:
            if slots is None:
                slots = jnp.arange(B, dtype=jnp.int32)
            # a block that starts a sequence starts from nothing,
            # whatever the slot's last owner left behind
            fresh = positions[:, 0] == 0
            tail0 = jnp.where(fresh[:, None, None], 0,
                              conv[jnp.clip(slots, 0, N - 1)])[:, None]
            v, tail = short_conv(
                u, tail0, w, length=write_mask.sum(-1).astype(jnp.int32))
            conv = conv.at[slots].set(tail[:, 0].astype(conv.dtype),
                                      mode="drop")
        y = (bcx[..., h:2 * h].astype(F32) * v[:, 0]).astype(x.dtype)
        return jnp.dot(y, self.out_proj._value), {"conv": conv}


class Lfm2Attention(_Params):
    """GQA with head norms before rotary (module doc); the paged write
    and the attention are the Llama family's."""

    def __init__(self, c: Lfm2MoeConfig):
        super().__init__()
        self.config = c
        self._std = c.initializer_range
        h, nh, kh, d = (c.hidden_size, c.num_attention_heads,
                        c.num_key_value_heads, c.head_dim)
        self.q_proj = self._mk(h, nh * d)
        self.k_proj = self._mk(h, kh * d)
        self.v_proj = self._mk(h, kh * d)
        self.out_proj = self._mk(nh * d, h)
        self.q_layernorm = self._mk(d, one=True)
        self.k_layernorm = self._mk(d, one=True)

    def init_cache(self, num_blocks: int, block_size: int, dtype):
        c = self.config
        shape = (num_blocks, block_size,
                 c.num_key_value_heads * c.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def forward_paged(self, x, positions, cache, block_tables, write_mask):
        from ...ops.pallas import registry as _kreg
        c = self.config
        nh, kh, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        B, S = x.shape[:2]
        heads = lambda w, n: jnp.dot(x, w._value).reshape(B, S, n, d)

        def normed(t, w):
            t = _rms(t, w._value, c.norm_eps)
            return _rope_half(t, positions, c.rope_theta).astype(x.dtype)
        o, kpool, vpool = paged_write_attend(
            normed(heads(self.q_proj, nh), self.q_layernorm),
            normed(heads(self.k_proj, kh), self.k_layernorm),
            heads(self.v_proj, kh), positions, write_mask,
            cache["k"], cache["v"], block_tables,
            kv_kernel="paged_attention",
            kv_mode=_kreg.resolve("paged_attention"))
        return (jnp.dot(o.astype(x.dtype), self.out_proj._value),
                {"k": kpool, "v": vpool})


class Lfm2MLP(_Params):
    def __init__(self, c: Lfm2MoeConfig):
        super().__init__()
        self._std = c.initializer_range
        h, f = c.hidden_size, c.intermediate_size
        self.w1, self.w3, self.w2 = (self._mk(h, f), self._mk(h, f),
                                     self._mk(f, h))

    def apply_values(self, x):
        y = _swiglu(x.reshape(-1, x.shape[-1]), self.w1._value,
                    self.w3._value, self.w2._value)
        return y.astype(x.dtype).reshape(x.shape)


class Lfm2MoeDecoderLayer(_Params):
    def __init__(self, c: Lfm2MoeConfig, l: int):
        super().__init__()
        self.config = c
        self.operator_norm = self._mk(c.hidden_size, one=True)
        self.ffn_norm = self._mk(c.hidden_size, one=True)
        self.is_attention, self.is_moe = c.is_attention(l), c.is_moe(l)
        if self.is_attention:
            self.self_attn = Lfm2Attention(c)
        else:
            self.conv = Lfm2ShortConv(c)
        if self.is_moe:
            self.feed_forward = DroplessMoELayer(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                top_k=c.num_experts_per_tok, held_experts=c.held_experts,
                routed_scaling_factor=c.routed_scaling_factor,
                initializer_range=c.initializer_range, norm_eps=1e-6)
        else:
            self.feed_forward = Lfm2MLP(c)

    def forward_paged(self, h, positions, cache, block_tables, write_mask,
                      slots):
        eps = self.config.norm_eps
        x = _rms(h, self.operator_norm._value, eps).astype(h.dtype)
        if self.is_attention:
            with jax.named_scope("lfm2.attn"):
                a, cache = self.self_attn.forward_paged(
                    x, positions, cache, block_tables, write_mask)
        else:
            with jax.named_scope("lfm2.conv"):
                a, cache = self.conv.forward_paged(
                    x, positions, cache, write_mask, slots)
        h = h + a.astype(h.dtype)
        x = _rms(h, self.ffn_norm._value, eps).astype(h.dtype)
        counts = None
        if self.is_moe:
            with jax.named_scope("lfm2.experts"):
                y, *counts = self.feed_forward.apply_values(
                    x, count_rows=True)
            counts = jnp.stack([jnp.asarray(n, jnp.int32) for n in counts])
        else:
            with jax.named_scope("lfm2.dense_ffn"):
                y = self.feed_forward.apply_values(x)
        return h + y, cache, counts


class Lfm2MoeModel(_Params):
    def __init__(self, c: Lfm2MoeConfig):
        super().__init__()
        from ...nn.layer.container import LayerList
        self.config = c
        self._std = c.initializer_range
        self.embed_tokens = self._mk(c.vocab_size, c.hidden_size)
        self.layers = LayerList([Lfm2MoeDecoderLayer(c, l)
                                 for l in range(c.num_hidden_layers)])
        self.embedding_norm = self._mk(c.hidden_size, one=True)


class Lfm2MoeForCausalLM(_Params):
    """Causal LM over :class:`Lfm2MoeModel`, the head tied to the
    embedding, served through the block-paged cache API (module doc)."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.model = Lfm2MoeModel(config)

    def supports_kv_cache(self) -> bool:
        return True

    def has_recurrent_state(self) -> bool:
        """The conv layers' tails are per-slot state that no block table
        addresses: the server allocates it with the slots and refuses
        what only knows K/V blocks (prefix sharing, speculation,
        migration)."""
        return not all(self.config.is_attention(l)
                       for l in range(self.config.num_hidden_layers))

    def step_counters(self) -> Tuple[str, ...]:
        """What ``forward_paged``'s third value counts, summed over the
        expert layers: the picks that landed on the held experts, each
        layer's largest held expert's load, and the row-products done
        (rows x the experts each was multiplied by: 8 a pick in the
        masked pass of 4-of-32 experts, 1 at best)."""
        return ("moe_picks_here", "moe_max_expert_load",
                "moe_rows_multiplied")

    def loops_on_device(self, n_tokens: int) -> bool:
        """Whether ``forward_paged`` over ``n_tokens`` tokens lowers a
        device loop whose steps branch (the expert layers' grouped
        dispatch): ``GenerationServer`` puts no ``conditional`` of its
        own behind such a program."""
        return any(lyr.is_moe and lyr.feed_forward.loops_on_device(n_tokens)
                   for lyr in self.model.layers)

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         num_slots: Optional[int] = None):
        """Per layer ``{"k", "v": [num_blocks, block, KH * 64]}``
        (attention; physical block 0 is the trash block) or ``{"conv":
        [num_slots, 2, hidden]}`` (conv)."""
        if num_slots is None and self.has_recurrent_state():
            raise ValueError("a model with recurrent state needs "
                             "num_slots for its per-slot state")
        dt = jnp.dtype(self.config.compute_dtype)
        return [lyr.self_attn.init_cache(int(num_blocks), int(block_size),
                                         dt) if lyr.is_attention
                else lyr.conv.init_cache(int(num_slots), dt)
                for lyr in self.model.layers]

    def forward_paged(self, input_ids, positions, pools, block_tables,
                      write_mask, gather_at=None,
                      verify_mode: bool = False, slots=None):
        """(logits, caches, counters) through the paged caches; the
        module doc says what ``slots`` and the counters are."""
        if verify_mode:
            from ...inference.recurrent_state import \
                RecurrentStateUnsupported
            raise RecurrentStateUnsupported(
                "a multi-token step that starts mid-sequence (speculative "
                "verification, suffix prefill) would need snapshots of "
                "the convolution tails to roll back to")
        c = self.config
        raw = lambda t: t._value if isinstance(t, Tensor) else t
        ids, pos, wm = raw(input_ids), raw(positions), raw(write_mask)
        tbl = raw(block_tables)
        if slots is not None:
            slots = raw(slots).astype(jnp.int32)
        pos = pos.astype(jnp.int32)
        embed = self.model.embed_tokens._value
        h = embed[ids].astype(jnp.dtype(c.compute_dtype))
        new_pools, counts = [], jnp.zeros((3,), jnp.int32)
        for lyr, cache in zip(self.model.layers, pools):
            cache = {k: raw(v) for k, v in cache.items()}
            h, cache, n = lyr.forward_paged(h, pos, cache, tbl, wm, slots)
            new_pools.append(cache)
            if n is not None:
                counts = counts + n
        h = _rms(h, self.model.embedding_norm._value,
                 c.norm_eps).astype(h.dtype)
        if gather_at is not None:
            h = jnp.take_along_axis(
                h, raw(gather_at)[:, None, None].astype(jnp.int32), axis=1)
        logits = jnp.einsum("bsh,vh->bsv", h, embed,
                            preferred_element_type=F32)
        return Tensor(logits), new_pools, counts
