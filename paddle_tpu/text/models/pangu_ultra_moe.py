"""openPangu-Ultra-MoE decoder-only LM (``model_type``
``pangu_ultra_moe``; DeepSeek-V3's family with sandwich norms): latent
attention in EVERY layer, a dense SwiGLU FFN in the leading layers and
sparse experts with one shared expert behind them, served through
``GenerationServer`` like any other causal LM.

Per layer, with ``x = RMSNorm(h; input_layernorm)``:

- **latent attention with a low-rank query and rotary**
  (:class:`~paddle_tpu.text.models.kimi_linear.LatentAttention`, shared
  with Kimi-Linear): ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` = heads
  x ``[q_nope | q_pe]``; ``[c_kv | k_pe] = x W_kva``, ``c =
  RMSNorm(c_kv)``; ``q_pe`` and ``k_pe`` take rotary (rotate-half over
  the ``qk_rope_head_dim`` dims, no scaling), ``k_pe`` is one vector a
  token for all heads.  The cache holds ``[c | rotated k_pe]`` per
  token in pages ``[num_blocks, block, 1, 640]``, whatever the head
  count; prefill expands K and V from the latent, decode absorbs the
  expansion into the query and the output and attends over the pages
  through the ``paged_attention`` kernel.
- **sandwich norm**: a norm before AND after each sublayer, the second
  on the sublayer's output before the residual add: ``h += RMSNorm(a;
  post_attention_layernorm)``; ``z = RMSNorm(h; pre_mlp_layernorm)``;
  ``h += RMSNorm(FFN(z); post_mlp_layernorm)``.
- the FFN is a dense SwiGLU in the first ``first_k_dense_replace``
  layers and :class:`~paddle_tpu.nn.layer.moe.DroplessMoELayer` behind
  them (sigmoid router in float32 over all ``n_routed_experts``, the
  top ``num_experts_per_tok`` by score with no bias and no groups,
  weights the chosen scores over their sum + 1e-20 times
  ``routed_scaling_factor``, one shared expert; ``held_experts`` makes
  this chip's share of an expert-parallel layer).
- one RMSNorm after the last layer, an untied head.
- **multi-token prediction** (``num_nextn_predict_layers`` > 0;
  DeepSeek-V3's module): :meth:`PanguUltraMoEForCausalLM.mtp_logits`.
  A served cut holds none: in a pipeline it lives on the last stage
  and only drafts.

Serving only.  No per-slot state: ``has_recurrent_state()`` is False
and the pools are block-paged alone, but a multi-token step attends
over its own block only, so it has to start its sequence
(``prefill_starts_sequences_only()``: the server refuses prefix
sharing and speculation).  ``forward_paged`` returns the counters
:meth:`PanguUltraMoEForCausalLM.step_counters` names as a third value;
``loops_on_device`` tells the server which of its programs hold the
expert layers' device loop, ``prefill_attn_pairs`` how much of the
attention square a prefill call multiplies.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...framework.core import Tensor
from ...nn.layer.moe import DroplessMoELayer
from ...observability.scopes import ATTN, EMBED, FFN, HEAD, MOE, MTP
from .kimi_linear import (KimiMLP, LatentAttention, _Params, _rms,
                          attend_plan)

__all__ = ["PanguUltraMoEConfig", "PanguUltraMoEForCausalLM",
           "pangu_ultra_moe_tiny"]

F32 = jnp.float32


@dataclasses.dataclass
class PanguUltraMoEConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432          # the dense leading layers' FFN
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25600000.0
    sandwich_norm: bool = True
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256             # the router's width
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    # (first, count) of the routed experts this chip holds; None = all
    held_experts: Optional[Tuple[int, int]] = None
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    max_position_embeddings: int = 131072
    compute_dtype: str = "bfloat16"

    def is_moe(self, l: int) -> bool:
        return l >= self.first_k_dense_replace


def pangu_ultra_moe_tiny(**kw) -> PanguUltraMoEConfig:
    """Small config for tests: five layers (one dense, four over
    experts), 4 heads of 16 + 8 / 12 behind a query of rank 24, 16
    experts of which the first 8 are held, top 2, one MTP layer."""
    d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
             num_hidden_layers=5, first_k_dense_replace=1,
             num_attention_heads=4, q_lora_rank=24, kv_lora_rank=24,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
             rope_theta=10000.0, moe_intermediate_size=32,
             n_routed_experts=16, num_experts_per_tok=2,
             held_experts=(0, 8), max_position_embeddings=128,
             compute_dtype="float32")
    d.update(kw)
    return PanguUltraMoEConfig(**d)


class PanguDecoderLayer(_Params):
    """One decoder layer with its four norms (two where
    ``sandwich_norm`` is off: the pre-norm block the family started
    from).  ``moe`` says which FFN; the MTP module's layer is of the
    expert kind."""

    def __init__(self, c: PanguUltraMoEConfig, moe: bool):
        super().__init__()
        self.config = c
        self.is_moe = moe
        self.input_layernorm = self._mk(c.hidden_size, one=True)
        self.pre_mlp_layernorm = self._mk(c.hidden_size, one=True)
        if c.sandwich_norm:
            self.post_attention_layernorm = self._mk(c.hidden_size,
                                                     one=True)
            self.post_mlp_layernorm = self._mk(c.hidden_size, one=True)
        self.self_attn = LatentAttention(c, c.q_lora_rank, c.rope_theta)
        if moe:
            self.mlp = DroplessMoELayer(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                top_k=c.num_experts_per_tok, held_experts=c.held_experts,
                shared_hidden=(c.moe_intermediate_size
                               * c.n_shared_experts),
                routed_scaling_factor=c.routed_scaling_factor,
                initializer_range=c.initializer_range)
        else:
            self.mlp = KimiMLP(c)     # the same dense SwiGLU

    def _sublayers(self, h, attend):
        """``attend(x) -> (a, cache)``; returns (h, cache, the expert
        layer's three counters or None)."""
        c = self.config
        norm = lambda t, w: _rms(t, w._value, c.rms_norm_eps).astype(h.dtype)
        with jax.named_scope(ATTN):
            a, cache = attend(norm(h, self.input_layernorm))
            a = a.astype(h.dtype)
            if c.sandwich_norm:
                a = norm(a, self.post_attention_layernorm)
            h = h + a
        counts = None
        with jax.named_scope(MOE if self.is_moe else FFN):
            z = norm(h, self.pre_mlp_layernorm)
            if self.is_moe:
                y, *counts = self.mlp.apply_values(z, count_rows=True)
            else:
                y = self.mlp.apply_values(z)
            if c.sandwich_norm:
                y = norm(y, self.post_mlp_layernorm)
            h = h + y
        if counts is not None:
            counts = jnp.stack([jnp.asarray(n, jnp.int32) for n in counts])
        return h, cache, counts

    def forward_paged(self, h, positions, cache, block_tables, write_mask):
        return self._sublayers(h, lambda x: self.self_attn.forward_paged(
            x, positions, cache, block_tables, write_mask))

    def forward_block(self, h, positions):
        """A fresh block from position 0, nothing cached."""
        h, _, _ = self._sublayers(h, lambda x: (
            self.self_attn.forward_block(x, positions), None))
        return h


class PanguMTPLayer(_Params):
    """DeepSeek-V3's multi-token-prediction module: the next token's
    embedding and the main model's last hidden state, each normalised,
    side by side through ``eh_proj``, one decoder layer of the expert
    kind, its own final norm."""

    def __init__(self, c: PanguUltraMoEConfig):
        super().__init__()
        self._std = c.initializer_range
        h = c.hidden_size
        self.enorm = self._mk(h, one=True)
        self.hnorm = self._mk(h, one=True)
        self.eh_proj = self._mk(2 * h, h)
        self.layer = PanguDecoderLayer(c, moe=True)
        self.norm = self._mk(h, one=True)


class PanguUltraMoEModel(_Params):
    def __init__(self, c: PanguUltraMoEConfig):
        super().__init__()
        from ...nn.layer.container import LayerList
        self.config = c
        self._std = c.initializer_range
        self.embed_tokens = self._mk(c.vocab_size, c.hidden_size)
        self.layers = LayerList([PanguDecoderLayer(c, c.is_moe(l))
                                 for l in range(c.num_hidden_layers)])
        self.norm = self._mk(c.hidden_size, one=True)


class PanguUltraMoEForCausalLM(_Params):
    """Causal LM over :class:`PanguUltraMoEModel`, untied head, served
    through the block-paged cache API (module doc)."""

    def __init__(self, config: PanguUltraMoEConfig):
        super().__init__()
        from ...nn.layer.container import LayerList
        self.config = config
        self._std = config.initializer_range
        self.model = PanguUltraMoEModel(config)
        self.lm_head = self._mk(config.hidden_size, config.vocab_size)
        if config.num_nextn_predict_layers > 0:
            self.mtp = LayerList([
                PanguMTPLayer(config)
                for _ in range(config.num_nextn_predict_layers)])

    def supports_kv_cache(self) -> bool:
        return True

    def has_recurrent_state(self) -> bool:
        return False

    def prefill_starts_sequences_only(self) -> bool:
        """A multi-token step attends over its own block only (the
        latent layers expand K and V from the fresh block), so it has
        to start its sequence: ``GenerationServer`` refuses what would
        run one from the middle (a shared prefix's suffix prefill,
        speculative verification)."""
        return True

    def step_counters(self) -> Tuple[str, ...]:
        """What ``forward_paged``'s third value counts, summed over the
        expert layers: the picks that landed on the held experts, each
        layer's largest held expert's load, and the row-products done
        (rows x the experts each was multiplied by)."""
        return ("moe_picks_here", "moe_max_expert_load",
                "moe_rows_multiplied")

    def loops_on_device(self, n_tokens: int) -> bool:
        """Whether ``forward_paged`` over ``n_tokens`` tokens lowers a
        device loop whose steps branch (the expert layers' grouped
        dispatch): ``GenerationServer`` puts no ``conditional`` of its
        own behind such a program."""
        return any(lyr.is_moe and lyr.mlp.loops_on_device(n_tokens)
                   for lyr in self.model.layers)

    def prefill_attn_pairs(self, batch: int, bucket: int):
        """(multiplied, whole square): the (query, key) pairs that a
        prefill call of ``batch`` rows x ``bucket`` tokens takes in its
        attention layers; ``GenerationServer`` adds them up in
        ``stats()`` (a share of 1.0: no key is skipped)."""
        done, square = attend_plan(
            batch, bucket, self.config.num_attention_heads)[2:]
        n = len(self.model.layers)
        return n * done, n * square

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         num_slots: Optional[int] = None):
        """Per layer ``{"latent": [num_blocks, block, 1, 640]}``
        (physical block 0 is the trash block); ``num_slots`` is taken
        for the protocol's sake, no layer keeps per-slot state."""
        dt = jnp.dtype(self.config.compute_dtype)
        return [lyr.self_attn.init_cache(int(num_blocks), int(block_size),
                                         dt) for lyr in self.model.layers]

    def forward_paged(self, input_ids, positions, pools, block_tables,
                      write_mask, gather_at=None,
                      verify_mode: bool = False, slots=None):
        """(logits, caches, counters) through the latent pages;
        ``slots`` is taken for the protocol's sake."""
        if verify_mode:
            from ...inference.recurrent_state import \
                MidSequenceStepUnsupported
            raise MidSequenceStepUnsupported(
                "a multi-token step that starts mid-sequence (speculative "
                "verification, suffix prefill): the latent layers attend "
                "over the fresh block only")
        c = self.config
        raw = lambda t: t._value if isinstance(t, Tensor) else t
        ids, pos, wm = raw(input_ids), raw(positions), raw(write_mask)
        tbl = raw(block_tables)
        pos = pos.astype(jnp.int32)
        with jax.named_scope(EMBED):
            h = self.model.embed_tokens._value[ids].astype(
                jnp.dtype(c.compute_dtype))
        new_pools, counts = [], jnp.zeros((3,), jnp.int32)
        for lyr, cache in zip(self.model.layers, pools):
            cache = {k: raw(v) for k, v in cache.items()}
            h, cache, n = lyr.forward_paged(h, pos, cache, tbl, wm)
            new_pools.append(cache)
            if n is not None:
                counts = counts + n
        with jax.named_scope(HEAD):
            h = _rms(h, self.model.norm._value,
                     c.rms_norm_eps).astype(h.dtype)
            if gather_at is not None:
                h = jnp.take_along_axis(
                    h, raw(gather_at)[:, None, None].astype(jnp.int32),
                    axis=1)
            logits = jnp.dot(h, self.lm_head._value,
                             preferred_element_type=F32)
        return Tensor(logits), new_pools, counts

    def hidden_block(self, input_ids, positions):
        """The residual stream behind the last layer (before the final
        norm) of a fresh block from position 0, nothing cached:
        ``mtp_logits``'s ``hidden``."""
        raw = lambda t: t._value if isinstance(t, Tensor) else t
        pos = raw(positions).astype(jnp.int32)
        h = self.model.embed_tokens._value[raw(input_ids)].astype(
            jnp.dtype(self.config.compute_dtype))
        for lyr in self.model.layers:
            h = lyr.forward_block(h, pos)
        return h

    def mtp_logits(self, hidden, next_ids, positions, depth: int = 0):
        """Logits for token i + 2 + ``depth`` from ``hidden`` [B, S, h]
        (position i's residual stream behind the main model's last
        layer) and ``next_ids`` [B, S] (token i + 1): ``u = [RMSNorm(E(
        next); enorm) | RMSNorm(hidden; hnorm)] W_eh``, one decoder
        layer over ``u`` as a fresh block from position 0 (no cache),
        the module's own norm, the main model's embedding and head."""
        if self.config.num_nextn_predict_layers <= depth:
            raise ValueError("this model holds no such MTP module "
                             "(num_nextn_predict_layers)")
        c, m = self.config, self.mtp[depth]
        raw = lambda t: t._value if isinstance(t, Tensor) else t
        hid, pos = raw(hidden), raw(positions).astype(jnp.int32)
        norm = lambda t, w: _rms(t, w._value, c.rms_norm_eps).astype(
            hid.dtype)
        with jax.named_scope(MTP):
            e = self.model.embed_tokens._value[raw(next_ids)].astype(
                hid.dtype)
            u = jnp.dot(jnp.concatenate(
                [norm(e, m.enorm), norm(hid, m.hnorm)], -1),
                m.eh_proj._value)
            u = norm(m.layer.forward_block(u, pos), m.norm)
            return Tensor(jnp.dot(u, self.lm_head._value,
                                  preferred_element_type=F32))
