"""MiniMax-M3 decoder-only LM (MiniMax-M3 428B-A23B, language model):
grouped-query attention made block-sparse by a learned indexer (MSA) in
EVERY layer, a dense FFN in the leading layers and sigmoid-routed
experts with one shared expert behind them, served through
``GenerationServer`` like any other causal LM.

Notation: layer input ``h``; ``N(v; w) = v / rms(v) * (1 + w)`` (the
Gemma norm, eps ``rms_norm_eps``); ``x = N(h; input_layernorm)``.

- **attention**: ``q_i = x W_q[i]`` (64 heads), ``k_g = x W_k[g]``,
  ``v_g = x W_v[g]`` (4 kv heads of 128); each head normalised over
  its 128 dims, ``q_i <- N(q_i; q_norm)``, ``k_g <- N(k_g; k_norm)`` (one
  weight vector each, shared by the heads); rotary (rotate-half, theta
  ``rope_theta``) on the first ``rotary_dim`` dims, the rest left as
  they are.  Query head ``i`` reads kv head ``i // 16``.
- **the indexer** (``msa_index`` scope inside ``attn``): ``msa_index_heads``
  = 4 queries a kv head, ``u_gj = N(x W_I[g, j]; index_norm)`` of
  ``head_dim`` dims with the same rotary, summed over ``j``.  Each page
  of ``msa_block`` = 128 cached tokens has one pooled key a kv head, the
  elementwise max of its cached (normalised, rotated) keys; a page's
  score is ``sum_j u_gj . kmax_g,b / sqrt(head_dim)``.  A query at
  position ``t`` (page ``b_t = t // 128``) attends over page 0, its own
  page and the one before (``msa_local`` = 2), and the ``msa_top_k`` =
  16 best-scored pages among ``1 .. b_t - 2`` (ties to the lower page):
  with 19 pages or fewer it sees every page, and the layer is full
  attention.
- **FFN**: ``z = N(h; post_attention_layernorm)``; the expert form is
  ``((clip(z W_u, -7, 7) + 1) * g * sigmoid(1.702 g)) W_d`` with
  ``g = min(z W_g, 7)`` (``swigluoai``), dense at
  ``dense_intermediate_size`` in the layers ``moe_layer_freq`` marks 0,
  and :class:`~paddle_tpu.nn.layer.moe.DroplessMoELayer` in the others
  (sigmoid router in float32 over all ``num_local_experts``, the top 4
  by score + bias, weights the chosen scores over their sum times
  ``routed_scaling_factor``, one shared expert; ``held_experts`` makes
  this chip's share of an expert-parallel layer).
- a final Gemma norm, an untied head.

The cache holds, per layer, K and V in HEAD-major pages ``[num_blocks,
4, 128, 128]`` (a (page, kv head) is one tile, so a kv head's selection
copies only its own tiles) and the pooled keys ``kmax`` ``[num_blocks,
4, 128]`` (float32), by physical block like the pages: a function of the page's
own contents, rebuilt by whatever writes the page (a prefill sets it
from the page's tokens, a decode write at offset 0 restarts it and
raises it elsewhere).  Decode selects with the indexer in XLA and reads
the selected tiles through the ``paged_sparse_attention`` kernel;
prefill takes each query's own selection as a mask over the causal
square, a group of query chunks at a time (:func:`msa_prefill_attend`).

The selection is made in float32, as the router's is: which 16 of a
few hundred pages a query reads is a choice among near-equal scores,
and the bfloat16 rounding of the normalised input, of the indexer's
queries and of the keys flips it.  So the indexer's queries and the
pooled keys come from the float32 normalised input (:func:`_dot_f32`),
the pooled keys stay float32 and the scores are taken at the highest
precision; the attention's own K, V and queries are bfloat16.

Serving only.  A prefill call has to start its sequence: the pooled
keys of a page and the selection of its queries are made from the
call's own keys (``prefill_starts_sequences_only()``: the server
refuses prefix sharing and speculation).  ``forward_paged`` returns the
counters :meth:`MiniMaxM3ForCausalLM.step_counters` names as a third
value.  The multi-token-prediction modules are not held: they only
draft.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...framework.core import Tensor
from ...nn.layer.moe import DroplessMoELayer, _swiglu
from ...observability.scopes import ATTN, EMBED, FFN, HEAD, MOE
from .kimi_linear import _chunk_groups, _Params, _rms, _rope_half

__all__ = ["MiniMaxM3Config", "MiniMaxM3ForCausalLM", "minimax_m3_tiny",
           "select_pages", "msa_prefill_attend"]

F32 = jnp.float32
# the indexer's ops, nested in ``attn``: not a name of the vocabulary,
# so a reader of device time by block files them under ``attn``
MSA_INDEX = "msa_index"
# the float32 score block a step of a prefill call's attention may hold
_SCORE_BLOCK_BYTES = 2 ** 29
# tokens of a prefill call's FFN computed at a time (a device loop over
# the call's tokens where they are more)
FFN_CHUNK = 4096
# every FFN's activation, the dense layers' and the experts' alike
ACT = "swigluoai"


@dataclasses.dataclass
class MiniMaxM3Config:
    vocab_size: int = 200064
    hidden_size: int = 6144
    intermediate_size: int = 3072           # one routed expert's width
    dense_intermediate_size: int = 12288
    shared_intermediate_size: int = 3072
    num_hidden_layers: int = 60
    moe_layer_freq: Tuple[int, ...] = (0, 0, 0) + (1,) * 57
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 128
    rotary_dim: int = 64
    rope_theta: float = 5e6
    num_local_experts: int = 128            # the router's width
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    # (first, count) of the routed experts this chip holds; None = all
    held_experts: Optional[Tuple[int, int]] = None
    msa_block: int = 128
    msa_top_k: int = 16
    msa_local: int = 2
    msa_index_heads: int = 4
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    max_position_embeddings: int = 1048576
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        self.moe_layer_freq = tuple(
            int(f) for f in self.moe_layer_freq)[:self.num_hidden_layers]
        if len(self.moe_layer_freq) != self.num_hidden_layers:
            raise ValueError(f"moe_layer_freq names {len(self.moe_layer_freq)}"
                             f" of {self.num_hidden_layers} layers")

    def is_moe(self, l: int) -> bool:
        return self.moe_layer_freq[l] == 1

    @property
    def msa_pages(self) -> int:
        """The most pages a query reads: page 0, the local ones and the
        top-k."""
        return 1 + self.msa_local + self.msa_top_k


def minimax_m3_tiny(**kw) -> MiniMaxM3Config:
    """Small config for tests: five layers (one dense, four over
    experts), 8 query heads on 2 kv heads of 32 (16 rotated), pages of
    8 tokens, the top 2 pages, 16 experts of which the first 8 are
    held, top 4."""
    d = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
             dense_intermediate_size=96, shared_intermediate_size=32,
             num_hidden_layers=5, moe_layer_freq=(0, 1, 1, 1, 1),
             num_attention_heads=8, num_key_value_heads=2, head_dim=32,
             rotary_dim=16, rope_theta=10000.0, num_local_experts=16,
             held_experts=(0, 8), msa_block=8, msa_top_k=2,
             initializer_range=0.1, max_position_embeddings=256,
             compute_dtype="float32")
    d.update(kw)
    return MiniMaxM3Config(**d)


# ---------------------------------------------------------------------
# the mathematics, on raw arrays
# ---------------------------------------------------------------------
def _gnorm(v, w, eps):
    """The Gemma norm ``v / rms(v) * (1 + w)``, float32."""
    return _rms(v, 1.0 + w.astype(F32), eps)


def _dot_f32(xf, w):
    """``xf`` (float32) by ``w`` with float32 products and sums.  For a
    bfloat16 ``w`` (exact in two bfloat16 parts of ``xf``: its leading
    bits and the rest) two one-pass products over the stacked parts,
    about 16 bits of ``xf`` where one pass keeps 8; the weights are read
    once."""
    if w.dtype == F32:
        return jnp.dot(xf, w, precision=jax.lax.Precision.HIGHEST)
    hi = xf.astype(w.dtype)
    lo = (xf - hi.astype(F32)).astype(w.dtype)
    y = jnp.dot(jnp.stack([hi, lo]), w, preferred_element_type=F32)
    return y[0] + y[1]


def _rope_part(x, positions, rotary_dim: int, theta: float):
    """Rotate-half rotary on the first ``rotary_dim`` dims of ``x``
    [B, S, H, D] (float32), the rest as it is."""
    return jnp.concatenate(
        [_rope_half(x[..., :rotary_dim], positions, theta),
         x[..., rotary_dim:]], -1)


def select_pages(scores, bt, top_k: int, local: int):
    """Which pages a query reads: page 0, its ``local`` pages ``bt -
    local + 1 .. bt``, and the ``top_k`` best ``scores`` among pages
    ``1 .. bt - local`` (ties to the lower page).  ``scores`` [..., P]
    float32, ``bt`` [...] the query's page; returns bool [..., P]."""
    n = scores.shape[-1]
    idx = jnp.arange(n, dtype=jnp.int32)
    bt = bt[..., None]
    cand = (idx >= 1) & (idx <= bt - local)
    forced = (idx == 0) | ((idx > bt - local) & (idx <= bt))
    k = min(top_k, n)
    if k == 0:
        return forced
    val, at = jax.lax.top_k(jnp.where(cand, scores, -jnp.inf), k)
    picked = jnp.any((at[..., None] == idx)
                     & (val > -jnp.inf)[..., None], axis=-2)
    return forced | picked


def selected_list(sel, n_sel: int):
    """The pages a selection mask ``sel`` [..., P] names, ascending,
    padded with 0: (logical [..., n_sel] int32, count [...])."""
    n = sel.shape[-1]
    idx = jnp.arange(n, dtype=jnp.int32)
    # the largest keys are the lowest selected pages
    key, _ = jax.lax.top_k(jnp.where(sel, n - idx, -1), n_sel)
    count = jnp.sum(sel, -1).astype(jnp.int32)
    live = jnp.arange(n_sel, dtype=jnp.int32) < count[..., None]
    return jnp.where(live, n - key, 0).astype(jnp.int32), count


def page_max(k, wm, block: int):
    """Pooled keys of a fresh block: ``k`` [B, S, KH, D] written from a
    page boundary, ``wm`` [B, S]; the elementwise max over each page's
    written tokens, and whether the page holds any.  Returns ([B, S /
    block, KH, D], [B, S / block])."""
    B, S, KH, D = k.shape
    kk = jnp.where(wm[:, :, None, None], k, -jnp.inf)
    kk = kk.reshape(B, S // block, block, KH, D).max(2)
    held = wm.reshape(B, S // block, block).any(-1)
    return jnp.where(held[:, :, None, None], kk, 0).astype(k.dtype), held


def msa_plan(B: int, S: int, H: int):
    """What :func:`msa_prefill_attend` multiplies for ``[B, S, H, *]``
    queries: ``(chunk, groups, pairs multiplied, pairs of the whole
    square)``.  ``chunk``: queries a step, halved from 512 (down to
    16) while a float32 ``[B, H, chunk, S]`` score block is over
    ``_SCORE_BLOCK_BYTES`` (at 64 heads and 40,960 keys: 32 queries,
    512 rows of a kv head's product); ``groups`` of consecutive chunks,
    each over the keys up to its last query (at most 12, as in
    ``attend_plan``)."""
    chunk = 512
    while chunk > 16 and 4 * B * H * chunk * S > _SCORE_BLOCK_BYTES:
        chunk //= 2
    if S <= chunk or S % chunk:
        chunk = S
    groups = _chunk_groups(S // chunk)
    per = S // groups
    seen = sum(per * per * (j + 1) for j in range(groups))
    return chunk, groups, B * H * seen, B * H * S * S


def msa_prefill_attend(h, k, v, kmax, pos, c: MiniMaxM3Config, project,
                       out):
    """``h`` plus the causal MSA of a fresh block: every query over the
    pages its own selection names, the selection taken as a mask over
    the square.  ``h`` [B, S, hidden] the residual stream, ``k``/``v``
    [B, S, KH, D], ``kmax`` [B, S/bs, KH, D] (the block's pooled keys),
    ``pos`` [B, S] from 0; ``project(h, pos)`` gives queries [B, n, H,
    D] and indexer queries (summed over their heads, float32) [B, n, KH,
    D], norm included, ``out(o)`` the output projection [B, n, hidden].  The
    queries go in groups of consecutive chunks (:func:`msa_plan`): a
    group's queries are projected at once, its chunks attend in a device
    loop over the keys up to the group's last query (float32 scores,
    normalised behind the weighted sum), and its output is projected
    and added into ``h`` in place, so no tensor of every query is
    live beside ``h``."""
    B, S = h.shape[:2]
    KH, D, bs = k.shape[2], k.shape[3], c.msa_block
    H = c.num_attention_heads
    R = H // KH
    chunk, groups, _, _ = msa_plan(B, S, H)
    per = S // groups
    scale = 1.0 / (D ** 0.5)
    for j in range(groups):
        g0, end = j * per, (j + 1) * per
        kj, vj, mj = k[:, :end], v[:, :end], kmax[:, :end // bs]
        hg, pg = h[:, g0:end], pos[:, g0:end]
        qg, ug = project(hg, pg)
        qg = qg.reshape(B, per, KH, R, D)

        def one(i, o, kj=kj, vj=vj, mj=mj, end=end, qg=qg, ug=ug, pg=pg):
            take = lambda t: jax.lax.dynamic_slice_in_dim(t, i * chunk,
                                                          chunk, 1)
            qc, uc, pc = take(qg), take(ug), take(pg)
            with jax.named_scope(MSA_INDEX):
                sc = jnp.einsum("bqgd,bpgd->bqgp", uc, mj,
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=F32) * scale
                sel = select_pages(sc, pc[:, :, None] // bs, c.msa_top_k,
                                   c.msa_local)          # [B, q, KH, P]
            live = jnp.repeat(sel, bs, axis=-1) & (
                jnp.arange(end)[None, None, None, :]
                <= pc[:, :, None, None])                  # [B, q, KH, end]
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qc, kj,
                           preferred_element_type=F32) * scale
            live = jnp.moveaxis(live, 2, 1)[:, :, None]    # [B,KH,1,q,end]
            s = jnp.where(live, s, -1e30)
            e = jnp.where(live, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
            oc = jnp.einsum("bgrqk,bkgd->bqgrd", e.astype(vj.dtype), vj,
                            preferred_element_type=F32)
            den = jnp.moveaxis(e.sum(-1), 3, 1)[..., None]  # [B,q,KH,R,1]
            return jax.lax.dynamic_update_slice_in_dim(
                o, (oc / den).astype(o.dtype), i * chunk, 1)
        o = jax.lax.fori_loop(0, per // chunk, one,
                              jnp.zeros((B, per, KH, R, D), v.dtype))
        a = out(o.reshape(B, per, H * D))
        h = jax.lax.dynamic_update_slice_in_dim(h, hg + a.astype(h.dtype),
                                                g0, 1)
    return h


# ---------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------
class MiniMaxAttention(_Params):
    """GQA with per-head norms, partial rotary and the MSA indexer over
    head-major pages (module doc)."""

    def __init__(self, c: MiniMaxM3Config):
        super().__init__()
        self.config = c
        self._std = c.initializer_range
        h, nh, kh, d = (c.hidden_size, c.num_attention_heads,
                        c.num_key_value_heads, c.head_dim)
        self.q_proj = self._mk(h, nh * d)
        self.k_proj = self._mk(h, kh * d)
        self.v_proj = self._mk(h, kh * d)
        self.o_proj = self._mk(nh * d, h)
        self.q_norm = self._mk(d)
        self.k_norm = self._mk(d)
        self.index_proj = self._mk(h, kh * c.msa_index_heads * d)
        self.index_norm = self._mk(d)

    def init_cache(self, num_blocks: int, block_size: int, dtype):
        c = self.config
        if block_size != c.msa_block:
            raise ValueError(f"block_size {block_size} is not the MSA "
                             f"page of {c.msa_block} tokens")
        kh, d = c.num_key_value_heads, c.head_dim
        shape = (num_blocks, kh, block_size, d)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                "kmax": jnp.zeros((num_blocks, kh, d), F32)}

    def _project(self, xf, positions, dtype):
        """The queries [B, S, H, D] (``dtype``) and the indexer's queries
        summed over its heads [B, S, KH, D] (float32) of the float32
        normalised input ``xf`` [B, S, h]."""
        c = self.config
        kh, ih = c.num_key_value_heads, c.msa_index_heads
        B, S = xf.shape[:2]
        q = self._rot(self._proj(xf.astype(dtype), self.q_proj), self.q_norm,
                      positions)
        with jax.named_scope(MSA_INDEX):
            u = self._rot(self._proj(xf, self.index_proj, exact=True),
                          self.index_norm, positions)
            ubar = u.reshape(B, S, kh, ih, c.head_dim).sum(3)
        return q.astype(dtype), ubar

    def _proj(self, x, w, exact=False):
        """``x`` [B, S, h] by ``w`` as heads [B, S, n, D]; ``exact``: a
        float32 ``x`` by :func:`_dot_f32`."""
        B, S = x.shape[:2]
        y = _dot_f32(x, w._value) if exact else jnp.dot(x, w._value)
        return y.reshape(B, S, -1, self.config.head_dim)

    def _rot(self, t, norm, positions):
        """Each head normalised, its first ``rotary_dim`` dims rotated
        (float32)."""
        c = self.config
        return _rope_part(_gnorm(t, norm._value, c.rms_norm_eps), positions,
                          c.rotary_dim, c.rope_theta)

    def _out(self, o):
        return jnp.dot(o, self.o_proj._value)

    def forward_paged(self, h, norm, positions, cache, block_tables,
                      write_mask):
        """(``h`` plus the attention of ``x = N(h; norm)``, cache, (pages
        read, pages live)): the pages counts are the decode step's (zeros
        for a prefill call)."""
        c = self.config
        kh, d, bs = c.num_key_value_heads, c.head_dim, c.msa_block
        B, S = h.shape[:2]
        eps = c.rms_norm_eps
        normed = lambda t: _gnorm(t, norm._value, eps)
        xf = normed(h)
        x = xf.astype(h.dtype)
        kpool, vpool, kmax = cache["k"], cache["v"], cache["kmax"]
        # float32 keys for the pooled keys; the pages hold them rounded
        kf = self._rot(self._proj(xf, self.k_proj, exact=True), self.k_norm,
                       positions)
        k = kf.astype(x.dtype)
        v = self._proj(x, self.v_proj).astype(vpool.dtype)
        if S > 1:
            # a fresh block from position 0 (a prefill call): whole pages
            # (physical block = table[page]; a page no token of the row
            # reaches goes to the trash block), and each page's pooled
            # key from its own written tokens.  A page is one scatter
            # row of the pool's trailing dims, so the pool keeps its
            # layout: a write at (page, head, offset) made XLA re-lay
            # the whole pool twice a layer
            P = S // bs
            with jax.named_scope(MSA_INDEX):
                pk, held = page_max(kf, write_mask, bs)
            pg = jnp.where(held, block_tables[:, :P], 0).reshape(-1)
            heads_major = lambda t: jnp.swapaxes(
                t.reshape(B * P, bs, kh, d), 1, 2)
            kpool = kpool.at[pg].set(heads_major(k))
            vpool = vpool.at[pg].set(heads_major(v))
            with jax.named_scope(MSA_INDEX):
                kmax = kmax.at[pg].set(
                    pk.reshape(-1, kh, d).astype(kmax.dtype))
            h = msa_prefill_attend(
                h, k, v, pk, positions, c,
                lambda hc, pc: self._project(normed(hc), pc, h.dtype),
                self._out)
            zero = jnp.zeros((), jnp.int32)
            return h, {"k": kpool, "v": vpool, "kmax": kmax}, (zero, zero)
        # one token a row: physical block = table[pos // bs], offset =
        # pos % bs, one (head, offset) row of D a kv head; masked writes
        # go to the trash block (0, 0)
        blk = jnp.take_along_axis(block_tables, positions // bs, axis=1)
        phys = jnp.where(write_mask[:, 0], blk[:, 0], 0)
        off = jnp.where(write_mask[:, 0], positions[:, 0] % bs, 0)
        g = jnp.arange(kh)[None, :]
        kpool = kpool.at[phys[:, None], g, off[:, None]].set(k[:, 0])
        vpool = vpool.at[phys[:, None], g, off[:, None]].set(v[:, 0])
        q, ubar = self._project(xf, positions, h.dtype)
        o, kmax, counts = self._decode(q, kf, ubar, positions, write_mask,
                                       phys, off, kpool, vpool, kmax,
                                       block_tables)
        a = self._out(o.reshape(B, 1, -1))
        return (h + a.astype(h.dtype),
                {"k": kpool, "v": vpool, "kmax": kmax}, counts)

    def _decode(self, q, k, ubar, positions, write_mask, phys, off, kpool,
                vpool, kmax, block_tables):
        from ...ops.pallas import registry as _kreg
        c = self.config
        bs, kh, d = c.msa_block, c.num_key_value_heads, c.head_dim
        B = q.shape[0]
        with jax.named_scope(MSA_INDEX):
            # the step's key raises its page's running max (a write at
            # offset 0 starts the page afresh)
            kn = k.reshape(B, kh, d).astype(kmax.dtype)
            kmax = kmax.at[phys].set(
                jnp.where((off == 0)[:, None, None], kn,
                          jnp.maximum(kmax[phys], kn)))
            bt = positions[:, 0] // bs
            sc = jnp.einsum("bgd,bpgd->bgp", ubar[:, 0], kmax[block_tables],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=F32) / (d ** 0.5)
            sel = select_pages(sc, bt[:, None], c.msa_top_k, c.msa_local)
            logical, count = selected_list(sel, c.msa_pages)
            pages = jnp.take_along_axis(
                jnp.broadcast_to(block_tables[:, None],
                                 (B, kh, block_tables.shape[1])),
                logical, axis=2)
            pages = jnp.where(jnp.arange(c.msa_pages) < count[..., None],
                              pages, 0)
        o = _kreg.dispatch("paged_sparse_attention", q, kpool, vpool, pages,
                           logical, count, positions,
                           mode=_kreg.resolve("paged_sparse_attention"))
        wm = write_mask[:, 0]
        read = jnp.sum(jnp.where(wm[:, None], count, 0))
        live = kh * jnp.sum(jnp.where(wm, bt + 1, 0))
        return o, kmax, (read.astype(jnp.int32), live.astype(jnp.int32))


class MiniMaxMLP(_Params):
    """The dense leading layers' FFN, in the experts' clamped form."""

    def __init__(self, c: MiniMaxM3Config):
        super().__init__()
        self._std = c.initializer_range
        h, f = c.hidden_size, c.dense_intermediate_size
        self.gate_proj, self.up_proj, self.down_proj = (
            self._mk(h, f), self._mk(h, f), self._mk(f, h))

    def apply_values(self, x):
        return _swiglu(x, self.gate_proj._value, self.up_proj._value,
                       self.down_proj._value, ACT).astype(x.dtype)


class MiniMaxDecoderLayer(_Params):
    def __init__(self, c: MiniMaxM3Config, l: int):
        super().__init__()
        self.config = c
        self._std = c.initializer_range
        self.input_layernorm = self._mk(c.hidden_size)
        self.post_attention_layernorm = self._mk(c.hidden_size)
        self.self_attn = MiniMaxAttention(c)
        self.is_moe = c.is_moe(l)
        if self.is_moe:
            self.mlp = DroplessMoELayer(
                c.hidden_size, c.intermediate_size, c.num_local_experts,
                top_k=c.num_experts_per_tok, held_experts=c.held_experts,
                shared_hidden=c.shared_intermediate_size,
                routed_scaling_factor=c.routed_scaling_factor,
                initializer_range=c.initializer_range, act=ACT)
        else:
            self.mlp = MiniMaxMLP(c)

    def ffn_chunk(self, n_tokens: int) -> int:
        """Tokens of the FFN a step of the prefill's device loop takes:
        the largest divisor of ``n_tokens`` up to ``FFN_CHUNK`` (3,584
        of the 43,008-token bucket)."""
        ch = min(FFN_CHUNK, n_tokens)
        while n_tokens % ch:
            ch -= 1
        return ch

    def _ffn(self, h):
        """``h`` [T, hidden] plus the FFN sublayer's output, and the
        expert layer's three counters (None in a dense layer).  A
        prefill call's tokens go ``ffn_chunk(T)`` at a time in a device
        loop, norm included, each chunk's output added into ``h`` in
        place, so its temporaries are a chunk's."""
        T = h.shape[0]
        ch = self.ffn_chunk(T)
        w, eps = self.post_attention_layernorm._value, \
            self.config.rms_norm_eps

        def one(hc):
            zc = _gnorm(hc, w, eps).astype(hc.dtype)
            if self.is_moe:
                y, *n = self.mlp.apply_values(zc, count_rows=True)
                n = jnp.stack([jnp.asarray(m, jnp.int32) for m in n])
            else:
                y, n = self.mlp.apply_values(zc), None
            return hc + y.astype(hc.dtype), n
        if ch == T:
            return one(h)

        def step(i, carry):
            h, n = carry
            hc, m = one(jax.lax.dynamic_slice_in_dim(h, i * ch, ch, 0))
            h = jax.lax.dynamic_update_slice_in_dim(h, hc, i * ch, 0)
            if n is not None:   # picks and row-products add up
                n = jnp.stack([n[0] + m[0], jnp.maximum(n[1], m[1]),
                               n[2] + m[2]])
            return h, n
        zero = jnp.zeros((3,), jnp.int32) if self.is_moe else None
        return jax.lax.fori_loop(0, T // ch, step, (h, zero))

    def forward_paged(self, h, positions, cache, block_tables, write_mask):
        with jax.named_scope(ATTN):
            h, cache, pages = self.self_attn.forward_paged(
                h, self.input_layernorm, positions, cache, block_tables,
                write_mask)
        with jax.named_scope(MOE if self.is_moe else FFN):
            y, counts = self._ffn(h.reshape(-1, h.shape[-1]))
            h = y.reshape(h.shape)
        return h, cache, counts, pages


class MiniMaxM3Model(_Params):
    def __init__(self, c: MiniMaxM3Config):
        super().__init__()
        from ...nn.layer.container import LayerList
        self.config = c
        self._std = c.initializer_range
        self.embed_tokens = self._mk(c.vocab_size, c.hidden_size)
        self.layers = LayerList([MiniMaxDecoderLayer(c, l)
                                 for l in range(c.num_hidden_layers)])
        self.norm = self._mk(c.hidden_size)


class MiniMaxM3ForCausalLM(_Params):
    """Causal LM over :class:`MiniMaxM3Model`, untied head, served
    through the block-paged cache API (module doc)."""

    def __init__(self, config: MiniMaxM3Config):
        super().__init__()
        self.config = config
        self._std = config.initializer_range
        self.model = MiniMaxM3Model(config)
        self.lm_head = self._mk(config.hidden_size, config.vocab_size)

    def supports_kv_cache(self) -> bool:
        return True

    def has_recurrent_state(self) -> bool:
        return False

    def prefill_starts_sequences_only(self) -> bool:
        """A multi-token step makes its pages' pooled keys and its
        queries' selections from its own keys, so it has to start its
        sequence: ``GenerationServer`` refuses what would run one from
        the middle (a shared prefix's suffix prefill, speculative
        verification)."""
        return True

    def step_counters(self) -> Tuple[str, ...]:
        """What ``forward_paged``'s third value counts, summed over the
        layers: the expert layers' picks that landed on the held
        experts, each layer's largest held expert's load and the
        row-products done; the (page, kv head) tiles the sparse decode
        read, and those a dense read of every live page would have."""
        return ("moe_picks_here", "moe_max_expert_load",
                "moe_rows_multiplied", "msa_pages_read", "msa_pages_live")

    def loops_on_device(self, n_tokens: int) -> bool:
        """Whether ``forward_paged`` over ``n_tokens`` tokens lowers a
        device loop whose steps branch (the expert layers' grouped
        dispatch): ``GenerationServer`` puts no ``conditional`` of its
        own behind such a program."""
        return any(lyr.is_moe and lyr.mlp.loops_on_device(
            lyr.ffn_chunk(n_tokens)) for lyr in self.model.layers)

    def prefill_attn_pairs(self, batch: int, bucket: int):
        """(multiplied, whole square): the (query, key) pairs a prefill
        call of ``batch`` rows x ``bucket`` tokens multiplies in its
        attention layers (the selection masks; it skips nothing yet)."""
        done, square = msa_plan(batch, bucket,
                                self.config.num_attention_heads)[2:]
        n = len(self.model.layers)
        return n * done, n * square

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         num_slots: Optional[int] = None):
        """Per layer ``{"k", "v": [num_blocks, KH, block, D], "kmax":
        [num_blocks, KH, D]}`` (physical block 0 is the trash block);
        ``num_slots`` is taken for the protocol's sake."""
        dt = jnp.dtype(self.config.compute_dtype)
        return [lyr.self_attn.init_cache(int(num_blocks), int(block_size),
                                         dt) for lyr in self.model.layers]

    def forward_paged(self, input_ids, positions, pools, block_tables,
                      write_mask, gather_at=None,
                      verify_mode: bool = False, slots=None):
        """(logits, caches, counters) through the paged caches; ``slots``
        is taken for the protocol's sake."""
        if verify_mode:
            from ...inference.recurrent_state import \
                MidSequenceStepUnsupported
            raise MidSequenceStepUnsupported(
                "a multi-token step that starts mid-sequence (speculative "
                "verification, suffix prefill): the MSA layers pool and "
                "select over the fresh block only")
        c = self.config
        raw = lambda t: t._value if isinstance(t, Tensor) else t
        ids, pos, wm = raw(input_ids), raw(positions), raw(write_mask)
        tbl = raw(block_tables).astype(jnp.int32)
        pos = pos.astype(jnp.int32)
        if ids.shape[1] > 1 and ids.shape[1] % c.msa_block:
            raise ValueError(f"a prefill block of {ids.shape[1]} tokens is "
                             f"not whole pages of {c.msa_block}")
        with jax.named_scope(EMBED):
            h = self.model.embed_tokens._value[ids].astype(
                jnp.dtype(c.compute_dtype))
        moe, pages = jnp.zeros((3,), jnp.int32), jnp.zeros((2,), jnp.int32)
        new_pools = []
        for lyr, cache in zip(self.model.layers, pools):
            cache = {k: raw(v) for k, v in cache.items()}
            h, cache, n, p = lyr.forward_paged(h, pos, cache, tbl, wm)
            new_pools.append(cache)
            if n is not None:
                moe = moe + n
            pages = pages + jnp.stack(p)
        with jax.named_scope(HEAD):
            h = _gnorm(h, self.model.norm._value,
                       c.rms_norm_eps).astype(h.dtype)
            if gather_at is not None:
                h = jnp.take_along_axis(
                    h, raw(gather_at)[:, None, None].astype(jnp.int32),
                    axis=1)
            logits = jnp.dot(h, self.lm_head._value,
                             preferred_element_type=F32)
        return Tensor(logits), new_pools, jnp.concatenate([moe, pages])
