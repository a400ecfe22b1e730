"""Kimi-Linear decoder-only LM (moonshotai Kimi-Linear-48B-A3B): a
hybrid of linear and latent attention over sparse experts, served
through ``GenerationServer`` like any other causal LM.

Per layer (pre-norm RMSNorm blocks, untied head):

- **KDA** (Kimi Delta Attention) in three layers of four: q, k, v
  behind a width-4 causal depthwise convolution and SiLU, L2-normalised
  q and k, a per-channel decay ``g = -exp(A_log) * softplus(low-rank(x)
  + dt_bias)`` and a write strength ``beta = sigmoid(x W_b)``; per head
  the float32 state ``S [d_k, d_v]`` follows the gated delta rule
  ``S <- Diag(exp g) S;  u = beta (v - S^T k);  S <- S + k u^T;
  o = S^T q``; the output is RMS-normalised per head, gated by a
  low-rank sigmoid gate and projected.  Its cache is NOT keys and
  values: a recurrent state ``[slots, H, d_k, d_v]`` float32 and the
  convolutions' last three inputs ``[slots, 3, 3, H*d_k]``, fixed in
  size whatever the context.
- **MLA** (latent attention, no rotary: :class:`LatentAttention`, which
  the Pangu Ultra MoE model shares with a low-rank query and rotary)
  in the fourth: the cache holds
  one latent row ``[c | k_pe]`` per token for ALL heads, in pages
  ``[num_blocks, block, 1, 640]`` (576 values padded to whole lane
  tiles, so that a page is the ``paged_latent_attention`` kernel's
  page).
  Prefill expands K and V from the latent and attends in plain XLA,
  query chunks in groups that each take only the keys they may see
  (:func:`_attend`, :func:`attend_plan`); decode absorbs the
  expansion into the query and the output and attends over the latent
  pages through the block table.
- the FFN is a dense SwiGLU in the first layer and
  :class:`~paddle_tpu.nn.layer.moe.DroplessMoELayer` in the others
  (sigmoid router over all experts, top-k, one shared expert;
  ``held_experts`` makes this chip's share of an expert-parallel
  layer).

Serving only: the chunked scan of the KDA prefill has no backward pass
here, and the expert layer is written for inference (nn/layer/moe.py).

The paged-cache protocol differs from a K/V model's in four places.
``init_paged_cache`` takes ``num_slots``.  ``forward_paged`` takes
``slots=`` ([B] int32): each row's slot in a batched prefill, where
rows are not slots (a row that holds no sequence names ``num_slots``,
which no write reaches); without it row i is slot i, which is what a
decode step over every slot is.  And it returns a third value, the
int32 counters :meth:`KimiLinearForCausalLM.step_counters` names.
``loops_on_device(n_tokens)`` tells the server which of its programs
hold the expert layers' device loop, ``prefill_attn_pairs(batch,
bucket)`` how much of the attention square a prefill call multiplies.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...framework.core import Tensor
from ...nn.initializer import Constant, Normal
from ...nn.layer.layers import Layer
from ...nn.layer.moe import DroplessMoELayer, _swiglu
from ...observability.scopes import ATTN, EMBED, FFN, HEAD, MIXER, MOE

__all__ = ["KimiLinearConfig", "KimiLinearForCausalLM", "kimi_linear_tiny",
           "LatentAttention", "kda_chunked", "kda_step", "short_conv"]

F32 = jnp.float32
_HP = jax.lax.Precision.HIGHEST
_SUB = 16          # sub-block of a KDA chunk whose decays are exact
KDA_CHUNK = 64     # tokens a step of the prefill scan (fla's chunk_kda)


@dataclasses.dataclass
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216          # the dense first layer's FFN
    num_hidden_layers: int = 27
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)  # 1-indexed
    first_k_dense_replace: int = 1
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    gate_low_rank: int = 128               # decay and output gate
    num_attention_heads: int = 32          # MLA
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64             # plain dims: no rotary
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    moe_intermediate_size: int = 1024
    num_experts: int = 256                 # the router's width
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    # (first, count) of the routed experts this chip holds; None = all
    held_experts: Optional[Tuple[int, int]] = None
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    max_position_embeddings: int = 4096
    compute_dtype: str = "bfloat16"

    def is_mla(self, l: int) -> bool:
        return (l + 1) in self.full_attn_layers

    def is_moe(self, l: int) -> bool:
        return l >= self.first_k_dense_replace


def kimi_linear_tiny(**kw) -> KimiLinearConfig:
    """Small config for tests: four layers (KDA, KDA, KDA, MLA; the
    first dense), 2 heads of everything, 8 experts, top 2."""
    d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
             num_hidden_layers=4, kda_num_heads=2, kda_head_dim=16,
             gate_low_rank=8, num_attention_heads=2, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24,
             moe_intermediate_size=32, num_experts=8,
             num_experts_per_token=2, max_position_embeddings=128,
             compute_dtype="float32")
    d.update(kw)
    return KimiLinearConfig(**d)


# ---------------------------------------------------------------------
# the mathematics, on raw arrays
# ---------------------------------------------------------------------
def _rms(v, w, eps):
    h = v.astype(F32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + eps)
    return h * w.astype(F32)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def short_conv(x, tail, w, length=None):
    """Causal depthwise convolution with carried state.  ``x``
    [B, P, S, C] (P projections side by side), ``tail`` [B, P, W-1, C]
    the inputs before ``x``, ``w`` [P, W, C].  Returns ``(y float32
    [B, P, S, C], new tail)``; the new tail is the last W-1 inputs up
    to ``length`` [B] (default S), so padding past it shifts nothing.
    """
    S, W = x.shape[2], w.shape[1]
    cat = jnp.concatenate([tail.astype(x.dtype), x], axis=2)
    wf = w.astype(F32)
    y = sum(wf[None, :, j, None, :] * cat[:, :, j:j + S].astype(F32)
            for j in range(W))
    if length is None:
        return y, cat[:, :, S:]
    at = length.astype(jnp.int32)[:, None, None, None] \
        + jnp.arange(W - 1, dtype=jnp.int32)[None, None, :, None]
    return y, jnp.take_along_axis(cat, at, axis=2)


def kda_step(S, q, k, v, g, beta):
    """One token of the gated delta rule on the float32 state.  ``S``
    [B, H, dk, dv]; ``q, k, g`` [B, H, dk]; ``v`` [B, H, dv]; ``beta``
    [B, H].  A row with ``g = 0`` and ``beta = 0`` leaves ``S`` as it
    is, bit for bit.  Sums on the vector unit: the state never passes
    through the MXU's bfloat16."""
    Sd = S * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(Sd * k[..., None], axis=-2))
    o = jnp.sum(Sd * q[..., None], axis=-2) \
        + u * jnp.sum(k * q, -1, keepdims=True)
    return Sd + k[..., None] * u[..., None, :], o


def _kda_chunk(S, q, k, v, g, beta):
    """One chunk of C tokens (a multiple of 16) in closed form.  ``S``
    [B, H, dk, dv]; ``q, k, g`` [B, H, C, dk]; ``v`` [B, H, C, dv];
    ``beta`` [B, H, C].  With G the running sum of g inside the chunk,
    the pseudo-values U solve ``(I + Diag(beta) A) U = beta (V - (K e^G)
    S)``, ``A[i, j] = sum_c k_i k_j e^(G_i - G_j)`` for j < i, and
    ``O = (Q e^G) S + P U`` with P the same sum over q_i k_j, j <= i.
    Every exponent taken is <= 0: inside a sub-block of 16 the decays
    between two tokens are computed pair by pair, and across
    sub-blocks they factor through the boundary between them, so no
    decay, however strong, overflows.  Returns (S after the chunk, O).
    """
    C = q.shape[-2]
    nsb = C // _SUB
    G = jnp.cumsum(g, axis=-2)
    i = jnp.arange(_SUB)
    low = (i[:, None] >= i[None, :])[..., None]           # j <= i
    a_rows, p_rows = [], []
    for I in range(nsb):
        sl = slice(I * _SUB, (I + 1) * _SUB)
        Gi, ki, qi = G[..., sl, :], k[..., sl, :], q[..., sl, :]
        E = jnp.exp(jnp.where(
            low, Gi[..., :, None, :] - Gi[..., None, :, :], -jnp.inf))
        kE = ki[..., None, :, :] * E
        a = jnp.sum(ki[..., :, None, :] * kE, -1)
        p = jnp.sum(qi[..., :, None, :] * kE, -1)
        if I:
            r = G[..., I * _SUB - 1, :][..., None, :]
            dec = jnp.exp(Gi - r)
            km = k[..., :I * _SUB, :] * jnp.exp(r - G[..., :I * _SUB, :])
            a = jnp.concatenate([jnp.einsum(
                "bhid,bhjd->bhij", ki * dec, km, precision=_HP), a], -1)
            p = jnp.concatenate([jnp.einsum(
                "bhid,bhjd->bhij", qi * dec, km, precision=_HP), p], -1)
        padw = [(0, 0)] * (a.ndim - 1) + [(0, C - (I + 1) * _SUB)]
        a_rows.append(jnp.pad(a, padw))
        p_rows.append(jnp.pad(p, padw))
    A = jnp.concatenate(a_rows, -2) * jnp.tril(jnp.ones((C, C), F32), -1)
    P = jnp.concatenate(p_rows, -2)
    M = beta[..., None] * A
    eG = jnp.exp(G)
    rhs = beta[..., None] * (v - jnp.einsum(
        "bhck,bhkv->bhcv", k * eG, S, precision=_HP))
    # forward substitution by sub-blocks; a 16 x 16 unit-triangular
    # block inverts as (I - m)(I + m^2)(I + m^4)(I + m^8)
    eye = jnp.eye(_SUB, dtype=F32)
    mm = lambda x, y: jnp.matmul(x, y, precision=_HP)
    U = None
    for I in range(nsb):
        sl = slice(I * _SUB, (I + 1) * _SUB)
        r_i = rhs[..., sl, :]
        if I:
            r_i = r_i - mm(M[..., sl, :I * _SUB], U)
        m = M[..., sl, sl]
        T, pw, n = eye - m, mm(m, m), 2
        while n < _SUB:
            T, pw, n = mm(T, eye + pw), mm(pw, pw), 2 * n
        u_i = mm(T, r_i)
        U = u_i if U is None else jnp.concatenate([U, u_i], -2)
    O = jnp.einsum("bhck,bhkv->bhcv", q * eG, S, precision=_HP) + mm(P, U)
    g_end = G[..., -1, :]
    S = jnp.exp(g_end)[..., None] * S + jnp.einsum(
        "bhck,bhcv->bhkv", k * jnp.exp(g_end[..., None, :] - G), U,
        precision=_HP)
    return S, O


def kda_chunked(S, q, k, v, g, beta, chunk: Optional[int] = None):
    """The gated delta rule over a block of tokens as a scan over
    chunks.  ``S`` [B, H, dk, dv]; ``q, k, g`` [B, L, H, dk]; ``v``
    [B, L, H, dv]; ``beta`` [B, L, H]; all float32.  A position with
    ``g = 0`` and ``beta = 0`` (padding) changes nothing.  Returns
    (S after the block, O [B, L, H, dv])."""
    chunk = KDA_CHUNK if chunk is None else chunk
    if chunk % _SUB:
        raise ValueError(f"kda chunk must be a multiple of {_SUB}")
    B, L = q.shape[:2]
    pad = -L % chunk
    nc = (L + pad) // chunk

    def chunks(x):                 # [B, L, H, *d] -> [nc, B, H, C, *d]
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((B, nc, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)
    S, O = jax.lax.scan(lambda s, t: _kda_chunk(s, *t), S,
                        tuple(chunks(x) for x in (q, k, v, g, beta)))
    O = jnp.moveaxis(jnp.moveaxis(O, 2, 3), 0, 1)   # [B, nc, C, H, dv]
    return S, O.reshape((B, nc * chunk) + O.shape[3:])[:, :L]


# the largest float32 score block ``[B, H, chunk, S]`` of ``_attend``:
# 4 rows x 32 heads x 512 queries x 3,072 keys
_SCORE_BLOCK_BYTES = 3 * 2 ** 28


def _rope_half(x, positions, theta: float):
    """Rotary embedding over the whole head, rotate-half pairing (dim i
    with dim i + D/2), on ``x`` [B, S, H, D] in float32."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions[:, :, None].astype(F32) * freq            # [B, S, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _chunk_groups(n: int) -> int:
    """Groups of consecutive query chunks that ``_attend`` makes of
    ``n`` chunks: the largest divisor of ``n`` up to twelve (a group is
    a whole number of chunks; each group lays its prefix of the keys
    out once more, so past a dozen the copies cost what the skipped
    products save: PERF.md, PR 36)."""
    return max(g for g in range(1, 13) if n % g == 0)


def attend_plan(B: int, S: int, H: int, chunk: Optional[int] = None):
    """What :func:`_attend` multiplies for queries ``[B, S, H, *]``:
    ``(chunk, groups, pairs multiplied, pairs of the whole square)``, a
    pair being one (query, key) of one head and row.  ``chunk``: 512
    queries, halved while a float32 ``[B, H, chunk, S]`` score block is
    over ``_SCORE_BLOCK_BYTES`` (at 128 heads, 2 rows and 3,072 keys:
    256 queries at a time); all ``S`` where they fit one chunk or do
    not divide into chunks.  With ``G`` groups ``(G + 1) / 2G`` of the
    square is multiplied."""
    if chunk is None:
        chunk = 512
        while chunk > 16 and 4 * B * H * chunk * S > _SCORE_BLOCK_BYTES:
            chunk //= 2
    if S <= chunk or S % chunk:
        chunk = S
    groups = _chunk_groups(S // chunk)
    per = S // groups                       # queries a group
    seen = sum(per * per * (j + 1) for j in range(groups))
    return chunk, groups, B * H * seen, B * H * S * S


def _attend(q, k, v, scale, chunk: Optional[int] = None):
    """Causal attention of a fresh block, float32 scores, in plain XLA.
    ``q, k`` [B, S, H, Dk], ``v`` [B, S, H, Dv]: the two head sizes may
    differ.  Queries go ``chunk`` at a time, one after another with no
    device loop, so that one ``[B, H, chunk, keys]`` score block is
    live, in groups of consecutive chunks (:func:`attend_plan`); a
    chunk takes the keys up to its group's last query and no others,
    so the keys none of them may see are never multiplied.  The
    softmax is normalised behind the weighted sum: the division runs
    on ``[B, chunk, H, Dv]``, not on the score block."""
    B, S, H, _ = q.shape
    chunk, groups, _, _ = attend_plan(B, S, H, chunk)
    per = S // groups
    out = []
    for q0 in range(0, S, chunk):
        end = (q0 // per + 1) * per
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:q0 + chunk], k[:, :end],
                       preferred_element_type=F32) * scale
        live = (q0 + jnp.arange(chunk))[:, None] >= jnp.arange(end)[None, :]
        s = jnp.where(live, s, -1e30)
        e = jnp.exp(s - s.max(-1, keepdims=True))
        o = jnp.einsum("bhqk,bkhd->bqhd", e.astype(v.dtype), v[:, :end],
                       preferred_element_type=F32)
        out.append((o / jnp.moveaxis(e.sum(-1), 1, 2)[..., None]).astype(
            v.dtype))
    return out[0] if len(out) == 1 else jnp.concatenate(out, 1)


# ---------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------
class _Params(Layer):
    def _mk(self, *shape, std=None, one=False):
        init = Constant(1.0) if one else Normal(
            0.0, self._std if std is None else std)
        return self.create_parameter(shape, default_initializer=init)


class KimiDeltaAttention(_Params):
    """KDA with its per-slot state (module doc).  With ``slots`` the
    rows are a block of tokens each: :func:`kda_chunked` from the
    named slot's state (zero where the block starts a sequence).
    Without, row i is slot i, and one token a row is :func:`kda_step`
    on the whole state array in place."""

    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        self.config = c
        self._std = c.initializer_range
        h, H, dk = c.hidden_size, c.kda_num_heads, c.kda_head_dim
        W, r = c.short_conv_kernel_size, c.gate_low_rank
        kd = H * dk
        self.q_proj, self.k_proj, self.v_proj = (
            self._mk(h, kd), self._mk(h, kd), self._mk(h, kd))
        # the public model draws these U(+-1/2) (fan-in 4)
        self.q_conv, self.k_conv, self.v_conv = (
            self._mk(W, kd, std=0.3), self._mk(W, kd, std=0.3),
            self._mk(W, kd, std=0.3))
        self.f_a, self.f_b = self._mk(h, r), self._mk(r, kd)
        self.A_log = self._mk(H, std=0.1)
        self.dt_bias = self._mk(kd, std=0.1)
        self.b_proj = self._mk(h, H)
        self.g_a, self.g_b = self._mk(h, r), self._mk(r, kd)
        self.o_norm = self._mk(dk, one=True)
        self.o_proj = self._mk(kd, h)

    def init_cache(self, num_slots: int, dtype):
        c = self.config
        H, dk = c.kda_num_heads, c.kda_head_dim
        return {"state": jnp.zeros((num_slots, H, dk, dk), F32),
                "conv": jnp.zeros((num_slots, 3,
                                   c.short_conv_kernel_size - 1, H * dk),
                                  dtype)}

    def forward_paged(self, x, positions, cache, write_mask, slots):
        c = self.config
        H, dk = c.kda_num_heads, c.kda_head_dim
        B, S = x.shape[:2]
        v_ = lambda p: p._value
        state, conv = cache["state"], cache["conv"]
        N = state.shape[0]
        proj = jnp.stack([jnp.dot(x, v_(self.q_proj)),
                          jnp.dot(x, v_(self.k_proj)),
                          jnp.dot(x, v_(self.v_proj))], axis=1)
        cw = jnp.stack([v_(self.q_conv), v_(self.k_conv),
                        v_(self.v_conv)])
        wm = write_mask
        wf = wm.astype(F32)
        g = -jnp.exp(v_(self.A_log).astype(F32))[None, None, :, None] \
            * jax.nn.softplus(
                jnp.dot(jnp.dot(x, v_(self.f_a)), v_(self.f_b)).astype(F32)
                + v_(self.dt_bias).astype(F32)).reshape(B, S, H, dk)
        g = g * wf[..., None, None]
        beta = jax.nn.sigmoid(jnp.dot(x, v_(self.b_proj)).astype(F32)) \
            * wf[..., None]
        step = S == 1 and slots is None
        if step:
            if B != N:
                raise ValueError(
                    f"a KDA decode step runs over every slot: got {B} "
                    f"rows for {N} slots (name the rows' slots)")
            tail0, S0 = conv, state
            y, tail = short_conv(proj, tail0, cw)
            tail = jnp.where(wm[:, :, None, None], tail,
                             tail0.astype(tail.dtype))
        else:
            if slots is None:
                slots = jnp.arange(B, dtype=jnp.int32)
            at = jnp.clip(slots, 0, N - 1)
            # a block that starts a sequence starts from nothing,
            # whatever the slot's last owner left behind
            fresh = positions[:, 0] == 0
            tail0 = jnp.where(fresh[:, None, None, None], 0, conv[at])
            S0 = jnp.where(fresh[:, None, None, None], 0.0, state[at])
            y, tail = short_conv(proj, tail0, cw,
                                 length=wm.sum(-1).astype(jnp.int32))
        y = jax.nn.silu(y).reshape(B, 3, S, H, dk)
        q = _l2(y[:, 0]) * dk ** -0.5
        k = _l2(y[:, 1])
        v = y[:, 2]
        if step:
            S1, o = kda_step(S0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                             beta[:, 0])
            o = o[:, None]
            cache = {"state": S1, "conv": tail.astype(conv.dtype)}
        else:
            S1, o = kda_chunked(S0, q, k, v, g, beta)
            cache = {"state": state.at[slots].set(S1, mode="drop"),
                     "conv": conv.at[slots].set(tail.astype(conv.dtype),
                                                mode="drop")}
        o = _rms(o, v_(self.o_norm), c.rms_norm_eps)
        gate = jax.nn.sigmoid(jnp.dot(jnp.dot(x, v_(self.g_a)),
                                      v_(self.g_b)).astype(F32))
        o = (o * gate.reshape(B, S, H, dk)).astype(x.dtype)
        return jnp.dot(o.reshape(B, S, H * dk), v_(self.o_proj)), cache


class LatentAttention(_Params):
    """Latent attention (MLA): expanded for a fresh block, absorbed
    over the latent pages for a decode step.  One module for the
    models that cache a latent row ``[c | k_pe]`` a token, all heads
    over that one row; ``c`` names the widths (``hidden_size``,
    ``num_attention_heads``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``v_head_dim``, ``kv_lora_rank``, ``rms_norm_eps``,
    ``initializer_range``).

    ``q_lora_rank`` None: a full-rank ``q_proj`` (Kimi-Linear).  An
    int: ``q = RMSNorm(x q_a_proj; q_a_norm) q_b_proj`` (DeepSeek-V3,
    Pangu Ultra MoE).  ``rope_theta`` None: the ``qk_rope_head_dim``
    dims are plain dims (Kimi-Linear's ``mla_use_nope``).  A float:
    they take rotary at the token's position (rotate-half), ``q_pe``
    per head and ``k_pe`` once a token, BEFORE the page write and the
    absorb, so that the page holds the rotated ``k_pe`` and a decode
    step rotates its query alone."""

    def __init__(self, c, q_lora_rank: Optional[int] = None,
                 rope_theta: Optional[float] = None):
        super().__init__()
        self.config = c
        self._std = c.initializer_range
        self.q_lora_rank, self.rope_theta = q_lora_rank, rope_theta
        h, nh = c.hidden_size, c.num_attention_heads
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        self.latent_width = c.kv_lora_rank + dr
        self.page_width = -(-self.latent_width // 128) * 128
        if q_lora_rank is None:
            self.q_proj = self._mk(h, nh * (dn + dr))
        else:
            self.q_a_proj = self._mk(h, q_lora_rank)
            self.q_a_norm = self._mk(q_lora_rank, one=True)
            self.q_b_proj = self._mk(q_lora_rank, nh * (dn + dr))
        self.kv_a_proj = self._mk(h, self.latent_width)
        self.kv_a_norm = self._mk(c.kv_lora_rank, one=True)
        self.kv_b_proj = self._mk(c.kv_lora_rank, nh * (dn + dv))
        self.o_proj = self._mk(nh * dv, h)

    def init_cache(self, num_blocks: int, block_size: int, dtype):
        return {"latent": jnp.zeros(
            (num_blocks, block_size, 1, self.page_width), dtype)}

    def _project(self, x, positions):
        """(q [B, S, nh, dn + dr], the normalised latent [B, S, kvr],
        k_pe [B, S, dr]); with rotary both ``pe`` parts come back
        rotated."""
        c = self.config
        nh, kvr = c.num_attention_heads, c.kv_lora_rank
        dn, dr = c.qk_nope_head_dim, c.qk_rope_head_dim
        B, S = x.shape[:2]
        v_ = lambda p: p._value
        if self.q_lora_rank is None:
            q = jnp.dot(x, v_(self.q_proj))
        else:
            cq = _rms(jnp.dot(x, v_(self.q_a_proj)), v_(self.q_a_norm),
                      c.rms_norm_eps).astype(x.dtype)
            q = jnp.dot(cq, v_(self.q_b_proj))
        q = q.reshape(B, S, nh, dn + dr)
        kva = jnp.dot(x, v_(self.kv_a_proj))
        lat = _rms(kva[..., :kvr], v_(self.kv_a_norm),
                   c.rms_norm_eps).astype(x.dtype)
        kpe = kva[..., kvr:]
        if self.rope_theta is not None:
            rot = lambda t: _rope_half(t.astype(F32), positions,
                                       self.rope_theta).astype(x.dtype)
            q = jnp.concatenate([q[..., :dn], rot(q[..., dn:])], -1)
            kpe = rot(kpe[:, :, None])[:, :, 0]
        return q, lat, kpe

    def _kv_b(self):
        c = self.config
        return self.kv_b_proj._value.reshape(
            c.kv_lora_rank, c.num_attention_heads,
            c.qk_nope_head_dim + c.v_head_dim)

    def _expanded(self, q, lat, kpe):
        """Causal attention of a fresh block with K and V expanded from
        the latent: [B, S, nh, dv]."""
        c = self.config
        nh, dn, dr = (c.num_attention_heads, c.qk_nope_head_dim,
                      c.qk_rope_head_dim)
        B, S = lat.shape[:2]
        kv = jnp.einsum("bsc,chd->bshd", lat, self._kv_b()).astype(
            lat.dtype)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            kpe[:, :, None], (B, S, nh, dr))], -1)
        # not the flash kernel: it takes ONE head size of 64, 128
        # or 256 for q, k and v, and padded to 256 it hung a v5e
        # once in ~100k calls (PERF.md, PR 27)
        return _attend(q, k, kv[..., dn:], (dn + dr) ** -0.5)

    def forward_block(self, x, positions):
        """A fresh block from position 0 with no cache: what
        :meth:`forward_paged` gives for it, nothing written."""
        B, S = x.shape[:2]
        o = self._expanded(*self._project(x, positions))
        return jnp.dot(o.reshape(B, S, -1), self.o_proj._value)

    def forward_paged(self, x, positions, cache, block_tables, write_mask):
        from ...ops.pallas import registry as _kreg
        c = self.config
        nh, kvr = c.num_attention_heads, c.kv_lora_rank
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        B, S = x.shape[:2]
        pool = cache["latent"]
        bs, wp = pool.shape[1], pool.shape[-1]
        q, lat, kpe = self._project(x, positions)
        row = jnp.concatenate(
            [lat, kpe,
             jnp.zeros((B, S, wp - self.latent_width), x.dtype)], -1)
        # one latent row per token into its page; masked writes divert
        # to the trash block (0, 0), as the K/V pools' do
        blk = jnp.take_along_axis(block_tables,
                                  (positions // bs).astype(jnp.int32), 1)
        blk = jnp.where(write_mask, blk, 0).reshape(-1)
        off = jnp.where(write_mask, positions % bs, 0).reshape(-1)
        pool = pool.at[blk, off, 0].set(
            row.reshape(B * S, wp).astype(pool.dtype))
        scale = (dn + dr) ** -0.5
        if S > 1:
            # a fresh block, expanded and causal: it attends over
            # itself only, so it has to start its sequence
            fresh = positions[:, 0] == 0
            if not isinstance(fresh, jax.core.Tracer) \
                    and not bool(fresh.all()):
                raise ValueError(
                    "latent attention over a block of tokens takes the "
                    "block from position 0 (no suffix prefill)")
            o = self._expanded(q, lat, kpe)
            # under a trace nothing can raise: a row that starts
            # mid-sequence reads NaN, not a plausible wrong answer
            o = jnp.where(fresh[:, None, None, None], o, jnp.nan)
        else:
            # one query per row, absorbed: q~_h = [W_uk_h^T q_nope_h |
            # q_pe_h], all heads over the one latent row per token
            # (K = the whole page row, V = its first kvr lanes: one
            # pool, each page read once), o_h = W_uv_h sum p c
            kvb = self._kv_b()
            qa = jnp.einsum("bhd,chd->bhc", q[:, 0, :, :dn],
                            kvb[..., :dn], preferred_element_type=F32)
            qt = jnp.concatenate(
                [qa, q[:, 0, :, dn:].astype(F32),
                 jnp.zeros((B, nh, wp - self.latent_width), F32)], -1)
            ol = _kreg.dispatch("paged_latent_attention",
                                qt.astype(pool.dtype)[:, None], pool,
                                block_tables, positions, kvr, scale)
            ol = ol.reshape(B, nh, kvr)
            o = jnp.einsum("bhc,chd->bhd", ol, kvb[..., dn:],
                           preferred_element_type=F32
                           ).astype(x.dtype)[:, None]
        return (jnp.dot(o.reshape(B, S, nh * dv), self.o_proj._value),
                {"latent": pool})


class KimiMLP(_Params):
    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        self._std = c.initializer_range
        h, f = c.hidden_size, c.intermediate_size
        self.gate_proj, self.up_proj, self.down_proj = (
            self._mk(h, f), self._mk(h, f), self._mk(f, h))

    def apply_values(self, x):
        y = _swiglu(x.reshape(-1, x.shape[-1]), self.gate_proj._value,
                    self.up_proj._value, self.down_proj._value)
        return y.astype(x.dtype).reshape(x.shape)


class KimiDecoderLayer(_Params):
    def __init__(self, c: KimiLinearConfig, l: int):
        super().__init__()
        self.config = c
        self.input_layernorm = self._mk(c.hidden_size, one=True)
        self.post_attention_layernorm = self._mk(c.hidden_size, one=True)
        self.is_mla, self.is_moe = c.is_mla(l), c.is_moe(l)
        self.self_attn = (LatentAttention(c, None, None) if self.is_mla
                          else KimiDeltaAttention(c))
        if self.is_moe:
            self.mlp = DroplessMoELayer(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                top_k=c.num_experts_per_token,
                held_experts=c.held_experts,
                shared_hidden=(c.moe_intermediate_size
                               * c.num_shared_experts),
                routed_scaling_factor=c.routed_scaling_factor,
                initializer_range=c.initializer_range)
        else:
            self.mlp = KimiMLP(c)

    def forward_paged(self, h, positions, cache, block_tables, write_mask,
                      slots):
        eps = self.config.rms_norm_eps
        with jax.named_scope(ATTN if self.is_mla else MIXER):
            x = _rms(h, self.input_layernorm._value, eps).astype(h.dtype)
            if self.is_mla:
                a, cache = self.self_attn.forward_paged(
                    x, positions, cache, block_tables, write_mask)
            else:
                a, cache = self.self_attn.forward_paged(
                    x, positions, cache, write_mask, slots)
            h = h + a.astype(h.dtype)
        counts = None
        with jax.named_scope(MOE if self.is_moe else FFN):
            x = _rms(h, self.post_attention_layernorm._value,
                     eps).astype(h.dtype)
            if self.is_moe:
                y, n_here, load = self.mlp.apply_values(x)
            else:
                y = self.mlp.apply_values(x)
            h = h + y
        if self.is_moe:
            counts = jnp.stack([n_here, load]).astype(jnp.int32)
        return h, cache, counts


class KimiLinearModel(_Params):
    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        from ...nn.layer.container import LayerList
        self.config = c
        self._std = c.initializer_range
        self.embed_tokens = self._mk(c.vocab_size, c.hidden_size)
        self.layers = LayerList([KimiDecoderLayer(c, l)
                                 for l in range(c.num_hidden_layers)])
        self.norm = self._mk(c.hidden_size, one=True)


class KimiLinearForCausalLM(_Params):
    """Causal LM over :class:`KimiLinearModel`, served through the
    block-paged cache API (module doc)."""

    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.config = config
        self._std = config.initializer_range
        self.model = KimiLinearModel(config)
        self.lm_head = self._mk(config.hidden_size, config.vocab_size)

    def supports_kv_cache(self) -> bool:
        return True

    def has_recurrent_state(self) -> bool:
        """Part of the cache is per-slot state that no block table
        addresses: the server allocates it with the slots and refuses
        what only knows K/V blocks (prefix sharing, speculation,
        migration)."""
        return not all(self.config.is_mla(l)
                       for l in range(self.config.num_hidden_layers))

    def prefill_starts_sequences_only(self) -> bool:
        """The latent layers' multi-token step attends over its own
        block only, so it has to start its sequence: for a model of
        latent layers alone (no per-slot state, nothing of the above
        refused on that ground) the server refuses prefix sharing and
        speculation on this one."""
        return any(self.config.is_mla(l)
                   for l in range(self.config.num_hidden_layers))

    def step_counters(self) -> Tuple[str, ...]:
        """What ``forward_paged``'s third value counts, summed over the
        expert layers: the picks that landed on the held experts, and
        each layer's largest held expert's load.  ``GenerationServer``
        fetches them behind a decode step's tokens and adds them up in
        ``stats()`` under these names."""
        return ("moe_picks_here", "moe_max_expert_load")

    def loops_on_device(self, n_tokens: int) -> bool:
        """Whether ``forward_paged`` over ``n_tokens`` tokens lowers a
        device loop whose steps branch: the expert layers' grouped
        dispatch does.  ``GenerationServer`` puts no ``conditional`` of
        its own behind such a program (a v5e stopped on one)."""
        return any(lyr.is_moe and lyr.mlp.loops_on_device(n_tokens)
                   for lyr in self.model.layers)

    def prefill_attn_pairs(self, batch: int, bucket: int):
        """(multiplied, whole square): the (query, key) pairs that a
        prefill call of ``batch`` rows x ``bucket`` tokens takes in its
        latent layers; ``GenerationServer`` adds them up in ``stats()``
        (a share of 1.0: no key is skipped)."""
        n = sum(lyr.is_mla for lyr in self.model.layers)
        done, square = attend_plan(
            batch, bucket, self.config.num_attention_heads)[2:]
        return n * done, n * square

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         num_slots: Optional[int] = None):
        """Per layer ``{"latent": [num_blocks, block, 1, 640]}`` (MLA;
        physical block 0 is the trash block) or ``{"state": [num_slots,
        H, dk, dv] float32, "conv": [num_slots, 3, 3, H*dk]}`` (KDA)."""
        if num_slots is None and self.has_recurrent_state():
            raise ValueError("a model with recurrent state needs "
                             "num_slots for its per-slot state")
        dt = jnp.dtype(self.config.compute_dtype)
        return [lyr.self_attn.init_cache(int(num_blocks), int(block_size),
                                         dt) if lyr.is_mla
                else lyr.self_attn.init_cache(int(num_slots), dt)
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
                "the recurrent state to roll back to")
        c = self.config
        raw = lambda t: t._value if isinstance(t, Tensor) else t
        ids, pos, wm = raw(input_ids), raw(positions), raw(write_mask)
        tbl = raw(block_tables)
        if slots is not None:
            slots = raw(slots).astype(jnp.int32)
        pos = pos.astype(jnp.int32)
        with jax.named_scope(EMBED):
            h = self.model.embed_tokens._value[ids].astype(
                jnp.dtype(c.compute_dtype))
        new_pools, counts = [], jnp.zeros((2,), jnp.int32)
        for lyr, cache in zip(self.model.layers, pools):
            cache = {k: raw(v) for k, v in cache.items()}
            h, cache, n = lyr.forward_paged(h, pos, cache, tbl, wm, slots)
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
