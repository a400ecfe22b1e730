"""Mixture-of-Experts layer with expert parallelism over the 'ep' mesh axis.

The reference (PaddlePaddle ~v2.0) has NO MoE/expert parallelism — SURVEY
§2.6 marks it absent; later Paddle ships paddle.incubate MoE. Built here
greenfield as a first-class TPU capability (SURVEY §5.7 directive), GShard
style (Lepikhin et al. 2020), the canonical TPU formulation:

- dense, statically-shaped dispatch: tokens route to experts through
  one-hot dispatch/combine einsums (no gather/scatter with dynamic
  shapes — everything tiles onto the MXU);
- per-expert capacity C = ceil(tokens/E * capacity_factor); overflow
  tokens are dropped from the expert path (their combine weight is 0, the
  residual connection outside the layer carries them);
- stacked expert FFN weights [E, d, h] annotated with
  ``dist_spec = P('ep', None, None)``: under a mesh with an 'ep' axis the
  dispatch einsum becomes XLA's all-to-all over ICI, exactly the GShard
  lowering — no hand-written collectives;
- load-balancing auxiliary loss (switch/GShard aux) exposed as
  ``layer.l_aux`` and differentiable.

What :class:`MoELayer` is still for: TRAINING with a capacity (top-1 /
top-2, dropped overflow, an auxiliary loss, experts sharded over 'ep'
by XLA).  Models that route as today's sparse decoders do -- top-k of
many experts by a sigmoid score, gated experts, a shared expert, no
token dropped, and a chip that holds only a share of the experts --
take :class:`DroplessMoELayer` below (serving; ``KimiLinearForCausalLM``
is its user).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...framework.core import Tensor, _apply
from ..initializer import Normal, XavierNormal
from .layers import Layer, Parameter

__all__ = ["MoELayer", "DroplessMoELayer", "dropless_moe",
           "masked_pass_pays"]


def _mark_ep(param, spec):
    from ...distributed.meta_parallel import mark_sharding
    return mark_sharding(param, spec)


class MoELayer(Layer):
    """Top-k gated mixture of expert FFNs.

    Args:
        d_model: token embedding dim.
        d_hidden: per-expert FFN hidden dim.
        num_experts: number of experts (shard over 'ep' when the mesh has
            that axis).
        top_k: 1 (Switch) or 2 (GShard).
        capacity_factor: per-expert buffer slack.
        activation: FFN nonlinearity name in nn.functional.
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 top_k: int = 2, capacity_factor: float = 1.25,
                 activation: str = "gelu", gate_noise: float = 0.0,
                 name=None):
        super().__init__()
        if top_k not in (1, 2):
            raise ValueError("top_k must be 1 (Switch) or 2 (GShard)")
        if num_experts < max(top_k, 2):
            raise ValueError(
                f"num_experts ({num_experts}) must be >= max(top_k, 2)")
        acts = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
                "silu": jax.nn.silu, "swish": jax.nn.silu,
                "tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid}
        if activation not in acts:
            raise ValueError(f"activation must be one of {sorted(acts)}")
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.gate_noise = gate_noise
        self._act = acts[activation]  # raw jax fn: runs INSIDE the op
        init = XavierNormal()
        g_init = Normal(0.0, 0.02)
        self.gate_weight = Parameter(g_init((d_model, num_experts)))
        self.w1 = _mark_ep(Parameter(init((num_experts, d_model, d_hidden))),
                           P("ep", None, None))
        self.b1 = _mark_ep(Parameter(jnp.zeros((num_experts, d_hidden),
                                               jnp.float32)), P("ep", None))
        self.w2 = _mark_ep(Parameter(init((num_experts, d_hidden, d_model))),
                           P("ep", None, None))
        self.b2 = _mark_ep(Parameter(jnp.zeros((num_experts, d_model),
                                               jnp.float32)), P("ep", None))
        self.l_aux: Optional[Tensor] = None

    def _capacity(self, n_tokens: int) -> int:
        c = int(math.ceil(n_tokens / self.num_experts
                          * self.capacity_factor * self.top_k))
        return max(c, 2)

    def forward(self, x):
        E, K = self.num_experts, self.top_k
        B, S, D = x.shape
        N = B * S
        C = self._capacity(N)
        act_fn = self._act
        noise = self.gate_noise if self.training else 0.0
        nkey = None
        if noise > 0.0:
            from ...framework.random import split_key
            nkey = split_key(1)

        def fn(xv, wg, w1, b1, w2, b2):
            tok = xv.reshape(N, D)
            logits = (tok @ wg).astype(jnp.float32)   # routing in f32
            if nkey is not None:
                logits = logits + noise * jax.random.normal(
                    nkey, logits.shape, jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)    # [N, E]

            def one_route(p, prior_mask):
                masked = jnp.where(prior_mask, -jnp.inf, jnp.log(p + 1e-20))
                idx = jnp.argmax(masked, axis=-1)             # [N]
                mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)
                return idx, mask

            idx1, mask1 = one_route(probs, jnp.zeros((N, E), bool))
            routes = [(idx1, mask1)]
            if K == 2:
                idx2, mask2 = one_route(probs, mask1.astype(bool))
                routes.append((idx2, mask2))

            # capacity assignment: running position of each token within
            # its chosen expert (GShard cumsum trick); later routes queue
            # behind earlier ones
            occupancy = jnp.zeros((E,), jnp.float32)
            dispatch = jnp.zeros((N, E, C), jnp.float32)
            combine = jnp.zeros((N, E, C), jnp.float32)
            gates_sum = jnp.zeros((N,), jnp.float32)
            for (idx, mask) in routes:
                pos = jnp.cumsum(mask, axis=0) - mask + occupancy[None, :]
                pos_tok = (pos * mask).sum(-1)                 # [N]
                keep = (pos_tok < C) & (mask.sum(-1) > 0)
                gate_raw = (probs * mask).sum(-1)              # [N]
                gate_val = gate_raw * keep
                pos_oh = jax.nn.one_hot(pos_tok.astype(jnp.int32), C,
                                        dtype=jnp.float32)
                d = mask[:, :, None] * pos_oh[:, None, :] \
                    * keep[:, None, None]
                dispatch = dispatch + d
                combine = combine + d * gate_val[:, None, None]
                occupancy = occupancy + (mask * keep[:, None]).sum(0)
                # denominator uses the PRE-drop gates: a token whose
                # second route overflows keeps weight g1/(g1+g2), not 1.0
                # — the GShard normalisation is capacity-independent
                gates_sum = gates_sum + gate_raw
            if K == 2:
                # GShard: the two gates renormalise by their sum;
                # Switch (K=1) keeps the raw router prob as the scale
                combine = combine / jnp.maximum(gates_sum,
                                                1e-9)[:, None, None]

            # load-balancing aux loss (GShard eq.4 / Switch): E * <f, m>
            me = probs.mean(axis=0)                        # mean router prob
            ce = mask1.mean(axis=0)                        # top-1 fraction
            l_aux = (me * ce).sum() * E

            # expert compute: [E, C, D] batched FFN — the E dim rides the
            # 'ep' mesh axis (XLA all-to-all in, all-to-all out)
            expert_in = jnp.einsum("nec,nd->ecd", dispatch,
                                   tok.astype(jnp.float32)).astype(xv.dtype)
            h = jnp.einsum("ecd,edh->ech", expert_in, w1) + b1[:, None, :]
            h = act_fn(h)
            out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
            y = jnp.einsum("nec,ecd->nd", combine.astype(xv.dtype), out)
            return y.reshape(B, S, D), l_aux

        out, l_aux = _apply(fn, x, self.gate_weight, self.w1, self.b1,
                            self.w2, self.b2, op_name="moe")
        self.l_aux = l_aux
        return out

    def extra_repr(self):
        return (f"d_model={self.d_model}, d_hidden={self.d_hidden}, "
                f"num_experts={self.num_experts}, top_k={self.top_k}")


# ---------------------------------------------------------------------
# dropless experts, sorted/grouped dispatch (serving)
# ---------------------------------------------------------------------

# rows of the expert-sorted picks that ONE step multiplies by ONE
# expert's weights: each expert's group is padded to whole blocks, so a
# block never straddles two experts and a step is three plain matmuls
_GROUP_BLOCK = 256
# FLOPs a byte of weights at which the MXU and the HBM of the chip this
# layer is served on take equal time (TPU v5e: 197 TFLOP/s over
# 819 GB/s).  A bf16 expert multiplies one row with one FLOP a byte it
# streams, so T rows take T / _RIDGE of the time its weights take.
_RIDGE = 197e12 / 819e9


def masked_pass_pays(n_tokens: int, top_k: int, num_experts: int) -> bool:
    """Whether ``n_tokens`` tokens take the masked dense pass: every
    held expert multiplies every token, and ``1 - top_k / num_experts``
    of those row-products are in vain.  While the MXU time of the rows
    multiplied in vain hides under the time the expert's weights take
    to stream, the pass costs what ANY form costs (the weights read
    once, the chosen rows multiplied) and no dropless form can win;
    past that the sorted/grouped dispatch multiplies fewer rows.  The
    line follows the router's shape and the chip, not a model: 128 rows
    of 8-of-256 experts take the pass and 256 rows do not (124 and 248
    rows in vain against a ridge of 240.5; PERF.md PR 27 measured 1.96
    ms against the grouped product's 3.91 at 128), 256 rows of 4-of-32
    do (224 in vain; PR 33)."""
    return n_tokens * (1.0 - top_k / num_experts) <= _RIDGE


# the gated expert's activation, on the float32 gate and up products:
# ``swiglu`` is silu(gate) * up; ``swigluoai`` (MiniMax-M3, gpt-oss) is
# the clamped form (up + 1) * gate * sigmoid(1.702 gate), the gate
# clipped above at 7 and the up product to [-7, 7]
SWIGLU_OAI_ALPHA = 1.702
SWIGLU_OAI_LIMIT = 7.0
GLU_ACTS = ("swiglu", "swigluoai")


def _glu(g, u, act="swiglu"):
    if act == "swiglu":
        return jax.nn.silu(g) * u
    if act == "swigluoai":
        # the clamps as selects: XLA's CPU backend cannot run the bf16
        # product behind a min/clip fusion ("Unsupported element type
        # for DotThunk"); the values are the same
        lim = SWIGLU_OAI_LIMIT
        g = jnp.where(g > lim, lim, g)
        u = jnp.where(u > lim, lim, jnp.where(u < -lim, -lim, u))
        return (u + 1.0) * g * jax.nn.sigmoid(SWIGLU_OAI_ALPHA * g)
    raise ValueError(f"unknown expert activation {act!r}")


def _swiglu(x, wg, wu, wd, act="swiglu"):
    h = _glu(jnp.dot(x, wg, preferred_element_type=jnp.float32),
             jnp.dot(x, wu, preferred_element_type=jnp.float32), act)
    return jnp.dot(h.astype(x.dtype), wd,
                   preferred_element_type=jnp.float32)


def _route(x, router_w, router_b, top_k, scale, held, norm_eps=1e-20):
    """Sigmoid router in float32 over ALL experts: (local [T, k] the
    picks' index among the held experts, w [T, k] their combine
    weights, here [T, k] whether the pick is held here).  ``norm_eps``
    is the constant the model adds to the chosen scores' sum."""
    first, count = held
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               router_w.astype(jnp.float32)))
    _, idx = jax.lax.top_k(s + router_b.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + norm_eps) * scale
    local = (idx - first).astype(jnp.int32)
    return local, w, (local >= 0) & (local < count)


def _experts_masked(x, local, w, wg, wu, wd, act="swiglu"):
    """Every held expert over every token, the result weighed by the
    combine weights (nought where the token did not choose the
    expert): for few tokens.  Returns ``(y, picks here, the largest
    load, row-products done)``."""
    count = wg.shape[0]
    onehot = jax.nn.one_hot(local, count, dtype=jnp.float32)   # [T,k,E]
    wt = (onehot * w[..., None]).sum(1)                        # [T, E]
    h = _glu(jnp.einsum("td,edf->etf", x, wg,
                        preferred_element_type=jnp.float32),
             jnp.einsum("td,edf->etf", x, wu,
                        preferred_element_type=jnp.float32), act)
    out = jnp.einsum("etf,efd->etd", h.astype(x.dtype), wd,
                     preferred_element_type=jnp.float32)
    load = onehot.sum((0, 1)).astype(jnp.int32)
    return (jnp.einsum("etd,te->td", out, wt), load.sum(), load.max(),
            x.shape[0] * count)


def _experts_grouped(x, local, w, here, wg, wu, wd, act="swiglu"):
    """Sorted/grouped dispatch in plain XLA: the picks sorted by expert
    (picks on experts that live elsewhere sort last and are never
    touched), each held expert's group laid out in whole blocks of
    ``_GROUP_BLOCK`` rows, and one step per block that multiplies the
    block by its expert's three matrices (a dynamic slice of the
    stacked weights, never a gather per pick).  The number of steps is
    static, the worst case ``P / block + count``; a step past the
    blocks in use is skipped by a ``cond``, so the work follows the
    picks that landed here.  No Mosaic kernel and no loop whose trip
    count is computed on the device: with ``lax.ragged_dot`` (on TPU a
    megablox-style kernel of XLA's own) or the megablox kernel here,
    prefill traffic stopped a v5e; with this form the same traffic ran
    clean (PERF.md, PR 27).  Returns ``(y, picks here, the largest
    load, (blocks in use, rows a block))``.
    """
    (T, d), top_k, count = x.shape, local.shape[1], wg.shape[0]
    P_ = T * top_k
    blk = min(_GROUP_BLOCK, P_)
    i32 = jnp.int32
    eid = jnp.where(here, local, count).reshape(P_)
    order = jnp.argsort(eid, stable=True).astype(i32)
    bounds = jnp.searchsorted(eid[order], jnp.arange(count + 1, dtype=i32),
                              side="left").astype(i32)
    sizes = bounds[1:] - bounds[:-1]                     # [count]
    n_here = bounds[count]
    # expert e owns blocks [b_end[e] - n_blk[e], b_end[e])
    n_blk = (sizes + blk - 1) // blk
    b_end = jnp.cumsum(n_blk).astype(i32)
    b_start = b_end - n_blk
    nb = P_ // blk + count                               # static bound
    # each block's expert, each padded row's pick (clamped: a row past
    # its group's end multiplies some real token and is never read)
    b_exp = jnp.minimum(jnp.searchsorted(
        b_end, jnp.arange(nb, dtype=i32), side="right"), count - 1
        ).astype(i32)
    row = jnp.arange(blk, dtype=i32)[None, :] \
        + ((jnp.arange(nb, dtype=i32) - b_start[b_exp]) * blk)[:, None]
    src = jnp.clip(bounds[b_exp][:, None] + row, 0, P_ - 1)
    tok = order[src] // top_k                            # [nb, blk]

    def one_block(t):
        tb, e, used = t

        def run():
            ds = lambda m: jax.lax.dynamic_index_in_dim(m, e, 0, False)
            return _swiglu(x[tb], ds(wg), ds(wu), ds(wd),
                           act).astype(x.dtype)
        return jax.lax.cond(used, run,
                            lambda: jnp.zeros((blk, d), x.dtype))
    steps = jnp.arange(nb, dtype=i32)
    n_used = b_end[-1]                                   # blocks in use
    out = jax.lax.map(one_block, (tok, b_exp, steps < n_used))
    # where each pick sits in the sorted order, then in the blocks
    inv = jnp.zeros((P_,), i32).at[order].set(jnp.arange(P_, dtype=i32))
    e_of = jnp.minimum(eid, count - 1)
    at = b_start[e_of] * blk + inv - bounds[e_of]
    # a pick that is not here selects 0, not 0 * row
    picked = out.reshape(nb * blk, d)[jnp.clip(at, 0, nb * blk - 1)]
    picked = picked.reshape(T, top_k, d).astype(jnp.float32)
    y = jnp.where(here[..., None], picked * w[..., None], 0.0).sum(1)
    return y, n_here, sizes.max(), (n_used, blk)


def dropless_moe(x, router_w, router_b, wg, wu, wd, *, top_k: int,
                 scale: float, held, norm_eps: float = 1e-20,
                 count_rows: bool = False, act: str = "swiglu"):
    """The held experts' part of a sigmoid-routed expert layer.

    ``x`` [T, d]; ``router_w`` [d, E] and ``router_b`` [E] over ALL E
    experts of the layer; ``wg``/``wu`` [count, d, f] and ``wd``
    [count, f, d] of the experts ``held = (first, count)``.  Router in
    float32: scores ``sigmoid(x W_r)``, the ``top_k`` chosen by score +
    bias, weights the chosen scores over their sum + ``norm_eps``,
    times ``scale``.

    ``act`` is the experts' activation (:func:`_glu`: ``swiglu`` or
    the clamped ``swigluoai``).

    No token is dropped, no weight is gathered per pick, and what the
    absent experts would have added is left out.  Few tokens (a decode
    step; :func:`masked_pass_pays` draws the line) take the masked
    dense pass, which costs what reading the weights costs; more take
    the sorted/grouped dispatch, the same sum, in which no expert
    multiplies a token that did not choose it (but for the padding of
    each group to whole blocks).

    Returns ``(y [T, d] float32, picks_here, max_expert_load)`` and,
    with ``count_rows``, a fourth value: the row-products done (rows x
    the experts each was multiplied by).
    """
    # (six positional arguments: perfbench/tools/kimi_gap_study.py wraps
    # ``_route`` by them to record a model's picks)
    other_eps = {} if norm_eps == 1e-20 else {"norm_eps": norm_eps}
    local, w, here = _route(x, router_w, router_b, top_k, scale, held,
                            **other_eps)
    if masked_pass_pays(x.shape[0], top_k, router_w.shape[1]):
        *out, rows = _experts_masked(x, local, w, wg, wu, wd, act)
    else:
        *out, (n_used, blk) = _experts_grouped(x, local, w, here, wg, wu,
                                               wd, act)
        rows = n_used * blk if count_rows else None
    return (*out, rows) if count_rows else tuple(out)


class DroplessMoELayer(Layer):
    """Sparse experts as today's decoders route them (DeepSeek-V3
    style): ``top_k`` of ``num_experts`` gated (SwiGLU) experts by a
    sigmoid router with a selection bias, optionally one shared expert
    every token takes, no capacity and no dropped token.

    ``held_experts=(first, count)`` makes this chip's share of an
    expert-parallel layer: the router keeps its full width, only the
    ``count`` experts from ``first`` have weights here, and the result
    is THEIR part of the layer's output plus the shared expert's (which
    every chip computes alike).  ``act`` is every expert's activation,
    the shared one's too (:func:`_glu`).  The exchange between chips is
    not this layer's; on one chip it runs without it.

    Serving only: written for inference (``lax.cond`` per block of the
    sorted picks; no auxiliary loss, no gradient tests).
    :class:`MoELayer` is the trainable layer.  After a call
    ``last_counts`` holds (picks that landed here, the largest held
    expert's load).
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 top_k: int = 8, held_experts=None,
                 shared_hidden: Optional[int] = None,
                 routed_scaling_factor: float = 1.0,
                 initializer_range: float = 0.02,
                 norm_eps: float = 1e-20, act: str = "swiglu"):
        super().__init__()
        if act not in GLU_ACTS:
            raise ValueError(f"unknown expert activation {act!r}")
        first, count = held_experts or (0, num_experts)
        if not (0 <= first and count >= 1
                and first + count <= num_experts):
            raise ValueError(
                f"held_experts={held_experts} outside 0..{num_experts}")
        if not 1 <= top_k <= num_experts:
            raise ValueError("top_k must lie in 1..num_experts")
        self.d_model, self.d_hidden = d_model, d_hidden
        self.num_experts, self.top_k = num_experts, top_k
        self.held_experts = (int(first), int(count))
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_eps = float(norm_eps)
        self.act = act
        init = Normal(0.0, initializer_range)

        def mk(*shape):
            return self.create_parameter(shape, default_initializer=init)
        self.router = mk(d_model, num_experts)
        self.router_bias = mk(num_experts)
        self.gate_w = mk(count, d_model, d_hidden)
        self.up_w = mk(count, d_model, d_hidden)
        self.down_w = mk(count, d_hidden, d_model)
        self.shared = bool(shared_hidden)
        if self.shared:
            self.shared_gate = mk(d_model, shared_hidden)
            self.shared_up = mk(d_model, shared_hidden)
            self.shared_down = mk(shared_hidden, d_model)
        self.last_counts = None

    def apply_values(self, xv, count_rows: bool = False):
        """``xv`` [..., d] (a raw array) -> (y like xv, picks_here,
        max_expert_load[, row-products]): what :meth:`forward`
        computes, for callers that are themselves inside a traced
        program."""
        tok = xv.reshape(-1, self.d_model)
        y, *counts = dropless_moe(
            tok, self.router._value, self.router_bias._value,
            self.gate_w._value, self.up_w._value, self.down_w._value,
            top_k=self.top_k, scale=self.routed_scaling_factor,
            held=self.held_experts, norm_eps=self.norm_eps,
            count_rows=count_rows, act=self.act)
        if self.shared:
            y = y + _swiglu(tok, self.shared_gate._value,
                            self.shared_up._value,
                            self.shared_down._value, self.act)
        return (y.astype(xv.dtype).reshape(xv.shape), *counts)

    def loops_on_device(self, n_tokens: int) -> bool:
        """Whether ``n_tokens`` tokens take the grouped dispatch, which
        lowers to a device loop whose steps branch (a ``while`` of
        ``conditional``s)."""
        return not masked_pass_pays(n_tokens, self.top_k, self.num_experts)

    def forward(self, x):
        y, n_here, load = self.apply_values(x._value)
        self.last_counts = (n_here, load)
        return Tensor(y, stop_gradient=True)

    def extra_repr(self):
        return (f"d_model={self.d_model}, d_hidden={self.d_hidden}, "
                f"num_experts={self.num_experts}, top_k={self.top_k}, "
                f"held_experts={self.held_experts}")
