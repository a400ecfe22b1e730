"""Fused optimizer-apply over a ZeRO shard (ISSUE 13 kernel 1).

The elastic data plane's flat optimizers (``fleet/elastic.py``
``_FlatSGD/_FlatMomentum/_FlatAdam``) update a contiguous f32 shard of
the global parameter vector.  PERF.md round 9 measured this pass as
bandwidth-dominated: one step reads grad+param+moments and writes
param+moments, and XLA materializes every intermediate between the
reads and the writes.  This kernel does the whole update in ONE pass
over VMEM-resident tiles: each (rows, 128) tile of param/grad/moments
streams HBM->VMEM once, the update runs on the VPU, and the results
stream back — the minimum possible byte traffic
(``(2 + 2*slots) * 4 * N`` bytes for ``slots`` moment vectors).

World invariance (the PR 9 elastic contract): the update is strictly
ELEMENTWISE with every constant pinned to f32, so a shard's update
equals the same slice of the full-vector update bit-for-bit — padding
rides in zero-filled tail lanes that are sliced off before return and
can never perturb real elements.  The parity test pins sgd/momentum
BIT-EXACT against :func:`opt_apply_ref` (the jnp reference, also the
fallback path) and adam to one rounding of its sums' operands (below),
and pins shard-slicing invariance bit-exactly at (offset, length) pairs.

Host-engine note (honest): the elastic trainer's numpy engine computes
the same expressions, but XLA CPU contracts mul+add chains into FMA
(single rounding) where numpy rounds twice — measured ~1% of elements
differ by ~1 ulp (amplified through Adam's rsqrt to ~5e-5 relative
worst-case).  Within EITHER engine every bit-contract (N->M->N
reshard, slot-ordered reduction) holds exactly; mixing engines
mid-run is refused by the elastic trainer for exactly this reason.

Hyper-parameter layout (``hyper`` f32 ``[1, 8]``, SMEM in the kernel):
``[lr, b1, b2, eps, c1, c2, mu, one_m_b1_or_b2...]`` — see ``HYPER``.
``c1/c2`` (Adam bias corrections) are pure functions of the global
step computed on HOST in float64 exactly as the numpy engine does, so
``t`` never enters the device program and no retrace happens per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import registry

__all__ = ["KINDS", "SLOTS", "pack_hyper", "opt_apply_ref",
           "opt_apply_pallas"]

KINDS = ("sgd", "momentum", "adam")
# moment-vector names per optimizer kind, in argument order
SLOTS = {"sgd": (), "momentum": ("u",), "adam": ("m", "v")}

# hyper vector layout: index -> meaning
_H_LR, _H_B1, _H_B2, _H_EPS, _H_C1, _H_C2, _H_MU = range(7)
_H_OMB1, _H_OMB2 = 7, 8
HYPER_LEN = 9

_LANES = 128
_TILE_ROWS = 256          # 256x128 f32 tiles: 128 KiB per operand


def pack_hyper(kind: str, *, lr, betas=(0.9, 0.999), eps=1e-8,
               momentum=0.9, t: int = 1) -> np.ndarray:
    """Build the f32 hyper vector.  ``c1/c2`` are computed exactly as
    the numpy engine does (python-float pow, one f32 rounding)."""
    h = np.zeros((1, HYPER_LEN), np.float32)
    h[0, _H_LR] = np.float32(lr)
    h[0, _H_B1] = np.float32(betas[0])
    h[0, _H_B2] = np.float32(betas[1])
    h[0, _H_EPS] = np.float32(eps)
    h[0, _H_C1] = np.float32(1.0 - float(betas[0]) ** int(t))
    h[0, _H_C2] = np.float32(1.0 - float(betas[1]) ** int(t))
    h[0, _H_MU] = np.float32(momentum)
    h[0, _H_OMB1] = np.float32(1) - np.float32(betas[0])
    h[0, _H_OMB2] = np.float32(1) - np.float32(betas[1])
    return h


def _update_math(kind, p, g, slots, hy):
    """ONE definition of the update expressions, shared by the XLA
    reference and the kernel body so both compile the same op chain
    (which is what makes the parity test bit-exact).  ``hy(i)``
    returns the i-th hyper scalar."""
    lr = hy(_H_LR)
    if kind == "sgd":
        return p - lr * g, ()
    if kind == "momentum":
        (u,) = slots
        u_n = hy(_H_MU) * u + g
        return p - lr * u_n, (u_n,)
    if kind == "adam":
        m, v = slots
        m_n = hy(_H_B1) * m + hy(_H_OMB1) * g
        v_n = hy(_H_B2) * v + hy(_H_OMB2) * g * g
        mhat = m_n / hy(_H_C1)
        vhat = v_n / hy(_H_C2)
        return p - lr * mhat / (jnp.sqrt(vhat) + hy(_H_EPS)), (m_n, v_n)
    raise ValueError(f"unknown optimizer kind {kind!r} "
                     f"(expected one of {KINDS})")


def opt_apply_ref(kind, p, g, slots, hyper):
    """XLA reference: the fallback path and the parity oracle."""
    hyper = jnp.asarray(hyper, jnp.float32)
    p_n, s_n = _update_math(kind, p, g, tuple(slots),
                            lambda i: hyper[0, i])
    return (p_n,) + tuple(s_n)


def _opt_apply_kernel(kind, nslots, hyper_ref, p_ref, g_ref, *refs):
    slot_refs = refs[:nslots]
    out_refs = refs[nslots:]
    p_n, s_n = _update_math(kind, p_ref[...], g_ref[...],
                            tuple(r[...] for r in slot_refs),
                            lambda i: hyper_ref[0, i])
    out_refs[0][...] = p_n
    for r, s in zip(out_refs[1:], s_n):
        r[...] = s


def opt_apply_pallas(kind, p, g, slots, hyper, *, interpret=False):
    """One-pass fused update over flat f32 vectors.

    The flat shard is zero-padded up to a whole number of
    ``(_TILE_ROWS, 128)`` f32 tiles; pad elements update to finite
    garbage in the padded buffer and are sliced off before return
    (elementwise => they cannot affect real elements)."""
    n = p.shape[0]
    rows = -(-n // _LANES)
    gsz = max(1, -(-rows // _TILE_ROWS))
    pad = gsz * _TILE_ROWS * _LANES - n

    def tile(x):
        return jnp.pad(jnp.asarray(x, jnp.float32), (0, pad)).reshape(
            gsz * _TILE_ROWS, _LANES)

    nslots = len(slots)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    blk = pl.BlockSpec((_TILE_ROWS, _LANES), lambda i: (i, 0))
    outs = pl.pallas_call(
        functools.partial(_opt_apply_kernel, kind, nslots),
        grid=(gsz,),
        in_specs=[smem] + [blk] * (2 + nslots),
        out_specs=[blk] * (1 + nslots),
        out_shape=[jax.ShapeDtypeStruct(
            (gsz * _TILE_ROWS, _LANES), jnp.float32)] * (1 + nslots),
        interpret=interpret,
    )(jnp.asarray(hyper, jnp.float32), tile(p), tile(g),
      *[tile(s) for s in slots])
    return tuple(o.reshape(-1)[:n] for o in outs)


registry.register(
    "opt_apply", opt_apply_pallas, opt_apply_ref,
    tolerance="sgd, momentum: bit-exact vs xla_ref (np.array_equal); "
              "adam: one rounding of its sums' operands (XLA CPU FMA "
              "contraction picks either product of b1*m + (1-b1)*g)",
    doc="fused sgd/momentum/adam apply over a flat ZeRO shard: one "
        "pass reading grad+param+moments, writing param+moments",
)
