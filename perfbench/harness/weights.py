"""Weights from the seed, on the device, in one jitted call.

A configuration's reference states its leaves (``param_specs``: name ->
(shape, init)); a leaf's values depend only on (seed, its group, its
place in the group), so the
reference can make one layer again, alone, after the program's copy is
freed -- it takes nothing that the program has made.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def group_of(name: str) -> str:
    """Leaves are made a group at a time (one random draw per group,
    cut into the leaves, so that the program that makes them stays
    small): ``layers.<l>.*`` is group ``layers.<l>``, the rest is
    ``globals``."""
    parts = name.split(".")
    return ".".join(parts[:2]) if parts[0] == "layers" else "globals"


def _group_leaves(specs: dict, group: str):
    return [n for n in specs if group_of(n) == group]


def make_tree(specs: dict, key, dtype, names=None):
    """name -> array for ``names`` (default: all of ``specs``).

    ``specs``: name -> (shape, (kind, scale)) with kind "normal"
    (scale*N(0,1)), "ones" (1 + scale*N(0,1): norm scales) or "zeros"
    (scale*N(0,1): biases) -- no leaf is a constant the arithmetic
    could skip.  A leaf's values depend only on (seed, its group, its
    place in the group), never on which other leaves are asked for."""
    import math
    names = list(names if names is not None else specs)
    out = {}
    for group in dict.fromkeys(group_of(n) for n in names):
        leaves = _group_leaves(specs, group)
        sizes = [math.prod(specs[n][0]) for n in leaves]
        k = jax.random.fold_in(key, zlib.crc32(group.encode()) & 0x7FFFFFFF)
        flat = jax.random.normal(k, (sum(sizes),), jnp.float32)
        off = 0
        for n, size in zip(leaves, sizes):
            if n in names:
                shape, (kind, scale) = specs[n]
                x = flat[off:off + size].reshape(tuple(shape)) * scale
                if kind == "ones":
                    x = x + 1.0
                elif kind not in ("normal", "zeros"):
                    raise ValueError(f"unknown init {kind!r}")
                out[n] = x.astype(dtype)
            off += size
    return out
