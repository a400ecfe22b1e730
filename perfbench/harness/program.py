"""What the harness does to the program under test, for any
configuration: build it without storage, then give it the benchmark's
weights, made on the device from the seed in one jitted call."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import weights as W


def install_weights(model, name_map: dict, specs: dict, seed: int, dtype,
                    mesh=None):
    """Set every parameter of ``model`` (built under ``abstract_init``)
    from the seed.  ``name_map``: program name -> reference name, or a
    list of reference names that the program keeps stacked on a leading
    axis.  Under a mesh each leaf is made sharded over its first
    dimension that the devices divide (the program re-lays it as its
    own rules say)."""
    params = dict(model.named_parameters())
    missing = set(params) - set(name_map)
    if missing:
        raise KeyError(f"no reference leaf for program parameters "
                       f"{sorted(missing)}")

    def make(key):
        tree = W.make_tree(specs, key, dtype)
        out = {}
        for pname in params:
            ref = name_map[pname]
            if isinstance(ref, (list, tuple)):
                out[pname] = jnp.stack([tree[r] for r in ref])
            else:
                out[pname] = tree[ref]
        return out

    out_sh = None
    if mesh is not None and mesh.devices.size > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        axes = tuple(mesh.axis_names)
        n = mesh.devices.size

        def sh(shape):
            for i, d in enumerate(shape):
                if d % n == 0 and d >= n:
                    return NamedSharding(
                        mesh, P(*([None] * i + [axes])))
            return NamedSharding(mesh, P())
        shapes = jax.eval_shape(make, W.seed_key(0))
        out_sh = {k: sh(v.shape) for k, v in shapes.items()}
    maker = jax.jit(make, out_shardings=out_sh)
    vals = maker(W.seed_key(seed))
    for pname, p in params.items():
        want = tuple(p._value.shape)
        if tuple(vals[pname].shape) != want:
            raise ValueError(f"{pname}: reference shape "
                             f"{vals[pname].shape} != program {want}")
        p._value = vals[pname]
    # the same values again, for the change of the parameters
    return lambda: maker(W.seed_key(seed))


def split_by_reference(name_map: dict, tree: dict) -> dict:
    """program-name -> array tree to reference-name -> array (a stacked
    program leaf gives one entry per layer)."""
    out = {}
    for pname, v in tree.items():
        ref = name_map[pname]
        if isinstance(ref, (list, tuple)):
            for i, r in enumerate(ref):
                out[r] = v[i]
        else:
            out[ref] = v
    return out
