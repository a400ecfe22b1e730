"""Device-mesh topology — the TPU-native replacement for NCCL rings.

The reference manages communicators as a table of NCCL comms keyed by
``(ring_id, rank)`` (reference: paddle/fluid/platform/collective_helper.h:65),
bootstrapped by TCP-broadcasting a ``ncclUniqueId``
(reference: paddle/fluid/platform/gen_comm_id_helper.cc:284).  On TPU all of
that collapses into a single ``jax.sharding.Mesh`` with *named axes*: XLA
lowers collectives onto ICI links from the axis names alone; there are no
rings, ids, or comm streams to manage.

Axis vocabulary (any subset may be size 1 / absent):

====  =========================================================
dp    pure data parallel (params replicated, grads psummed)
fsdp  sharded data parallel (ZeRO: params/grads/opt-state sharded)
tp    tensor (model) parallel — column/row-parallel matmuls
pp    pipeline parallel — stage axis
sp    sequence/context parallel — ring attention / Ulysses
ep    expert parallel (MoE)
====  =========================================================

``init_mesh`` builds the global mesh once from degrees; everything else
(fleet strategies, parallel layers, collective API) reads it through
``get_mesh()``.

Spec construction lives in ONE place (ISSUE 15): this module mints no
PartitionSpecs of its own — ``batch_spec`` and the per-dim constraint
helpers delegate to :mod:`paddle_tpu.distributed.planner.spec_layout`,
the canonical role registry the auto-sharding planner shares.
"""
from __future__ import annotations

import contextlib
import math
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .planner.spec_layout import AXES, get_layout as _layout

__all__ = [
    "AXES", "init_mesh", "get_mesh", "set_mesh", "mesh_axis_size",
    "data_axes", "batch_spec", "named_sharding", "maybe_constrain",
    "reform_mesh", "on_reform", "target_platform",
]

_global_mesh: Optional[Mesh] = None

# per-mesh recompile hooks (ISSUE 17): owners of compiled programs
# (DistributedTrainStep) register here so an elastic reform_mesh()
# invalidates them in one place instead of every driver knowing every
# owner.  Weak references: a registered step must not be kept alive —
# dead entries are pruned at fire time.
_reform_hooks: list = []


def on_reform(hook) -> None:
    """Register a callable invoked with the NEW mesh after every
    :func:`reform_mesh`.  Bound methods are held weakly (a registered
    owner stays collectable); other callables are held strongly."""
    import weakref
    try:
        ref = weakref.WeakMethod(hook)
    except TypeError:
        ref = (lambda h=hook: h)
    _reform_hooks.append(ref)


def _fire_reform(mesh: Mesh) -> None:
    dead = []
    for ref in list(_reform_hooks):
        hook = ref()
        if hook is None:
            dead.append(ref)
            continue
        hook(mesh)
    for ref in dead:
        try:
            _reform_hooks.remove(ref)
        except ValueError:
            pass


def init_mesh(degrees: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Create and install the global mesh.

    ``degrees`` maps axis name -> size (missing axes get 1; a single ``-1``
    entry absorbs the remaining devices, like a reshape).  The product must
    equal the device count.  Replaces the reference's ``c_comm_init`` /
    ``init_parallel_env`` comm bootstrap (reference:
    paddle/fluid/operators/collective/c_comm_init_op.cc,
    python/paddle/distributed/parallel.py:57).
    """
    global _global_mesh
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    degrees = dict(degrees or {})
    for ax in degrees:
        if ax not in AXES:
            raise ValueError(f"unknown mesh axis {ax!r}; valid: {AXES}")
    sizes = [degrees.get(ax, 1) for ax in AXES]
    if -1 in sizes:
        i = sizes.index(-1)
        rest = math.prod(s for s in sizes if s != -1)
        if n % rest:
            raise ValueError(f"{n} devices not divisible by {rest}")
        sizes[i] = n // rest
    elif math.prod(sizes) != n:
        # default: put all remaining devices on dp
        if n % math.prod(sizes):
            raise ValueError(
                f"mesh degrees {degrees} (= {math.prod(sizes)}) do not "
                f"divide device count {n}")
        sizes[AXES.index("dp")] *= n // math.prod(sizes)
    arr = np.asarray(devices).reshape(sizes)
    _global_mesh = Mesh(arr, AXES)
    return _global_mesh


def reform_mesh(degrees: Optional[Dict[str, int]] = None,
                devices: Optional[Sequence] = None) -> Mesh:
    """Re-form the global mesh after an elastic membership change.

    The elastic controller (fleet/elastic.py) calls this on every
    generation transition: the installed mesh is dropped and rebuilt
    from the CURRENT device set, so anything reading ``get_mesh()``
    afterwards sees the post-transition topology.  On a multi-host TPU
    this is the site where the runtime re-initialises the coordination
    service for the surviving hosts; in single-host worlds it
    re-derives the all-``dp`` mesh.  Compiled programs holding the old
    mesh must be rebuilt by their owners: every hook registered via
    :func:`on_reform` fires with the new mesh (DistributedTrainStep
    registers its ``reform`` method, dropping its compiled program so
    the next call re-lays params and recompiles for the new world)."""
    set_mesh(None)
    mesh = init_mesh(degrees if degrees is not None else {"dp": -1},
                     devices=devices)
    _fire_reform(mesh)
    return mesh


def set_mesh(mesh: Optional[Mesh]):
    global _global_mesh
    _global_mesh = mesh


def get_mesh(create: bool = True) -> Optional[Mesh]:
    """The installed global mesh; lazily builds an all-``dp`` mesh."""
    global _global_mesh
    if _global_mesh is None and create:
        init_mesh({"dp": -1})
    return _global_mesh


def target_platform() -> str:
    """Platform of the devices programs are being compiled FOR: the
    installed mesh's devices when there is one, else the process
    default backend.  Every backend-sniffing dispatch site (kernel
    registry, flash eligibility, RNG impl, pool donation) asks this
    instead of ``jax.default_backend()``, so an AOT compile against a
    TPU topology from a CPU host takes the TPU code paths
    (tests/test_tpu_lowering.py)."""
    mesh = get_mesh(create=False)
    if mesh is not None:
        return mesh.devices.flat[0].platform
    return jax.default_backend()


def mesh_axis_size(axis: str) -> int:
    mesh = get_mesh()
    return mesh.shape.get(axis, 1) if mesh is not None else 1


def data_axes(mesh: Optional[Mesh] = None):
    """The axes a batch dimension is sharded over (dp and fsdp both
    consume batch — ZeRO shards the *data* axis; reference sharding
    optimizer keeps DP semantics: fleet/meta_optimizers/sharding_optimizer.py:33)."""
    mesh = mesh or get_mesh()
    axes = tuple(ax for ax in ("dp", "fsdp")
                 if mesh is not None and mesh.shape.get(ax, 1) > 1)
    return axes or ("dp",)


def batch_spec(ndim: int, mesh: Optional[Mesh] = None) -> PartitionSpec:
    """PartitionSpec sharding dim0 over the data axes (the 'batch'
    activation role of the SpecLayout registry)."""
    return _layout().batch(ndim, data_axes(mesh))


def named_sharding(spec: PartitionSpec,
                   mesh: Optional[Mesh] = None) -> NamedSharding:
    return NamedSharding(mesh or get_mesh(), spec)


def constrain_dim(x, dim: int, axis):
    """Constrain ONE dim of an activation to a mesh axis (or tuple of
    axes, e.g. ``('dp','fsdp')`` for a batch dim), leaving every other
    dim UNCONSTRAINED. A full PartitionSpec with None entries would
    force those dims to replicated — clobbering the batch's dp/fsdp
    sharding and making XLA emit an involuntary full reshard (all-gather
    + re-slice) around the constraint. UNCONSTRAINED lets the partitioner
    keep whatever layout is already flowing."""
    mesh = get_mesh(create=False)
    if isinstance(axis, (tuple, list)):
        axis = tuple(a for a in axis
                     if mesh is not None and mesh.shape.get(a, 1) > 1)
        if not axis:
            return x
        if len(axis) == 1:
            axis = axis[0]
    elif mesh is None or mesh.shape.get(axis, 1) <= 1:
        return x
    if mesh is None:
        return x
    try:
        if isinstance(x, jax.core.Tracer):
            spec = _layout().dim_spec(x.ndim, dim, axis,
                                      unconstrained_rest=True)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
        # concrete array: actually lay it out (UNCONSTRAINED is only
        # meaningful under jit; eager device_put needs explicit Nones)
        spec = _layout().dim_spec(x.ndim, dim, axis)
        return jax.device_put(x, NamedSharding(mesh, spec))
    except ValueError:
        return x


def maybe_constrain(x, spec: Optional[PartitionSpec]):
    """Sharding constraint when a mesh is active, identity otherwise.

    Traced values get ``with_sharding_constraint`` (a compiler hint);
    concrete arrays get ``jax.device_put`` — eagerly the constraint must
    actually MOVE data (e.g. ColumnParallelLinear(gather_output=True)
    promises a replicated result readable on every host), which
    with_sharding_constraint does not guarantee outside jit."""
    if spec is None:
        return x
    mesh = get_mesh(create=False)
    if mesh is None:
        return x
    try:
        if isinstance(x, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
        # concrete: UNCONSTRAINED is only meaningful under jit — map those
        # entries to None (replicated) for an actual device_put layout
        return jax.device_put(
            x, NamedSharding(mesh, _layout().concrete(spec)))
    except (ValueError, KeyError):
        return x
