"""User-facing collective API (parity: python/paddle/distributed/collective.py).

Reference implementation: each function appends a ``c_*`` NCCL graph op
keyed by ``ring_id`` (reference: distributed/collective.py ->
operators/collective/*).  Here a collective is a compiled XLA program over
the group's devices: the eager Tensor is interpreted as this process's
value replicated on every rank of the group (SPMD single-controller view),
``shard_map`` runs the collective on all ranks at once, and XLA lowers it
onto ICI.  For collectives *inside* jitted SPMD code use
``paddle_tpu.distributed.communication`` (axis-name primitives) directly.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map(f, mesh, in_specs, out_specs, **kw):
    """``jax.shard_map`` with the replication (vma) checker off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kw)

from ..framework.core import Tensor
from . import mesh as mesh_mod

__all__ = [
    "ReduceOp", "Group", "new_group", "get_group", "wait", "barrier",
    "all_reduce", "all_gather", "reduce", "reduce_scatter", "broadcast",
    "scatter", "alltoall", "send", "recv", "split",
]


class ReduceOp:
    """Parity: paddle.distributed.ReduceOp (SUM/MAX/MIN/PROD)."""
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3


class Group:
    """A set of ranks (device subset) forming a collective ring.

    Replaces the reference's ``ring_id`` + NCCLComm table
    (reference: paddle/fluid/platform/collective_helper.h:65).
    """

    def __init__(self, gid: int, devices):
        self.id = gid
        self.devices = list(devices)
        self.nranks = len(self.devices)
        self._mesh = Mesh(np.asarray(self.devices), ("world",))

    @property
    def world_size(self):
        return self.nranks

    def __repr__(self):
        return f"Group(id={self.id}, nranks={self.nranks})"


_groups = {}


def _default_group() -> Group:
    if 0 not in _groups:
        _groups[0] = Group(0, jax.devices())
    return _groups[0]


def get_group(gid: int = 0) -> Group:
    return _groups.get(gid) or _default_group()


def new_group(ranks: Optional[Sequence[int]] = None, backend=None) -> Group:
    """Parity: paddle.distributed.new_group (reference collective.py)."""
    devs = jax.devices()
    if ranks is None:
        ranks = list(range(len(devs)))
    gid = max(_groups, default=0) + 1
    g = Group(gid, [devs[r] for r in ranks])
    _groups[gid] = g
    return g


def _resolve_group(group) -> Group:
    if group is None or group == 0:
        return _default_group()
    if isinstance(group, Group):
        return group
    return get_group(int(group))


def _as_value(t, group: Optional[Group] = None):
    v = t._value if isinstance(t, Tensor) else jnp.asarray(t)
    if group is not None:
        # lay the (replicated) value out over the group's devices so the
        # shard_map'd collective can consume it
        v = jax.device_put(v, NamedSharding(group._mesh, P()))
    return v


@functools.lru_cache(maxsize=512)
def _compiled(kind, gid, shape, dtype, extra=None):
    g = _groups.get(gid) or _default_group()
    mesh = g._mesh
    rep = P()  # everything replicated: per-rank value == this controller's

    def run(fn):
        sm = shard_map(fn, mesh=mesh, in_specs=(rep,), out_specs=rep)
        return jax.jit(sm)

    n = g.nranks
    if kind.startswith("all_reduce"):
        op = kind.split(":")[1]
        red = {"0": lambda x: lax.psum(x, "world"),
               "1": lambda x: lax.pmax(x, "world"),
               "2": lambda x: lax.pmin(x, "world"),
               "3": lambda x: jnp.prod(lax.all_gather(x, "world"),
                                       axis=0)}[op]
        return run(lambda x: red(x))
    if kind == "all_gather":
        sm = shard_map(lambda x: lax.all_gather(x, "world"),
                       mesh=mesh, in_specs=(rep,),
                       out_specs=P())
        return jax.jit(sm)
    if kind == "broadcast":
        root = int(extra)
        def bc(x):
            idx = lax.axis_index("world")
            return lax.psum(jnp.where(idx == root, x, jnp.zeros_like(x)),
                            "world")
        return run(bc)
    if kind == "reduce_scatter":
        sm = shard_map(
            lambda x: lax.psum_scatter(x, "world", scatter_dimension=0,
                                       tiled=True),
            mesh=mesh, in_specs=(rep,), out_specs=P("world"))
        return jax.jit(sm)
    raise ValueError(kind)


def all_reduce(tensor: Tensor, op: int = ReduceOp.SUM, group=None,
               use_calc_stream: bool = True):
    """In-place allreduce (parity: reference collective.py all_reduce ->
    c_allreduce_{sum,max,min,prod} ops)."""
    g = _resolve_group(group)
    fn = _compiled(f"all_reduce:{op}", g.id, tuple(tensor.shape),
                   str(tensor.dtype))
    tensor._value = fn(_as_value(tensor, g))
    return tensor


def reduce(tensor: Tensor, dst: int = 0, op: int = ReduceOp.SUM, group=None,
           use_calc_stream: bool = True):
    """Reduce-to-root: with a replicated eager view, identical to
    all_reduce (every rank materialises the result)."""
    return all_reduce(tensor, op, group)


def all_gather(tensor_list: List[Tensor], tensor: Tensor, group=None,
               use_calc_stream: bool = True):
    """Gathers per-rank values; fills ``tensor_list`` with ``nranks``
    entries (parity: reference collective.py all_gather -> c_allgather)."""
    g = _resolve_group(group)
    fn = _compiled("all_gather", g.id, tuple(tensor.shape),
                   str(tensor.dtype))
    stacked = np.asarray(fn(_as_value(tensor, g)))  # (nranks, *shape)
    del tensor_list[:]
    for r in range(g.nranks):
        tensor_list.append(Tensor(jnp.asarray(stacked[r])))
    return tensor_list


def reduce_scatter(tensor: Tensor, op: int = ReduceOp.SUM, group=None):
    """Sum across ranks, return this rank's shard of dim0."""
    g = _resolve_group(group)
    fn = _compiled("reduce_scatter", g.id, tuple(tensor.shape),
                   str(tensor.dtype))
    out = fn(_as_value(tensor, g))
    # single-controller: return the global (sharded) array's local view of
    # rank 0 == first chunk
    chunk = tensor.shape[0] // g.nranks
    return Tensor(jnp.asarray(out)[:chunk])


def broadcast(tensor: Tensor, src: int = 0, group=None,
              use_calc_stream: bool = True):
    """Parity: reference collective.py broadcast -> c_broadcast op."""
    g = _resolve_group(group)
    fn = _compiled("broadcast", g.id, tuple(tensor.shape),
                   str(tensor.dtype), extra=src)
    tensor._value = fn(_as_value(tensor, g))
    return tensor


def scatter(tensor: Tensor, tensor_list: Optional[List[Tensor]] = None,
            src: int = 0, group=None, use_calc_stream: bool = True):
    """Rank r receives ``tensor_list[r]``.  Single-controller view: the
    caller holds all shards; this process's slot is its process index."""
    if tensor_list:
        r = jax.process_index() % len(tensor_list)
        tensor._value = _as_value(tensor_list[r])
    return tensor


def alltoall(in_tensor_list: List[Tensor], out_tensor_list: List[Tensor],
             group=None, use_calc_stream: bool = True):
    """Parity: alltoall. Single-controller: transpose of the scatter/gather
    pattern — with a replicated view every rank's row r is this list's
    entry r."""
    del out_tensor_list[:]
    out_tensor_list.extend(Tensor(_as_value(t)) for t in in_tensor_list)
    return out_tensor_list


def send(tensor: Tensor, dst: int = 0, group=None, use_calc_stream=True):
    """P2P send (reference: operators/collective/send_v2_op).  In the
    single-controller SPMD model P2P exists only inside compiled programs
    (``communication.ppermute``); eager send is a no-op on one controller."""
    return tensor


def recv(tensor: Tensor, src: int = 0, group=None, use_calc_stream=True):
    return tensor


def barrier(group=None):
    """Parity: reference barrier_op.cc — block until all ranks arrive.
    Single controller: flush outstanding device work."""
    g = _resolve_group(group)
    x = all_reduce(Tensor(jnp.zeros((), jnp.int32)), ReduceOp.SUM, g)
    jax.block_until_ready(x._value)


def wait(tensor: Tensor, group=None, use_calc_stream: bool = True):
    jax.block_until_ready(_as_value(tensor))
    return tensor


def split(x, size, operation: str, axis: int = 0, num_partitions: int = 1,
          gather_out: bool = True, weight_attr=None, bias_attr=None,
          name=None):
    """Model-parallel building block (parity:
    reference python/paddle/distributed/collective.py:566 ``split`` with
    ``_parallel_linear:492`` / ``_parallel_embedding:526``).

    operation='linear':    axis=0 -> row-parallel, axis=1 -> column-parallel
    operation='embedding': row-sharded vocab table

    The reference creates per-rank weight shards plus explicit
    c_allreduce/c_concat ops; here the full layer is created with its weight
    *annotated* with a 'tp' PartitionSpec — XLA SPMD inserts the collectives.
    """
    from .meta_parallel import (ColumnParallelLinear, RowParallelLinear,
                                VocabParallelEmbedding)
    if operation == "linear":
        in_f, out_f = size
        if axis == 0:
            layer = RowParallelLinear(in_f, out_f, has_bias=bias_attr is not False)
        else:
            layer = ColumnParallelLinear(in_f, out_f,
                                         has_bias=bias_attr is not False,
                                         gather_output=gather_out)
    elif operation == "embedding":
        vocab, emb = size
        layer = VocabParallelEmbedding(vocab, emb)
    else:
        raise ValueError(f"unsupported split operation {operation!r}")
    return layer(x)
