"""DistributedTrainStep — one pjit'd hybrid-parallel training step.

This is the TPU-native collapse of the reference's whole meta-optimizer
stack: where the reference rewrites the Program graph per strategy
(sharding_optimizer.py:33 partitions vars and converts allreduce ops,
recompute via backward.py:725, gradient merge via
gradient_merge_optimizer.py, AMP via mixed_precision/decorator.py) and then
executes it with SSA executors + NCCL ops, here ONE compiled XLA program
carries the entire step — forward, backward, optimizer — with shardings:

- batch dim0 sharded over ('dp','fsdp')      -> data parallelism; XLA
  emits the gradient reduction (fused, overlapped) — no Reducer, no
  c_allreduce ops
- ZeRO stage1: optimizer state sharded over 'fsdp'
       stage2: + gradients materialised sharded (reduce_scatter)
       stage3: + parameters sharded (all_gather inside fwd/bwd)
- tensor-parallel params keep their layer-annotated 'tp' specs
- recompute -> jax.checkpoint; gradient merge -> in-graph k-step
  accumulation with lax.cond; buffers (BN stats) thread functionally

Buffers are donated (params/opt-state/accumulators), so peak HBM matches
an in-place executor.
"""
from __future__ import annotations

import math
import time as _time
import weakref
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...framework.compile_cache import ensure_compile_cache
from ...framework.core import Tensor, no_grad
from ...framework.random import split_key, use_key
from ...jit import _tree_to_values
from ...observability import flight_recorder as _flight
from ...observability.timeline import StepTimeline
from .. import mesh as mesh_mod

__all__ = ["DistributedTrainStep", "param_partition_spec",
           "zero_shard_ranges", "flatten_zero_state",
           "unflatten_zero_state", "zero_shard", "zero_unshard",
           "zero_reshard", "LRSchedule", "make_lr_schedule",
           "fused_optimizer_apply"]

# storage suffix for 8-bit optimizer-state scales ("m" -> "m@scale");
# "@" cannot collide with real slot names
_SCALE_SUFFIX = "@scale"

# opt_state's position in the step signature: input [params, buffers,
# opt_state, ...], output [loss, params, buffers, opt_state, ...].
# _build asserts these against the actual spec trees it constructs, so
# the offload host-memory overrides and the traced slot fetch can never
# silently address a different subtree after a signature reshuffle.
_OPT_IN_SLOT = 2
_OPT_OUT_SLOT = 3

# slots that sit under a sqrt in the optimizer's denominator (Adam/
# Lamb "v", Adamax "inf_norm", Adagrad "moment", RMSProp
# "mean_square"): their codes round AWAY from zero, never toward it
_DENOM_SLOTS = frozenset({"v", "inf_norm", "moment", "mean_square"})


def _q8_encode(x, round_up=False):
    """f32 slot -> (int8 codes, f32 per-row scales) in signed-sqrt space.

    8-bit optimizer state (greenfield; the reference keeps f32 slots —
    low-precision moments are the VERDICT-named enabler for fitting the
    7B step on 8 v5e chips).  Linear quantization in sqrt space
    compresses the dynamic range enough for Adam's second moment: 127
    levels over sqrt(v) bound the per-row step error at ~2/127.
    Per-last-dim-row absmax scales keep the blocks aligned with any
    leading-dim ZeRO sharding; a sharded LAST dim still works (XLA
    reduces the row max across shards).

    ``round_up`` (denominator slots, ADVICE r5): round |codes| UP so a
    nonzero second moment can never decode to exactly 0.  v = g^2
    survives nearest-rounding only over a ~254:1 per-row range of |g|
    while m = g survives over ~64516:1, so a small-but-live coordinate
    could decode v to 0 with m intact — and the update becomes
    m_hat/(0+eps), a ~1e8x step blow-up.  Ceiling the magnitude floors
    decoded v at (s/1)^2 per row instead; the bias is upward (slightly
    smaller steps), which is the safe direction.
    """
    y = jnp.sign(x) * jnp.sqrt(jnp.abs(x))
    s = jnp.maximum(jnp.max(jnp.abs(y), axis=-1), 1e-12) / 127.0
    c = y / s[..., None]
    if round_up:
        # clip BEFORE the int8 cast: float slop can push the row max to
        # ceil(127.0000001) = 128, which wraps to -128 in int8
        q = jnp.clip(jnp.sign(c) * jnp.ceil(jnp.abs(c)),
                     -127.0, 127.0).astype(jnp.int8)
    else:
        q = jnp.round(c).astype(jnp.int8)
    return q, s


def _q8_decode(q, s):
    y = q.astype(jnp.float32) * s[..., None]
    return jnp.sign(y) * (y * y)


def _transform_slots(st, pshape, mdt, direction):
    """THE slot-storage transform (single source of truth for the
    decode/encode/at-rest-cast paths): param-shaped floating (or int8)
    leaves convert between f32 working form and the storage dtype;
    scalar machinery (beta_pow, decay flags) and sub-shaped scale
    leaves pass through.  ``direction``: "decode" -> f32 working form;
    "encode"/"storage" -> at-rest form (identical math; "storage"
    additionally handles ShapeDtypeStruct avals for abstract_init)."""
    int8_mode = mdt == jnp.int8
    d = {}
    for k, v in st.items():
        if k.endswith(_SCALE_SUFFIX):
            if direction != "decode":
                d[k] = v        # already-encoded scale rides along
            continue
        param_shaped = (hasattr(v, "shape") and tuple(v.shape) == pshape)
        if not param_shaped:
            d[k] = v
            continue
        if direction == "decode":
            if int8_mode and v.dtype == jnp.int8:
                d[k] = _q8_decode(v, st[k + _SCALE_SUFFIX])
            elif jnp.issubdtype(v.dtype, jnp.floating):
                d[k] = v.astype(jnp.float32)
            else:
                d[k] = v
            continue
        # encode/storage: f32 working form -> at-rest dtype
        if not jnp.issubdtype(v.dtype, jnp.floating):
            d[k] = v
        elif isinstance(v, jax.ShapeDtypeStruct):
            if int8_mode and len(pshape) >= 1:
                d[k] = jax.ShapeDtypeStruct(v.shape, jnp.int8)
                d[k + _SCALE_SUFFIX] = jax.ShapeDtypeStruct(
                    v.shape[:-1], jnp.float32)
            elif not int8_mode:
                d[k] = jax.ShapeDtypeStruct(v.shape, mdt)
            else:
                d[k] = v
        elif int8_mode:
            if len(pshape) >= 1:
                d[k], d[k + _SCALE_SUFFIX] = _q8_encode(
                    v.astype(jnp.float32),
                    round_up=k in _DENOM_SLOTS)
            else:
                d[k] = v
        else:
            d[k] = v.astype(mdt)
    return d


# -- deterministic ZeRO host-shard math (ISSUE 9 elastic training) -----
#
# The elastic membership controller (fleet/elastic.py) partitions the
# GLOBAL flattened parameter / optimizer-state vector over the live
# worker set.  These helpers are the single source of truth for that
# partition: a reshard after a membership change is a PURE function of
# (global state, new world size), so an N->M transition loads exactly
# the shards a fresh M-worker run would load from the same checkpoint.
# The partition rule (contiguous ranges, remainder spread over the
# leading ranks) deliberately matches UtilBase.get_file_shard.

class LRSchedule:
    """t-indexed learning-rate schedule for the flat elastic
    optimizers (ISSUE 10 satellite; PR 9 follow-up (b)).

    The value is a PURE function of the 1-based global step count
    ``t`` and the construction config — no internal state, nothing to
    checkpoint beyond ``t`` itself (which the elastic checkpoints
    already carry as ``opt_t``).  That makes the schedule
    world-invariant BY CONSTRUCTION: every worker of every generation
    evaluates the identical f32 lr for step t, so an N->M reshard
    mid-schedule stays bit-exact with the fault-free run.

    Kinds (``warmup_steps`` prepends a linear ramp to all of them):

    ``constant``  ``base_lr``
    ``step``      ``base_lr * gamma ** ((t - warmup) // step_size)``
    ``cosine``    ``min_lr + (base_lr - min_lr) * (1 + cos(pi*p)) / 2``
                  with progress ``p = (t - warmup) / (total - warmup)``
                  clipped to [0, 1] (requires ``total_steps``)
    ``linear``    ``base_lr + (min_lr - base_lr) * p`` (same ``p``)

    Math runs in float64 and rounds ONCE to f32 at the end — the same
    value on every host, every world size.
    """

    KINDS = ("constant", "step", "cosine", "linear")

    def __init__(self, kind: str, base_lr: float,
                 warmup_steps: int = 0,
                 total_steps: Optional[int] = None,
                 min_lr: float = 0.0, step_size: int = 1000,
                 gamma: float = 0.5):
        if kind not in self.KINDS:
            raise ValueError(f"lr schedule kind must be one of "
                             f"{self.KINDS}, got {kind!r}")
        if kind in ("cosine", "linear") and not total_steps:
            raise ValueError(f"{kind!r} schedule needs total_steps")
        if step_size < 1:
            raise ValueError("step_size must be >= 1")
        self.kind = kind
        self.base_lr = float(base_lr)
        self.warmup_steps = int(warmup_steps)
        self.total_steps = None if total_steps is None else \
            int(total_steps)
        self.min_lr = float(min_lr)
        self.step_size = int(step_size)
        self.gamma = float(gamma)

    def __call__(self, t: int) -> np.float32:
        t = int(t)
        w = self.warmup_steps
        if w > 0 and t <= w:
            return np.float32(self.base_lr * t / w)
        if self.kind == "constant":
            return np.float32(self.base_lr)
        if self.kind == "step":
            return np.float32(
                self.base_lr * self.gamma ** ((t - w - 1)
                                              // self.step_size))
        span = max(1, self.total_steps - w)
        p = min(1.0, max(0.0, (t - w) / span))
        if self.kind == "cosine":
            return np.float32(
                self.min_lr + (self.base_lr - self.min_lr)
                * 0.5 * (1.0 + math.cos(math.pi * p)))
        # linear
        return np.float32(
            self.base_lr + (self.min_lr - self.base_lr) * p)

    def __repr__(self):
        return (f"LRSchedule({self.kind!r}, base_lr={self.base_lr}, "
                f"warmup_steps={self.warmup_steps}, "
                f"total_steps={self.total_steps}, "
                f"min_lr={self.min_lr}, step_size={self.step_size}, "
                f"gamma={self.gamma})")


def make_lr_schedule(kind: str, base_lr: float, **kw) -> LRSchedule:
    """Build an :class:`LRSchedule`; accepts a plain config dict via
    ``make_lr_schedule(**cfg)`` (the launcher/worker-config spelling)."""
    return LRSchedule(kind, base_lr, **kw)


def zero_shard_ranges(total: int, world: int):
    """Contiguous ``[start, stop)`` ranges partitioning a flat
    length-``total`` vector over ``world`` ranks.  Covers every element
    exactly once for ANY (total, world) — world need not divide total;
    ranks beyond ``total`` get empty ranges."""
    total, world = int(total), int(world)
    if world < 1:
        raise ValueError(f"world must be >= 1, got {world}")
    base, rem = divmod(total, world)
    out, start = [], 0
    for r in range(world):
        size = base + (1 if r < rem else 0)
        out.append((start, start + size))
        start += size
    return out


def flatten_zero_state(tree: Dict[str, Any]):
    """``{name: ndarray}`` -> ``(flat f32 vector, meta)`` with a
    deterministic (sorted-name) layout.  ``meta`` is
    ``[(name, shape), ...]`` — feed it back to
    :func:`unflatten_zero_state`.  All leaves must share one dtype (the
    elastic data plane is f32): mixing dtypes in one flat vector would
    silently upcast shards."""
    meta, parts, dtype = [], [], None
    for name in sorted(tree):
        v = np.asarray(tree[name])
        if dtype is None:
            dtype = v.dtype
        elif v.dtype != dtype:
            raise ValueError(
                f"flatten_zero_state needs one dtype; {name!r} is "
                f"{v.dtype}, expected {dtype}")
        meta.append((name, tuple(v.shape)))
        parts.append(v.reshape(-1))
    flat = (np.concatenate(parts) if parts
            else np.zeros(0, dtype or np.float32))
    return flat, meta


def unflatten_zero_state(flat: np.ndarray, meta) -> Dict[str, Any]:
    """Inverse of :func:`flatten_zero_state` (views into ``flat``)."""
    out, ofs = {}, 0
    for name, shape in meta:
        n = int(np.prod(shape)) if shape else 1
        out[name] = flat[ofs:ofs + n].reshape(shape)
        ofs += n
    if ofs != flat.size:
        raise ValueError(
            f"flat vector has {flat.size} elements, meta describes {ofs}")
    return out


def zero_shard(flat: np.ndarray, rank: int, world: int) -> np.ndarray:
    """Rank ``rank``'s contiguous shard of the global flat vector."""
    lo, hi = zero_shard_ranges(flat.size, world)[rank]
    return flat[lo:hi]


def zero_unshard(shards) -> np.ndarray:
    """Reassemble the global flat vector from rank-ordered shards."""
    shards = list(shards)
    return (np.concatenate([np.asarray(s).reshape(-1) for s in shards])
            if shards else np.zeros(0, np.float32))


def zero_reshard(shards, new_world: int):
    """Reshard rank-ordered shards from their current world size to
    ``new_world``: merge to the global vector, re-partition.  Pure —
    bit-exact round trips (N->M->N) and identical to what a fresh
    ``new_world`` run would shard from the same global vector."""
    flat = zero_unshard(shards)
    return [zero_shard(flat, r, new_world) for r in range(new_world)]


_FUSED_APPLY_CACHE: Dict[tuple, Any] = {}


def fused_optimizer_apply(kind: str, p: np.ndarray, g: np.ndarray,
                          slots: Dict[str, np.ndarray], *, t: int,
                          lr, betas=(0.9, 0.999), eps=1e-8,
                          momentum=0.9):
    """Fused one-pass optimizer apply over a flat ZeRO shard (ISSUE 13).

    Device analog of the flat elastic sgd/momentum/adam: reads
    grad+param+moments and writes param+moments in ONE pass through the
    ``opt_apply`` kernel of the Pallas tier (``ops/pallas/opt_apply``;
    mode — pallas on TPU, XLA reference elsewhere, interpret for
    parity — resolved by the kernel registry).  Strictly elementwise
    with every constant pinned to f32, so the PR 9 world-invariance
    contract holds bit-for-bit WITHIN the fused engine: the update of
    a shard equals the same slice of the full-vector update, for any
    world size.  Adam's bias corrections are computed on host from the
    global step exactly like the numpy engine, so ``t`` never enters
    the device program and steady-state steps never retrace (the jit
    cache below is keyed by (kind, mode, shard length) only).

    Returns ``(new_param, new_slots_dict)`` as numpy f32 arrays.
    """
    from ...ops.pallas import registry as _kreg
    from ...ops.pallas.opt_apply import SLOTS, pack_hyper
    slot_names = SLOTS[kind]          # raises KeyError on unknown kind
    hyper = pack_hyper(kind, lr=lr, betas=betas, eps=eps,
                       momentum=momentum, t=t)
    mode = _kreg.resolve("opt_apply")
    key = (kind, mode, int(p.size))
    fn = _FUSED_APPLY_CACHE.get(key)
    if fn is None:

        def _run(pv, gv, sv, hy):
            return _kreg.dispatch("opt_apply", kind, pv, gv, sv, hy)

        fn = _FUSED_APPLY_CACHE[key] = jax.jit(_run)
        if len(_FUSED_APPLY_CACHE) > 256:   # bound shape-bucket growth
            _FUSED_APPLY_CACHE.pop(next(iter(_FUSED_APPLY_CACHE)))
    out = fn(np.asarray(p, np.float32), np.asarray(g, np.float32),
             tuple(np.asarray(slots[n], np.float32)
                   for n in slot_names), hyper)
    p_new = np.asarray(out[0], np.float32)
    return p_new, {n: np.asarray(o, np.float32)
                   for n, o in zip(slot_names, out[1:])}


def _tree_to_tensors(obj):
    # jit's helper wraps jax arrays only; batch elements may be numpy too
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_to_tensors(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _tree_to_tensors(v) for k, v in obj.items()}
    return Tensor(obj) if hasattr(obj, "dtype") else obj


def param_partition_spec(value, mesh, annotated: Optional[P],
                         zero3: bool) -> P:
    """Final PartitionSpec for one parameter.

    Layer annotation ('tp' etc.) wins per-dim; ZeRO-3 additionally shards
    the largest remaining dim that the 'fsdp' axis divides (the reference's
    sharding_optimizer partitions whole params by numel round-robin,
    sharding/shard.py — per-dim sharding is the XLA-friendly equivalent).
    The derivation itself lives in SpecLayout (ISSUE 15): the planner
    scores candidate meshes with the identical rule."""
    from ..planner.spec_layout import get_layout
    fsdp = mesh.shape.get("fsdp", 1) if zero3 else 1
    return get_layout().zero3_augment(tuple(value.shape), annotated, fsdp)


class DistributedTrainStep:
    """Compile (model, loss_fn, optimizer, strategy) into one sharded step.

    Usage::
        step = DistributedTrainStep(model, loss_fn, opt, strategy)
        for x, y in loader:
            loss = step(x, y)

    ``guard_health=True`` additionally computes train_guard's fused
    health reduction ([global_norm, nonfinite_count, loss]) INSIDE the
    compiled step — XLA folds it into the backward/update sweep, so
    unlike an out-of-jit health_check() there is no extra dispatch and
    no second pass over the grad tree.  After each call the f32[3]
    device array is on ``self.last_health``; hand it to
    ``TrainGuard.check`` (its fetch is the step's single guard host
    transfer).
    """

    def __init__(self, model, loss_fn, optimizer, strategy=None, mesh=None,
                 guard_health=False):
        from .strategy import DistributedStrategy
        self._guard_health = bool(guard_health)
        self.last_health = None    # f32[3] device array per call
        self._model = model
        self._loss_fn = loss_fn
        self._opt = optimizer
        self._strategy = strategy or DistributedStrategy()
        if mesh is None:
            degrees = self._strategy.mesh_degrees()
            cur = mesh_mod.get_mesh(create=False)
            want = {k: v for k, v in degrees.items() if v not in (1, -1)}
            if cur is None or any(cur.shape.get(k, 1) != v
                                  for k, v in want.items()):
                mesh = mesh_mod.init_mesh(degrees)
            else:
                mesh = cur
        self._mesh = mesh
        # Align with the OPTIMIZER's parameter list (opt_state order), not
        # the model's: fine-tuning may optimize a subset; frozen params ride
        # along as (non-differentiated) buffers.
        all_named = dict(model.named_parameters())
        opt_plist = list(getattr(optimizer, "_parameter_list", None) or [])
        if opt_plist:
            id2name = {id(p): n for n, p in all_named.items()}
            self._param_names = []
            for p in opt_plist:
                n = id2name.get(id(p))
                if n is None:
                    raise ValueError(
                        "optimizer holds a parameter that is not part of "
                        "the model passed to DistributedTrainStep")
                self._param_names.append(n)
        else:
            self._param_names = list(all_named)
        self._params = {n: all_named[n] for n in self._param_names}
        self._buffers = {n: b for n, b in model.state_dict().items()
                         if n not in self._params}
        sh = self._strategy.sharding_configs
        self._zero_stage = sh["stage"] if self._strategy.sharding else 0
        # sharding offload (reference distributed_strategy.proto:27
        # `optimize_offload`, consumed by sharding_optimizer.py:33): the
        # AdamW slots live in HOST memory and stream through the device
        # only during the optimizer epilogue — XLA inserts the transfers
        # from the pinned_host in/out shardings.  moment_dtype (greenfield
        # low-precision-moments analog) stores param-shaped slots in
        # bf16/fp16, upcast to f32 only inside the update.
        self._offload = bool(sh.get("offload", False)) \
            if self._strategy.sharding else False
        _mdt = str(sh.get("moment_dtype", "float32")).lower()
        _mdt_map = {"float32": jnp.float32, "fp32": jnp.float32,
                    "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
                    "float16": jnp.float16, "fp16": jnp.float16,
                    "int8": jnp.int8}
        if _mdt not in _mdt_map:
            # a typo here would silently keep f32 slots and OOM the
            # run the knob was set to save
            raise ValueError(
                f"sharding_configs.moment_dtype={_mdt!r} is not one of "
                f"{sorted(_mdt_map)}")
        self._moment_dtype = (_mdt_map[_mdt] if self._strategy.sharding
                              else jnp.float32)
        if self._offload:
            plat = self._mesh.devices.flat[0].platform
            if plat not in ("tpu", "gpu"):
                raise NotImplementedError(
                    "sharding_configs.offload=True compiles host-resident "
                    "optimizer state into the step (pinned_host memory "
                    f"space), which the {plat!r} backend does not support "
                    "in compiled programs; use sharding_configs."
                    "moment_dtype='bfloat16' for the in-HBM alternative")
            _gm_k = (self._strategy.gradient_merge_configs["k_steps"]
                     if self._strategy.gradient_merge else 1)
            if _gm_k > 1 or self._strategy.dgc:
                # the host<->device streaming rides STATIC in/out
                # shardings, so every micro-step would pay the full
                # round trip even when lax.cond skips the apply —
                # multiplying exactly the cost offload amortizes
                raise NotImplementedError(
                    "sharding_configs.offload does not compose with "
                    "gradient_merge or DGC (the optimizer-state round "
                    "trip cannot be gated per micro-step); use "
                    "moment_dtype='bfloat16'/'int8' instead")
        gm = self._strategy.gradient_merge_configs
        self._k_steps = gm["k_steps"] if self._strategy.gradient_merge else 1
        self._gm_avg = gm["avg"]
        self._compiled = None
        self._key_dev = None     # device-resident RNG chain
        self._key_epoch = -1     # rng epoch the chain was minted under
        self._step_dev = None    # device-resident step counter
        self._lr_cache = None    # (float, device scalar)
        self._accum = None  # gradient-merge accumulators
        self._dgc_state = None  # DGC (u, v) accumulator pair
        self._use_dgc = bool(self._strategy.dgc)
        self._step_i = np.int64(0)
        # step timeline (ISSUE 5): phase spans/histograms, sampled by
        # PADDLE_TRACE_EVERY; both exporters off -> near-zero cost
        self._obs = StepTimeline("train_step")
        # compile observatory (ISSUE 7): every distinct batch signature
        # is one lowering/compile — classified first_build /
        # new_shape_bucket / avoidable_retrace and logged to the flight
        # recorder with wall time + XLA memory analysis
        self._sig_seen: set = set()
        self._shape_seen: set = set()
        self._use_scaling = False  # set by _build for float16 AMP
        # (loss_scale, consecutive_finite_steps, consecutive_bad_steps)
        self._amp_state = None
        from .strategy import warn_noop_toggles
        warn_noop_toggles(self._strategy)
        # per-mesh recompile hook (ISSUE 17): an elastic reform_mesh()
        # drops this step's compiled program so the next call re-lays
        # and recompiles for the new world (weakly held — registering
        # does not pin the step alive)
        self.reforms = 0
        mesh_mod.on_reform(self.reform)

    # sharding derivation ---------------------------------------------
    def _param_specs(self) -> Dict[str, P]:
        mesh = self._mesh
        zero3 = self._zero_stage >= 3
        specs = {}
        for n, p in self._params.items():
            ann = getattr(p, "dist_spec", None)
            specs[n] = param_partition_spec(p._value, mesh, ann, zero3)
        return specs

    def _opt_state_specs(self, opt_state, pspecs):
        """Moment tensors follow their parameter's spec; under ZeRO-1/2
        (params replicated) moments still shard over 'fsdp' (the
        'optimizer moments' role of the SpecLayout registry)."""
        from ..planner.spec_layout import get_layout
        lay = get_layout()
        mesh = self._mesh
        fsdp = mesh.shape.get("fsdp", 1)
        out = []
        for name, st in zip(self._param_names, opt_state):
            p = self._params[name]
            d = {}
            for k, v in st.items():
                if hasattr(v, "shape") and v.shape == p._value.shape:
                    d[k] = lay.moment_spec(
                        tuple(v.shape), getattr(p, "dist_spec", None),
                        pspecs[name], self._zero_stage, fsdp)
                else:
                    d[k] = lay.replicated()
            out.append(d)
        return out

    def _batch_spec_tree(self, vals):
        from ..planner.spec_layout import get_layout
        lay = get_layout()
        data_axes = mesh_mod.data_axes(self._mesh)
        nshard = int(np.prod([self._mesh.shape[a] for a in data_axes]))

        def spec(v):
            if hasattr(v, "ndim") and v.ndim >= 1 \
                    and v.shape[0] % nshard == 0:
                return lay.batch(v.ndim, data_axes)
            return lay.replicated()
        return jax.tree_util.tree_map(spec, vals)

    def _shardings(self, tree_of_specs):
        mesh = self._mesh
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tree_of_specs,
            is_leaf=lambda x: isinstance(x, P))

    # compile ----------------------------------------------------------
    def _build(self, batch_vals, opt_state):
        model, loss_fn, opt = self._model, self._loss_fn, self._opt
        names = self._param_names
        strategy = self._strategy
        k_steps, gm_avg = self._k_steps, self._gm_avg
        use_remat = strategy.recompute

        # AMP (reference: AMPOptimizer -> mixed_precision/decorator.py graph
        # rewrite + amp ops). TPU-native: master params stay f32; inside the
        # step every f32 param/batch leaf is cast to the compute dtype, so
        # matmuls/convs hit the MXU in bf16 and the f32 grads fall out of
        # the cast's VJP. float16 additionally runs the reference's dynamic
        # loss-scaling state machine (check_finite_and_unscale +
        # update_loss_scaling ops) inside the same compiled step.
        amp_on = bool(strategy.amp)
        acfg = strategy.amp_configs
        amp_jdt = (jnp.bfloat16
                   if str(acfg.get("dtype", "bfloat16")) in
                   ("bfloat16", "bf16")
                   else jnp.float16)
        # fp16 ALWAYS runs the scaling path (reference: check_finite_and_
        # unscale runs regardless); use_dynamic_loss_scaling only controls
        # whether the scale moves — off means a constant init_loss_scaling
        use_scaling = bool(amp_on and amp_jdt == jnp.float16)
        dyn_scaling = bool(acfg["use_dynamic_loss_scaling"])
        if use_scaling and k_steps > 1:
            raise NotImplementedError(
                "float16 loss scaling (dynamic or static) + gradient_merge "
                "is not supported; use bfloat16 (TPU-native, no scaling "
                "needed)")
        if self._use_dgc and (use_scaling or k_steps > 1):
            raise NotImplementedError(
                "strategy.dgc cannot combine with float16 loss scaling or "
                "gradient_merge (the reference treats DGC as its own meta "
                "optimizer too)")
        if self._guard_health and self._use_dgc:
            raise NotImplementedError(
                "guard_health covers the plain, fp16-loss-scaling and "
                "gradient_merge steps (bf16 AMP / ZeRO / TP / PP); "
                "DGC's error-feedback accumulators still need a "
                "health-vector design (ROADMAP)")

        def _amp_cast(tree):
            return jax.tree_util.tree_map(
                lambda v: v.astype(amp_jdt)
                if hasattr(v, "dtype") and v.dtype == jnp.float32 else v,
                tree)

        # the bf16 copies of ZeRO-sharded params must be PINNED to the
        # param's sharding: without the constraint XLA's partitioner
        # all-gathers the f32 master first and casts after, doubling
        # both the gather traffic and the gathered temp (measured on the
        # 7B pp2xfsdp4 buffer assignment: f32[4096,11008] all-gathers
        # where bf16 ones suffice)
        _cast_pspecs = self._param_specs()

        def _amp_cast_params(pvals):
            out = {}
            for k, v in pvals.items():
                if hasattr(v, "dtype") and v.dtype == jnp.float32:
                    c = v.astype(amp_jdt)
                    out[k] = jax.lax.with_sharding_constraint(
                        c, NamedSharding(self._mesh, _cast_pspecs[k]))
                else:
                    out[k] = v
            return out

        def loss_of(pvals, buffer_vals, key, args):
            if amp_on:
                pvals = _amp_cast_params(pvals)
                args = _amp_cast(args)
            targs = _tree_to_tensors(args)
            with use_key(key):
                st = model.state_dict()
                old = {k: t._value for k, t in st.items()}
                try:
                    for k, t in st.items():
                        if k in pvals:
                            t._value = pvals[k]
                        elif k in buffer_vals:
                            t._value = buffer_vals[k]
                    out = loss_fn(*targs)
                    new_bufs = {k: st[k]._value for k in buffer_vals}
                finally:
                    for k, t in st.items():
                        t._value = old[k]
            lv = out._value if isinstance(out, Tensor) else out
            if amp_on:
                lv = lv.astype(jnp.float32)
            return lv, new_bufs

        if use_remat:
            # whole-step rematerialisation: residuals are not saved, the
            # forward is recomputed during backward (reference analog:
            # RecomputeOptimizer re-executes checkpointed segments,
            # fluid/backward.py:725).  Models can additionally scope finer
            # remat blocks via fleet.utils.recompute.
            loss_of = jax.checkpoint(loss_of)

        def grads_of(pvals, buffer_vals, key, args):
            (loss, bufs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(pvals, buffer_vals, key, args)
            return loss, bufs, grads

        mdt = self._moment_dtype
        low_moments = mdt != jnp.float32
        int8_moments = mdt == jnp.int8
        pshapes = [tuple(self._params[n]._value.shape) for n in names]

        def _decode_one(i, st):
            return _transform_slots(st, pshapes[i], mdt, "decode")

        def _encode_one(i, st):
            return _transform_slots(st, pshapes[i], mdt, "encode")

        def apply_opt(pvals, grads, opt_state, lr):
            # fusion fence (measured on a v5e, BERT-base): without it XLA
            # fuses each dW matmul INTO its Adam elementwise epilogue and
            # the constrained tiling runs the matmul at ~31% MFU (1.24ms
            # vs 0.39ms ideal for a [16384,3072]x[16384,768] dW). The
            # barrier keeps dW a pure MXU kernel; the update stays a
            # cheap memory-bound elementwise pass.
            grads = {n: jax.lax.optimization_barrier(g)
                     for n, g in grads.items()}
            plist = [pvals[n] for n in names]
            glist = [grads[n] for n in names]
            # lr is a traced scalar so schedulers work without retracing
            if low_moments:
                # int8 storage: sequential scheduling so the per-param
                # f32 decode/encode scratch is reused, not accumulated
                new_ps, new_ss = opt.functional_update(
                    plist, glist, opt_state, lr=lr,
                    sequential=int8_moments,
                    state_decode=_decode_one, state_encode=_encode_one)
            else:
                new_ps, new_ss = opt.functional_update(
                    plist, glist, opt_state, lr=lr)
            return dict(zip(names, new_ps)), new_ss

        if use_scaling:
            incr_every = int(acfg["incr_every_n_steps"])
            incr_ratio = float(acfg["incr_ratio"])
            decr_ratio = float(acfg["decr_ratio"])
            decr_every = int(acfg["decr_every_n_nan_or_inf"])
            guard_health = self._guard_health

            def step(pvals, bufs, opt_state, amp_state, lr, key, args):
                scale, good, bad = amp_state

                def scaled(p, b, k, a):
                    l, nb = loss_of(p, b, k, a)
                    return l * scale, nb

                (slv, nbufs), grads = jax.value_and_grad(
                    scaled, has_aux=True)(pvals, bufs, key, args)
                grads = jax.tree_util.tree_map(
                    lambda g: (g / scale).astype(jnp.float32), grads)
                finite = jnp.all(jnp.stack(
                    [jnp.all(jnp.isfinite(g))
                     for g in jax.tree_util.tree_leaves(grads)]))
                if guard_health:
                    # fused health over the UNSCALED f32 grads + the
                    # unscaled loss: rides the same compiled step, so
                    # the scaling path now exposes step.last_health
                    # exactly like the plain path (ROADMAP gap closed;
                    # the skip policy reads the bad/ok indicator, the
                    # scale state machine still owns its own finite
                    # bit).  precise=True here: the isfinite masks were
                    # already materialised for `finite` above, so the
                    # masked norm costs no extra pass over the tree.
                    from ...train_guard import fused_health
                    health = fused_health(
                        jax.tree_util.tree_leaves(grads),
                        loss=slv / scale, precise=True)

                def apply_branch(op):
                    pv, st = op
                    return apply_opt(pv, grads, st, lr)

                def skip_branch(op):  # overflow: drop the step
                    pv, st = op
                    return dict(pv), [dict(s) for s in st]

                new_p, new_s = jax.lax.cond(finite, apply_branch,
                                            skip_branch,
                                            (pvals, opt_state))
                # update_loss_scaling state machine (reference
                # operators/amp/update_loss_scaling_op.cc): grow after
                # incr_every consecutive finite steps, shrink only after
                # decr_every CONSECUTIVE nan/inf steps. Static mode
                # (use_dynamic_loss_scaling=False): constant scale,
                # overflow steps still dropped.
                if dyn_scaling:
                    good = jnp.where(finite, good + 1, 0)
                    bad = jnp.where(finite, 0, bad + 1)
                    grow = good >= incr_every
                    shrink = bad >= decr_every
                    new_scale = jnp.where(
                        grow, scale * incr_ratio,
                        jnp.where(shrink, scale * decr_ratio, scale))
                    good = jnp.where(grow, 0, good)
                    bad = jnp.where(shrink, 0, bad)
                else:
                    new_scale = scale
                if guard_health:
                    return (slv / scale, new_p, nbufs, new_s,
                            (new_scale, good, bad), health)
                return (slv / scale, new_p, nbufs, new_s,
                        (new_scale, good, bad))
            donate = (0, 1, 2, 3)
        elif self._use_dgc:
            # DGC (reference: fleet/meta_optimizers/dgc_optimizer.py +
            # sparse_all_reduce_op_handle.cc).  Under SPMD the dp-sum is
            # already fused into the backward by XLA, so compression acts
            # on the global gradient: momentum-corrected top-k with error
            # feedback (fleet/dgc.py).  Before rampup_begin_step the
            # user's Momentum optimizer applies uncompressed grads; once
            # compressing, momentum lives in DGC's u accumulator and the
            # apply becomes plain SGD (reference dgc_momentum_op.h
            # selects momentum-vs-sgd on rampup_begin_step).  The
            # sparsity list ramps in-graph via lax.switch — one static
            # top-k branch per stage.
            from ...optimizer import SGD as _SGD, Momentum as _Momentum
            from .dgc import dgc_compress, rampup_stage_index
            if not isinstance(opt, (_Momentum, _SGD)):
                raise ValueError(
                    "strategy.dgc requires a Momentum or SGD optimizer "
                    "(parity: the reference's DGCMomentumOptimizer)")
            if getattr(opt, "_nesterov", False):
                raise NotImplementedError(
                    "strategy.dgc does not support use_nesterov=True "
                    "(DGC's u-accumulator implements plain momentum)")
            dcfg = strategy.dgc_configs
            # DGC inherits the wrapped optimizer's momentum (reference:
            # DGCMomentumOptimizer); the config key covers SGD users
            dgc_m = float(getattr(opt, "_momentum",
                                  dcfg.get("momentum", 0.9)))
            spars = dcfg.get("sparsity", [0.999])
            spars = [float(s) for s in (spars if isinstance(
                spars, (list, tuple)) else [spars])]
            warm = int(dcfg.get("rampup_begin_step", 0))
            ramp = int(dcfg.get("rampup_step", 1))
            n_stage = len(spars)

            def step(pvals, bufs, opt_state, dgc_state, i, lr, key, args):
                loss, nbufs, grads = grads_of(pvals, bufs, key, args)

                def warm_branch(op):
                    st, g, pv, ost = op
                    new_p, new_s = apply_opt(pv, g, ost, lr)
                    return new_p, new_s, {"u": dict(st["u"]),
                                          "v": dict(st["v"])}

                def make_comp(sp):
                    def comp(op):
                        st, g, pv, ost = op
                        new_st, g2 = dgc_compress(st, g, momentum=dgc_m,
                                                  sparsity=sp)
                        # sgd apply keeps the optimizer's grad_clip +
                        # weight_decay exactly like functional_update
                        # does on the warmup path — only the momentum
                        # accumulation moves into DGC's u
                        glist = [g2[n] for n in names]
                        if opt._grad_clip is not None:
                            glist = opt._grad_clip.apply_values(glist)
                        new_p = {}
                        for n, gv in zip(names, glist):
                            if opt._weight_decay is not None:
                                gv = opt._weight_decay.apply_gradient(
                                    pv[n], gv)
                            new_p[n] = (pv[n] - lr.astype(pv[n].dtype)
                                        * gv.astype(pv[n].dtype))
                        return new_p, [dict(s) for s in ost], new_st
                    return comp

                branches = [warm_branch] + [make_comp(s) for s in spars]
                stage = jnp.clip(
                    rampup_stage_index(i, warm, ramp, n_stage),
                    0, n_stage - 1)
                sel = jnp.where(i < warm, 0, 1 + stage)
                new_p, new_s, new_dgc = jax.lax.switch(
                    sel, branches, (dgc_state, grads, pvals, opt_state))
                return loss, new_p, nbufs, new_s, new_dgc
            donate = (0, 1, 2, 3)
        elif k_steps <= 1:
            guard_health = self._guard_health

            def step(pvals, bufs, opt_state, lr, key, args):
                loss, nbufs, grads = grads_of(pvals, bufs, key, args)
                if guard_health:
                    from ...train_guard import fused_health
                    # fast mode: one pass per grad — the skip policy
                    # needs the bad/ok bit, not an element census
                    health = fused_health(
                        jax.tree_util.tree_leaves(grads), loss=loss,
                        precise=False)
                new_p, new_s = apply_opt(pvals, grads, opt_state, lr)
                if guard_health:
                    return loss, new_p, nbufs, new_s, health
                return loss, new_p, nbufs, new_s
            donate = (0, 1, 2)
        else:
            guard_health = self._guard_health

            def step(pvals, bufs, opt_state, accum, i, lr, key, args):
                loss, nbufs, grads = grads_of(pvals, bufs, key, args)
                accum = jax.tree_util.tree_map(jnp.add, accum, grads)
                if guard_health:
                    # ISSUE 15 satellite (ROADMAP gap): the health
                    # vector is computed over the POST-ADD accumulator
                    # — the per-microbatch vector FOLDED across the
                    # accumulation window.  A poisoned microbatch
                    # taints the accumulated gradient until the window
                    # applies-and-zeroes, so TrainGuard sees exactly
                    # the state the optimizer is about to consume at
                    # the apply tick, and the vector resets with the
                    # window.  Loss is the current microbatch's.
                    from ...train_guard import fused_health
                    health = fused_health(
                        jax.tree_util.tree_leaves(accum), loss=loss,
                        precise=False)
                do_apply = (i + 1) % k_steps == 0

                def apply_branch(op):
                    pv, acc, st = op
                    g = jax.tree_util.tree_map(
                        (lambda a: a / k_steps) if gm_avg else (lambda a: a),
                        acc)
                    np_, ns = apply_opt(pv, g, st, lr)
                    zeros = jax.tree_util.tree_map(jnp.zeros_like, acc)
                    return np_, zeros, ns

                def skip_branch(op):
                    pv, acc, st = op
                    return dict(pv), acc, st

                new_p, accum, new_s = jax.lax.cond(
                    do_apply, apply_branch, skip_branch,
                    (pvals, accum, opt_state))
                if guard_health:
                    return loss, new_p, nbufs, new_s, accum, health
                return loss, new_p, nbufs, new_s, accum
            donate = (0, 1, 2, 3)

        # the RNG chain advances ON DEVICE: the step splits its key and
        # returns the successor, so __call__ never mints/ships a key per
        # step (one more tiny host->device transfer on the dispatch
        # path of every step)
        inner_step = step
        has_i = self._use_dgc or k_steps > 1
        offload = self._offload
        # populated after sspecs are derived below; the closure cell is
        # shared so the traced step sees the final device shardings
        _offload_dev_sh: list = []
        opt_in, opt_out = _OPT_IN_SLOT, _OPT_OUT_SLOT

        def step(*a):
            head, (lr, key, args) = a[:-3], a[-3:]
            key, next_key = jax.random.split(key)
            if offload:
                # host->device fetch of the optimizer slots; the update's
                # results ride the pinned_host out_shardings back, so the
                # slots only transit HBM during the optimizer epilogue
                fetched = [
                    {k: jax.device_put(v, _offload_dev_sh[i][k])
                     if hasattr(v, "shape") else v for k, v in st.items()}
                    for i, st in enumerate(head[opt_in])]
                head = (*head[:opt_in], fetched, *head[opt_in + 1:])
            if has_i:
                # the step counter advances on device too (same
                # argument as the key)
                *head0, i = head
                out = inner_step(*head0, i, lr, key, args)
                return (*out, next_key, i + 1)
            out = inner_step(*head, lr, key, args)
            return (*out, next_key)

        # shardings ----------------------------------------------------
        pspecs = self._param_specs()
        sspecs = self._opt_state_specs(opt_state, pspecs)
        bspec = self._batch_spec_tree(batch_vals)
        bufspec = {k: P() for k in self._buffers}
        in_specs = [pspecs, bufspec, sspecs]
        out_specs = [P(), pspecs, bufspec, sspecs]
        # every step variant lays its signature out as
        # [params, buffers, opt_state, ...] in / [loss, params, buffers,
        # opt_state, ...] out; the offload overrides below and the
        # traced fetch address opt_state through the named slots, and
        # these identity asserts catch any future reordering at build
        # time instead of silently hosting the wrong subtree
        assert in_specs[_OPT_IN_SLOT] is sspecs, \
            "opt_state moved out of input slot %d" % _OPT_IN_SLOT
        assert out_specs[_OPT_OUT_SLOT] is sspecs, \
            "opt_state moved out of output slot %d" % _OPT_OUT_SLOT
        if use_scaling:
            in_specs += [(P(), P(), P()), P(), P(), bspec]  # amp_state,lr,key
            out_specs += [(P(), P(), P())]
            if self._guard_health:
                out_specs += [P()]   # the fused health vector (f32[3])
        elif self._use_dgc:
            dspec = {"u": pspecs, "v": pspecs}  # (u,v) shard like params
            in_specs += [dspec, P(), P(), P(), bspec]
            out_specs += [dspec]
        elif k_steps > 1:
            gspecs = pspecs  # accumulators shard like their params
            in_specs += [gspecs, P(), P(), P(), bspec]
            out_specs += [gspecs]
            if self._guard_health:
                out_specs += [P()]   # the folded health vector (f32[3])
        else:
            in_specs += [P(), P(), bspec]
            if self._guard_health:
                out_specs += [P()]   # the fused health vector (f32[3])
        out_specs += [P()]   # the advanced RNG key
        if has_i:
            out_specs += [P()]   # the advanced step counter
        sh = self._shardings
        self._use_scaling = use_scaling
        if use_scaling and self._amp_state is None:
            self._amp_state = (
                jnp.asarray(float(acfg["init_loss_scaling"]), jnp.float32),
                jnp.asarray(0, jnp.int32),   # consecutive finite steps
                jnp.asarray(0, jnp.int32))   # consecutive nan/inf steps
        in_sh = sh(tuple(in_specs))
        out_sh = sh(tuple(out_specs))
        if offload:
            mesh = self._mesh

            def host(tree):
                return jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s,
                                            memory_kind="pinned_host"),
                    tree, is_leaf=lambda x: isinstance(x, P))
            # opt state rides the named slots asserted above
            in_sh = (*in_sh[:_OPT_IN_SLOT],
                     host(in_specs[_OPT_IN_SLOT]),
                     *in_sh[_OPT_IN_SLOT + 1:])
            out_sh = (*out_sh[:_OPT_OUT_SLOT],
                      host(out_specs[_OPT_OUT_SLOT]),
                      *out_sh[_OPT_OUT_SLOT + 1:])
            _offload_dev_sh.extend(
                [{k: NamedSharding(mesh, d[k]) for k in d}
                 for d in sspecs])
        return jax.jit(step, donate_argnums=donate,
                       in_shardings=in_sh, out_shardings=out_sh)

    def _storage_cast(self, opt_state):
        """Slots in their at-rest dtype (sharding_configs.moment_dtype):
        param-shaped floating leaves cast (int8 mode additionally grows
        a per-row "<slot>@scale" leaf), scalar machinery stays f32.
        No-op (returns the same arrays) once already cast, and aval-only
        under abstract_init."""
        mdt = self._moment_dtype
        if mdt == jnp.float32:
            return opt_state
        return [
            _transform_slots(st, tuple(self._params[n]._value.shape),
                             mdt, "storage")
            for n, st in zip(self._param_names, opt_state)]

    def _state_sharding(self, spec):
        """NamedSharding for one optimizer slot — host-resident under
        sharding offload."""
        if self._offload:
            return NamedSharding(self._mesh, spec,
                                 memory_kind="pinned_host")
        return NamedSharding(self._mesh, spec)

    def _ensure_built(self, arg_vals, param_vals, buffer_vals,
                      opt_state):
        """Compile the step on first use and lay params/opt-state out on
        their final shardings once (ZeRO-3 may add 'fsdp' dims on top of
        layer-annotated 'tp' specs); afterwards every step's args
        already match the jit shardings.  Returns the relaid opt_state
        (the caller's ``param_vals`` dict is updated in place)."""
        if self._compiled is not None:
            return opt_state
        ensure_compile_cache()
        self._compiled = self._build(arg_vals, opt_state)
        pspecs = self._param_specs()
        for n, p in self._params.items():
            p._value = jax.device_put(
                p._value, NamedSharding(self._mesh, pspecs[n]))
            param_vals[n] = p._value
        sspecs = self._opt_state_specs(opt_state, pspecs)
        opt_state = [
            {k: jax.device_put(v, self._state_sharding(d[k]))
             if hasattr(v, "shape") else v for k, v in st.items()}
            for st, d in zip(opt_state, sspecs)]
        self._opt.load_opt_state(opt_state)
        if self._k_steps > 1 and self._accum is None:
            self._accum = {
                n: jnp.zeros_like(
                    v, device=NamedSharding(self._mesh, pspecs[n]))
                for n, v in param_vals.items()}
        if self._use_dgc and self._dgc_state is None:
            self._dgc_state = {
                ax: {n: jnp.zeros_like(
                    v, device=NamedSharding(self._mesh, pspecs[n]))
                    for n, v in param_vals.items()}
                for ax in ("u", "v")}
        return opt_state

    def reform(self, mesh=None):
        """Adopt the (re-formed) global mesh: drop the compiled program
        and every mesh-derived cache, so the next call re-lays params
        and optimizer state on the new topology and recompiles for it.
        Logical state (params, moments, rng chain, step counter) is
        preserved — this invalidates LAYOUT, not values.  Called
        automatically by ``mesh.reform_mesh()`` via the ``on_reform``
        registry; safe to call by hand after installing a mesh."""
        self._mesh = mesh if mesh is not None else mesh_mod.get_mesh()
        self._compiled = None
        self._lr_cache = None
        if self._accum is not None or self._dgc_state is not None:
            # accumulators are created once in _ensure_built; re-lay
            # them here or they would pin the dead mesh's sharding
            pspecs = self._param_specs()

            def relay(d):
                return {n: jax.device_put(
                    v, NamedSharding(self._mesh, pspecs[n]))
                    for n, v in d.items()}

            if self._accum is not None:
                self._accum = relay(self._accum)
            if self._dgc_state is not None:
                self._dgc_state = {ax: relay(d)
                                   for ax, d in self._dgc_state.items()}
        self.reforms += 1

    def _assemble_call_args(self, param_vals, buffer_vals, opt_state,
                            lr, key, arg_vals) -> tuple:
        """The compiled step's positional argument tuple for the live
        variant — the single source of truth ``__call__``,
        :meth:`compile_abstract` and :meth:`audit` all share."""
        if self._use_scaling:
            return (param_vals, buffer_vals, opt_state, self._amp_state,
                    lr, key, arg_vals)
        if self._use_dgc or self._k_steps > 1:
            if self._step_dev is None:
                self._step_dev = jnp.asarray(self._step_i, jnp.int32)
            extra = self._dgc_state if self._use_dgc else self._accum
            return (param_vals, buffer_vals, opt_state, extra,
                    self._step_dev, lr, key, arg_vals)
        return (param_vals, buffer_vals, opt_state, lr, key, arg_vals)

    def _arg_names(self) -> list:
        names = ["params", "buffers", "opt_state"]
        if self._use_scaling:
            names.append("amp_state")
        elif self._use_dgc:
            names += ["dgc_state", "step"]
        elif self._k_steps > 1:
            names += ["accum", "step"]
        return names + ["lr", "key", "batch"]

    # compile observatory -----------------------------------------------
    def _note_retrace(self, arg_sig, wall_ms: float):
        """Classify + log one retrace (called when the batch signature
        changed).  A signature seen before is a jit cache hit, not a
        retrace — nothing is logged.  Same shapes with new dtypes is an
        AVOIDABLE retrace (the caller could cast at the source); a new
        shape tuple is a legitimate new bucket (pad-and-prime it away
        if it recurs — the serving engine's bucket trick)."""
        if arg_sig in self._sig_seen:
            return
        shapes = tuple(s for s, _ in arg_sig)
        if not self._sig_seen:
            cause = "first_build"
        elif shapes in self._shape_seen:
            cause = "avoidable_retrace"
        else:
            cause = "new_shape_bucket"
        self._sig_seen.add(arg_sig)
        self._shape_seen.add(shapes)
        # memory analysis needs the executable, which the jit call path
        # does not hand out: reaching it costs one AOT compile (cached
        # for later lower().compile() callers like cost_analysis), so
        # it resolves lazily — immediately in full flight mode, on
        # demand via flight_recorder.compile_log(resolve=True) else.
        # The thunk sits in the recorder's process-global log, so it
        # holds the step WEAKLY: a strong reference to the jitted step
        # kept the model and its optimizer state (6.4 GB for the 536M
        # decoder) on the device after the last user reference died
        step_ref, specs = weakref.ref(self), self._last_call_args

        def mem_cb():
            step = step_ref()
            return (None if step is None else
                    step._compiled.lower(*specs).compile())
        _flight.note_compile(
            "DistributedTrainStep", cause, wall_ms, key=shapes,
            n_buckets=len(self._shape_seen), mem_cb=mem_cb)

    # static analysis ---------------------------------------------------
    def audit(self, *args, include_hlo: bool = True, **thresholds):
        """Run the jaxpr program auditor (GraftLint pillar 1,
        :mod:`paddle_tpu.analysis`) over the compiled step program.

        Returns an :class:`~paddle_tpu.analysis.AuditReport`: per-input
        donation status, the collective inventory (jaxpr primitives +
        post-SPMD HLO instructions when ``include_hlo``), widening-cast
        count, and rule findings (undonated buffers, dtype creep, host
        callbacks, baked-in constants).  This surface is also the hook
        the auto-sharding planner (ROADMAP item 4) reuses for memory /
        collective predictions.

        After the step has run once, the audit covers the LIVE variant
        and batch signature (``args`` are ignored); before the first
        run, pass a sample batch — the step is built for it exactly as
        ``__call__`` would.
        """
        from ...analysis.jaxpr_audit import audit_traced
        if not hasattr(self, "_last_call_args"):
            if not args:
                raise RuntimeError(
                    "audit() before the first step needs a sample "
                    "batch: step.audit(*batch)")
            arg_vals = _tree_to_values(list(args))
            param_vals = {n: p._value for n, p in self._params.items()}
            buffer_vals = {n: b._value for n, b in self._buffers.items()}
            opt_state = self._storage_cast(self._opt.opt_state())
            opt_state = self._ensure_built(arg_vals, param_vals,
                                           buffer_vals, opt_state)
            lr = jnp.asarray(float(self._opt.get_lr()), jnp.float32)
            key = split_key()
            call_args = self._assemble_call_args(
                param_vals, buffer_vals, opt_state, lr, key, arg_vals)
            specs = jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)
                if hasattr(v, "shape") and hasattr(v, "dtype") else v,
                call_args)
        else:
            specs = self._last_call_args
        traced = self._compiled.trace(*specs)
        hlo = None
        if include_hlo:
            try:
                hlo = self._compiled.lower(
                    *specs).compile().as_text()
            except Exception:   # backend can't compile this geometry
                hlo = None
        return audit_traced(traced, program="DistributedTrainStep",
                            arg_names=self._arg_names(), hlo_text=hlo,
                            **thresholds)

    # rng / step checkpointing -----------------------------------------
    def rng_state(self) -> dict:
        """Serializable state of the device-resident RNG chain + step
        counter. Include it in a training checkpoint and feed it back to
        :meth:`load_rng_state` on resume: the dropout stream continues
        bit-exactly where the interrupted run left off (the per-step
        keys are split ON DEVICE, so the global paddle.seed stream alone
        cannot reproduce an in-flight chain)."""
        from ...framework.random import key_to_data, split_key
        if self._key_dev is None:
            from ...framework.random import rng_epoch
            self._key_dev = split_key()
            self._key_epoch = rng_epoch()
        return {"key": key_to_data(self._key_dev),
                "step": int(self._step_i)}

    def load_rng_state(self, state: dict):
        from ...framework.random import data_to_key, rng_epoch
        self._key_dev = data_to_key(state["key"])
        self._key_epoch = rng_epoch()
        self._step_i = np.int64(int(state["step"]))
        self._step_dev = jnp.asarray(self._step_i, jnp.int32)

    # run --------------------------------------------------------------
    def __call__(self, *args):
        # one "train_step" span per SAMPLED step (trace_every) with
        # h2d / dispatch / host phase children; phase histograms land
        # in the registry on every step while metrics are enabled
        with self._obs.step(int(self._step_i)):
            return self._call_impl(*args)

    def _call_impl(self, *args):
        obs = self._obs
        with obs.phase("h2d"):
            arg_vals = _tree_to_values(list(args))
            param_vals = {n: p._value for n, p in self._params.items()}
            buffer_vals = {n: b._value for n, b in self._buffers.items()}
            opt_state = self._storage_cast(self._opt.opt_state())
        opt_state = self._ensure_built(arg_vals, param_vals, buffer_vals,
                                       opt_state)
        # the key chain and step counter live on device (the compiled
        # step returns their successors); lr re-uploads only when the
        # scheduler moves — each would otherwise cost a host->device
        # transfer per step. A paddle.seed() re-seed is noticed via the
        # rng epoch and re-mints the chain.
        from ...framework.random import rng_epoch
        if self._key_dev is None or self._key_epoch != rng_epoch():
            self._key_dev = split_key()
            self._key_epoch = rng_epoch()
        key = self._key_dev
        lr_f = float(self._opt.get_lr())
        if self._lr_cache is None or self._lr_cache[0] != lr_f:
            self._lr_cache = (lr_f, jnp.asarray(lr_f, jnp.float32))
        lr = self._lr_cache[1]
        call_args = self._assemble_call_args(param_vals, buffer_vals,
                                             opt_state, lr, key, arg_vals)
        t_disp0 = _time.perf_counter()
        with obs.phase("dispatch"), no_grad():
            if self._use_scaling and self._guard_health:
                (loss, new_p, new_b, new_s, self._amp_state,
                 self.last_health,
                 self._key_dev) = self._compiled(*call_args)
            elif self._use_scaling:
                (loss, new_p, new_b, new_s, self._amp_state,
                 self._key_dev) = self._compiled(*call_args)
            elif self._use_dgc:
                (loss, new_p, new_b, new_s, self._dgc_state,
                 self._key_dev, self._step_dev) = self._compiled(*call_args)
            elif self._k_steps > 1 and self._guard_health:
                (loss, new_p, new_b, new_s, self._accum,
                 self.last_health, self._key_dev,
                 self._step_dev) = self._compiled(*call_args)
            elif self._k_steps > 1:
                (loss, new_p, new_b, new_s, self._accum,
                 self._key_dev, self._step_dev) = self._compiled(*call_args)
            elif self._guard_health:
                (loss, new_p, new_b, new_s, self.last_health,
                 self._key_dev) = self._compiled(*call_args)
            else:
                (loss, new_p, new_b, new_s,
                 self._key_dev) = self._compiled(*call_args)
        disp_ms = (_time.perf_counter() - t_disp0) * 1e3
        with obs.phase("host"):
            # cheap signature over just the batch args: params/opt-state
            # avals are fixed after _build, but a different batch shape
            # retraces the jit silently and cost_analysis must report
            # the live variant
            arg_sig = tuple((tuple(v.shape), str(v.dtype))
                            for v in jax.tree_util.tree_leaves(arg_vals)
                            if hasattr(v, "shape"))
            if getattr(self, "_last_arg_sig", None) != arg_sig:
                self._last_arg_sig = arg_sig
                # only shape/dtype structs are kept (holding the arrays
                # would pin a full batch + donated-state aliases in HBM)
                self._last_call_args = jax.tree_util.tree_map(
                    lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)
                    if hasattr(v, "shape") and hasattr(v, "dtype") else v,
                    call_args)
                self._note_retrace(arg_sig, disp_ms)
            if _flight.enabled():
                # recent-step history for the postmortem ring (the
                # dispatch wall includes trace+compile on a retrace
                # step, which is exactly the anomaly worth seeing)
                _flight.record("step", i=int(self._step_i),
                               ms=round(disp_ms, 3))
            self._step_i += 1   # host mirror (authoritative: _step_dev)
            for n, p in self._params.items():
                p._value = new_p[n]
            for n, b in self._buffers.items():
                b._value = new_b[n]
            self._opt.load_opt_state(new_s)
        return Tensor(loss)

    def compile_abstract(self, *args):
        """AOT-compile the full sharded step WITHOUT materializing state.

        For models constructed under ``framework.core.abstract_init``
        (params backed by ``jax.ShapeDtypeStruct``): lowers and compiles
        the exact program ``__call__`` would run — same specs, same
        donation — from avals alone, and returns the jax ``Compiled``.
        Use ``.memory_analysis()`` on the result to prove per-device HBM
        for geometries no host could hold (the north-star Llama-2-7B
        ZeRO-3 x pipeline config, BASELINE configs[4]; reference
        capability: sharding_optimizer.py:33 + fluid/optimizer.py:3718
        composed).  Batch args are real (tiny) arrays.
        """
        acfg = self._strategy.amp_configs
        fp16 = (self._strategy.amp
                and str(acfg.get("dtype", "bfloat16")) in
                ("float16", "fp16"))
        if fp16 or self._use_dgc or self._k_steps > 1:
            raise NotImplementedError(
                "compile_abstract covers the plain step (bf16 AMP / "
                "ZeRO / TP / PP); fp16 scaling, DGC and gradient-merge "
                "carry extra state not needed for geometry proofs")
        arg_vals = _tree_to_values(list(args))
        param_vals = {n: p._value for n, p in self._params.items()}
        buffer_vals = {n: b._value for n, b in self._buffers.items()}
        opt_state = self._storage_cast(self._opt.opt_state())
        if self._compiled is None:
            ensure_compile_cache()
            self._compiled = self._build(arg_vals, opt_state)
        lr = jnp.asarray(float(self._opt.get_lr()), jnp.float32)
        key = split_key()
        call_args = self._assemble_call_args(param_vals, buffer_vals,
                                             opt_state, lr, key, arg_vals)
        t0 = _time.perf_counter()
        compiled = self._compiled.lower(*call_args).compile()
        _flight.note_compile(
            "DistributedTrainStep", "abstract",
            (_time.perf_counter() - t0) * 1e3, compiled=compiled)
        return compiled

    def cost_analysis(self):
        """XLA-reported cost of the compiled step program.

        Returns a dict (e.g. ``{'flops': ..., 'bytes accessed': ...}``)
        from the compiler's own cost model — a timing-independent ground
        truth for plausibility-checking measured throughput (the analog
        of the reference's FLAGS_benchmark per-op accounting,
        reference: paddle/fluid/platform/flags.cc FLAGS_benchmark).
        Empty dict if the step has not run yet or analysis is unavailable.
        """
        if self._compiled is None or not hasattr(self, "_last_call_args"):
            return {}
        try:
            # saved args are ShapeDtypeStructs; compile() hits jax's cache
            out = self._compiled.lower(
                *self._last_call_args).compile().cost_analysis()
            return dict(out or {})
        except Exception:
            return {}
