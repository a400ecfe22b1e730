"""SpecLayout — THE canonical sharding layer (ISSUE 15 tentpole, half 1).

Every PartitionSpec in the distributed stack is minted here.  Before
this module, sharding decisions were spread across four sites —
``distributed/mesh.py`` (batch specs, per-dim constraints),
``distributed/meta_parallel.py`` (tensor-parallel layer weights),
``distributed/pipeline.py`` (layer-stack specs) and the per-model code
in ``text/models/llama.py`` (stacked-decoder specs, head/seq
constraints) — each hand-building ``P(...)`` tuples.  Now they all
*consume* one registry mapping tensor **roles** to canonical specs over
the named mesh axes (exemplar shape: SNIPPETS.md [2], canonical
per-tensor-role PartitionSpecs; [3], one central mesh module), so the
auto-sharding planner (``planner/search.py``) can reason about any
candidate mesh from the same source of truth the executed programs use.

Axis vocabulary (identical to the pre-refactor ``mesh.AXES``; any axis
may be absent / size 1):

====  =========================================================
dp    pure data parallel (params replicated, grads psummed)
fsdp  sharded data parallel (ZeRO: params/grads/opt-state sharded)
tp    tensor (model) parallel — column/row-parallel matmuls
pp    pipeline parallel — stage axis
sp    sequence/context parallel — ring attention / Ulysses
ep    expert parallel (MoE)
====  =========================================================

Parameter roles (the registry keys; canonical templates are tuples over
axis names / ``None``, trailing dims implicitly ``None``):

==============  ======================  =============================
role            template                consumed by
==============  ======================  =============================
embedding       ("tp", None)            VocabParallelEmbedding
attn_qkv        (None, "tp")            LlamaAttention q/k/v_proj
attn_out        ("tp", None)            LlamaAttention o_proj
mlp_in          (None, "tp")            LlamaMLP gate/up_proj
mlp_out         ("tp", None)            LlamaMLP down_proj
logits          (None, "tp")            LlamaForCausalLM lm_head
col_linear      (None, "tp")            ColumnParallelLinear weight
col_bias        ("tp",)                 ColumnParallelLinear bias
row_linear      ("tp", None)            RowParallelLinear weight
norm            ()                      RMSNorm / biases (replicated)
==============  ======================  =============================

Activation roles map a *dimension* to a mesh axis (``act_axis``):
``batch`` -> ("dp", "fsdp"), ``attn_heads``/``kv_heads`` -> "tp",
``seq`` -> "sp", ``experts`` -> "ep".  Layer-stacked parameters prefix
the "pp" axis (``stack``); ZeRO-3 augments a param spec with "fsdp" on
the largest divisible free dim (``zero3_augment``); optimizer moments
follow their parameter (``moment_spec``) — the "optimizer moments"
role of the ISSUE's table.

This module deliberately imports nothing heavier than ``jax.sharding``
so ``mesh.py`` (and everything above it) can depend on it without
cycles.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple, Union

from jax.sharding import PartitionSpec as P

__all__ = [
    "AXES", "PARAM_ROLES", "ACT_ROLES", "SpecLayout", "get_layout",
    "set_layout",
]

# canonical mesh axis order: batch-like axes first, then model axes
# (mesh.init_mesh reshapes the device array in exactly this order)
AXES = ("dp", "fsdp", "pp", "tp", "sp", "ep")

# role -> canonical template.  Entries are axis names (str), tuples of
# axis names, or None; dims beyond the template are None (replicated).
PARAM_ROLES: Dict[str, Tuple] = {
    "embedding":   ("tp", None),
    "attn_qkv":    (None, "tp"),
    "attn_out":    ("tp", None),
    "mlp_in":      (None, "tp"),
    "mlp_out":     ("tp", None),
    "logits":      (None, "tp"),
    "col_linear":  (None, "tp"),
    "col_bias":    ("tp",),
    "row_linear":  ("tp", None),
    "norm":        (),
    "scalar":      (),
}

# activation role -> the mesh axis (or axis tuple) that dimension
# shards over
ACT_ROLES: Dict[str, Union[str, Tuple[str, ...]]] = {
    "batch":      ("dp", "fsdp"),
    "attn_heads": "tp",
    "kv_heads":   "tp",
    "col_out":    "tp",     # column-parallel output feature dim
    "seq":        "sp",
    "experts":    "ep",
}

# the layer-stack axis: StackedLlamaDecoder / pipeline_apply leading dim
STACK_AXIS = "pp"


@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """Canonical PartitionSpecs per tensor role over named mesh axes.

    Frozen and stateless: every method is a pure function of the role
    registry, so the planner can evaluate candidate meshes with the
    identical spec derivation the executed programs use.  A custom
    layout (renamed axes, alternative role templates) can be installed
    with :func:`set_layout`; the default instance uses the canonical
    tables above.
    """

    param_roles: Dict[str, Tuple] = dataclasses.field(
        default_factory=lambda: dict(PARAM_ROLES))
    act_roles: Dict[str, Union[str, Tuple[str, ...]]] = \
        dataclasses.field(default_factory=lambda: dict(ACT_ROLES))
    stack_axis: str = STACK_AXIS

    # -- parameter roles ----------------------------------------------
    def param_spec(self, role: str, ndim: Optional[int] = None) -> P:
        """The canonical spec for one parameter role; with ``ndim`` the
        template is padded with ``None`` to that rank."""
        try:
            tpl = self.param_roles[role]
        except KeyError:
            raise KeyError(
                f"unknown parameter role {role!r}; registered: "
                f"{sorted(self.param_roles)}") from None
        ent = list(tpl)
        if ndim is not None:
            if len(ent) > ndim:
                raise ValueError(
                    f"role {role!r} template {tpl} needs >= {len(ent)} "
                    f"dims, got ndim={ndim}")
            ent += [None] * (ndim - len(ent))
        return P(*ent)

    def replicated(self, ndim: int = 0) -> P:
        """Fully replicated ('norm'/'scalar' role shape)."""
        return P(*([None] * ndim)) if ndim else P()

    # -- activations --------------------------------------------------
    def act_axis(self, role: str):
        """The mesh axis (or axis tuple) an activation role's dimension
        shards over — feed to ``mesh.constrain_dim``."""
        try:
            return self.act_roles[role]
        except KeyError:
            raise KeyError(
                f"unknown activation role {role!r}; registered: "
                f"{sorted(self.act_roles)}") from None

    def batch(self, ndim: int, data_axes: Sequence[str]) -> P:
        """Batch spec: dim0 over the (live) data axes, rest replicated.

        ``data_axes`` is the caller-filtered subset of the 'batch'
        activation role's axes that are actually present in the mesh
        (``mesh.data_axes``).  Dim0 always carries the axis TUPLE
        (even a 1-tuple) — the exact pre-refactor form, so compiled
        programs stay bit-identical."""
        return P(tuple(data_axes), *([None] * (ndim - 1)))

    def attention_bhsd(self, batch_axes: Sequence[str],
                       head_axis: Optional[str]) -> P:
        """Spec of a ``(B, H, S, D)`` attention operand run per shard
        (the flash-attention ``shard_map``): batch over the caller-
        filtered live data axes, heads over the 'attn_heads' axis when
        it divides them, sequence and head-dim whole.  ``head_axis``
        None is also the ``[B, 1, 1, S_k]`` bias-row form."""
        return P(tuple(batch_axes) or None, head_axis, None, None)

    # -- per-dim constraint specs (mesh.constrain_dim building blocks)
    def dim_spec(self, ndim: int, dim: int, axis,
                 unconstrained_rest: bool = False) -> P:
        """A spec constraining exactly one dim to ``axis`` (None =
        replicated).  ``unconstrained_rest`` leaves the other dims
        ``UNCONSTRAINED`` (the traced/with_sharding_constraint form —
        a ``None`` there would clobber whatever layout is flowing);
        otherwise they are ``None`` (the eager/device_put form)."""
        fill = P.UNCONSTRAINED if unconstrained_rest else None
        ent = [fill] * ndim
        ent[dim] = axis
        return P(*ent)

    def concrete(self, spec: P) -> P:
        """Map UNCONSTRAINED entries to None — the eager ``device_put``
        form of a traced constraint spec."""
        return P(*(None if s is P.UNCONSTRAINED else s for s in spec))

    # -- layer stacking / pipeline ------------------------------------
    def stack(self, inner: Optional[Sequence], ndim: int) -> P:
        """Spec for a layer-STACKED parameter: leading dim on the stack
        ('pp') axis, remaining dims from the per-layer annotation
        ``inner`` (None entries pad to ``ndim``)."""
        rest = (tuple(inner) if inner is not None
                else (None,) * (ndim - 1))
        rest = rest + (None,) * (ndim - 1 - len(rest))
        return P(self.stack_axis, *rest)

    # -- ZeRO / optimizer state ---------------------------------------
    def zero3_augment(self, shape: Sequence[int],
                      annotated: Optional[Sequence],
                      fsdp: int) -> P:
        """Final spec of a parameter under ZeRO-3: the layer annotation
        wins per-dim; 'fsdp' additionally shards the largest remaining
        dim it divides (the XLA-friendly equivalent of the reference's
        whole-param round-robin, sharding/shard.py)."""
        ndim = len(shape)
        ent = list(annotated) if annotated is not None else [None] * ndim
        ent += [None] * (ndim - len(ent))
        if fsdp > 1:
            dims = sorted(range(ndim), key=lambda d: -shape[d])
            for d in dims:
                if ent[d] is None and shape[d] % fsdp == 0 \
                        and shape[d] >= fsdp:
                    ent[d] = "fsdp"
                    break
        return P(*ent)

    def moment_spec(self, shape: Sequence[int],
                    annotated: Optional[Sequence], param_spec: P,
                    zero_stage: int, fsdp: int) -> P:
        """The 'optimizer moments' role: a param-shaped slot follows its
        parameter's spec; under ZeRO-1/2 (params replicated) the slots
        still shard over 'fsdp'."""
        if zero_stage >= 3:
            return param_spec
        if zero_stage >= 1:
            return self.zero3_augment(shape, annotated, fsdp)
        return param_spec

    # -- accounting (shared with the planner's memory model) ----------
    def sharded_numel(self, shape: Sequence[int], spec: P,
                      axis_sizes: Dict[str, int]) -> int:
        """Per-device element count of one array under ``spec`` on a
        mesh with the given axis sizes (ceil per dim — XLA pads
        non-dividing shards)."""
        n = 1
        for d, s in enumerate(shape):
            ax = spec[d] if d < len(spec) else None
            if ax is None or ax is P.UNCONSTRAINED:
                f = 1
            elif isinstance(ax, (tuple, list)):
                f = 1
                for a in ax:
                    f *= int(axis_sizes.get(a, 1))
            else:
                f = int(axis_sizes.get(ax, 1))
            n *= -(-int(s) // max(f, 1))
        return n


_layout = SpecLayout()


def get_layout() -> SpecLayout:
    """The installed layout (default: the canonical tables above)."""
    return _layout


def set_layout(layout: SpecLayout) -> SpecLayout:
    """Install a custom layout; returns the previous one."""
    global _layout
    prev, _layout = _layout, layout
    return prev
