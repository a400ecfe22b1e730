"""GraftLint pillar 1 — the jaxpr program auditor (ISSUE 6 tentpole).

The reference frames its graph layer around analyzability: ~104 IR
passes over the Program graph (``framework/ir/pass.h``).  The TPU-native
analog keeps a thin jaxpr-level pass layer: any jittable step (or a
loaded :class:`~paddle_tpu.inference.Predictor`) is traced to its
ClosedJaxpr and walked by a fixed set of audit rules that prove the
properties a human reviewer otherwise has to eyeball per PR:

``jaxpr.undonated-buffer``  (error)
    a large input leaf whose (shape, dtype) matches an output but is not
    donated — params/opt-state round-tripped without ``donate_argnums``
    hold both copies live and double peak HBM on a real chip.
``jaxpr.dtype-widen-state`` (error)
    a low-precision (bf16/f16) input leaf comes back as a WIDER float of
    the same shape — silent state upcast creep (the 2x-HBM failure mode
    of a moment_dtype knob quietly ignored).
``jaxpr.dtype-f64``         (error)
    an equation first *produces* float64 from non-f64 inputs (or an f64
    leaf enters the program) — f64 creep runs at 1/8th MXU rate and
    doubles every downstream buffer.
``jaxpr.host-callback``     (error)
    a host callback primitive (pure_callback / io_callback / ...)
    inside the compiled step — every host sync must route through the
    train_guard ``_host_fetch`` funnel *outside* the program.
``jaxpr.large-const``       (warning)
    a large closed-over constant baked into the program — it is
    re-uploaded with every executable and invisible to checkpointing.

Beyond findings, the report carries a **collective inventory** (count +
bytes of psum / all_gather / ppermute / ... at the jaxpr level, plus the
post-SPMD HLO instruction counts when a compiled text is available), a
**kernel inventory** (ISSUE 13: pallas/Mosaic custom calls classified
as device kernels — never host callbacks — by name and count, with the
compiled ``tpu_custom_call`` targets mirrored from HLO) and
a per-input **donation table** — the observable surface
``DistributedTrainStep.audit()`` / ``Predictor.audit()`` expose and the
auto-sharding planner (ROADMAP item 4) will reuse for memory and
collective predictions.
"""
from __future__ import annotations

import re
from collections import Counter
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .findings import SEV_ERROR, SEV_WARNING, Finding

__all__ = ["AuditReport", "audit_fn", "audit_traced", "audit_jaxpr",
           "collective_inventory", "hlo_collective_inventory",
           "kernel_inventory", "hlo_kernel_inventory",
           "COLLECTIVE_PRIMS", "CALLBACK_PRIMS", "KERNEL_PRIMS"]

# jaxpr-level collective primitives -> the family they are counted
# under.  The names are those of the installed JAX (0.9): inside a
# ``shard_map`` that tracks varying axes a ``lax.psum`` traces to
# ``psum_invariant`` (and the transpose of a ``pvary``, i.e. the
# gradient of a replicated input, is one too); ``psum`` itself is what
# ``check_vma=False`` bodies emit.  tests/test_graft_lint.py traces
# every family through ``shard_map``, so a renamed primitive fails
# there instead of reading as "no collectives".
COLLECTIVE_PRIMS = {
    "psum": "psum", "psum_invariant": "psum", "unreduced_psum": "psum",
    "pmax": "pmax", "pmin": "pmin",
    "all_gather": "all_gather", "all_gather_invariant": "all_gather",
    "all_gather_reduced": "all_gather",
    "all_to_all": "all_to_all", "ragged_all_to_all": "all_to_all",
    "ppermute": "ppermute", "pgather": "pgather",
    "reduce_scatter": "reduce_scatter",
    "unreduced_reduce_scatter": "reduce_scatter",
}

# host-callback primitives: anything here inside a step program is a
# per-step host round trip
CALLBACK_PRIMS = {"pure_callback", "io_callback", "callback",
                  "outside_call", "host_callback_call"}
DEBUG_PRIMS = {"debug_callback", "debug_print"}

# device-kernel primitives (ISSUE 13): pallas custom calls are KERNELS
# — device code behind a custom-call boundary, NOT host callbacks.
# They land in the report's kernel inventory (name + count) so a step
# program's custom-call surface is auditable; they must never trip the
# jaxpr.host-callback rule.
KERNEL_PRIMS = {"pallas_call", "tpu_custom_call", "mosaic"}
# post-SPMD HLO: what a compiled pallas call looks like on TPU
_HLO_KERNEL_TARGETS = ("tpu_custom_call", "mosaic", "__gpu$xla.gpu")

# post-SPMD HLO collective instructions (what XLA actually emits once
# shardings partition the program — jaxpr psums may be absent entirely
# for jit-with-shardings programs)
_HLO_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all",
                    "collective-broadcast")
_HLO_SHAPE_RE = re.compile(r"([a-z]+[0-9]+)\[([0-9,]*)\]")
_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_FLOAT_WIDTH = {"bfloat16": 2, "float16": 2, "float32": 4, "float64": 8}


def _aval_nbytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * int(
            np.dtype(aval.dtype).itemsize)
    except Exception:       # extended dtypes (PRNG keys): size unknowable
        return 0


def _dtype_str(aval) -> str:
    try:
        return str(np.dtype(aval.dtype))
    except Exception:
        return str(getattr(aval, "dtype", "?"))


def _sig(aval) -> Tuple[Tuple[int, ...], str]:
    return (tuple(getattr(aval, "shape", ())), _dtype_str(aval))


def _iter_jaxprs(obj):
    """Yield every Jaxpr reachable from ``obj`` (an eqn params value):
    ClosedJaxpr / Jaxpr / containers thereof — covers pjit, scan, cond
    branches, shard_map, custom_jvp/vjp and future wrapper primitives
    without naming them."""
    if obj is None:
        return
    if hasattr(obj, "jaxpr") and hasattr(obj, "consts"):   # ClosedJaxpr
        yield obj.jaxpr
        return
    if hasattr(obj, "eqns") and hasattr(obj, "invars"):    # Jaxpr
        yield obj
        return
    if isinstance(obj, (list, tuple)):
        for o in obj:
            yield from _iter_jaxprs(o)


def iter_eqns(jaxpr):
    """Every equation in ``jaxpr``, recursing into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _iter_jaxprs(v):
                yield from iter_eqns(sub)


@dataclass
class AuditReport:
    """The audit result for one traced program."""

    program: str
    findings: List[Finding] = field(default_factory=list)
    collectives: Dict[str, Dict[str, int]] = field(default_factory=dict)
    hlo_collectives: Optional[Dict[str, Dict[str, int]]] = None
    donation: List[Dict] = field(default_factory=list)
    widening_casts: int = 0
    # ISSUE 13: pallas/Mosaic custom calls classified as device
    # KERNELS — {kernel_name: count}; hlo_kernels mirrors the compiled
    # custom-call targets when HLO text was audited
    kernels: Dict[str, int] = field(default_factory=dict)
    hlo_kernels: Optional[Dict[str, int]] = None

    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == SEV_ERROR]

    def ok(self) -> bool:
        return not self.errors()

    def collective_count(self, kind: Optional[str] = None) -> int:
        """Collective ops in the program.  When compiled HLO text was
        audited, the post-SPMD counts (arrays moved) are the ground truth
        (jit-with-shardings programs carry no jaxpr collectives at
        all); otherwise the jaxpr primitive counts are used.  ``kind``
        filters to one family (``"psum"`` maps to HLO ``all-reduce``,
        etc.)."""
        alias = {"psum": "all-reduce", "all_gather": "all-gather",
                 "reduce_scatter": "reduce-scatter",
                 "ppermute": "collective-permute",
                 "all_to_all": "all-to-all"}
        if self.hlo_collectives is not None:
            return sum(v["count"]
                       for k, v in self.hlo_collectives.items()
                       if kind is None or alias.get(kind, kind) == k)
        return sum(v["count"] for k, v in self.collectives.items()
                   if kind is None or k == kind)

    def donated_fraction(self) -> float:
        tot = sum(d["bytes"] for d in self.donation)
        don = sum(d["bytes"] for d in self.donation if d["donated"])
        return (don / tot) if tot else 1.0

    def summary(self) -> str:
        lines = [f"audit[{self.program}]: "
                 f"{len(self.errors())} error(s), "
                 f"{len(self.findings) - len(self.errors())} other "
                 f"finding(s), donated {self.donated_fraction():.0%} "
                 f"of {sum(d['bytes'] for d in self.donation)} input "
                 f"bytes, {self.widening_casts} widening cast(s)"]
        inv = dict(self.collectives)
        if self.hlo_collectives:
            inv.update({f"hlo:{k}": v
                        for k, v in self.hlo_collectives.items()})
        if inv:
            lines.append("  collectives: " + ", ".join(
                f"{k} x{v['count']} ({v['bytes']}B)"
                for k, v in sorted(inv.items())))
        kinv = dict(self.kernels)
        if self.hlo_kernels:
            kinv.update({f"hlo:{k}": v
                         for k, v in self.hlo_kernels.items()})
        if kinv:
            lines.append("  kernels: " + ", ".join(
                f"{k} x{v}" for k, v in sorted(kinv.items())))
        for f in self.findings:
            lines.append("  " + f.format())
        return "\n".join(lines)

    def asdict(self) -> Dict:
        return {"program": self.program,
                "findings": [f.asdict() for f in self.findings],
                "collectives": self.collectives,
                "hlo_collectives": self.hlo_collectives,
                "donation": self.donation,
                "widening_casts": self.widening_casts,
                "kernels": self.kernels,
                "hlo_kernels": self.hlo_kernels}


def collective_inventory(closed_jaxpr) -> Dict[str, Dict[str, int]]:
    """Count + output bytes of every collective primitive in the jaxpr
    (recursively — shard_map bodies are where they live)."""
    inv: Dict[str, Dict[str, int]] = {}
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        fam = COLLECTIVE_PRIMS.get(eqn.primitive.name)
        if fam is None:
            continue
        d = inv.setdefault(fam, {"count": 0, "bytes": 0})
        d["count"] += 1
        d["bytes"] += sum(_aval_nbytes(v.aval) for v in eqn.outvars)
    return inv


def _kernel_name(eqn) -> str:
    """Kernel name of a pallas/Mosaic custom call: the ``name=`` the
    call site gave ``pallas_call``, else the kernel function's own name
    (kept on the kernel jaxpr's ``debug_info``), else the primitive."""
    nm = eqn.params.get("name")
    if not nm:
        dbg = getattr(eqn.params.get("jaxpr"), "debug_info", None)
        nm = getattr(dbg, "func_name", None)
    return str(nm) if nm else eqn.primitive.name


def kernel_inventory(closed_jaxpr) -> Dict[str, int]:
    """Count device-kernel custom calls (pallas_call etc.) per kernel
    name — the ISSUE 13 classification: kernels, not host callbacks."""
    inv: Dict[str, int] = {}
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name in KERNEL_PRIMS:
            nm = _kernel_name(eqn)
            inv[nm] = inv.get(nm, 0) + 1
    return inv


def hlo_kernel_inventory(hlo_text: str) -> Dict[str, int]:
    """Count compiled custom-call instructions whose target is a known
    device-kernel entry point (``tpu_custom_call`` is what a pallas
    kernel lowers to on TPU)."""
    inv: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "custom-call" not in line:
            continue
        m = re.search(r'custom_call_target="([^"]+)"', line)
        if not m:
            continue
        tgt = m.group(1)
        if any(t in tgt for t in _HLO_KERNEL_TARGETS):
            inv[tgt] = inv.get(tgt, 0) + 1
    return inv


def hlo_collective_inventory(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Count + bytes of collective instructions in compiled HLO text —
    the post-SPMD ground truth for jit-with-shardings programs, where
    the jaxpr carries no explicit collectives at all.  ``count`` is the
    number of arrays moved: XLA's combiner passes merge neighbouring
    collectives into one tuple-shaped instruction as its thresholds see
    fit, so ``(f32[16], f32[16,8]) all-reduce(...)`` counts 2 — what
    the program asked for, whatever the compiler batched."""
    inv: Dict[str, Dict[str, int]] = {}
    for line in hlo_text.splitlines():
        for op in _HLO_COLLECTIVES:
            marker = f" {op}("
            idx = line.find(marker)
            if idx < 0 or "=" not in line[:idx]:
                continue
            # result type sits between '=' and the op name:
            #   %x = f32[128,256]{1,0} all-reduce(...)
            typ = line[line.index("=") + 1:idx]
            nbytes = 0
            shapes = _HLO_SHAPE_RE.findall(typ)
            for dt, dims in shapes:
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                nbytes += n * _HLO_DTYPE_BYTES.get(dt, 4)
            d = inv.setdefault(op, {"count": 0, "bytes": 0})
            d["count"] += max(1, len(shapes))
            d["bytes"] += nbytes
            break
    return inv


def audit_jaxpr(closed_jaxpr, *, program: str = "program",
                in_names: Optional[Sequence[str]] = None,
                donated: Optional[Sequence[bool]] = None,
                check_donation: bool = True,
                min_donate_bytes: int = 1 << 20,
                min_state_bytes: int = 256,
                min_const_bytes: int = 64 * 1024) -> AuditReport:
    """Run every audit rule over one ClosedJaxpr.

    ``in_names``/``donated`` align with the jaxpr's flat ``in_avals``;
    missing entries default to ``arg[i]`` / not-donated.
    """
    in_avals = list(closed_jaxpr.in_avals)
    out_avals = list(closed_jaxpr.out_avals)
    names = list(in_names or [])
    names += [f"arg[{i}]" for i in range(len(names), len(in_avals))]
    don = list(donated or [])
    don += [False] * (len(in_avals) - len(don))
    rep = AuditReport(program=program)

    # donation table (reported even when the rule is off)
    for i, aval in enumerate(in_avals):
        rep.donation.append({"input": names[i], "donated": bool(don[i]),
                             "bytes": _aval_nbytes(aval),
                             "shape": list(getattr(aval, "shape", ())),
                             "dtype": _dtype_str(aval)})

    # rule: undonated-buffer -------------------------------------------
    if check_donation:
        outs_by_sig = Counter(_sig(a) for a in out_avals)
        donated_by_sig: Counter = Counter()
        for i, aval in enumerate(in_avals):
            if don[i]:
                donated_by_sig[_sig(aval)] += 1
        for i, aval in enumerate(in_avals):
            if don[i] or _aval_nbytes(aval) < min_donate_bytes:
                continue
            s = _sig(aval)
            if outs_by_sig[s] > donated_by_sig[s]:
                donated_by_sig[s] += 1   # one output slot consumed
                rep.findings.append(Finding(
                    SEV_ERROR, "jaxpr.undonated-buffer",
                    f"{program}::{names[i]}",
                    f"input {names[i]} ({_dtype_str(aval)}"
                    f"{list(aval.shape)}, {_aval_nbytes(aval)} bytes) "
                    "aliases an output of the same shape/dtype but is "
                    "not donated — both copies stay live and peak HBM "
                    "doubles; add it to donate_argnums",
                    data={"bytes": _aval_nbytes(aval)}))

    # rule: dtype-widen-state ------------------------------------------
    out_float_by_shape: Dict[Tuple[int, ...], set] = {}
    for a in out_avals:
        w = _FLOAT_WIDTH.get(_dtype_str(a))
        if w:
            out_float_by_shape.setdefault(
                tuple(a.shape), set()).add(_dtype_str(a))
    for i, aval in enumerate(in_avals):
        dt = _dtype_str(aval)
        w = _FLOAT_WIDTH.get(dt)
        if not w or w >= 4 or _aval_nbytes(aval) < min_state_bytes:
            continue
        wider = sorted(d for d in out_float_by_shape.get(
            tuple(aval.shape), ()) if _FLOAT_WIDTH[d] > w)
        same = [d for d in out_float_by_shape.get(tuple(aval.shape), ())
                if _FLOAT_WIDTH[d] <= w]
        if wider and not same:
            rep.findings.append(Finding(
                SEV_ERROR, "jaxpr.dtype-widen-state",
                f"{program}::{names[i]}",
                f"{dt} input {names[i]} {list(aval.shape)} only comes "
                f"back as {'/'.join(wider)} — state silently widened "
                "(low-precision storage lost on the round trip)"))

    # rules over equations ---------------------------------------------
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        prim = eqn.primitive.name
        if prim in KERNEL_PRIMS:
            # a pallas custom call is a DEVICE kernel: inventoried,
            # never flagged as a host callback (its inner jaxpr is
            # still recursed for the other rules)
            nm = _kernel_name(eqn)
            rep.kernels[nm] = rep.kernels.get(nm, 0) + 1
            continue
        if prim in CALLBACK_PRIMS or prim in DEBUG_PRIMS:
            sev = SEV_ERROR if prim in CALLBACK_PRIMS else SEV_WARNING
            cb = eqn.params.get("callback")
            cb_s = "" if cb is None else f" ({str(cb)[:60]})"
            rep.findings.append(Finding(
                sev, "jaxpr.host-callback",
                f"{program}::{prim}",
                f"host callback primitive {prim!r}" + cb_s
                + " inside the compiled program — a host round trip "
                "per step; route host work through the train_guard "
                "_host_fetch funnel outside the step"))
            continue
        if prim == "convert_element_type":
            new = eqn.params.get("new_dtype")
            old = getattr(getattr(eqn.invars[0], "aval", None),
                          "dtype", None)
            try:
                # NB: ml_dtypes bfloat16 is NOT numpy kind 'f' — width
                # comes from the explicit float table, not dtype.kind
                wn = _FLOAT_WIDTH.get(str(np.dtype(new))) if new is not \
                    None else None
                wo = _FLOAT_WIDTH.get(str(np.dtype(old))) if old is not \
                    None else None
                if wn and wo and wn > wo:
                    rep.widening_casts += 1
            except TypeError:
                pass
        # f64 creep: flag the eqn that first PRODUCES f64 from narrower
        # inputs (downstream f64-consuming eqns are fallout, not cause)
        for ov in eqn.outvars:
            if _dtype_str(ov.aval) == "float64" and not any(
                    _dtype_str(getattr(iv, "aval", None)) == "float64"
                    for iv in eqn.invars if hasattr(iv, "aval")):
                rep.findings.append(Finding(
                    SEV_ERROR, "jaxpr.dtype-f64",
                    f"{program}::{prim}",
                    f"{prim} produces float64 "
                    f"{list(ov.aval.shape)} from non-f64 inputs — f64 "
                    "creep (1/8th MXU rate, 2x buffer bytes); cast "
                    "explicitly or fix the accidental promotion"))
                break
    for i, aval in enumerate(in_avals):
        if _dtype_str(aval) == "float64":
            rep.findings.append(Finding(
                SEV_ERROR, "jaxpr.dtype-f64",
                f"{program}::{names[i]}",
                f"input {names[i]} enters the program as float64"))

    # rule: large-const ------------------------------------------------
    for i, c in enumerate(closed_jaxpr.consts):
        nbytes = getattr(c, "nbytes", 0) or 0
        if nbytes >= min_const_bytes:
            rep.findings.append(Finding(
                SEV_WARNING, "jaxpr.large-const",
                f"{program}::const[{i}]",
                f"closed-over constant {_dtype_str(c)}"
                f"{list(np.shape(c))} ({nbytes} bytes) baked into the "
                "program — it bloats every serialized executable and "
                "bypasses checkpointing; pass it as an argument",
                data={"bytes": int(nbytes)}))

    rep.collectives = collective_inventory(closed_jaxpr)
    return rep


def _names_from_args_info(args_info, arg_names=None) -> List[str]:
    import jax
    flat = jax.tree_util.tree_flatten_with_path(args_info)[0]
    names = []
    for path, _ in flat:
        ks = jax.tree_util.keystr(path)
        # paths look like "[0][2][0]['m']": [0] = the args tuple,
        # next index = the positional arg — swap it for its name
        m = re.match(r"^\[0\]\[(\d+)\](.*)$", ks)
        if m and arg_names:
            i = int(m.group(1))
            nm = arg_names[i] if i < len(arg_names) else f"arg{i}"
            names.append(nm + m.group(2))
        else:
            names.append(ks)
    return names


def audit_traced(traced, *, program: str = "program",
                 arg_names: Optional[Sequence[str]] = None,
                 hlo_text: Optional[str] = None,
                 check_donation: bool = True, **thresholds) -> AuditReport:
    """Audit a ``jax.jit(...).trace(...)`` result: the jaxpr plus jax's
    own per-leaf donation flags (``args_info``)."""
    import jax
    flat_info = jax.tree_util.tree_leaves(traced.args_info)
    donated = [bool(getattr(a, "donated", False)) for a in flat_info]
    names = _names_from_args_info(traced.args_info, arg_names)
    rep = audit_jaxpr(traced.jaxpr, program=program, in_names=names,
                      donated=donated, check_donation=check_donation,
                      **thresholds)
    if hlo_text is not None:
        rep.hlo_collectives = hlo_collective_inventory(hlo_text)
        rep.hlo_kernels = hlo_kernel_inventory(hlo_text)
    return rep


def audit_fn(fn, args: Sequence, *, donate_argnums=(), program=None,
             arg_names: Optional[Sequence[str]] = None,
             include_hlo: bool = False, check_donation: bool = True,
             **thresholds) -> AuditReport:
    """Audit any jittable function against example args (arrays or
    ``jax.ShapeDtypeStruct`` avals — nothing is executed)."""
    import jax
    jitted = jax.jit(fn, donate_argnums=tuple(donate_argnums))
    traced = jitted.trace(*args)
    hlo = None
    if include_hlo:
        try:
            hlo = traced.lower().compile().as_text()
        except Exception:   # backend can't compile (e.g. TPU-only ops)
            hlo = None
    return audit_traced(
        traced, program=program or getattr(fn, "__name__", "program"),
        arg_names=arg_names, hlo_text=hlo,
        check_donation=check_donation, **thresholds)
