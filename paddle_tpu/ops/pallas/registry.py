"""Kernel registry/dispatch for the Pallas tier (ISSUE 13 tentpole).

Every kernel in ``paddle_tpu/ops/pallas/`` registers three things:

- a **pallas implementation** (``pallas_fn(*args, interpret=..., **kw)``)
  — the hand-tiled TPU kernel, also runnable under the Pallas
  interpreter so parity tests stay green on the CPU backend;
- an **XLA reference** (``xla_ref_fn``) — the plain-jnp implementation
  that is simultaneously the fallback path and the parity oracle (the
  per-kernel tolerance is documented on the registration and pinned by
  an always-on tier-1 test);
- an optional **eligibility gate** — static shape/dtype constraints the
  *compiled* kernel needs (tile divisibility, supported head dims).
  Ineligible calls fall back to the XLA reference and are counted as
  ``fallback`` so a silent downgrade is observable.

Mode resolution per kernel, first match wins:

1. a process-local :func:`set_mode` override (tests, A/B benches);
2. ``PADDLE_PALLAS_<KERNEL>`` env (``pallas | xla_ref | interpret``);
3. ``PADDLE_PALLAS=0`` — the global escape hatch: everything runs the
   XLA reference;
4. default: the kernel's ``tpu_default`` (``pallas`` unless the
   registration says otherwise) when the programs being compiled
   target a TPU (``distributed.mesh.target_platform`` — the installed
   mesh's devices, else the default backend), ``xla_ref`` elsewhere.

Dispatch counters: python-side per-(kernel, path) counts prove which
implementation actually ran — mirrored into the always-on labeled
``pallas_dispatch{kernel=,path=}`` counter on ``/metrics``.  Note the
counters tick when the *python* dispatch runs: once per call for eager
callers (the elastic host loop), once per **trace** for dispatches
inside a jitted program (the paged-attention path inside the serving
engine's compiled decode step) — either way a nonzero count is proof
the path was selected, and a count that stays flat across steady-state
calls of a jitted caller is the no-retrace proof the bench asserts.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

__all__ = ["KernelSpec", "register", "kernels", "resolve", "set_mode",
           "dispatch", "note", "dispatch_counts",
           "reset_dispatch_counts", "MODES"]

MODES = ("pallas", "xla_ref", "interpret")


@dataclass
class KernelSpec:
    """One registered kernel: implementations + documented tolerance."""

    name: str
    pallas_fn: Callable
    xla_ref_fn: Callable
    tolerance: str                    # parity bound vs the XLA reference
    eligible_fn: Optional[Callable] = None
    doc: str = ""
    # mode on a TPU target when nothing overrides it: "xla_ref" parks a
    # kernel Mosaic cannot lower yet behind its reference instead of
    # raising at trace time in every caller
    tpu_default: str = "pallas"


_REGISTRY: Dict[str, KernelSpec] = {}
_OVERRIDES: Dict[str, str] = {}
_COUNTS: Dict[str, Dict[str, int]] = {}
_lock = threading.Lock()


def register(name: str, pallas_fn: Callable, xla_ref_fn: Callable, *,
             tolerance: str, eligible: Optional[Callable] = None,
             doc: str = "", tpu_default: str = "pallas") -> KernelSpec:
    if tpu_default not in ("pallas", "xla_ref"):
        raise ValueError(
            f"tpu_default must be 'pallas' or 'xla_ref', got "
            f"{tpu_default!r}")
    spec = KernelSpec(name=name, pallas_fn=pallas_fn,
                      xla_ref_fn=xla_ref_fn, tolerance=tolerance,
                      eligible_fn=eligible, doc=doc,
                      tpu_default=tpu_default)
    with _lock:
        _REGISTRY[name] = spec
        _COUNTS.setdefault(name, {})
    return spec


def kernels() -> Dict[str, KernelSpec]:
    """The registered kernel table (name -> spec) — the README
    tolerance table and the bench ``kernels`` metric iterate this."""
    with _lock:
        return dict(_REGISTRY)


def set_mode(name: str, mode: Optional[str]):
    """Process-local mode override (``None`` clears it)."""
    if mode is not None and mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    with _lock:
        if mode is None:
            _OVERRIDES.pop(name, None)
        else:
            _OVERRIDES[name] = mode


def resolve(name: str) -> str:
    """Resolve the execution mode for ``name`` (see module docstring)."""
    with _lock:
        ov = _OVERRIDES.get(name)
    if ov is not None:
        return ov
    env = os.environ.get("PADDLE_PALLAS_" + name.upper())
    if env:
        if env not in MODES:
            raise ValueError(
                f"PADDLE_PALLAS_{name.upper()}={env!r}: must be one of "
                f"{MODES}")
        return env
    if os.environ.get("PADDLE_PALLAS", "1") == "0":
        return "xla_ref"
    from ...distributed.mesh import target_platform
    if target_platform() != "tpu":
        return "xla_ref"
    spec = _REGISTRY.get(name)
    return spec.tpu_default if spec is not None else "pallas"


def note(name: str, path: str):
    """Record a dispatch on ``path`` for a kernel that routes itself
    (flash attention's custom-vjp entry point cannot go through
    :func:`dispatch`, but its counters must tell the same story)."""
    from ...framework import monitor as _monitor
    with _lock:
        d = _COUNTS.setdefault(name, {})
        d[path] = d.get(path, 0) + 1
    _monitor.stat_add("pallas_dispatch",
                      labels={"kernel": name, "path": path})


def dispatch(name: str, *args, mode: Optional[str] = None, **kwargs):
    """Resolve + count + run one kernel call.

    ``pallas`` mode falls back to the XLA reference (counted as
    ``fallback``) when the eligibility gate rejects the shapes —
    ``interpret`` mode has no tile constraints and never falls back.

    ``mode`` pre-empts :func:`resolve` — callers whose surrounding jit
    cache must key on the mode (the quantization layers' ``_apply``
    closures) resolve it OUTSIDE the traced function and bind it as a
    closure default, then pass it here; otherwise a mode switch after
    the first trace would silently replay the old path.
    """
    spec = _REGISTRY.get(name)
    if spec is None:
        raise KeyError(f"unknown pallas kernel {name!r}; registered: "
                       f"{sorted(_REGISTRY)}")
    if mode is None:
        mode = resolve(name)
    elif mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if mode == "xla_ref":
        note(name, "xla_ref")
        return spec.xla_ref_fn(*args, **kwargs)
    if mode == "pallas" and spec.eligible_fn is not None \
            and not spec.eligible_fn(*args, **kwargs):
        note(name, "fallback")
        return spec.xla_ref_fn(*args, **kwargs)
    note(name, mode)
    return spec.pallas_fn(*args, interpret=(mode == "interpret"),
                          **kwargs)


def dispatch_counts(name: Optional[str] = None) -> Dict:
    with _lock:
        if name is not None:
            return dict(_COUNTS.get(name, {}))
        return {k: dict(v) for k, v in _COUNTS.items()}


def reset_dispatch_counts(name: Optional[str] = None):
    with _lock:
        if name is None:
            for d in _COUNTS.values():
                d.clear()
        else:
            _COUNTS.get(name, {}).clear()
