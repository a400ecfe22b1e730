"""paddle_tpu.native — C++ runtime components built lazily per host.

The reference ships ~500k LoC of C++ for kernels + runtime; under XLA the
kernel side collapses, but the host runtime around the TPU (sparse
parameter server tables, high-QPS data ingest) stays genuinely native.
These are compiled on first use with the host toolchain (g++) into
``_build/`` — never committed, and keyed on source + build flags + host
CPU, so a tree copied to another machine rebuilds instead of loading a
foreign ``-march=native`` binary.

pybind11 is not available in this image; the ABI is plain C loaded via
ctypes (see each .cc file's ``extern "C"`` block).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading

__all__ = ["load_library", "NativeBuildError"]

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_CACHE = {}


class NativeBuildError(RuntimeError):
    pass


def _build_dir() -> str:
    d = os.environ.get("PADDLE_TPU_NATIVE_CACHE")
    if not d:
        d = os.path.join(_SRC_DIR, "_build")
    os.makedirs(d, exist_ok=True)
    return d


# -march=native makes the artifact host-specific, and -ffp-contract=off
# is a numerics contract (the SIMD fused-push path, ISSUE 16, is
# bit-exact with the scalar path only if neither may contract a*b+c
# into an FMA) — both therefore belong in the artifact key
_CXXFLAGS = ("-O3", "-march=native", "-ffp-contract=off", "-std=c++17",
             "-shared", "-fPIC", "-pthread")


def _host_cpu() -> str:
    """What ``-march=native`` resolved against: ISA + CPU model + its
    feature flags.  Part of the artifact key, so a ``_build/`` directory
    carried to a machine with another CPU (the chip tool copies the
    tree as it stands) is rebuilt there, never dlopen'ed."""
    ident = {"machine": platform.machine()}
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:      # first processor's entries
                key, _, val = line.partition(":")
                key = key.strip()
                if key in ("model name", "flags", "Features"):
                    ident.setdefault(key, val.strip())
                elif not line.strip() and len(ident) > 1:
                    break       # end of the first processor block
    except OSError:
        ident["processor"] = platform.processor()
    return repr(sorted(ident.items()))


def load_library(name: str) -> ctypes.CDLL:
    """Compile ``<name>.cc`` (if no artifact matches this source, these
    flags and this host CPU) and dlopen it.  Raises
    :class:`NativeBuildError` when the toolchain is missing or the
    build fails — a caller that wants the pure-Python implementation
    asks for it (``SparseTable(backend="python")``); it is never
    substituted because ``g++`` happened to be absent."""
    with _LOCK:
        if name in _CACHE:
            return _CACHE[name]
        src = os.path.join(_SRC_DIR, f"{name}.cc")
        cxx = os.environ.get("CXX", "g++")
        h = hashlib.sha256()
        with open(src, "rb") as f:
            h.update(f.read())
        h.update(" ".join((cxx,) + _CXXFLAGS).encode())
        h.update(_host_cpu().encode())
        out = os.path.join(_build_dir(),
                           f"{name}-{h.hexdigest()[:16]}.so")
        if not os.path.exists(out):
            # per-process temp name: concurrent workers with a cold cache
            # must not os.replace a half-written .so over each other
            tmp = f"{out}.{os.getpid()}.tmp"
            cmd = [cxx, *_CXXFLAGS, src, "-o", tmp]
            try:
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=300)
                if r.returncode != 0:
                    # -march=native can be rejected on exotic hosts
                    r = subprocess.run(
                        [c for c in cmd if c != "-march=native"],
                        capture_output=True, text=True, timeout=300)
            except (OSError, subprocess.TimeoutExpired) as e:
                raise NativeBuildError(
                    f"building {name}.cc: C++ toolchain unavailable "
                    f"({e})") from e
            if r.returncode != 0:
                raise NativeBuildError(
                    f"building {name}.cc failed:\n{r.stderr[-4000:]}")
            os.replace(tmp, out)
        lib = ctypes.CDLL(out)
        _CACHE[name] = lib
        return lib


def ps_core() -> ctypes.CDLL:
    """The sparse-table core (ps_core.cc) with argtypes declared."""
    lib = load_library("ps_core")
    if getattr(lib, "_pts_ready", False):
        return lib
    c = ctypes
    i64p = c.POINTER(c.c_int64)
    f32p = c.POINTER(c.c_float)
    lib.pts_create.restype = c.c_void_p
    lib.pts_create.argtypes = [c.c_int, c.c_int, c.c_float, c.c_float,
                               c.c_float, c.c_float, c.c_float, c.c_uint64,
                               c.c_int]
    lib.pts_free.argtypes = [c.c_void_p]
    lib.pts_set_lr.argtypes = [c.c_void_p, c.c_float]
    lib.pts_version.restype = c.c_uint64
    lib.pts_version.argtypes = [c.c_void_p]
    lib.pts_set_version.argtypes = [c.c_void_p, c.c_uint64]
    lib.pts_set_entry.argtypes = [c.c_void_p, c.c_int, c.c_double]
    lib.pts_pull.argtypes = [c.c_void_p, i64p, c.c_int64, f32p]
    lib.pts_push.argtypes = [c.c_void_p, i64p, c.c_int64, f32p]
    lib.pts_push_delta.argtypes = [c.c_void_p, i64p, c.c_int64, f32p]
    lib.pts_size.restype = c.c_int64
    lib.pts_size.argtypes = [c.c_void_p]
    lib.pts_export.restype = c.c_int64
    lib.pts_export.argtypes = [c.c_void_p, i64p, f32p, c.c_int64]
    lib.pts_entry_export.restype = c.c_int64
    lib.pts_entry_export.argtypes = [c.c_void_p, c.c_int, i64p, i64p,
                                     c.c_int64]
    lib.pts_entry_import.argtypes = [c.c_void_p, i64p, c.c_int64, i64p,
                                     i64p, c.c_int64]
    lib.pts_import.argtypes = [c.c_void_p, i64p, c.c_int64, f32p]
    lib.pts_stride.restype = c.c_int
    lib.pts_stride.argtypes = [c.c_void_p]
    lib.pts_export_full.restype = c.c_int64
    lib.pts_export_full.argtypes = [c.c_void_p, i64p, f32p, c.c_int64]
    lib.pts_import_full.argtypes = [c.c_void_p, i64p, c.c_int64, f32p]
    lib.pts_clear.argtypes = [c.c_void_p]
    # feature lifecycle (ISSUE 14)
    lib.pts_set_clock.argtypes = [c.c_void_p, c.c_uint64]
    lib.pts_touch_all.argtypes = [c.c_void_p, c.c_uint64]
    lib.pts_admitted_total.restype = c.c_uint64
    lib.pts_admitted_total.argtypes = [c.c_void_p]
    lib.pts_evicted_total.restype = c.c_uint64
    lib.pts_evicted_total.argtypes = [c.c_void_p]
    lib.pts_slots.restype = c.c_int64
    lib.pts_slots.argtypes = [c.c_void_p]
    lib.pts_ttl_sweep.restype = c.c_int64
    lib.pts_ttl_sweep.argtypes = [c.c_void_p, c.c_uint64, i64p, c.c_int64]
    lib.pts_evict.restype = c.c_int64
    lib.pts_evict.argtypes = [c.c_void_p, i64p, c.c_int64]
    lib.pts_set_vals.argtypes = [c.c_void_p, i64p, c.c_int64, f32p]
    lib.ps_segsum_inv.argtypes = [i64p, c.c_int64, c.c_int, f32p, f32p]
    # tiered spill + zero-copy pull + int8 wire + geo stamps (ISSUE 16)
    u64p = c.POINTER(c.c_uint64)
    i32p = c.POINTER(c.c_int32)
    i8p = c.POINTER(c.c_int8)
    lib.pts_simd_available.restype = c.c_int
    lib.pts_simd_available.argtypes = []
    lib.pts_set_simd.argtypes = [c.c_int]
    lib.pts_enable_spill.restype = c.c_int
    lib.pts_enable_spill.argtypes = [c.c_void_p, c.c_char_p]
    lib.pts_spill_enabled.restype = c.c_int
    lib.pts_spill_enabled.argtypes = [c.c_void_p]
    lib.pts_spill_sweep.restype = c.c_int64
    lib.pts_spill_sweep.argtypes = [c.c_void_p, c.c_uint64]
    lib.pts_spill_recover.restype = c.c_int64
    lib.pts_spill_recover.argtypes = [c.c_void_p, c.c_char_p]
    lib.pts_spill_stats.argtypes = [c.c_void_p, u64p]
    lib.pts_spill_advise.argtypes = [c.c_void_p]
    lib.pts_pin_read.argtypes = [c.c_void_p]
    lib.pts_unpin_read.argtypes = [c.c_void_p]
    lib.pts_resolve.argtypes = [c.c_void_p, i64p, c.c_int64, u64p]
    lib.pts_pull_plan.restype = c.c_int64
    lib.pts_pull_plan.argtypes = [c.c_void_p, i64p, c.c_int64, i32p, u64p]
    lib.pts_sendv_addrs.restype = c.c_int64
    lib.pts_sendv_addrs.argtypes = [
        c.c_int, u64p, c.c_int64, c.c_int64, c.c_void_p, c.c_int64,
        c.c_void_p, c.c_int64, c.c_int64]
    lib.pts_pull_q8.argtypes = [c.c_void_p, i64p, c.c_int64, i8p, f32p]
    lib.pts_geo_get.argtypes = [c.c_void_p, i64p, c.c_int64, i64p, i32p]
    lib.pts_geo_put.argtypes = [c.c_void_p, i64p, c.c_int64, i64p, i32p]
    lib.pts_geo_export.restype = c.c_int64
    lib.pts_geo_export.argtypes = [c.c_void_p, i64p, i64p, i32p, c.c_int64]
    lib._pts_ready = True
    return lib


def datafeed() -> ctypes.CDLL:
    """The MultiSlot ingest core (datafeed.cc) with argtypes declared."""
    lib = load_library("datafeed")
    if getattr(lib, "_dfd_ready", False):
        return lib
    c = ctypes
    u8p = c.POINTER(c.c_uint8)
    u64p = c.POINTER(c.c_uint64)
    i64p = c.POINTER(c.c_int64)
    f32p = c.POINTER(c.c_float)
    lib.dfd_create.restype = c.c_void_p
    lib.dfd_create.argtypes = [c.c_int, u8p]
    lib.dfd_free.argtypes = [c.c_void_p]
    lib.dfd_load.restype = c.c_int64
    lib.dfd_load.argtypes = [c.c_void_p, c.POINTER(c.c_char_p), c.c_int,
                             c.c_int]
    lib.dfd_size.restype = c.c_int64
    lib.dfd_size.argtypes = [c.c_void_p]
    lib.dfd_shuffle.argtypes = [c.c_void_p, c.c_uint64]
    lib.dfd_partition.argtypes = [c.c_void_p, c.c_int, c.c_int]
    lib.dfd_view_size.restype = c.c_int64
    lib.dfd_view_size.argtypes = [c.c_void_p]
    lib.dfd_batch_sizes.restype = c.c_int
    lib.dfd_batch_sizes.argtypes = [c.c_void_p, c.c_int64, c.c_int, i64p]
    lib.dfd_batch_sparse.argtypes = [c.c_void_p, c.c_int64, c.c_int,
                                     c.c_int, u64p, i64p]
    lib.dfd_batch_dense.argtypes = [c.c_void_p, c.c_int64, c.c_int, c.c_int,
                                    c.c_int, f32p]
    lib.dfd_release.argtypes = [c.c_void_p]
    lib._dfd_ready = True
    return lib
