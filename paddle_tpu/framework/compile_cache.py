"""The one place the persistent XLA compile cache is located.

Every owner of compiled programs (``DistributedTrainStep``,
``GenerationServer.start``, ``inference.Predictor``) and the two drivers
(``perfbench/run.py``, ``chip_smoke.py``) call :func:`ensure_compile_cache`
before their first compile.  The location rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it by itself — this
  module never overrides it, whatever a caller passes;
- unset: ``path`` if the caller gave one (``Config.set_optim_cache_dir``),
  else a FIXED directory inside the checkout (``<repo>/.jax_cache``,
  git-ignored).  The path is part of the cache key, so a directory that
  moves between runs (a temp dir, a pid, a timestamp) never hits.

First caller wins for the process: JAX's cache location is process
state, and re-pointing it mid-run would split one run's programs over
two directories.
"""
from __future__ import annotations

import os
import threading
from typing import Optional

__all__ = ["ensure_compile_cache", "cache_dir", "cache_entries",
           "DEFAULT_CACHE_DIR"]

# <checkout>/.jax_cache — three levels up from this file
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = threading.Lock()
_enabled: Optional[str] = None


def ensure_compile_cache(path: Optional[str] = None) -> str:
    """Enable JAX's persistent compilation cache (idempotent) and
    return the directory it lives in — see the module docstring for
    the location rule."""
    global _enabled
    with _lock:
        if _enabled is not None:
            return _enabled
        import jax
        from jax._src import compilation_cache as _cc
        env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if env:
            d = env        # JAX already reads it; set no location
        else:
            d = os.path.abspath(path or DEFAULT_CACHE_DIR)
            os.makedirs(d, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", d)
        # cache every program: the defaults skip sub-second compiles,
        # which would make "a second run adds no entries" depend on
        # which side of one second a compile happened to land
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        # a compile BEFORE this call froze JAX's lazily-initialized
        # cache in its disabled state (the init is memoized); reset so
        # the caller's compiles actually persist
        _cc.reset_cache()
        _enabled = d
        return d


def cache_dir() -> Optional[str]:
    """The live cache directory (None until the first
    :func:`ensure_compile_cache`)."""
    return _enabled


def cache_entries(path: Optional[str] = None) -> int:
    """Number of serialized executables under ``path`` (default: the
    live cache directory)."""
    d = path or _enabled
    if not d or not os.path.isdir(d):
        return 0
    return sum(1 for f in os.listdir(d) if f.endswith("-cache"))
