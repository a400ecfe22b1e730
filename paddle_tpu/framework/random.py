"""RNG state.

TPU-native re-design of the reference's stateful generators
(reference: paddle/fluid/framework/generator.h:119 DefaultCPUGenerator,
:126 GetDefaultCUDAGenerator — std::mt19937_64 / curand states).

JAX randomness is functional (explicit keys). To preserve the reference's
*stateful* API (``paddle.seed``, ops drawing fresh numbers each call) we
keep a process-global key and split it on every draw. Inside ``jax.jit``
traces the split still works (the key is a traced value only if captured;
here it is a host-side constant per trace, matching dygraph semantics).
"""
from __future__ import annotations

import threading

import jax

__all__ = ["seed", "get_rng_state", "set_rng_state", "split_key", "Generator"]

_lock = threading.Lock()
# Lazily initialised: creating a PRNGKey initializes the device backend,
# which must not happen at import time (a chip belongs to one process;
# importing the package must not claim it).
_KEY = None

# When a functional trace is active (jit/to_static), random ops split from
# a *traced* key passed per call instead of the host-side global state, so
# dropout/noise stay fresh across compiled steps (the reference's analog:
# seed attrs on dropout ops + per-op curand states).
_trace = threading.local()


class use_key:
    """Context: route split_key() to a traced key (functional RNG)."""

    def __init__(self, key):
        self._key = key

    def __enter__(self):
        self._prev = getattr(_trace, "key", None)
        _trace.key = self._key
        return self

    def __exit__(self, *exc):
        _trace.key = self._prev
        return False


def _impl():
    """Resolve FLAGS_rng_impl: TPU gets the hardware rng-bit-generator
    (threefry measured at 33% of a BERT-base train step on a v5e; rbg
    ~6%), other backends keep threefry."""
    from . import flags
    choice = getattr(flags.FLAGS, "rng_impl", "auto")
    if choice != "auto":
        return choice
    from ..distributed.mesh import target_platform
    return "rbg" if target_platform() == "tpu" else "threefry2x32"


def make_key(s: int):
    """One place every PRNGKey is minted: impl-aware (FLAGS_rng_impl).
    Returns a TYPED key (jax.random.key) so the impl travels with the
    value through split/fold_in regardless of the global default."""
    return jax.random.key(int(s) & 0xFFFFFFFF, impl=_impl())


def _key():
    global _KEY
    if _KEY is None:
        _KEY = make_key(0)
    return _KEY


# bumped by every seed(); consumers holding derived device-resident key
# chains (fleet/dist_step.py) compare epochs to notice a re-seed and
# re-mint their chain from the new global stream
_EPOCH = 0


def rng_epoch() -> int:
    return _EPOCH


def seed(s: int):
    """Reset the global RNG. Mirrors paddle.seed."""
    global _KEY, _EPOCH
    with _lock:
        _KEY = make_key(s)
        _EPOCH += 1
    return Generator(_KEY)


def split_key(num: int = 1):
    """Draw ``num`` fresh subkeys, advancing global (or trace-local) state."""
    tk = getattr(_trace, "key", None)
    if tk is not None:
        keys = jax.random.split(tk, num + 1)
        _trace.key = keys[0]
        subs = keys[1:]
        return subs[0] if num == 1 else list(subs)
    global _KEY
    with _lock:
        keys = jax.random.split(_key(), num + 1)
        _KEY = keys[0]
        subs = keys[1:]
    return subs[0] if num == 1 else list(subs)


def key_to_data(key):
    """Typed key -> serializable uint32 ndarray (np.save-able)."""
    import numpy as np
    try:
        return np.asarray(jax.random.key_data(key))
    except TypeError:       # already raw key data
        return np.asarray(key)


def data_to_key(data):
    """Inverse of key_to_data. The impl is inferred from the data shape
    (threefry keys are uint32[2], rbg uint32[4]) so states saved under
    one FLAGS_rng_impl restore correctly under another."""
    if hasattr(data, "dtype") and str(data.dtype).startswith("key"):
        return data            # already typed
    import numpy as np
    arr = np.asarray(data)
    impl = {2: "threefry2x32", 4: "rbg"}.get(arr.shape[-1], _impl())
    return jax.random.wrap_key_data(jax.numpy.asarray(arr), impl=impl)


def get_rng_state():
    """Serializable RNG state (uint32 ndarray — np.save/pickle safe)."""
    return key_to_data(_key())


def set_rng_state(state):
    global _KEY, _EPOCH
    with _lock:
        _KEY = data_to_key(state)
        _EPOCH += 1


class Generator:
    """Per-stream generator (parity surface with framework/generator.h)."""

    def __init__(self, key=None):
        self._key = key if key is not None else make_key(0)

    def manual_seed(self, s: int):
        self._key = make_key(s)
        return self

    def split(self, num: int = 1):
        keys = jax.random.split(self._key, num + 1)
        self._key = keys[0]
        return keys[1] if num == 1 else list(keys[1:])

    @property
    def state(self):
        return self._key
