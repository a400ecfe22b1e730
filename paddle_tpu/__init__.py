"""paddle_tpu — a TPU-native deep-learning framework.

Brand-new framework with the capability surface of the reference
(PaddlePaddle ~v2.0, /root/reference/), re-designed for TPU:

- eager ("dygraph") mode runs each op through XLA with a vjp-recorded
  autograd tape (framework/core.py);
- static mode is ``jax.jit`` tracing of the same code (jit/to_static) —
  the ProgramDesc IR of the reference collapses into jaxpr/StableHLO;
- distributed training is sharding annotations over a ``jax.sharding.Mesh``
  (data/tensor/pipeline/sequence/expert axes) with XLA ICI collectives,
  replacing NCCL rings, graph-rewrite meta-optimizers and SSA executors;
- the parameter-server sparse path is a host-side embedding service.

Top-level API mirrors ``paddle.*`` so reference user code ports by
changing the import.
"""
from __future__ import annotations

# the version names the API surface implemented (reference
# parity target ~v2.0), so utils.require_version gates pass
__version__ = "2.0.0"

from .framework import (  # noqa: F401
    CPUPlace, CUDAPinnedPlace, CUDAPlace, Place, TPUPlace, XPUPlace,
    Tensor, device_count, enable_grad, get_device, grad,
    get_cudnn_version, is_compiled_with_cuda, is_compiled_with_tpu,
    is_compiled_with_xpu,
    is_grad_enabled, no_grad, seed, set_device, set_grad_enabled, to_tensor,
    get_flags, set_flags, set_printoptions, ParamAttr,
)
from .framework.dtype import (  # noqa: F401
    bfloat16, bool, complex64, complex128, dtype, finfo, float16, float32,
    float64, iinfo, int8, int16, int32, int64, uint8,
    is_floating_point, is_integer,
)
from .tensor import *  # noqa: F401,F403
from .tensor import __all__ as _tensor_all
from .tensor import linalg  # noqa: F401  (paddle.linalg namespace)
from .tensor.array import (  # noqa: F401
    array_length, array_read, array_write, create_array)

from . import framework  # noqa: F401

# subpackages import lazily-tolerant: during the staged build some may not
# exist yet; once present they are first-class members of the namespace.
import importlib as _importlib

_SUBPACKAGES = [
    "amp", "autograd", "device", "distribution", "distributed", "hapi",
    "inference", "io",
    "jit", "metric", "nn", "observability", "onnx", "optimizer",
    "profiler", "quantization",
    "rec", "regularizer", "static", "sysconfig", "text", "utils", "vision",
    "incubate",
]

for _pkg in _SUBPACKAGES:
    try:
        globals()[_pkg] = _importlib.import_module(f".{_pkg}", __name__)
    except ModuleNotFoundError as _e:
        # tolerate only the subpackage itself being absent (staged build);
        # broken internals must surface
        if _e.name != f"{__name__}.{_pkg}":
            raise

if "io" in globals() and hasattr(globals().get("framework"), "io"):
    try:
        from .framework.io import load, save  # noqa: F401
    except ModuleNotFoundError:
        pass
if "hapi" in globals():
    from .hapi import Model, flops, summary  # noqa: F401
    from .hapi import callbacks  # noqa: F401
if "distributed" in globals():
    from .distributed.parallel import DataParallel  # noqa: F401

from . import train_guard  # noqa: F401
from .train_guard import NumericalDivergence, TrainGuard  # noqa: F401

# paddle-compat mode toggles: the reference flips between dygraph and
# static graph globally; here "static" only changes default tracing hints,
# since jit tracing subsumes the static graph.
_static_mode = False


def enable_static():
    global _static_mode
    _static_mode = True


def disable_static():
    global _static_mode
    _static_mode = False


def in_dynamic_mode() -> bool:
    return not _static_mode


def is_grad_enabled_():  # legacy alias
    return is_grad_enabled()


def disable_signal_handler():
    """No-op: the reference installs C++ signal handlers (platform/init.cc);
    JAX runtime handles its own."""


def set_default_dtype(d):
    from .framework import dtype as _d
    global _default_dtype
    _default_dtype = _d.convert_dtype(d)


def get_default_dtype():
    return globals().get("_default_dtype", "float32")


def summary_(*a, **k):  # placeholder to avoid name clash
    raise NotImplementedError


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """Parity: paddle.create_parameter (fluid/layers/tensor.py:97)."""
    from .nn.layer.layers import create_parameter as _cp
    return _cp(shape, dtype, attr=attr, is_bias=is_bias,
               default_initializer=default_initializer)


def get_cuda_rng_state():
    """CUDA-era API (reference fluid/framework.py); maps to the seeded
    jax key streams so checkpoint scripts round-trip."""
    from .framework import random as _r
    return _r.get_rng_state()


def set_cuda_rng_state(state):
    from .framework import random as _r
    _r.set_rng_state(state)


# ---------------------------------------------------------------------
# legacy compat surface (reference python/paddle/__init__.py exports)
# ---------------------------------------------------------------------
VarBase = Tensor   # pre-2.0 name for the eager tensor (imperative/层)


def in_dygraph_mode() -> bool:
    """Always True: this framework is eager-first (jit/to_static trace
    on demand), the reference's dygraph mode."""
    return True


def enable_dygraph(place=None):
    """No-op: dygraph is the only eager mode here."""
    return None


def disable_dygraph():
    """No-op with a loud contract: static-graph building collapses into
    tracing shims (paddle_tpu.static); there is no global mode bit."""
    return None


def monkey_patch_math_varbase():
    """No-op (reference patches Tensor operators at import; ours are
    defined directly on the class)."""
    return None


def monkey_patch_variable():
    """No-op (static Variable shims already carry the tensor surface)."""
    return None


def crop_tensor(x, shape=None, offsets=None, name=None):
    """Reference fluid.layers.crop_tensor (operators/crop_tensor_op.cc):
    slice ``shape``-sized region starting at ``offsets`` (defaults 0)."""
    import numpy as _np
    v = x._value if isinstance(x, Tensor) else _np.asarray(x)
    nd = v.ndim
    if shape is None:
        shape = list(v.shape)
    shape = [int(s.numpy()) if isinstance(s, Tensor) else int(s)
             for s in (shape.numpy() if isinstance(shape, Tensor)
                       else shape)]
    offsets = [0] * nd if offsets is None else [
        int(o.numpy()) if isinstance(o, Tensor) else int(o)
        for o in (offsets.numpy() if isinstance(offsets, Tensor)
                  else offsets)]
    shape = [v.shape[i] - offsets[i] if s == -1 else s
             for i, s in enumerate(shape)]
    import builtins
    sl = tuple(builtins.slice(o, o + s) for o, s in zip(offsets, shape))
    return x[sl] if isinstance(x, Tensor) else Tensor(v[sl])
