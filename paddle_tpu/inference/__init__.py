"""paddle_tpu.inference — deployment API over exported StableHLO.

Parity target: the reference inference engine
(reference: paddle/fluid/inference/api/analysis_predictor.h:82
AnalysisPredictor, paddle_analysis_config.h AnalysisConfig,
python/paddle/inference/).  The reference loads a serialized ProgramDesc,
runs ~100 IR analysis passes (fusion, memory optim, TensorRT subgraph
capture) and executes op-by-op with zero-copy feed/fetch
(analysis_predictor.cc:168 init, :215 PrepareProgram, :231
OptimizeInferenceProgram, ZeroCopyRun).

TPU-native collapse: the serialized artifact is StableHLO (written by
``paddle_tpu.jit.save``), so the entire analysis/optimization pipeline is
XLA compilation — fusion, layout, memory planning happen at load time via
``jax.jit`` of the deserialized function.  What remains for this layer is
the deployment surface: Config (device/precision knobs), Predictor with
named zero-copy input/output handles, and batch-size-polymorphic
execution (the export uses symbolic batch dims, so one artifact serves
any batch size — the reference needs TensorRT dynamic-shape profiles for
that).
"""
from __future__ import annotations

import os
import pickle
import time as _time
from typing import Dict, List, Optional

import numpy as np

from ..framework.compile_cache import ensure_compile_cache
from ..observability import flight_recorder as _flight

__all__ = ["Config", "Predictor", "Tensor", "create_predictor",
           "PrecisionType", "PlaceType", "get_version",
           "PredictorServer", "GenerationServer", "GenerationStream",
           "PrefixCache", "ServeError", "ServerOverloaded",
           "UpstreamUnavailable", "ServerClosed", "RequestTimeout",
           "ServerDraining", "GatewayRouter", "LocalReplica",
           "RemoteReplica", "GenerationRpcServer", "ReplicaLost",
           "MigrationUnsupported"]


def get_version() -> str:
    from .. import __version__
    return __version__


class PrecisionType:
    """Parity: paddle_analysis_config.h Precision enum."""
    Float32 = 0
    Half = 1      # on TPU: bfloat16 (MXU-native), not IEEE fp16
    Bfloat16 = 1
    Int8 = 2


class PlaceType:
    kUNK = -1
    kCPU = 0
    kGPU = 1   # accepted for API compat; maps to the accelerator (TPU)
    kTPU = 2
    kXPU = 3


class Config:
    """Inference config (parity: AnalysisConfig,
    reference paddle/fluid/inference/api/paddle_analysis_config.h).

    Accepts ``Config(model_dir)`` or ``Config(prog_file, params_file)``
    like the reference; here both name the ``jit.save`` path prefix
    (``<prefix>.pdmodel`` + ``<prefix>.pdiparams``).
    """

    def __init__(self, model_arg: Optional[str] = None,
                 params_file: Optional[str] = None):
        self._model_dir = None
        self._prog_file = None
        self._params_file = None
        if model_arg is not None and params_file is not None:
            self._prog_file = model_arg
            self._params_file = params_file
        elif model_arg is not None:
            self._model_dir = model_arg
        self._use_accelerator = True      # TPU by default when present
        self._device_id = 0
        self._precision = PrecisionType.Float32
        self._ir_optim = True             # recorded; XLA always optimizes
        self._memory_optim = True
        self._cpu_math_threads = 1
        self._enable_profile = False
        self._donate_inputs = False
        # persistent XLA compile cache location (reference API name:
        # AnalysisConfig::SetOptimCacheDir — there it caches optimized
        # IR programs, here serialized XLA executables).  None: the
        # framework's fixed default (framework/compile_cache.py).  A
        # second process cold-loads its compiled program from the cache
        # instead of re-running XLA.
        self._optim_cache_dir = None
        self._load_batch = 1              # batch the load-time AOT uses

    # -- model paths -------------------------------------------------
    def set_model(self, model_arg, params_file=None):
        self._model_dir = self._prog_file = self._params_file = None
        if params_file is not None:
            self._prog_file = model_arg
            self._params_file = params_file
        else:
            self._model_dir = model_arg

    def model_dir(self):
        return self._model_dir

    def prog_file(self):
        return self._prog_file

    def params_file(self):
        return self._params_file

    def _path_prefix(self):
        p = self._model_dir if self._model_dir is not None else self._prog_file
        if p is None:
            raise ValueError("Config has no model path; pass Config(path) "
                             "or use set_model()")
        # accept ".pdmodel" file path, a bare prefix, or a directory
        if p.endswith(".pdmodel"):
            return p[:-len(".pdmodel")]
        if os.path.isdir(p):
            cands = sorted(f for f in os.listdir(p)
                           if f.endswith(".pdmodel"))
            if not cands:
                raise FileNotFoundError(f"no .pdmodel under {p}")
            if len(cands) > 1:
                raise ValueError(
                    f"ambiguous model dir {p}: {cands}; pass the .pdmodel "
                    "path explicitly")
            return os.path.join(p, cands[0][:-len(".pdmodel")])
        return p

    def _params_path(self):
        """Params file: the explicit Config(prog, params) path wins,
        else <prefix>.pdiparams."""
        if self._params_file is not None:
            return self._params_file
        return self._path_prefix() + ".pdiparams"

    # -- device ------------------------------------------------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        # API-compat name; selects the accelerator (TPU). Memory pool
        # size is meaningless under XLA's allocator — recorded only.
        _warn_inert("enable_use_gpu",
                    "maps to the TPU accelerator; memory_pool_init_size"
                    "_mb is ignored (XLA owns device memory)")
        self._use_accelerator = True
        self._device_id = device_id

    def enable_use_tpu(self, device_id=0):
        self._use_accelerator = True
        self._device_id = device_id

    def disable_gpu(self):
        self._use_accelerator = False

    def use_gpu(self):
        return self._use_accelerator

    def gpu_device_id(self):
        return self._device_id

    def set_cpu_math_library_num_threads(self, n):
        _warn_inert("set_cpu_math_library_num_threads",
                    "recorded only; XLA owns host threading")
        self._cpu_math_threads = int(n)

    # -- precision / optimization ------------------------------------
    def enable_bf16(self):
        """TPU-native half precision: cast weights + compute to bf16."""
        self._precision = PrecisionType.Bfloat16

    enable_mkldnn_bfloat16 = enable_bf16   # reference API name

    def switch_ir_optim(self, flag=True):
        if not flag:
            _warn_inert("switch_ir_optim",
                        "False has no effect; XLA always optimizes the "
                        "compiled program")
        self._ir_optim = bool(flag)

    def ir_optim(self):
        return self._ir_optim

    def enable_memory_optim(self, flag=True):
        _warn_inert("enable_memory_optim",
                    "recorded only; XLA's buffer assignment already "
                    "reuses memory")
        self._memory_optim = bool(flag)

    def enable_profile(self):
        self._enable_profile = True

    def set_optim_cache_dir(self, path: Optional[str]):
        """Directory for the persistent compile cache (reference:
        AnalysisConfig::SetOptimCacheDir).  Honoured only when
        ``JAX_COMPILATION_CACHE_DIR`` is unset and no owner of compiled
        programs enabled the cache earlier in the process (first caller
        wins — see ``framework.compile_cache``); ``None`` keeps the
        fixed in-checkout default."""
        self._optim_cache_dir = path or None

    def set_load_batch(self, batch: int):
        """Batch size the load-time AOT compile specializes symbolic
        dims to (default 1).  Additional shapes compile on first use or
        via :meth:`Predictor.prewarm`."""
        self._load_batch = int(batch)

    def switch_use_feed_fetch_ops(self, flag):
        _warn_inert("switch_use_feed_fetch_ops",
                    "no feed/fetch ops exist under XLA — zero-copy "
                    "always")

    def switch_specify_input_names(self, flag=True):
        pass

    def enable_tensorrt_engine(self, *a, **kw):
        # The TensorRT subgraph role (fused low-precision serving) is
        # XLA compilation itself; bf16 covers the Half precision mode.
        _warn_inert("enable_tensorrt_engine",
                    "TensorRT does not exist on TPU; Half/Int8 "
                    "precision modes map to bf16 XLA compilation, other "
                    "arguments are ignored")
        prec = kw.get("precision_mode", PrecisionType.Float32)
        if prec in (PrecisionType.Half, PrecisionType.Int8):
            self._precision = PrecisionType.Bfloat16

    def tensorrt_engine_enabled(self):
        return False

    def summary(self) -> str:
        return ("Config(model=%s, accelerator=%s, precision=%s, "
                "ir_optim=%s)" % (self._path_prefix(), self._use_accelerator,
                                  self._precision, self._ir_optim))



def _warn_inert(knob: str, detail: str):
    """One warning per inert reference knob (the fleet strategy surface
    does the same via warn_noop_toggles — silent divergence from user
    intent is worse than noise)."""
    import warnings
    if knob not in _warned_knobs:
        _warned_knobs.add(knob)
        warnings.warn(f"inference.Config.{knob}: {detail}", stacklevel=3)


_warned_knobs: set = set()


class Tensor:
    """Zero-copy input/output handle (parity: ZeroCopyTensor,
    reference paddle/fluid/inference/api/details/zero_copy_tensor.cc).
    """

    def __init__(self, name: str, shape, dtype):
        self._name = name
        self._shape = list(shape)
        self._dtype = np.dtype(dtype)
        self._data: Optional[np.ndarray] = None

    @property
    def name(self):
        return self._name

    def reshape(self, shape):
        self._shape = list(shape)

    def shape(self):
        if self._data is not None:
            return list(self._data.shape)
        return self._shape

    def copy_from_cpu(self, arr: np.ndarray):
        arr = np.ascontiguousarray(arr)
        if self._dtype is not None and arr.dtype != self._dtype:
            arr = arr.astype(self._dtype)
        self._data = arr
        self._shape = list(arr.shape)

    def copy_to_cpu(self) -> np.ndarray:
        if self._data is None:
            raise RuntimeError(f"output '{self._name}' not computed yet; "
                               "call predictor.run() first")
        return np.asarray(self._data)

    # numpy-style convenience
    def numpy(self):
        return self.copy_to_cpu()


class Predictor:
    """Compile-once AOT predictor over a deserialized StableHLO artifact
    (parity: AnalysisPredictor, reference
    inference/api/analysis_predictor.cc:168).

    The constructor deserializes the export and AOT-compiles it against
    the meta's input specs (``jax.jit(...).lower(...).compile()``) —
    load time IS compile time, exactly like the reference's
    OptimizeInferenceProgram.  ``run()`` then only looks up the
    executable for its input shapes and dispatches: no retracing, no
    per-call Python flatten of the outputs, no handle-skeleton rebuild.
    One executable exists per input-shape signature (``num_compiles()``
    counts them; a steady-state server holds one per batch bucket), and
    with the persistent compile cache enabled (default) a second
    process cold-loads executables from disk instead of re-running XLA.
    """

    def __init__(self, config: Config):
        import jax
        import jax.numpy as jnp
        from jax import export as jexport

        self._config = config
        ensure_compile_cache(config._optim_cache_dir)
        prefix = config._path_prefix()
        with open(prefix + ".pdmodel", "rb") as f:
            self._exported = jexport.deserialize(bytearray(f.read()))
        with open(config._params_path(), "rb") as f:
            blob = pickle.load(f)
        meta = {}
        if os.path.exists(prefix + ".pdmeta"):
            with open(prefix + ".pdmeta", "rb") as f:
                meta = pickle.load(f)
        self._meta = meta

        if config._use_accelerator:
            dev = jax.devices()[config._device_id]
        else:
            dev = jax.devices("cpu")[0]
        self._device = dev

        # The exported program's parameter dtypes are baked into the
        # StableHLO, so bf16 serving stores weights in bf16 (halving HBM
        # footprint + load bandwidth) and upcasts inside one jitted
        # program around the exported call; the MXU executes f32 matmuls
        # as bf16 passes natively, so compute is already bf16-rate.
        bf16 = config._precision == PrecisionType.Bfloat16
        self._expected = {k: np.asarray(v).dtype
                          for k, v in {**blob["params"],
                                       **blob["buffers"]}.items()}

        def _put(v):
            a = jnp.asarray(v)
            if bf16 and a.dtype in (jnp.float32, jnp.float64):
                a = a.astype(jnp.bfloat16)
            return jax.device_put(a, dev)

        self._params = {k: _put(v) for k, v in blob["params"].items()}
        self._buffers = {k: _put(v) for k, v in blob["buffers"].items()}
        # exported artifacts bake the key SHAPE in at save time:
        # stay on portable threefry regardless of FLAGS_rng_impl
        self._rng = jax.random.PRNGKey(0)

        exported_call = self._exported.call
        if bf16:
            expected = self._expected

            def _model_call(params, buffers, rng, vals):
                # the upcast fuses into the compiled program; the f32
                # copies are compiler-managed, not per-run eager
                # materializations of the whole weight set
                up = lambda d: {k: v.astype(expected[k]) for k, v in
                                d.items()}
                return exported_call(up(params), up(buffers), rng,
                                     list(vals))
        else:
            def _model_call(params, buffers, rng, vals):
                return exported_call(params, buffers, rng, list(vals))

        def _flat_call(params, buffers, rng, vals):
            out, _bufs = _model_call(params, buffers, rng, vals)
            return tuple(_flatten(out))

        self._flat_call = _flat_call
        self._jit_call = jax.jit(_flat_call)
        self._executables: Dict[tuple, object] = {}
        self._compile_count = 0
        # per-executable compile provenance: shape key -> {cause,
        # batch, wall_ms} (PredictorServer.stats() surfaces these as
        # per-bucket prewarm/compile counts)
        self._compile_info: Dict[tuple, dict] = {}

        n = meta.get("n_inputs", len(meta.get("input_names", [])) or 1)
        names = meta.get("input_names") or [f"x{i}" for i in range(n)]
        shapes = meta.get("input_shapes") or [[-1]] * n
        dtypes = meta.get("input_dtypes") or ["float32"] * n
        self._input_names: List[str] = list(names)
        self._input_shapes = [list(s) for s in shapes]
        self._input_dtypes = [np.dtype(d) for d in dtypes]
        self._inputs: Dict[str, Tensor] = {
            nm: Tensor(nm, shp, dt)
            for nm, shp, dt in zip(names, shapes, dtypes)}
        self._output_names: List[str] = []
        self._outputs: Dict[str, Tensor] = {}

        # AOT compile at load against the meta input specs (symbolic
        # dims specialized: dim 0 -> load_batch, others -> 1).  Old
        # artifacts without recorded shapes keep the lazy compile-on-
        # first-run behavior.
        if meta.get("input_shapes"):
            try:
                self._compile_for_specs(self._specs_for_batch(
                    getattr(config, "_load_batch", 1)), cause="load")
            except Exception as e:     # pragma: no cover - degraded path
                import warnings
                warnings.warn(
                    "Predictor load-time AOT compile failed "
                    f"({type(e).__name__}: {e}); falling back to "
                    "compile-on-first-run", stacklevel=2)

    # -- AOT machinery -----------------------------------------------
    def _specs_for_batch(self, batch: int):
        """Concrete ShapeDtypeStructs from the meta input specs: the
        leading symbolic (-1) dim becomes ``batch``, interior symbolic
        dims become 1."""
        import jax
        specs = []
        for shp, dt in zip(self._input_shapes, self._input_dtypes):
            dims = []
            for j, d in enumerate(shp):
                if isinstance(d, int) and d >= 0:
                    dims.append(int(d))
                else:
                    dims.append(int(batch) if j == 0 else 1)
            specs.append(jax.ShapeDtypeStruct(tuple(dims), dt))
        return specs

    @staticmethod
    def _shape_key(vals) -> tuple:
        return tuple((tuple(int(d) for d in v.shape), str(v.dtype))
                     for v in vals)

    def _compile_for_specs(self, specs, cause: str = "new_shape_bucket"):
        """AOT lower + compile ONE executable for this input-shape
        signature; cache it and fix the output handle skeleton.  Each
        compile is logged to the flight recorder's compile observatory
        (cause = load / prewarm / new_shape_bucket, wall time, XLA
        memory analysis — the Predictor HOLDS its executables, so the
        memory observables are read off them for free)."""
        import jax
        key = self._shape_key(specs)
        exe = self._executables.get(key)
        if exe is not None:
            return exe
        t0 = _time.perf_counter()
        lowered = self._jit_call.lower(self._params, self._buffers,
                                       self._rng, tuple(specs))
        exe = lowered.compile()
        self._compile_count += 1
        self._executables[key] = exe
        try:
            batch = int(key[0][0][0])
        except (IndexError, TypeError, ValueError):
            batch = None
        self._compile_info[key] = {
            "cause": str(cause), "batch": batch,
            "wall_ms": round((_time.perf_counter() - t0) * 1e3, 3)}
        _flight.note_compile(
            f"Predictor[{os.path.basename(self._config._path_prefix())}]",
            cause, (_time.perf_counter() - t0) * 1e3,
            key=tuple(s for s, _ in key), compiled=exe,
            n_buckets=self._compile_count)
        if not self._output_names:
            out_avals = jax.eval_shape(self._flat_call, self._params,
                                       self._buffers, self._rng,
                                       tuple(specs))
            self._output_names = [f"out{i}"
                                  for i in range(len(out_avals))]
        return exe

    def num_compiles(self) -> int:
        """How many distinct XLA executables this predictor built — the
        steady-state zero-retrace contract: one per (model, input-shape
        bucket), never one per call."""
        return self._compile_count

    def compiled_shapes(self) -> List[tuple]:
        return list(self._executables.keys())

    def compile_records(self) -> List[dict]:
        """One record per built executable: {cause, batch, wall_ms} —
        cause is load / prewarm / new_shape_bucket.  The serving tier
        aggregates these into per-bucket compile counts."""
        return [dict(v) for v in self._compile_info.values()]

    def prewarm(self, batch_sizes) -> "Predictor":
        """Compile (or cache-load) the executable for each batch size
        ahead of traffic — a serving bucket never pays its compile
        inside a request."""
        for b in batch_sizes:
            self._compile_for_specs(self._specs_for_batch(int(b)),
                                    cause="prewarm")
        return self

    # -- handles -----------------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self._input_names)

    def get_input_handle(self, name: str) -> Tensor:
        return self._inputs[name]

    def get_output_names(self) -> List[str]:
        return list(self._output_names)

    def get_output_handle(self, name: str) -> Tensor:
        return self._outputs[name]

    # -- execution ---------------------------------------------------
    def run(self, inputs: Optional[List[np.ndarray]] = None):
        """Execute. Either pre-fill input handles (reference style) or
        pass arrays positionally; returns the list of output arrays.

        Steady state this is a dict lookup + one XLA dispatch: the
        executable, output names and handle skeleton were all fixed at
        compile time (load, prewarm, or this shape's first call)."""
        if inputs is not None:
            for nm, arr in zip(self._input_names, inputs):
                self._inputs[nm].copy_from_cpu(np.asarray(arr))
        vals = []
        for nm in self._input_names:
            h = self._inputs[nm]
            if h._data is None:
                raise RuntimeError(f"input '{nm}' has no data; call "
                                   "copy_from_cpu first")
            vals.append(h._data)

        exe = self._executables.get(self._shape_key(vals))
        if exe is None:
            exe = self._compile_for_specs(vals)
        flat = exe(self._params, self._buffers, self._rng, tuple(vals))

        if not self._outputs or len(self._outputs) != len(flat):
            self._outputs = {nm: Tensor(nm, (), np.float32)
                             for nm in self._output_names[:len(flat)]}
        results = []
        for nm, v in zip(self._output_names, flat):
            a = np.asarray(v)
            t = self._outputs[nm]
            t._data = a
            t._shape = list(a.shape)
            t._dtype = a.dtype
            results.append(a)
        return results

    # -- static analysis ---------------------------------------------
    def audit(self, batch: Optional[int] = None,
              include_hlo: bool = False, **thresholds):
        """Run the jaxpr program auditor (GraftLint pillar 1,
        :mod:`paddle_tpu.analysis`) over the serving program for one
        batch bucket (default: the load batch).

        Donation checking is off — a predictor's weights are reused
        across calls by design, never donated — so the rules that apply
        are dtype creep (an artifact exported f32 but silently upcast,
        or f64 creep in a custom head), host callbacks inside the
        serving program (a per-request host round trip), baked-in large
        constants, and the collective inventory.  Returns an
        :class:`~paddle_tpu.analysis.AuditReport`.
        """
        from ..analysis.jaxpr_audit import audit_traced
        b = int(batch) if batch is not None else \
            getattr(self._config, "_load_batch", 1)
        specs = self._specs_for_batch(b)
        traced = self._jit_call.trace(self._params, self._buffers,
                                      self._rng, tuple(specs))
        hlo = None
        if include_hlo:
            try:
                hlo = traced.lower().compile().as_text()
            except Exception:
                hlo = None
        prog = f"Predictor[{os.path.basename(self._config._path_prefix())}]"
        return audit_traced(
            traced, program=prog, check_donation=False, hlo_text=hlo,
            arg_names=["params", "buffers", "rng", "inputs"],
            **thresholds)

    def clone(self) -> "Predictor":
        return Predictor(self._config)

    def clear_intermediate_tensor(self):
        pass    # XLA owns intermediates; nothing persists between runs

    def try_shrink_memory(self):
        import gc
        gc.collect()


def _flatten(obj):
    if isinstance(obj, (list, tuple)):
        out = []
        for o in obj:
            out.extend(_flatten(o))
        return out
    if isinstance(obj, dict):
        out = []
        for k in sorted(obj):
            out.extend(_flatten(obj[k]))
        return out
    return [obj]


def create_predictor(config: Config) -> Predictor:
    """Parity: paddle.inference.create_predictor /
    CreatePaddlePredictor (analysis_predictor.cc:168)."""
    return Predictor(config)


from .gateway import (GatewayRouter, GenerationRpcServer,  # noqa: E402
                      LocalReplica, RemoteReplica, ReplicaLost)
from .generation_server import (GenerationServer,  # noqa: E402
                                GenerationStream)
from .migration import MigrationUnsupported  # noqa: E402
from .prefix_cache import PrefixCache  # noqa: E402
from .serving import (PredictorServer, RequestTimeout,  # noqa: E402
                      ServeError, ServerClosed, ServerDraining,
                      ServerOverloaded, UpstreamUnavailable)
