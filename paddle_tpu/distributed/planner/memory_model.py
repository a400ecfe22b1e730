"""Analytic memory + collective model for the auto-sharding planner.

The fast half of the planner's two-phase scoring (ISSUE 15): per-device
byte accounting computed from the SpecLayout role registry — the SAME
spec-derivation rules ``DistributedTrainStep`` compiles with — plus a
structured activation estimate.  The slow half (``search.verify_plan``)
replaces the estimate with XLA's own memory analysis via
``compile_abstract``; the analytic model exists to RANK candidates so
only the top-k pay a compile, and its error vs XLA is *measured*
(``tests/test_planner.py``, ``calibrate.py``), not assumed.

State terms (params / moments / grads / AMP shadow) are exact
dtype-width × sharded-numel accounting over the canonical specs.  The
activation terms are a component model (pipeline stash, attention
scores, MLP intermediates, loss head, ZeRO-3 gather working set) with
documented coefficients; MULTICHIP_r05's 7B rows land within a few
percent (pinned by tests/test_planner.py) and the proxy-suite error is
re-measured every bench round.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

from .spec_layout import SpecLayout, get_layout

__all__ = [
    "DTYPE_WIDTH", "ModelSpec", "TrainSpec", "MemoryBreakdown",
    "analytic_memory", "analytic_collectives", "PROXY_SUITE",
    "proxy_specs",
]

# dtype name -> bytes per element.  GOTCHA carried from GraftLint:
# ml_dtypes bfloat16 is NOT numpy kind 'f' — widths must come from an
# explicit table, never itemsize probing of python dtypes.
DTYPE_WIDTH = {
    "float32": 4, "fp32": 4, "float64": 8,
    "bfloat16": 2, "bf16": 2, "float16": 2, "fp16": 2,
    "int32": 4, "int64": 8, "int8": 1, "uint8": 1,
}


def _width(dtype: str) -> int:
    try:
        return DTYPE_WIDTH[str(dtype).lower()]
    except KeyError:
        raise ValueError(
            f"unknown dtype {dtype!r}; known: {sorted(DTYPE_WIDTH)}"
        ) from None


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Planner-facing description of a decoder LM.

    ``params()`` yields the parameter inventory — (name, shape, role,
    stacked) — from which per-device bytes follow via SpecLayout.  Built
    from a :class:`~paddle_tpu.text.models.llama.LlamaConfig` with
    :meth:`from_llama`; the inventory mirrors ``LlamaForCausalLM``'s
    ``named_parameters`` exactly (role templates from PARAM_ROLES).
    """

    name: str
    hidden: int
    intermediate: int
    layers: int
    heads: int
    kv_heads: int
    vocab: int
    max_seq: int
    scan_layers: bool = True
    tie_embeddings: bool = False
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @classmethod
    def from_llama(cls, cfg) -> "ModelSpec":
        """From a LlamaConfig (duck-typed: any object with the llama
        config fields works — no import of the model module needed)."""
        return cls(
            name=getattr(cfg, "name", "llama"),
            hidden=int(cfg.hidden_size),
            intermediate=int(cfg.intermediate_size),
            layers=int(cfg.num_hidden_layers),
            heads=int(cfg.num_attention_heads),
            kv_heads=int(cfg.kv_heads),
            vocab=int(cfg.vocab_size),
            max_seq=int(cfg.max_position_embeddings),
            scan_layers=bool(cfg.scan_layers),
            tie_embeddings=bool(cfg.tie_word_embeddings),
            remat=bool(cfg.remat))

    def params(self) -> List[Tuple[str, Tuple[int, ...], str, bool]]:
        """(name, shape, role, stacked) inventory.  ``stacked`` params
        (scan_layers) carry a leading layer dim and the 'pp' stack
        prefix; unstacked per-layer params are listed once per layer."""
        H, I, L = self.hidden, self.intermediate, self.layers
        hd, nh, kvh, V = self.head_dim, self.heads, self.kv_heads, \
            self.vocab
        per_layer = [
            ("input_layernorm.weight", (H,), "norm"),
            ("self_attn.q_proj.weight", (H, nh * hd), "attn_qkv"),
            ("self_attn.k_proj.weight", (H, kvh * hd), "attn_qkv"),
            ("self_attn.v_proj.weight", (H, kvh * hd), "attn_qkv"),
            ("self_attn.o_proj.weight", (nh * hd, H), "attn_out"),
            ("post_attention_layernorm.weight", (H,), "norm"),
            ("mlp.gate_proj.weight", (H, I), "mlp_in"),
            ("mlp.up_proj.weight", (H, I), "mlp_in"),
            ("mlp.down_proj.weight", (I, H), "mlp_out"),
        ]
        out: List[Tuple[str, Tuple[int, ...], str, bool]] = [
            ("model.embed_tokens.weight", (V, H), "embedding", False)]
        if self.scan_layers:
            for n, shape, role in per_layer:
                out.append((f"model.decoder.{n}", (L,) + shape, role,
                            True))
        else:
            for li in range(L):
                for n, shape, role in per_layer:
                    out.append((f"model.layers.{li}.{n}", shape, role,
                                False))
        out.append(("model.norm.weight", (H,), "norm", False))
        if not self.tie_embeddings:
            out.append(("lm_head.weight", (H, V), "logits", False))
        return out

    def n_params(self) -> int:
        return sum(int(math.prod(s)) for _, s, _, _ in self.params())


@dataclasses.dataclass(frozen=True)
class TrainSpec:
    """The training regime the planner sizes for."""

    batch: int                      # GLOBAL batch (rows)
    seq: int
    amp_dtype: Optional[str] = "bfloat16"   # None -> f32 compute
    moments_dtype: str = "float32"
    zero_stage: int = 3
    optimizer: str = "adamw"        # slot count source
    microbatches: Optional[int] = None  # None -> 2 when pp>1 else 1

    # param-shaped slots per optimizer kind (scalar machinery ignored)
    _SLOTS = {"adam": 2, "adamw": 2, "momentum": 1, "sgd": 0,
              "adagrad": 1, "rmsprop": 1}

    @property
    def slot_count(self) -> int:
        try:
            return self._SLOTS[self.optimizer.lower()]
        except KeyError:
            raise ValueError(
                f"unknown optimizer {self.optimizer!r}; known: "
                f"{sorted(self._SLOTS)}") from None

    @property
    def compute_width(self) -> int:
        return _width(self.amp_dtype) if self.amp_dtype else 4

    def microbatches_for(self, pp: int) -> int:
        if self.microbatches is not None:
            return int(self.microbatches)
        return 2 if pp > 1 else 1


@dataclasses.dataclass
class MemoryBreakdown:
    """Per-device analytic bytes, by component.  ``args`` vs ``temps``
    mirrors XLA's memory-analysis split so predicted-vs-observed error
    can be attributed per half."""

    param_bytes: int = 0          # f32 master params (args)
    moment_bytes: int = 0         # optimizer slots at rest (args)
    batch_bytes: int = 0          # ids/labels (args)
    grad_bytes: int = 0           # f32 grads (temps)
    amp_cast_bytes: int = 0       # low-precision param shadow (temps)
    gather_bytes: int = 0         # ZeRO-3 per-layer gather ws (temps)
    stash_bytes: int = 0          # remat/pipeline activation stash
    attn_bytes: int = 0           # attention score working set
    mlp_bytes: int = 0            # MLP intermediate working set
    loss_bytes: int = 0           # lm-head / CE working set

    @property
    def arg_bytes(self) -> int:
        return self.param_bytes + self.moment_bytes + self.batch_bytes

    @property
    def temp_bytes(self) -> int:
        return (self.grad_bytes + self.amp_cast_bytes
                + self.gather_bytes + self.stash_bytes
                + self.attn_bytes + self.mlp_bytes + self.loss_bytes)

    @property
    def peak_bytes(self) -> int:
        return self.arg_bytes + self.temp_bytes

    def asdict(self) -> Dict[str, int]:
        d = dataclasses.asdict(self)
        d["arg_bytes"] = self.arg_bytes
        d["temp_bytes"] = self.temp_bytes
        d["peak_bytes"] = self.peak_bytes
        return d


def _final_specs(model: ModelSpec, train: TrainSpec,
                 axes: Dict[str, int], lay: SpecLayout):
    """(name, shape, final spec, moment spec) per parameter — the same
    derivation chain the compiled step uses: role template -> 'pp'
    stack prefix (stacked params) -> ZeRO-3 fsdp augmentation."""
    fsdp = int(axes.get("fsdp", 1))
    zero = int(train.zero_stage)
    out = []
    for name, shape, role, stacked in model.params():
        ann = lay.param_spec(role, ndim=len(shape) - (1 if stacked
                                                      else 0))
        if stacked:
            ann = lay.stack(tuple(ann), len(shape))
        pspec = lay.zero3_augment(shape, tuple(ann),
                                  fsdp if zero >= 3 else 1)
        mspec = lay.moment_spec(shape, tuple(ann), pspec, zero, fsdp)
        out.append((name, shape, pspec, mspec))
    return out


def analytic_memory(model: ModelSpec, train: TrainSpec,
                    axes: Dict[str, int],
                    lay: Optional[SpecLayout] = None,
                    temp_scale: float = 1.0) -> MemoryBreakdown:
    """Per-device peak-HBM estimate for one candidate mesh.

    ``axes`` maps axis name -> size (missing axes = 1).  ``temp_scale``
    is the calibration hook's multiplicative correction on the temp
    half (``Calibration.temp_scale``; 1.0 = uncalibrated).
    """
    lay = lay or get_layout()
    dp = int(axes.get("dp", 1))
    fsdp = int(axes.get("fsdp", 1))
    pp = int(axes.get("pp", 1))
    tp = int(axes.get("tp", 1))
    sp = int(axes.get("sp", 1))
    M = train.microbatches_for(pp)
    mb = MemoryBreakdown()

    m_w = _width(train.moments_dtype)
    int8_moments = train.moments_dtype.lower() == "int8"
    c_w = train.compute_width
    amp = train.amp_dtype is not None and c_w != 4

    from jax.sharding import PartitionSpec as P

    layer_gather_elems = 0   # one layer's params, tp-sharded but
    #                          fsdp-GATHERED (the ZeRO-3 working set)
    for name, shape, pspec, mspec in _final_specs(model, train, axes,
                                                  lay):
        n_dev = lay.sharded_numel(shape, pspec, axes)
        mb.param_bytes += n_dev * 4
        m_dev = lay.sharded_numel(shape, mspec, axes)
        if int8_moments and len(shape) >= 1:
            # int8 codes + one f32 scale per last-dim row
            row = max(1, shape[-1])
            mb.moment_bytes += train.slot_count * (
                m_dev + -(-m_dev // row) * 4)
        else:
            mb.moment_bytes += train.slot_count * m_dev * m_w
        # grads: f32; ZeRO>=2 materializes them reduce-scattered over
        # 'fsdp' (the moment layout), else the full (tp-annotated)
        # gradient lives per device
        gspec = mspec if train.zero_stage >= 2 else pspec
        mb.grad_bytes += lay.sharded_numel(shape, gspec, axes) * 4
        if amp:
            mb.amp_cast_bytes += n_dev * c_w
        if train.zero_stage >= 3 and fsdp > 1:
            # the fwd/bwd all-gather materializes the CURRENT layer's
            # params un-fsdp-sharded (still tp/pp-sharded); ~3 layer
            # buffers in flight (fwd gather + bwd recompute gather +
            # the layer's un-scattered grad — calibrated against the
            # MULTICHIP_r05 buffer assignment, where 2 left a one-
            # layer-sized deficit on every geometry)
            is_stacked = name.startswith("model.decoder.")
            if is_stacked or ".layers." in name:
                pl_shape = shape[1:] if is_stacked else shape
                ent = list(tuple(pspec)) + [None] * (
                    len(shape) - len(tuple(pspec)))
                ent = [None if s == "fsdp" else s for s in ent]
                if is_stacked:
                    ent = ent[1:]
                layer_gather_elems += lay.sharded_numel(
                    pl_shape, P(*ent), axes)
    if not model.scan_layers:
        layer_gather_elems //= max(model.layers, 1)
    mb.gather_bytes = int(3 * layer_gather_elems * (c_w if amp else 4))

    # -- batch args ---------------------------------------------------
    rows_dev = -(-train.batch // (dp * fsdp))
    mb.batch_bytes = 2 * rows_dev * train.seq * 4   # ids + labels i32

    # -- activations --------------------------------------------------
    H, I, L = model.hidden, model.intermediate, model.layers
    nh, kvh, hd = model.heads, model.kv_heads, model.head_dim
    V = model.vocab
    rows_mb = max(1, rows_dev // M)
    seq_loc = -(-train.seq // sp)
    tok_mb = rows_mb * seq_loc
    L_stage = -(-L // pp)
    act_w = c_w

    # remat/pipeline stash: per-layer scan carries saved for the
    # backward; GPipe's autodiff reverse wavefront holds every
    # microbatch's residuals (M_live = M), single-stage remat one
    # batch's.  Coefficients below (stash x1, attn x4, mlp x9 = 3
    # intermediates x ~3 live copies, loss x3) are calibrated against
    # XLA buffer assignments on the proxy sweep AND the MULTICHIP_r05
    # 7B rows — see PERF round 18 for the measured residual error.
    m_live = M if pp > 1 else 1
    mb.stash_bytes = int(L_stage * tok_mb * H * act_w * m_live)

    # attention working set of ONE recomputed layer: f32 score
    # buffers.  At seq >= 1024 the XLA path is CHUNKED (chunk=512) —
    # the chunk scan serializes liveness, ~2 buffers (fwd chunk + bwd
    # dscores); unchunked short-seq attention keeps ~4 alive (scores
    # + softmax out + dscores + transpose — measured in the proxy
    # buffer assignments).  Under sp the planner plans the RING path
    # (context_parallel="ring", the r05-proven mechanism), whose KV
    # block is the local shard
    chunked_attn = seq_loc >= 1024
    chunk = min(512, seq_loc) if chunked_attn else seq_loc
    attn_live = 2 if chunked_attn else 4
    mb.attn_bytes = int(attn_live * rows_mb * -(-nh // tp) * chunk
                        * seq_loc * 4)

    # MLP intermediates of one recomputed layer: gate/up/silu.  Under
    # the chunked-attention regime the layer recompute is serialized
    # by the chunk scan (~3 live); short-seq programs fuse more and
    # keep ~9 alive (measured, same sweep)
    mb.mlp_bytes = int((3 if chunked_attn else 9) * tok_mb
                       * -(-I // tp) * act_w)

    # loss head: the chunked-CE decision is made at TRACE time on the
    # full-batch logits shape (llama._CHUNK_BYTES_MIN) — the per-
    # device cost then follows the branch taken.  Chunked: [rows, 256,
    # V] f32 chunk buffers (fwd + bwd); unchunked: the full
    # [rows, seq, V] f32 logits ~3x (logits + log_softmax + dlogits).
    # The logits region is batch-sharded but NOT sp-sharded (full seq)
    global_logits = train.batch * train.seq * V * 4
    if global_logits >= int(1.5 * 1024 ** 3) and train.seq - 1 >= 512:
        mb.loss_bytes = int(2 * rows_dev * 256 * V * 4)
    else:
        mb.loss_bytes = int(3 * rows_dev * train.seq * V * 4)

    for f in ("grad_bytes", "amp_cast_bytes", "gather_bytes",
              "stash_bytes", "attn_bytes", "mlp_bytes", "loss_bytes"):
        setattr(mb, f, int(getattr(mb, f) * temp_scale))
    return mb


def analytic_collectives(model: ModelSpec, train: TrainSpec,
                         axes: Dict[str, int]) -> Dict[str, int]:
    """Per-device collective bytes per step, by mechanism (the analytic
    counterpart of the audit's HLO inventory; ground truth on verified
    plans comes from ``hlo_collective_inventory``)."""
    dp = int(axes.get("dp", 1))
    fsdp = int(axes.get("fsdp", 1))
    pp = int(axes.get("pp", 1))
    tp = int(axes.get("tp", 1))
    sp = int(axes.get("sp", 1))
    M = train.microbatches_for(pp)
    c_w = train.compute_width
    n_total = model.n_params()
    n_shard = n_total // max(pp, 1)   # params a device's stage holds
    rows_dev = -(-train.batch // (dp * fsdp))
    seq_loc = -(-train.seq // sp)
    tok_dev = rows_dev * seq_loc
    out: Dict[str, int] = {}
    if fsdp > 1 and train.zero_stage >= 3:
        # fwd + bwd param all-gather at compute width; grad
        # reduce-scatter in f32
        out["fsdp_all_gather"] = int(
            2 * n_shard * c_w * (fsdp - 1) / fsdp)
        out["fsdp_reduce_scatter"] = int(
            n_shard * 4 * (fsdp - 1) / fsdp)
    elif fsdp > 1:
        out["fsdp_grad_reduce"] = int(
            2 * n_shard * 4 * (fsdp - 1) / fsdp)
    if dp > 1:
        out["dp_all_reduce"] = int(2 * n_shard * 4 * (dp - 1) / dp)
    if tp > 1:
        # 2 row-parallel fwd all-reduces + 2 bwd input-grad
        # all-reduces per layer over the hidden activation
        out["tp_all_reduce"] = int(
            4 * model.layers * tok_dev * model.hidden * c_w
            * (tp - 1) / tp)
    if sp > 1:
        # ring attention: K and V each rotate sp-1 times per layer,
        # forward and (transposed) backward
        kv_bytes = (rows_dev * seq_loc * model.kv_heads
                    * model.head_dim * c_w)
        out["sp_permute"] = int(
            2 * 2 * (sp - 1) * model.layers * kv_bytes)
    if pp > 1:
        # GPipe rotation: activation payload every tick, fwd + bwd
        ticks = M + pp - 1
        tok_mb = max(1, rows_dev // M) * seq_loc
        out["pp_permute"] = int(2 * ticks * tok_mb * model.hidden
                                * c_w)
    out["total"] = sum(out.values())
    return out


# ----------------------------------------------------------------------
# proxy suite — the configs the planner's predicted-vs-XLA error is
# measured on (tests/test_planner.py pins the bound and re-measures it
# on every tier-1 run).  f32 compute: the CPU backend aborts on
# bf16 collectives without an XLA flag (see __graft_entry__), and the
# suite must verify in-process under tier-1.
# ----------------------------------------------------------------------

PROXY_SUITE = (
    dict(name="proxy_fsdp", hidden=256, intermediate=512, layers=4,
         heads=8, kv_heads=8, vocab=2048, seq=256, batch=16,
         scan_layers=True),
    dict(name="proxy_tp", hidden=256, intermediate=512, layers=4,
         heads=8, kv_heads=8, vocab=2048, seq=256, batch=8,
         scan_layers=True),
    dict(name="proxy_wide", hidden=512, intermediate=1024, layers=2,
         heads=8, kv_heads=8, vocab=4096, seq=512, batch=8,
         scan_layers=True),
)


def proxy_specs(entry: dict) -> Tuple[ModelSpec, TrainSpec]:
    """(ModelSpec, TrainSpec) for one PROXY_SUITE entry."""
    e = dict(entry)
    batch, seq = e.pop("batch"), e.pop("seq")
    ms = ModelSpec(max_seq=seq, tie_embeddings=False, remat=True, **e)
    ts = TrainSpec(batch=batch, seq=seq, amp_dtype=None,
                   moments_dtype="float32", zero_stage=3,
                   optimizer="adamw")
    return ms, ts
