"""Mesh search — the planner half of the auto-sharding subsystem.

``auto(model, chips=N)`` (exported as ``fleet.auto``) enumerates every
valid ``pp x fsdp x tp x sp`` factorization of the chip count, scores
each candidate with the fast analytic model
(:mod:`memory_model` — dtype-width accounting over the SpecLayout
specs + a structured activation estimate + a collective-bytes model),
then *verifies* the top-k by AOT lower-and-memory-analyze: the
``DistributedTrainStep.compile_abstract`` + XLA memory-analysis path
the MULTICHIP dryruns use, which needs NO devices beyond a virtual
mesh.  The result is a ranked list of **lowerable** configs, each with
predicted per-device peak HBM, collective bytes per step, and a
FITS/EXCEEDS verdict against the device HBM budget.

Ranking key (documented, deterministic): FITS before EXCEEDS, then
fewer analytic collective bytes per step (the step-time proxy — a real
measured step-time model with ICI/DCN weighting is the named ROADMAP
follow-up), then lower predicted peak, then the degree tuple.

Verification failures are *kept* (``Plan.verify_error``) but excluded
from the returned list, so every returned verified plan is proven
lowerable.

Every ``auto`` decision lands in the flight recorder as a
``plan.choose`` event, so a postmortem shows which config a run
launched with.
"""
from __future__ import annotations

import dataclasses
import time as _time
from typing import Dict, List, Optional, Sequence, Tuple

from .calibrate import Calibration, CalibrationReport
from .memory_model import (MemoryBreakdown, ModelSpec, TrainSpec,
                           analytic_collectives, analytic_memory)
from .spec_layout import SpecLayout, get_layout

__all__ = ["Plan", "Planner", "auto", "enumerate_meshes",
           "PlannerError"]


class PlannerError(RuntimeError):
    """Typed planner failure (no valid candidate, bad inputs)."""


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def mesh_tag(degrees: Dict[str, int]) -> str:
    """'pp2xfsdp4'-style tag (axes with degree > 1, canonical order)."""
    parts = [f"{ax}{degrees[ax]}" for ax in
             ("pp", "fsdp", "tp", "sp", "dp")
             if degrees.get(ax, 1) > 1]
    return "x".join(parts) if parts else "single"


@dataclasses.dataclass
class Plan:
    """One ranked candidate configuration."""

    degrees: Dict[str, int]
    chips: int
    model: ModelSpec
    train: TrainSpec
    memory: MemoryBreakdown
    collectives: Dict[str, int]
    hbm_budget_bytes: int
    # verify phase (filled by Planner.verify / auto(verify=...))
    verified: bool = False
    verified_peak_bytes: Optional[int] = None
    verified_mem: Optional[Dict[str, int]] = None
    hlo_collectives: Optional[Dict[str, Dict[str, int]]] = None
    verify_error: Optional[str] = None
    verify_wall_s: Optional[float] = None

    @property
    def tag(self) -> str:
        return mesh_tag(self.degrees)

    @property
    def predicted_peak_bytes(self) -> int:
        """Best available peak: XLA's own analysis once verified, the
        analytic estimate before."""
        if self.verified and self.verified_peak_bytes is not None:
            return self.verified_peak_bytes
        return self.memory.peak_bytes

    @property
    def analytic_peak_bytes(self) -> int:
        return self.memory.peak_bytes

    @property
    def fits(self) -> bool:
        return self.predicted_peak_bytes <= self.hbm_budget_bytes

    @property
    def verdict(self) -> str:
        return "FITS" if self.fits else "EXCEEDS"

    @property
    def collective_bytes(self) -> int:
        return int(self.collectives.get("total", 0))

    def sort_key(self) -> Tuple:
        # FITS plans: fewest collective bytes (the step-time proxy),
        # then lowest peak.  EXCEEDS plans: closest to fitting first —
        # a ranked overflow is actionable (drop moments width, add
        # chips), a comm-optimal-but-20-GiB plan is not.
        if self.fits:
            return (0, self.collective_bytes,
                    self.predicted_peak_bytes,
                    tuple(sorted(self.degrees.items())))
        return (1, self.predicted_peak_bytes, self.collective_bytes,
                tuple(sorted(self.degrees.items())))

    def asdict(self) -> Dict:
        gib = 1024.0 ** 3
        d = {
            "mesh": self.tag,
            "degrees": {k: v for k, v in self.degrees.items()
                        if v > 1},
            "chips": self.chips,
            "verdict": self.verdict,
            "predicted_peak_gib": round(
                self.predicted_peak_bytes / gib, 3),
            "analytic_peak_gib": round(
                self.analytic_peak_bytes / gib, 3),
            "hbm_budget_gib": round(self.hbm_budget_bytes / gib, 3),
            "collective_bytes_per_step": self.collective_bytes,
            "collectives": dict(self.collectives),
            "memory": self.memory.asdict(),
            "verified": self.verified,
        }
        if self.verified_peak_bytes is not None:
            d["verified_peak_gib"] = round(
                self.verified_peak_bytes / gib, 3)
            d["verified_mem"] = dict(self.verified_mem or {})
        if self.hlo_collectives is not None:
            d["hlo_collectives"] = {
                k: dict(v) for k, v in self.hlo_collectives.items()}
        if self.verify_error is not None:
            d["verify_error"] = self.verify_error
        if self.verify_wall_s is not None:
            d["verify_wall_s"] = round(self.verify_wall_s, 3)
        return d


def enumerate_meshes(chips: int, model: ModelSpec, train: TrainSpec,
                     include_dp: bool = False) -> List[Dict[str, int]]:
    """All VALID pp x fsdp x tp x sp (x dp) factorizations of ``chips``.

    Validity (derived from the model/train specs, the same rules the
    layers enforce at runtime):

    * ``pp`` needs a scan-stacked decoder and ``layers % pp == 0``
    * ``tp`` must divide heads, kv_heads, intermediate and vocab
    * ``sp`` must divide the sequence length
    * the global batch must divide over ``dp*fsdp`` and the microbatch
      count (``TrainSpec.microbatches_for(pp)``)
    * ``fsdp > 1`` needs ``zero_stage >= 1`` (otherwise the factor
      belongs to dp)
    """
    chips = int(chips)
    if chips < 1:
        raise PlannerError(f"chips must be >= 1, got {chips}")
    out, seen = [], set()
    for pp in _divisors(chips):
        if pp > 1 and (not model.scan_layers or model.layers % pp):
            continue
        rest_pp = chips // pp
        for tp in _divisors(rest_pp):
            if (model.heads % tp or model.kv_heads % tp
                    or model.intermediate % tp or model.vocab % tp):
                continue
            rest_tp = rest_pp // tp
            for sp in _divisors(rest_tp):
                if sp > 1 and train.seq % sp:
                    continue
                rest_sp = rest_tp // sp
                dp_opts = _divisors(rest_sp) if include_dp else [1]
                for dp in dp_opts:
                    fsdp = rest_sp // dp
                    if fsdp > 1 and train.zero_stage < 1:
                        continue
                    nshard = dp * fsdp
                    M = train.microbatches_for(pp)
                    if train.batch % max(nshard, 1):
                        continue
                    if (train.batch // max(nshard, 1)) % M:
                        continue
                    deg = {"pp": pp, "fsdp": fsdp, "tp": tp,
                           "sp": sp, "dp": dp}
                    key = tuple(sorted(deg.items()))
                    if key in seen:
                        continue
                    seen.add(key)
                    out.append(deg)
    if not out:
        raise PlannerError(
            f"no valid mesh factorization of {chips} chips for "
            f"{model.name} (batch {train.batch}, seq {train.seq})")
    return out


class Planner:
    """Two-phase planner over one (model, train) regime."""

    def __init__(self, model: ModelSpec, train: TrainSpec, *,
                 hbm_gib: float = 16.0,
                 layout: Optional[SpecLayout] = None,
                 temp_scale: float = 1.0):
        self.model = model
        self.train = train
        self.hbm_budget_bytes = int(float(hbm_gib) * 1024 ** 3)
        self.layout = layout or get_layout()
        self.temp_scale = float(temp_scale)
        self.last_analytic_s: Optional[float] = None
        self.last_verify_s: Optional[float] = None
        self.rejected: List[Plan] = []   # verify failures of last run

    # -- phase 1: analytic --------------------------------------------
    def score(self, degrees: Dict[str, int]) -> Plan:
        chips = 1
        for v in degrees.values():
            chips *= int(v)
        mem = analytic_memory(self.model, self.train, degrees,
                              self.layout, temp_scale=self.temp_scale)
        col = analytic_collectives(self.model, self.train, degrees)
        return Plan(degrees=dict(degrees), chips=chips,
                    model=self.model, train=self.train, memory=mem,
                    collectives=col,
                    hbm_budget_bytes=self.hbm_budget_bytes)

    def rank(self, chips: int,
             include_dp: bool = False) -> List[Plan]:
        t0 = _time.perf_counter()
        plans = [self.score(d) for d in
                 enumerate_meshes(chips, self.model, self.train,
                                  include_dp=include_dp)]
        plans.sort(key=Plan.sort_key)
        self.last_analytic_s = _time.perf_counter() - t0
        return plans

    # -- phase 2: verify ----------------------------------------------
    def verify(self, plan: Plan) -> Plan:
        """AOT lower + XLA memory analysis for one candidate (in
        place).  Needs ``plan.chips`` local (virtual) devices; failures
        land in ``plan.verify_error`` — the plan stays usable with its
        analytic numbers."""
        t0 = _time.perf_counter()
        try:
            peak, mem, hlo_col = _verify_compile(
                self.model, self.train, plan.degrees, plan.chips)
            plan.verified = True
            plan.verified_peak_bytes = int(peak)
            plan.verified_mem = mem
            plan.hlo_collectives = hlo_col
        except Exception as e:   # typed in verify_error, not raised:
            # a candidate that cannot lower is a RESULT, not a crash
            plan.verify_error = f"{type(e).__name__}: {e}"
        plan.verify_wall_s = _time.perf_counter() - t0
        return plan

    def plan(self, chips: int, *, verify_top_k: int = 0,
             include_dp: bool = False) -> List[Plan]:
        """Ranked plans; with ``verify_top_k`` > 0, verify candidates
        in rank order until that many LOWERABLE plans are found (or
        the candidate list is exhausted), drop the failures into
        ``self.rejected``, and return only lowerable plans re-ranked
        with their XLA-verified peaks."""
        plans = self.rank(chips, include_dp=include_dp)
        if verify_top_k <= 0:
            self.last_verify_s = None
            self.rejected = []
            return plans
        t0 = _time.perf_counter()
        good: List[Plan] = []
        self.rejected = []
        for p in plans:
            if len(good) >= verify_top_k:
                break
            self.verify(p)
            (good if p.verified else self.rejected).append(p)
        self.last_verify_s = _time.perf_counter() - t0
        good.sort(key=Plan.sort_key)
        return good

    # -- calibration hook ---------------------------------------------
    def calibrate(self, plan: Plan,
                  records: Optional[Sequence[dict]] = None,
                  apply: bool = True) -> CalibrationReport:
        """Measure predicted-vs-observed peak error against real
        compile-log records (``flight_recorder.compile_log``) and —
        with ``apply`` — install the fitted temp correction for
        subsequent analytic scores."""
        cal = Calibration.from_compile_log(records)
        rep = cal.report(plan.analytic_peak_bytes,
                         plan.memory.temp_bytes)
        if apply and rep.n_observations:
            self.temp_scale = rep.temp_scale
        return rep


def _verify_compile(model: ModelSpec, train: TrainSpec,
                    degrees: Dict[str, int], chips: int):
    """One candidate's AOT compile + memory analysis (the
    ``_dryrun_7b_one`` path, generalized).  Pure function of its
    inputs; saves/restores the global mesh."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from ...framework.core import abstract_init
    from ...text.models import LlamaForCausalLM, llama_tiny
    from .. import mesh as mesh_mod
    from ..fleet import DistributedStrategy
    from ..fleet.dist_step import DistributedTrainStep
    from ...analysis.jaxpr_audit import hlo_collective_inventory

    devices = jax.devices()
    if len(devices) < chips:
        raise PlannerError(
            f"verify needs {chips} local (virtual) devices, backend "
            f"has {len(devices)} — run under XLA_FLAGS=--xla_force_"
            f"host_platform_device_count={chips} (tools/plan.py does "
            "this re-exec automatically)")
    M = train.microbatches_for(degrees.get("pp", 1))
    cfg = llama_tiny(
        vocab_size=model.vocab, hidden_size=model.hidden,
        intermediate_size=model.intermediate,
        num_hidden_layers=model.layers,
        num_attention_heads=model.heads,
        num_key_value_heads=model.kv_heads,
        max_position_embeddings=train.seq,
        tie_word_embeddings=model.tie_embeddings,
        compute_dtype=(train.amp_dtype or "float32"),
        sequence_parallel=degrees.get("sp", 1) > 1,
        # sp plans ride RING attention (the r05-proven sp mechanism;
        # plain sp leaves attention/KV un-sharded over seq — measured
        # 116 vs 41 MiB temps on the sp2 proxy)
        context_parallel=("ring" if degrees.get("sp", 1) > 1
                          else None),
        scan_layers=model.scan_layers, remat=model.remat,
        pp_num_microbatches=M)
    prev_mesh = mesh_mod.get_mesh(create=False)
    try:
        mesh_mod.set_mesh(None)
        mesh = mesh_mod.init_mesh(
            {k: v for k, v in degrees.items() if v > 1} or {"dp": 1},
            devices=devices[:chips])
        paddle.seed(0)
        with abstract_init():
            lm = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=lm.parameters())
        strategy = DistributedStrategy()
        if train.amp_dtype:
            strategy.amp = True
            strategy.amp_configs = {"dtype": train.amp_dtype}
        if train.zero_stage:
            strategy.sharding = True
            strategy.sharding_configs = {
                "stage": train.zero_stage,
                "moment_dtype": train.moments_dtype}

        def loss_fn(ids, labels):
            loss, _ = lm(ids, labels=labels)
            return loss

        step = DistributedTrainStep(lm, loss_fn, opt, strategy,
                                    mesh=mesh)
        ids = paddle.to_tensor(
            np.zeros((train.batch, train.seq), np.int32))
        compiled = step.compile_abstract(ids, ids)
        ma = compiled.memory_analysis()
        arg = int(ma.argument_size_in_bytes)
        out = int(ma.output_size_in_bytes)
        tmp = int(ma.temp_size_in_bytes)
        alias = int(ma.alias_size_in_bytes)
        # donated state aliases its outputs: live set = args + temps +
        # un-aliased outputs (the dryrun peak formula)
        peak = arg + tmp + max(out - alias, 0)
        mem = {"argument_bytes": arg, "output_bytes": out,
               "temp_bytes": tmp, "alias_bytes": alias,
               "peak_bytes": peak}
        try:
            hlo_col = hlo_collective_inventory(compiled.as_text())
        except Exception:
            hlo_col = None
        return peak, mem, hlo_col
    finally:
        mesh_mod.set_mesh(prev_mesh)


def _as_model_spec(model) -> ModelSpec:
    if isinstance(model, ModelSpec):
        return model
    if hasattr(model, "hidden_size"):        # LlamaConfig-like
        return ModelSpec.from_llama(model)
    if hasattr(model, "config"):             # a live LlamaForCausalLM
        return ModelSpec.from_llama(model.config)
    if isinstance(model, dict):
        return ModelSpec(**model)
    raise PlannerError(
        f"cannot build a ModelSpec from {type(model).__name__}; pass "
        "a ModelSpec, a LlamaConfig, a model with .config, or a dict")


def auto(model, chips: int = 8, *, hbm_gib: float = 16.0,
         moments_dtype: str = "float32",
         amp_dtype: Optional[str] = "auto",
         batch: Optional[int] = None, seq: Optional[int] = None,
         zero_stage: int = 3, microbatches: Optional[int] = None,
         verify_top_k: int = 0, include_dp: bool = False,
         temp_scale: float = 1.0) -> List[Plan]:
    """``fleet.auto(model, chips=N)`` — the one-call planner.

    Returns the ranked plan list (see module docstring for the key);
    with ``verify_top_k`` > 0 every returned plan is PROVEN lowerable
    via ``compile_abstract`` and carries XLA's own per-device peak.

    ``amp_dtype="auto"`` reads the model config's ``compute_dtype``
    (bf16 models plan a bf16-AMP step, f32 models a plain one);
    ``batch`` defaults to one row per chip times the microbatch count;
    ``seq`` defaults to the model's max positions.
    """
    ms = _as_model_spec(model)
    if amp_dtype == "auto":
        cd = getattr(model, "compute_dtype",
                     getattr(getattr(model, "config", None),
                             "compute_dtype", None))
        amp_dtype = cd if cd in ("bfloat16", "float16") else None
    seq = int(seq or ms.max_seq)
    if batch is None:
        # one row per data shard x the largest microbatch count any
        # candidate uses — divisible for every factorization
        mb = microbatches if microbatches is not None else 2
        batch = chips * max(int(mb), 1)
    ts = TrainSpec(batch=int(batch), seq=seq, amp_dtype=amp_dtype,
                   moments_dtype=moments_dtype,
                   zero_stage=int(zero_stage),
                   microbatches=microbatches)
    planner = Planner(ms, ts, hbm_gib=hbm_gib,
                      temp_scale=temp_scale)
    plans = planner.plan(chips, verify_top_k=verify_top_k,
                         include_dp=include_dp)
    _note_choice(plans, planner, chips)
    return plans


def _note_choice(plans: Sequence[Plan], planner: Planner, chips: int):
    """Flight-recorder ``plan.choose`` event: which config this run
    would launch with (postmortems surface it; ISSUE 15 satellite)."""
    try:
        from ...observability import flight_recorder as _flight
        if not plans:
            _flight.record("plan.choose", chips=chips, mesh=None,
                           n_plans=0)
            return
        top = plans[0]
        _flight.record(
            "plan.choose", chips=chips, mesh=top.tag,
            verdict=top.verdict,
            peak_gib=round(top.predicted_peak_bytes / 1024 ** 3, 3),
            verified=top.verified, n_plans=len(plans),
            n_rejected=len(planner.rejected),
            collective_bytes=top.collective_bytes)
    except Exception:
        pass
