"""One run of a training cell: ``fleet.DistributedTrainStep.__call__``
fed host batches made from the seed and staged each step.

Set-up builds ONE object, the compiled step with its state, drives it
from the seed through its first three steps (through the window's own
call and feed, on rows that all differ), reads what ``correct``
compares, warms up until the step time is flat, and hands that same
object to the window.  In the window the loss of step i-IN_FLIGHT is
fetched after step i is dispatched (a training loop that reads its
loss every few steps), so that a stall of the host shorter than the
queue does not idle the device; the steps in flight at ``--seconds``
are finished and counted.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import json
import statistics
import time

import numpy as np

from . import common as C
from . import correct as K
from . import stats as S
from .program import install_weights, split_by_reference

CHECK_STEPS = 3
# steps dispatched ahead of the oldest unfetched loss: about 2 s of
# BERT steps.  One run in 17 on the chip held a 2.0 s stall in a single
# step (PERF.md Findings); with one step in flight that is 6% of a run.
IN_FLIGHT = 16
WARM_MIN, WARM_MAX, WARM_FLAT = 8, 40, 0.02


def _compiles():
    from paddle_tpu.observability import flight_recorder
    return len(flight_recorder.compile_log())


def run(cell, seed: int, seconds: float, trace: bool, t_proc0: float,
        require_chip: bool = True, controls=(), faults=(), sabotage=None):
    ph = C.Phases(t_proc0)
    import jax
    import jax.numpy as jnp
    devs, device = C.device_info(cell.chips, require_chip)
    ph.mark("jax_and_devices")
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.framework import compile_cache
    from .traffic import train_batches
    compile_cache.ensure_compile_cache()
    ph.mark("import_program")
    cfg, spec, tr = cell.config, cell.spec, cell.traffic
    binding, ref = cell.binding(), cell.reference()
    opt_cfg = spec["optimizer"]
    annotate = (jax.profiler.TraceAnnotation if trace
                else (lambda name: contextlib.nullcontext()))

    # ---- set-up ------------------------------------------------------
    mesh_mod.set_mesh(None)
    mesh = mesh_mod.init_mesh(dict(spec["mesh"]), devices=devs)
    paddle.seed(int(seed) & 0x7FFFFFFF)
    model = binding.build_training(cfg, tr["seq"])
    nm = binding.name_map(cfg, model)
    specs = ref.param_specs(cfg)
    remake = install_weights(model, nm, specs, seed, jnp.float32,
                             mesh=mesh)
    names = [n for n, _ in model.named_parameters()]
    jax.block_until_ready([p._value for p in model.parameters()])
    ph.mark("model_and_weights")
    opt = paddle.optimizer.AdamW(
        learning_rate=opt_cfg["lr"], beta1=opt_cfg["beta1"],
        beta2=opt_cfg["beta2"], epsilon=opt_cfg["eps"],
        weight_decay=opt_cfg["weight_decay"],
        parameters=model.parameters())
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    strategy.amp_configs = {"dtype": "bfloat16"}
    if spec.get("zero_stage"):
        strategy.sharding = True
        strategy.sharding_configs = {"stage": int(spec["zero_stage"])}
    step = fleet.DistributedTrainStep(model, binding.loss_fn(model), opt,
                                      strategy, mesh=mesh)
    if sabotage is not None:
        step = sabotage(step, model, opt)
    batches = train_batches(tr, cfg, cell.chips, seed)
    ph.mark("step_object_and_batches")
    B = len(next(iter(batches[0].values())))
    tokens_per_step = B * int(tr["seq"])

    def feed(i):
        """The window's own feed: stage host batch i, call the step."""
        with annotate("bench.stage_batch"):
            args = [paddle.to_tensor(a)
                    for a in binding.batch_args(batches[i % len(batches)])]
        return step(*args)

    def fetch(loss):
        with annotate("bench.wait_loss"):
            jax.block_until_ready(loss._value)
            return float(loss)

    @jax.jit
    def leaf_norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)),
                                    axis=tuple(range(1, v.ndim))
                                    if isinstance(nm[k], (list, tuple))
                                    else None))
                for k, v in tree.items()}

    def by_reference(tree):
        host = {k: np.asarray(v) for k, v in leaf_norms(tree).items()}
        return {k: float(v)
                for k, v in split_by_reference(nm, host).items()}

    # the first three steps: what `correct` compares
    prog = {"loss": []}
    for i in range(CHECK_STEPS):
        prog["loss"].append(fetch(feed(i)))
        if i == 0:
            ph.mark("first_step_compile_or_cache_load")
            m1 = {n: st["m"] for n, st in zip(names, opt.opt_state())}
            prog["gnorm"] = {k: v / (1.0 - opt_cfg["beta1"])
                             for k, v in by_reference(m1).items()}
            idx = K.sample_indices(specs, seed)
            prog["gsample"] = {
                k: np.asarray(v) / (1.0 - opt_cfg["beta1"])
                for k, v in jax.jit(lambda t: K.take_samples(
                    split_by_reference(nm, t), idx))(m1).items()}
            del m1
    cur = {n: p._value for n, p in model.named_parameters()}
    prog["dnorm"] = by_reference(
        jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b))(
            cur, remake()))
    del cur
    ph.mark("check_steps_and_readings")

    # warm up until the step time is flat
    times, i = [], CHECK_STEPS
    while len(times) < WARM_MAX:
        t = time.perf_counter()
        fetch(feed(i))
        times.append(time.perf_counter() - t)
        i += 1
        if len(times) >= WARM_MIN:
            last = times[-5:]
            med = statistics.median(last)
            if (max(last) - min(last)) <= WARM_FLAT * med:
                break
    C.log(f"warm-up: {len(times)} steps, last "
          f"{[round(x * 1e3, 1) for x in times[-5:]]} ms")
    ph.mark("warm_up")
    C.log("set-up " + json.dumps(ph.report()))
    gc.collect()
    gc.freeze()
    gc.disable()
    n_comp0 = _compiles()
    tracer = C.SubWindowTrace(cell.name) if trace else None

    # ---- the window --------------------------------------------------
    done, dispatched, inflight = [], [], collections.deque()
    try:
        t0 = time.perf_counter()
        if tracer:
            tracer.arm(t0, seconds)
        while time.perf_counter() - t0 < seconds:
            inflight.append(feed(i))
            dispatched.append(time.perf_counter())
            i += 1
            if len(inflight) > IN_FLIGHT:
                fetch(inflight.popleft())
                done.append(time.perf_counter())
        while inflight:
            last_loss = fetch(inflight.popleft())
            done.append(time.perf_counter())
    finally:
        gc.enable()
    n_comp1 = _compiles()
    summary = tracer.finish() if tracer else None
    peak = C.peak_memory(devs)
    del step, model, opt
    mesh_mod.set_mesh(None)
    gc.unfreeze()
    gc.collect()
    C.log(f"program freed: {C.bytes_in_use(devs) / 2**30:.2f} GiB still "
          f"in use, peak was {peak / 2**30:.2f} GiB")

    step_s = [b - a for a, b in zip([t0] + done, done)]
    rate = S.train_rate(done, t0, tokens_per_step, cell.chips)
    e2e = {"train_tokens_per_s_per_chip": rate, "setup_s": t0 - t_proc0}
    li, lv = S.longest(step_s)
    # a stall of the host that the queue absorbed still shows here
    di, dv = S.longest([b - a for a, b in zip(dispatched, dispatched[1:])])
    C.write_run_log(cell.name, seed, {
        "steps": len(done), "tokens_per_step": tokens_per_step,
        "window_s": done[-1] - t0, "longest_step_ms": lv * 1e3,
        "longest_step_index": li, "longest_step_at_s": done[li] - t0,
        "median_step_ms": statistics.median(step_s) * 1e3,
        "longest_dispatch_gap_ms": None if dv is None else dv * 1e3,
        "longest_dispatch_gap_index": di,
        "last_loss": last_loss, "first_losses": prog["loss"]})

    # ---- correct: the first three steps against the plain reference --
    t_ref = time.perf_counter()
    cc = spec["correct"]
    refd = K.train_reference(ref, cfg, seed, batches, opt_cfg,
                             cc["rows_per_block"], chips=cell.chips)
    numbers = K.train_numbers(prog, refd)
    at = numbers.pop("_at")
    C.log(f"reference took {time.perf_counter() - t_ref:.1f} s; losses "
          f"program {prog['loss']} reference {refd['loss']}; worst "
          f"leaves {at}")
    # each control and each planted fault: the reference so altered is
    # put in the program's place and judged by the cell's own limits;
    # it has to come out as not correct
    verdicts = {}
    for tag, kw in ([(f"control_{m}", {"q": m}) for m in controls]
                    + [(f"fault_{f}", {"fault": f}) for f in faults]):
        r = K.train_reference(ref, cfg, seed, batches, opt_cfg,
                              cc["rows_per_block"], chips=cell.chips, **kw)
        theirs = K.train_numbers(r, refd)
        theirs.pop("_at")
        for k, v in theirs.items():
            numbers[f"{tag}_{k}"] = v
        verdicts[tag.split("_", 1)[1]] = K.judge_in_place(
            theirs, cc["limits"], tag.replace("_", " ", 1))
    ok, table = K.judge(numbers, cc["limits"])
    for k, v in numbers.items():
        table.setdefault(k, [v, None])

    ctx = {"cell": cell, "cfg": cfg, "traffic": tr, "seconds": seconds,
           "end_to_end": e2e, "step_s": step_s, "steps": len(done),
           "tokens_per_step": tokens_per_step, "rate_per_chip": rate,
           "compiles_in_window": n_comp1 - n_comp0, "trace": summary,
           "peak_bytes": peak, "device": device, "chips": cell.chips,
           "batch": B}
    device = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if trace:
        breakdown = C.trace_device_fields(device, summary)
    metrics = C.read_metrics(cell, "per_layer" if trace else "end_to_end",
                             ctx)
    return {"correct": ok, "attempted": len(done), "failed": 0,
            "metrics": metrics, "device": device, "breakdown": breakdown,
            "compared": table, "verdicts": verdicts}
