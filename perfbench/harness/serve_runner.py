"""One run of a serving cell: ``GenerationServer.submit()`` ->
``GenerationStream`` under the cell's traffic mix.

One thread is every client: it polls the streams through their public
non-blocking ``__next__(timeout=0)`` and stamps each token as it
arrives.  Closed loop: a client's next request is sent the moment its
last one ends.  Open loop: a request is sent when it is due by the
mix's schedule and timed from when it was due; how late the generator
ran is in the run log.  The traffic runs through warm-up into the
window, so the window opens on steady traffic.  At ``--seconds`` no
more is submitted and the run drains what is in flight, so every
request of the window has its times.
"""
from __future__ import annotations

import contextlib
import gc
import json
import queue
import time

from . import common as C
from . import correct as K
from . import stats as S
from .program import install_weights
from .traffic import ServeTraffic

POLL_S = 0.001
DRAIN_TIMEOUT_S = 60.0
_COUNTERS = ("decode_steps", "decode_ms", "prefill_ms", "prefill_tokens",
             "prefill_batches", "tokens_generated", "traffic_compiles")


def _counters(server):
    st = server.stats()
    out = {k: st[k] for k in _COUNTERS}
    out["prefill_rows"] = sum(st["prefill_bucket_hits"].values())
    return out


def _delta(a, b):
    return {k: b[k] - a[k] for k in a}


def run(cell, seed: int, seconds: float, trace: bool, t_proc0: float,
        require_chip: bool = True, controls=(), sabotage=None):
    ph = C.Phases(t_proc0)
    import jax
    import jax.numpy as jnp
    devs, device = C.device_info(cell.chips, require_chip)
    ph.mark("jax_and_devices")
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.inference import GenerationServer
    compile_cache.ensure_compile_cache()
    ph.mark("import_program")
    cfg, srv, tr = cell.config, cell.spec["server"], cell.traffic
    binding, ref = cell.binding(), cell.reference()
    annotate = (jax.profiler.TraceAnnotation if trace
                else (lambda name: contextlib.nullcontext()))

    # ---- set-up: the program, the benchmark's weights, every shape --
    mesh_mod.set_mesh(None)
    model = binding.build_serving(cfg, srv["max_model_len"])
    nm = binding.name_map(cfg, model)
    install_weights(model, nm, ref.param_specs(cfg), seed, jnp.bfloat16)
    jax.block_until_ready([p._value for p in model.parameters()])
    ph.mark("model_and_weights")
    server = GenerationServer(
        model, num_slots=srv["num_slots"], block_size=srv["block_size"],
        max_model_len=srv["max_model_len"],
        prompt_buckets=srv["prompt_buckets"],
        max_prefill_batch=srv["max_prefill_batch"], prefix_cache=False,
        max_waiting=int(tr.get("max_waiting", 4 * tr.get("clients", 64))),
        request_timeout_s=600.0,
        seed=int(seed) & 0x7FFFFFFF)
    if sabotage is not None:
        sabotage(server)     # tests break the timed path underneath
    server.start()
    ph.mark(f"server_start_{server.num_compiles()}_programs")
    traffic = ServeTraffic(tr, cfg["vocab_size"], seed)
    clients = traffic.clients
    gc.collect()
    gc.freeze()
    gc.disable()

    reqs, active, next_k = [], {}, [0] * clients
    schedule = traffic.schedule() if traffic.open else None
    pending = next(schedule) if schedule else None
    late = []
    phase = "warmup"
    t_warm_end = time.perf_counter() + float(tr["warmup_s"])
    t0 = t1 = None
    c0 = c1 = None
    tc = {}
    tracer = C.SubWindowTrace(
        cell.name, on_edge=lambda w: tc.__setitem__(w, _counters(server))
    ) if trace else None

    def submit(client, rq=None, due=None):
        if rq is None:
            rq = traffic.request(client, next_k[client])
            next_k[client] += 1
        rec = {"client": client, "k": rq["k"], "prompt": rq["prompt"],
               "max_new": rq["max_new_tokens"], "token_times": [],
               "tokens": [], "done": False, "failed": False}
        with annotate("bench.submit"):
            now_ = time.perf_counter()
            rec["t_submit"] = now_ if due is None else due
            try:
                rec["stream"] = server.submit(
                    rq["prompt"], max_new_tokens=rq["max_new_tokens"])
            except Exception as e:       # noqa: BLE001 -- refused
                rec["done"], rec["failed"] = True, True
                C.log(f"request {client}/{rq['k']} refused: {e!r}")
        if due is not None:
            late.append(now_ - due)
        reqs.append(rec)
        if not rec["failed"]:
            active[len(reqs)] = rec

    try:
        t_traffic0 = time.perf_counter()
        if not traffic.open:
            for c in range(clients):
                submit(c)
        t_drain = None
        while True:
            now = time.perf_counter()
            if phase == "warmup" and now >= t_warm_end:
                c0 = _counters(server)
                t0 = time.perf_counter()
                phase = "window"
                ph.mark("warm_up_traffic")
                C.log("set-up " + json.dumps(ph.report()))
                if tracer:
                    tracer.arm(t0, seconds)
            elif phase == "window" and now >= t0 + seconds:
                t1 = t0 + seconds
                c1 = _counters(server)
                phase, t_drain = "drain", now
            while (pending is not None and phase != "drain"
                   and t_traffic0 + pending[0] <= now):
                submit(pending[1]["client"], pending[1],
                       due=t_traffic0 + pending[0])
                pending = next(schedule)
            with annotate("bench.wait_streams"):
                for key, r in list(active.items()):
                    client = r["client"]
                    st = r["stream"]
                    try:
                        while True:
                            tok = st.__next__(timeout=0)
                            r["token_times"].append(time.perf_counter())
                            r["tokens"].append(tok)
                    except queue.Empty:
                        continue
                    except StopIteration:
                        r["done"] = True
                    except Exception as e:       # noqa: BLE001
                        r["done"], r["failed"] = True, True
                        C.log(f"request {client}/{r['k']} failed: {e!r}")
                    del active[key]
                    if phase != "drain" and not traffic.open:
                        submit(client)
                if phase == "drain":
                    if not active:
                        break
                    if now - t_drain > DRAIN_TIMEOUT_S:
                        for r in active.values():
                            r["failed"] = True
                        C.log(f"{len(active)} requests never answered")
                        break
                time.sleep(POLL_S)
        t_end = time.perf_counter()
        summary = tracer.finish() if tracer else None
        peak = C.peak_memory(devs)
        stats_end = server.stats()
    finally:
        gc.enable()
        server.stop()
    # free the program's state before the reference runs
    del server, model
    gc.unfreeze()
    gc.collect()
    C.log(f"program freed: {C.bytes_in_use(devs) / 2**30:.2f} GiB still "
          f"in use, peak was {peak / 2**30:.2f} GiB")

    # ---- the window's numbers --------------------------------------
    # a traced run reads counters and client times over the part of
    # the window before the profiler started (its start and stop stall
    # the host), and the trace over the rest
    if tracer and "start" in tc and tracer.t_start:
        t1, c1, seconds = tracer.t_start, tc["start"], tracer.t_start - t0
    measured = [r for r in reqs if t0 <= r["t_submit"] < t1]
    for r in measured:
        if len(r["tokens"]) != r["max_new"]:
            r["failed"] = True
    failed = sum(1 for r in measured if r["failed"])
    ttft = S.ttft_samples(measured, worst=t_end)
    itl = S.itl_samples(measured)
    out_tokens = S.tokens_in_window(reqs, t0, t1)
    e2e = {"serve_out_tokens_per_s": out_tokens / seconds,
           "ttft_p95_ms": _ms(S.percentile(ttft, 95)),
           "itl_p95_ms": _ms(S.percentile(itl, 95)),
           "setup_s": t0 - t_proc0}
    gi, gv = S.longest(itl)
    ti, tv = S.longest(ttft)
    C.write_run_log(cell.name, seed, {
        "requests_submitted_in_window": len(measured),
        "requests_failed": failed, "out_tokens_in_window": out_tokens,
        "ttft_ms": {q: _ms(S.percentile(ttft, q)) for q in (50, 95)},
        "itl_ms": {q: _ms(S.percentile(itl, q)) for q in (50, 95)},
        "itl_samples": len(itl), "longest_itl_ms": _ms(gv),
        "longest_itl_index": gi, "longest_ttft_ms": _ms(tv),
        "longest_ttft_index": ti,
        "longest_ttft_at_s": (None if ti is None
                              else measured[ti]["t_submit"] - t0),
        "generator_late_ms_max": _ms(max(late)) if late else None,
        "drain_s": t_end - t1, "warmup_requests": sum(
            1 for r in reqs if r["t_submit"] < t0),
        "window": _delta(c0, c1)})

    # ---- correct: served tokens against the plain reference --------
    sample = K.choose_sample(measured, cell.spec["correct"]["sample"], seed)
    numbers = {"requests_unanswered": float(failed)}
    t_ref = time.perf_counter()
    if sample:
        gaps = K.serve_gaps(ref, cfg, seed, jnp.bfloat16, sample, controls,
                            pad_to=srv["max_model_len"])
        numbers["served_logit_gap"] = gaps["program"]["gap"]
        numbers["served_mismatch_share"] = gaps["program"]["mismatch_share"]
        numbers["served_tokens_compared"] = float(gaps["program"]["tokens"])
        for m in controls:
            numbers[f"control_{m}_logit_gap"] = gaps[m]["gap"]
            numbers[f"control_{m}_mismatch_share"] = \
                gaps[m]["mismatch_share"]
    C.log(f"reference took {time.perf_counter() - t_ref:.1f} s over "
          f"{len(sample)} requests")
    limits = cell.spec["correct"]["limits"]
    ok, table = K.judge(numbers, limits)
    ok = ok and bool(sample)
    for k, v in numbers.items():
        table.setdefault(k, [v, None])
    # each control's numbers put in the program's place and judged by
    # the cell's own limits: it has to come out as not correct
    verdicts = {}
    for m in controls if sample else ():
        verdicts[m] = K.judge_in_place(
            {"requests_unanswered": float(failed),
             "served_logit_gap": gaps[m]["gap"]}, limits, f"control {m}")

    ctx = {"cell": cell, "cfg": cfg, "traffic": tr, "server": srv,
           "seconds": seconds, "end_to_end": e2e, "measured": measured,
           "requests": reqs, "t0": t0, "t1": t1, "ttft": ttft, "itl": itl,
           "window": _delta(c0, c1), "stats_end": stats_end,
           "trace": summary, "trace_counters": (
               _delta(tc["start"], tc["stop"])
               if "start" in tc and "stop" in tc else None),
           "peak_bytes": peak, "device": device, "chips": cell.chips,
           "out_tokens": out_tokens}
    device = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if trace:
        breakdown = C.trace_device_fields(device, summary)
    metrics = C.read_metrics(cell, "per_layer" if trace else "end_to_end",
                             ctx)
    return {"correct": ok, "attempted": len(measured), "failed": failed,
            "metrics": metrics, "device": device, "breakdown": breakdown,
            "compared": table, "verdicts": verdicts}


def _ms(x):
    return None if x is None else x * 1e3
