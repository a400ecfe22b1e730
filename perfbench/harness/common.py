"""Pieces every runner shares: the look for the chip, the peak memory,
the traced sub-window, the run log and the result line."""
from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time

from . import manifest as M
from . import trace_reduce as TR

OUT_DIR = os.path.join(M.ROOT, "perfbench_out")
TRACE_LEN_S = 3.0        # the last seconds of a traced run's window


class NoChip(RuntimeError):
    pass


def log(msg: str):
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


class Phases:
    """Where set-up's seconds go: ``mark(name)`` logs the time since
    the last mark; ``report()`` is kept for the run log."""

    def __init__(self, t_proc0: float):
        self.t = t_proc0
        self.rows = []

    def mark(self, name: str):
        now = time.perf_counter()
        self.rows.append([name, round(now - self.t, 2)])
        self.t = now

    def report(self):
        from paddle_tpu.framework import compile_cache
        return {"phases_s": self.rows,
                "cache_dir": compile_cache.cache_dir(),
                "cache_entries": compile_cache.cache_entries()}


def device_info(chips: int, require_chip: bool):
    """The devices a cell runs on.  On the command line a TPU with at
    least ``chips`` chips is required: no fallback."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip:
        if info["platform"] != "tpu":
            raise NoChip(f"needs a TPU, JAX found {info}")
        from .peaks import peaks_for
        peaks_for(info["kind"])      # an unknown kind is an error
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {info}")
    return devs[:chips], info


def peak_memory(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend does
    not report it, as the CPU's)."""
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def bytes_in_use(devs) -> int:
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devs)


class SubWindowTrace:
    """Profiler trace of the LAST few seconds of a traced run's
    window, started and stopped by a helper thread so that the window's
    own thread keeps its pace.  Starting and stopping the profiler
    stalls the host for seconds, so a traced run reads its counters and
    client times over the part of the window BEFORE the trace
    (``t_start``), and the trace over the rest.  ``on_edge`` is called
    at start and stop (a runner reads its counters there).  Python's
    own tracer is off: it slows the host it would measure."""

    def __init__(self, cell_name: str, on_edge=None):
        self.dir = os.path.join(OUT_DIR, "trace", cell_name)
        self.on_edge = on_edge or (lambda which: None)
        self.t_start = None      # when the profiler was asked to start
        self._th = None
        self._stop = threading.Event()

    @staticmethod
    def start_offset(seconds: float) -> float:
        return max(seconds - TRACE_LEN_S, seconds * 0.5)

    def arm(self, window_t0: float, seconds: float):
        start = self.start_offset(seconds)
        length = seconds - start

        def work():
            import jax
            delay = window_t0 + start - time.perf_counter()
            if delay > 0 and self._stop.wait(delay):
                return
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            self.on_edge("start")
            self.t_start = time.perf_counter()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._stop.wait(length)
            jax.profiler.stop_trace()
            self.on_edge("stop")
        self._th = threading.Thread(target=work, name="perfbench-trace",
                                    daemon=True)
        self._th.start()

    def finish(self):
        """Wait for the helper, reduce the trace, delete its files."""
        if self._th is not None:
            self._th.join(timeout=300)
        path = TR.find_xplane(self.dir)
        if path is None:
            return None
        t = time.perf_counter()
        summary = TR.summarize(TR.events_from_xplane(path))
        log(f"trace reduced in {time.perf_counter() - t:.1f} s "
            f"({os.path.getsize(path) / 1e6:.1f} MB); Mosaic kernels "
            f"{summary['mosaic_kernels']}; custom calls "
            f"{summary['custom_calls']}; programs "
            f"{ {k: len(v) for k, v in summary['programs'].items()} }")
        shutil.rmtree(self.dir, ignore_errors=True)
        return summary


def trace_device_fields(device: dict, summary) -> dict:
    """``busy_s`` and ``window_s`` into ``device`` and the breakdown
    for the result line.  On the chip a trace with no device op is an
    error; a CPU rehearsal has no device plane and writes neither."""
    if summary is None or summary["busy_s"] <= 0:
        if device["platform"] == "tpu":
            raise RuntimeError("the traced window holds no device op")
        return None
    device["busy_s"] = summary["busy_s"]
    device["window_s"] = summary["window_s"]
    return {"device_ops": summary["device_ops"],
            "idle_gaps": summary["idle_gaps"]}


def read_metrics(cell, which: str, ctx: dict) -> dict:
    """The cell's end-to-end metrics (``which`` = "end_to_end", values
    the runner measured itself) or its per-layer metrics (one small
    reader each; a reader that finds nothing returns None and the
    metric is left out)."""
    out = {}
    if which == "end_to_end":
        for m in cell.end_to_end:
            v = ctx["end_to_end"].get(m["name"])
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out
    for m in cell.per_layer:
        v = cell.metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(correct, attempted, failed, metrics, device, breakdown,
                compared) -> str:
    res = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["compared"] = compared        # last: each number, its limit
    return json.dumps(res)


def write_run_log(cell_name: str, seed: int, record: dict):
    """The run explains itself: counts, the longest step or gap, its
    index and its time -- on stderr and in a small file."""
    log("run " + json.dumps(record))
    try:
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(os.path.join(OUT_DIR, f"{cell_name}.last_run.json"),
                  "w") as f:
            json.dump({"seed": seed, **record}, f)
    except OSError:
        pass
