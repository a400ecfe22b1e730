"""paddle_tpu.observability — unified tracing, metrics and timelines.

The ISSUE 5 subsystem, three pillars.  The file sinks (JSONL spans,
``/metrics``) are off by default and opt-in by env; the step
timeline's profiler annotations and in-memory ring are always on and
bounded (1.5 to 7 us a phase), and so is the flight recorder's ring:

1. **Cross-process tracing** (:mod:`.trace`): ``Span`` trees with
   trace/span-id propagation stamped through the PS RPC frame header,
   per-process JSONL sinks, clock-offset samples from RPC round trips;
   ``tools/trace_merge.py`` fuses the sinks into one Chrome/Perfetto
   trace where a trainer's ``ps.client.push`` span contains the
   server's ``ps.server.push`` apply span.
2. **Metrics** (:mod:`.metrics` over the
   :mod:`~paddle_tpu.framework.monitor` StatRegistry): counters,
   gauges and fixed-bucket histograms from the hot seams (PS retries/
   failovers, serving queue/latency, DataLoader prefetch, TrainGuard
   verdicts), exported as a Prometheus ``/metrics`` endpoint and/or a
   periodic JSONL flusher.
3. **Step timeline** (:mod:`.timeline`): per-step phase attribution
   for the train step (data wait / h2d / dispatch / health fetch /
   host) and the serving scheduler's loop (``serve.admit`` /
   ``serve.prefill.*`` / ``serve.decode.*``).  Every phase is a
   ``jax.profiler.TraceAnnotation`` (on the device ops' clock in a
   profiler trace) and one row of a bounded in-memory ring read with
   ``timeline.spans(name, since, until)``; JSONL spans with
   ``trace_every=N`` sampling and ``step_<phase>_ms`` histograms as
   before.

ISSUE 12 grows the subsystem into a FLEET observatory:

4. **Per-request tracing + tenants** (:mod:`.request_trace`): one
   span lane per serving request (submit -> queue -> admit -> prefill
   -> sampled decode -> finish) and ``tenant=`` usage accounting via
   the registry's new labeled series.
5. **Fleet aggregator** (:mod:`.aggregator`): scrapes N
   ``/metrics.json`` endpoints or flusher JSONL files, merges
   counters/le-buckets EXACTLY, computes per-process rates, flags
   stragglers (k x MAD below fleet median) and stale scrapees;
   ``/fleet`` + ``tools/fleet_top.py``.
6. **SLO engine** (:mod:`.slo`): declarative objectives (latency
   percentile, error rate, gauge bound) with multi-window burn
   rates; a breach is a flight-recorder event + postmortem bundle.
7. **Block scopes** (:mod:`.scopes`): one vocabulary of
   ``jax.named_scope`` names (``attn``, ``mixer``, ``moe``, ``ffn``,
   ``embed``, ``head``, ``sample``, ``mtp``) that every served model
   and the server's sampler write, so that a device trace's ``tf_op``
   splits a step's device time by block.

Env quick reference::

    PADDLE_TRACE=1  PADDLE_TRACE_DIR=... PADDLE_TRACE_ROLE=...
    PADDLE_TRACE_EVERY=16
    PADDLE_METRICS=1  PADDLE_METRICS_PORT=9464  PADDLE_METRICS_FILE=...
    PADDLE_METRICS_HOST=127.0.0.1   (loopback default; opt into wider)

Importable without jax (PS server subprocesses stay lightweight; the
first ``StepTimeline`` made imports ``jax.profiler``).
"""
from __future__ import annotations

from ..framework.monitor import (  # noqa: F401
    Histogram, enable_metrics, gauge_add, gauge_get, gauge_set,
    get_histogram, hist_observe, metrics_enabled, metrics_reset,
    metrics_snapshot, stat_add, stat_get)
from . import (aggregator, flight_recorder, metrics,  # noqa: F401
               request_trace, scopes, slo, timeline, trace)
from .aggregator import FleetAggregator  # noqa: F401
from .flight_recorder import (  # noqa: F401
    FlightRecorder, Watchdog, compile_log, flight_dump, flight_enabled,
    flight_record)
from .metrics import (  # noqa: F401
    MetricsFlusher, MetricsServer, prometheus_text, start_metrics_server)
from .request_trace import RequestTrace  # noqa: F401
from .slo import SLO, SloEngine  # noqa: F401
from .timeline import StepTimeline  # noqa: F401
from .trace import (  # noqa: F401
    Span, disable as disable_tracing, enable as enable_tracing, enabled
    as tracing_enabled, propagation_ctx, record_clock, server_span, span)

__all__ = [
    "trace", "metrics", "timeline", "flight_recorder", "aggregator",
    "slo", "request_trace", "scopes",
    "Span", "span", "server_span", "propagation_ctx", "record_clock",
    "enable_tracing", "disable_tracing", "tracing_enabled",
    "StepTimeline", "Histogram", "RequestTrace",
    "FleetAggregator", "SLO", "SloEngine",
    "FlightRecorder", "Watchdog", "flight_record", "flight_dump",
    "flight_enabled", "compile_log",
    "MetricsServer", "MetricsFlusher", "prometheus_text",
    "start_metrics_server",
    "enable_metrics", "metrics_enabled", "metrics_snapshot",
    "metrics_reset", "gauge_set", "gauge_add", "gauge_get",
    "hist_observe", "get_histogram", "stat_add", "stat_get",
]

# honour PADDLE_METRICS / PADDLE_METRICS_PORT / PADDLE_METRICS_FILE
metrics.enable_from_env()
# honour PADDLE_FLIGHT (full mode installs the dump triggers)
flight_recorder.enable_from_env()
