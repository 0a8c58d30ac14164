"""paddle_tpu.observability — the runtime's *metrics and tracing* half.

The profiler (``paddle_tpu.profiler``) answers "where did this step's
time go" with spans; this package answers the fleet questions — how
often the fused-conv Pallas path fired vs. fell back to XLA, how many
times each jitted entry point recompiled and for how long, what the
per-step tokens/s and device-memory watermarks were, and (since the
tracing half landed) what happened to EACH serving request — as cheap
always-on instruments with Prometheus/JSONL/Chrome-trace export.

Layout:
- ``metrics``:    thread-safe Counter/Gauge/Histogram/Summary registry
                  (lock-free writer hot path — a deque append, no lock
                  per op; Summary = streaming p50/p95/p99 over a
                  sliding sample window).
- ``exporters``:  Prometheus text exposition, JSONL snapshots, the
                  size-rotating JSONL sink (``RotatingJsonlSink``,
                  ``$PADDLE_TPU_SINK_DIR`` override), opt-in stdlib
                  http scrape endpoint (``start_http_server``).
- ``recompile``:  jax.monitoring compile listeners + ``entrypoint``
                  attribution + retrace warnings; compiles are ALSO
                  attributed into the active request trace.
- ``telemetry``:  ``StepTelemetry`` per-step records (step time, ips,
                  memory watermarks, compile deltas) feeding the hapi
                  callback and ``bench.py``; JSONL stream is rotation-
                  bounded.
- ``tracing``:    request-lifecycle spans/instants (default-on,
                  host-side only), Chrome-trace + JSONL export, the
                  flight-recorder ring + crash dumps, streaming
                  latency ``Digest``s.
- ``perf``:       per-executable cost/roofline attribution (XLA
                  ``cost_analysis``/``memory_analysis`` captured at
                  compile time, joined with measured entry timings
                  into MFU / achieved GB/s / roofline class), the HBM
                  ledger, OOM forensics dumps, and the
                  perf-regression-gate helpers.
- ``fleet``:      the fleet observability plane — traceparent
                  propagation helpers + catapult merge, the router-side
                  metric-federation aggregator, SLO burn-rate tracking
                  (``SLOConfig``/``SLOTracker``), and the robust
                  MAD straggler score.

Trace event schema (``tracing.events()`` rows / trace JSONL lines)::

    {"ph":   "X" (complete span) | "i" (instant),
     "name": span name — request lifecycle: request | queued |
             prefill | prefill_chunk | decode; instants: admitted |
             resume | first_token | prefix_cache_hit |
             prefix_cache_miss | cow_fork | preempted | requeued |
             completed | cancelled | expired | failed | rejected;
             engine: serving.step | engine.iter and its phases |
             engine.idle | engine.stall (instant: an iteration, or
             the gap between two, longer than tracing.STALL_NS, with
             its longest phase and the thread's CPU time); process
             (lane "proc", on once a loop is started): proc.watch
             (instant: the lane's first event) | proc.pause (the beat
             thread woke late and the collector's passes do not
             explain it: the whole process stood still; args cpu_ms,
             majflt) | proc.gc (a long or full pass of the collector;
             args gen, collected);
             generation: generation.prefill | generation.decode |
             generation.generate; compiles: xla_compile:<entry>,
     "cat":  request | engine | proc | generation | compile | profiler,
     "trace": serving request id | "engine" | "proc" | null,
     "tid":  recording OS thread ident,
     "ts_ns": monotonic perf_counter_ns start,
     "dur_ns": span duration (0 for instants),
     "args": optional small dict (slot, chunk range, block counts...)}

``chrome_trace()`` renders the same events as catapult JSON (one
swimlane per trace id; spans nest within the per-request ``request``
root span). ``GET /trace`` on the serving HTTP server serves it live.

``snapshot()`` is the one-call view of all of it — including the
serving gauges + block-pool stats (when an engine is alive) and the
tracing summary, so one snapshot captures the full system state.

Importing this package installs the jax.monitoring listeners (a list
append inside jax; per-event cost is one callback). ``disable()``
reduces every instrumentation site — metrics AND tracing — to a single
list-index check.
"""

from __future__ import annotations

import time

from . import exporters, fleet, metrics, perf, recompile, telemetry, tracing
from .exporters import (RotatingJsonlSink, parse_prometheus_text,
                        prometheus_text, render_families,
                        resolve_sink_path,
                        start_http_server, stop_http_server,
                        write_jsonl_snapshot)
from .fleet import (FleetMetricsAggregator, SLOConfig, SLOTracker,
                    attempt_trace_id, format_traceparent, mad_zscores,
                    merge_catapult, parse_traceparent)
from .metrics import (DEFAULT_BUCKETS, DEFAULT_QUANTILES, Counter, Gauge,
                      Histogram, MetricsRegistry, Summary, counter, gauge,
                      get_registry, histogram, summary)
from .metrics import _ENABLED
from .perf import (MEMORY_STATS_UNSUPPORTED, compare_to_baseline, dump_oom,
                   hbm_ledger, is_oom_error, ledger, peak_specs,
                   register_memory_component)
from .recompile import compile_events, current_entry, entry_stats, entrypoint
from .telemetry import StepTelemetry, memory_watermarks, step_records
from .tracing import (Digest, chrome_trace, disable_tracing, enable_tracing,
                      flight_dump, instant, register_state_provider, span,
                      trace_context, tracing_enabled)

__all__ = [
    "Counter", "Gauge", "Histogram", "Summary", "MetricsRegistry",
    "DEFAULT_BUCKETS", "DEFAULT_QUANTILES",
    "counter", "gauge", "histogram", "summary", "get_registry",
    "prometheus_text", "parse_prometheus_text", "render_families",
    "write_jsonl_snapshot",
    "start_http_server", "stop_http_server",
    "RotatingJsonlSink", "resolve_sink_path",
    "entrypoint", "current_entry", "compile_events", "entry_stats",
    "StepTelemetry", "memory_watermarks", "step_records",
    "tracing", "span", "instant", "trace_context", "chrome_trace",
    "flight_dump", "register_state_provider", "Digest",
    "enable_tracing", "disable_tracing", "tracing_enabled",
    "perf", "ledger", "hbm_ledger", "peak_specs", "is_oom_error",
    "dump_oom", "compare_to_baseline", "register_memory_component",
    "MEMORY_STATS_UNSUPPORTED",
    "fleet", "FleetMetricsAggregator", "SLOConfig", "SLOTracker",
    "attempt_trace_id", "format_traceparent", "parse_traceparent",
    "mad_zscores", "merge_catapult",
    "snapshot", "enable", "disable", "enabled",
]

# Recompile monitoring is the subsystem's reason to exist; subscribe as
# soon as the package is imported so no compile goes unattributed. Perf
# capture rides the same funnel (compile_or_get_cached wrapper + entrypoint
# call hook) — compile-time + host-side only, nothing on the dispatch
# fast path.
recompile.install()
perf.install()


def enable():
    _ENABLED[0] = True


def disable():
    """Kill switch: instrumentation sites reduce to one flag check."""
    _ENABLED[0] = False


def enabled() -> bool:
    return _ENABLED[0]


def _serving_state() -> dict:
    """The serving slice of a snapshot: every ``paddle_tpu_serving_*``
    / KV-block gauge currently registered (scrape-free), plus the live
    engine's ``stats()`` — queue, slots, block-pool accounting, prefix
    cache — via the flight-recorder state providers."""
    gauges = {}
    for m in get_registry().metrics():
        if m.kind != "gauge":
            continue
        if m.name.startswith(("paddle_tpu_serving_", "paddle_tpu_kv_")):
            samples = m.collect()
            if not m.labelnames:
                gauges[m.name] = samples[0]["value"] if samples else None
            else:
                gauges[m.name] = samples
    return {"gauges": gauges, **tracing.state_snapshot()}


def snapshot() -> dict:
    """Full observability state as one JSON-ready dict:

    - ``metrics``: every registered metric's samples (counters, gauges,
      histograms with bucket counts, summaries with quantiles),
    - ``compile_events``: the recent-compile flight recorder
      (entry, duration_s, ts),
    - ``entries``: per-entry-point call/compile/retrace totals,
    - ``steps``: the per-step telemetry ring (step time, ips, memory
      watermarks, compile deltas),
    - ``serving``: the serving gauges + (when an engine is alive) its
      full ``stats()`` incl. block-pool accounting — one call captures
      the whole system state, no scrape needed,
    - ``tracing``: span counts per phase, buffered-event count, last
      flight-dump path,
    - ``perf``: the per-executable cost/roofline ledger (flops, bytes,
      arithmetic intensity, MFU, roofline class), the HBM ledger
      (subsystem byte attribution + headroom), and the device peak
      table in force.
    """
    return {
        "ts": time.time(),
        "metrics": get_registry().collect(),
        "compile_events": compile_events(),
        "entries": entry_stats(),
        "steps": step_records(),
        "serving": _serving_state(),
        "tracing": tracing.summary(),
        "perf": perf.perf_snapshot(),
    }
