"""Performance observability: per-executable cost/roofline attribution,
the HBM ledger, OOM forensics, and the perf-regression gate helpers.

The fourth leg of the observability stack. The metrics half (PR 2)
counts events, the tracing half (PR 7) timelines requests; this module
answers the *efficiency* questions — is this executable compute- or
bandwidth-bound, what is its MFU, and where did the HBM go — the
numbers MFU-accounting practice (PaLM-style ``model_flops /
peak_flops`` reporting) and vLLM-class serving systems treat as
first-class telemetry.

How capture works (zero extra compiles, host-side only):

- every executable a jit dispatch builds funnels through
  ``jax._src.compiler.compile_or_get_cached`` — a fresh XLA compile
  and a persistent-compilation-cache load alike, inside the scope that
  emits the ``backend_compile_duration`` monitoring event the
  recompile monitor listens to. ``install()`` wraps it once; the
  wrapper reads the recompile monitor's ``entrypoint()`` stack (the
  compile runs synchronously on the dispatching thread) and extracts
  ``cost_analysis()`` / ``get_compiled_memory_stats()`` from the
  returned executable. Nothing is recompiled, nothing touches the
  dispatch fast path — capture costs one dict-read per *compile*.
- an entry that compiles several programs (e.g. a tiny dtype-convert
  plus the real step) keeps the DOMINANT executable's analysis (max
  flops, then max bytes) and counts the rest.
- an entry's time is what its OWNER hands it with ``note_entry_time``
  from an interval that ends in a host sync (the serving engine:
  dispatch of ``serving.step`` to its tokens on the host). A jitted
  call returns before the device has run it, so the wall time of the
  ``entrypoint()`` scope around it is the enqueue, and static FLOPs
  over that is no rate: this module no longer times those scopes.
  Where no synced interval exists (prefill chunks, the asynchronous
  train step) the ledger's achieved FLOP/s, GB/s, MFU and bandwidth
  fields are null. The device's own times are in a profiler trace
  (``perfbench/trace_reduce.py``).

Roofline classification compares each entry's arithmetic intensity
(flops / bytes accessed) against the device's machine balance
(peak FLOP/s / peak bytes/s) from ``peak_specs()``: a published
per-chip peak table with ``PADDLE_TPU_PEAK_FLOPS`` /
``PADDLE_TPU_PEAK_HBM_GBPS`` env overrides. CPU (and unknown device
kinds) get honest ``None`` peaks and a ``"unknown"`` roofline class —
never a made-up MFU. Note the GSPMD convention: ``cost_analysis`` for
a partitioned program reports PER-PARTITION numbers, matching the
per-chip peaks and the per-chip MFU convention.

The **HBM ledger** (``hbm_ledger()``) attributes live device bytes to
subsystems: components registered by their owners (the serving engine
registers its KV pools and model weights; ``ShardedTrainStep``
registers params/optimizer state), per-executable temp/output sizes
from the captured memory analyses, and headroom against PJRT's
``bytes_limit`` (``core/memory.py`` accessors; ``"unsupported"``
where the transport reports nothing — the one shared fallback label,
``MEMORY_STATS_UNSUPPORTED``).

**OOM forensics**: ``is_oom_error`` recognizes RESOURCE_EXHAUSTED /
allocator-failure shapes, and ``dump_oom`` writes a flight-recorder
dump whose ``extra`` names the top-k executables by temp bytes next to
the HBM ledger — so an OOM names its culprit instead of dying with an
XLA backtrace. A ``perf`` state provider is registered with the
flight recorder, so EVERY dump (engine crash, pool exhaustion,
SIGTERM) carries the ledger too.

**Perf-regression gate**: ``collect_bench_metrics`` flattens the
committed bench artifacts (serving / paged-KV / spec-decode tok/s,
capacity ratios), ``load_baseline`` reads
``benchmarks/perf_baseline.json`` (per-metric value + pinned
tolerance), and ``compare_to_baseline`` produces the verdict
``run_shards.py`` merges into ``telemetry_lane.json`` and fails the
lane on. This is what starts populating the BENCH_* trajectory
artifacts going forward.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from . import metrics as _m
from . import recompile as _rc

__all__ = [
    "install", "installed", "enable", "disable", "perf_enabled",
    "extract_cost_analysis", "extract_memory_analysis",
    "capture_compiled", "MEMORY_STATS_UNSUPPORTED",
    "peak_specs", "PEAK_FLOPS_ENV", "PEAK_HBM_ENV",
    "ledger", "ledger_entry", "note_entry_items", "note_entry_time",
    "reset",
    "register_memory_component", "unregister_memory_component",
    "hbm_ledger",
    "is_oom_error", "oom_report", "dump_oom",
    "collect_bench_metrics", "load_baseline", "compare_to_baseline",
    "mfu_gauge", "hbm_bw_util_gauge",
]

logger = logging.getLogger("paddle_tpu.observability")

# The one PJRT-absent fallback label: StepTelemetry JSONL records, the
# profiler summary, and the HBM ledger all spell "memory_stats gave us
# nothing" the same way.
MEMORY_STATS_UNSUPPORTED = "unsupported"
# ...and the human-facing spelling the profiler summary table prints.
PJRT_MEMORY_UNSUPPORTED_NOTE = (
    f"n/a (PJRT memory_stats {MEMORY_STATS_UNSUPPORTED})")

PEAK_FLOPS_ENV = "PADDLE_TPU_PEAK_FLOPS"
PEAK_HBM_ENV = "PADDLE_TPU_PEAK_HBM_GBPS"

# Published per-CHIP peaks: (dense bf16 FLOP/s, HBM GB/s). Matched
# against jax's device_kind by longest prefix, so "TPU v4 (podslice)"
# style strings still resolve. CPU is deliberately absent: no honest
# peak exists for arbitrary hosts, and the env override is the escape
# hatch for anything unlisted.
_PEAK_TABLE = (
    ("TPU v6", (918e12, 1640.0)),   # Trillium
    ("TPU v5p", (459e12, 2765.0)),
    ("TPU v5 lite", (197e12, 819.0)),
    ("TPU v5e", (197e12, 819.0)),
    ("TPU v4", (275e12, 1228.0)),
    ("TPU v3", (123e12, 900.0)),
    ("TPU v2", (45e12, 600.0)),
)

_enabled = [os.environ.get("PADDLE_TPU_PERF", "1") != "0"]
_installed = [False]
_install_lock = threading.Lock()

_lock = threading.Lock()
# entry -> ledger record (see _new_rec); writer paths take _lock only
# on compile capture (rare); note_entry_time appends to a deque.
_entries: Dict[str, dict] = {}

# timing window per entry: achieved numbers use the recent mean so a
# slow warmup call ages out of the published MFU
_TIMING_WINDOW = 64

# thread-local set of entries that compiled during the CURRENT call:
# note_entry_time drops that call's time (it includes the XLA
# compile — folding it in would understate steady-state MFU wildly)
_tls = threading.local()

mfu_gauge = _m.gauge(
    "paddle_tpu_mfu",
    "model FLOPs utilization per jitted entry point: captured "
    "executable flops / recent mean call time / peak device FLOP/s "
    "(absent peaks -> gauge not set)", ("entry",))
hbm_bw_util_gauge = _m.gauge(
    "paddle_tpu_hbm_bw_util",
    "achieved HBM bandwidth fraction per jitted entry point: captured "
    "bytes accessed / recent mean call time / peak HBM bytes/s "
    "(absent peaks -> gauge not set)", ("entry",))
_captures_total = _m.counter(
    "paddle_tpu_perf_captures_total",
    "compiled executables whose cost/memory analysis was captured into "
    "the perf ledger", ("entry",))
_oom_dumps_total = _m.counter(
    "paddle_tpu_oom_dumps_total",
    "OOM forensics dumps written (flight-recorder dumps triggered by "
    "allocation failures)")


def enable():
    _enabled[0] = True


def disable():
    """Reduce the capture + timing sites to one flag check (the bench
    A/B lane's OFF arm)."""
    _enabled[0] = False


def perf_enabled() -> bool:
    return _enabled[0] and _m._ENABLED[0]


# ---------------------------------------------------------------------------
# analysis extraction (the ONE cost-extraction path; distributed/engine.py
# and the profiler route through these)
# ---------------------------------------------------------------------------


def extract_cost_analysis(compiled) -> Optional[dict]:
    """XLA's per-execution cost model as ``{"flops", "bytes_accessed"}``
    from either a ``jax.stages.Compiled`` or a raw PJRT
    ``LoadedExecutable``; ``None`` when the backend reports nothing.
    GSPMD-partitioned programs report PER-PARTITION numbers (one
    device's share — the per-chip MFU convention)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if not ca:
        return None
    flops = ca.get("flops")
    bytes_accessed = ca.get("bytes accessed")
    if flops is None and bytes_accessed is None:
        return None
    return {"flops": flops, "bytes_accessed": bytes_accessed}


_MEM_FIELDS = (
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("temp_bytes", "temp_size_in_bytes"),
    ("generated_code_bytes", "generated_code_size_in_bytes"),
)


def extract_memory_analysis(compiled) -> Optional[dict]:
    """The compiled program's HBM breakdown (argument/output/temp/
    generated-code bytes) from either a ``jax.stages.Compiled``
    (``memory_analysis()``) or a raw PJRT ``LoadedExecutable``
    (``get_compiled_memory_stats()``); ``None`` when unsupported."""
    ma = None
    for getter in ("memory_analysis", "get_compiled_memory_stats"):
        fn = getattr(compiled, getter, None)
        if fn is None:
            continue
        try:
            ma = fn()
        except Exception:
            ma = None
        if ma is not None:
            break
    if ma is None:
        return None
    out = {k: getattr(ma, attr, None) for k, attr in _MEM_FIELDS}
    if all(v is None for v in out.values()):
        return None
    return out


# ---------------------------------------------------------------------------
# capture (rides the compile_or_get_cached funnel + the entrypoint() stack)
# ---------------------------------------------------------------------------


def _new_rec() -> dict:
    return {
        "flops": None, "bytes_accessed": None,
        "argument_bytes": None, "output_bytes": None,
        "temp_bytes": None, "generated_code_bytes": None,
        "compiles_captured": 0, "captured_ts": None,
        "calls": 0, "total_time_s": 0.0, "items": 0,
        "mesh": None,
        "recent": deque(maxlen=_TIMING_WINDOW),
    }


def _rec(entry: str) -> dict:
    rec = _entries.get(entry)
    if rec is None:
        with _lock:
            rec = _entries.setdefault(entry, _new_rec())
    return rec


def capture_compiled(entry: str, compiled) -> Optional[dict]:
    """Record ``compiled``'s cost/memory analysis under ``entry`` —
    keeping the dominant executable when the entry already holds one.
    The ``compile_or_get_cached`` wrapper calls this for every
    executable jit builds or loads. Returns the stored analysis."""
    cost = extract_cost_analysis(compiled)
    mem = extract_memory_analysis(compiled)
    if cost is None and mem is None:
        return None
    rec = _rec(entry)
    with _lock:
        rec["compiles_captured"] += 1
        new_key = ((cost or {}).get("flops") or 0.0,
                   (cost or {}).get("bytes_accessed") or 0.0)
        old_key = (rec["flops"] or 0.0, rec["bytes_accessed"] or 0.0)
        if rec["captured_ts"] is None or new_key >= old_key:
            if cost:
                rec["flops"] = cost["flops"]
                rec["bytes_accessed"] = cost["bytes_accessed"]
            if mem:
                for k, _ in _MEM_FIELDS:
                    rec[k] = mem[k]
            rec["captured_ts"] = time.time()
    compiled_now = getattr(_tls, "compiled", None)
    if compiled_now is None:
        compiled_now = _tls.compiled = set()
    compiled_now.add(entry)
    _captures_total.labels(entry).inc()
    return {**(cost or {}), **(mem or {})}


def note_entry_time(entry: str, dt_s: float):
    """One call of ``entry`` took ``dt_s``: the measured half of the
    ledger join, handed over by the entry's owner from an interval that
    ends in a host sync (see the module docstring; an interval that
    ends when the jitted call returns measures the enqueue). A call
    that compiled something on this thread is warmup — its time
    includes the XLA compile and stays out of the achieved-rate
    window."""
    if not perf_enabled():
        return
    compiled_now = getattr(_tls, "compiled", None)
    if compiled_now and entry in compiled_now:
        compiled_now.discard(entry)
        return
    rec = _rec(entry)
    rec["calls"] += 1
    rec["total_time_s"] += dt_s
    rec["recent"].append(dt_s)


def note_entry_mesh(entry: str, axes: Dict[str, int]):
    """Tag ``entry`` as compiled over a device mesh (e.g. ``{"tp": 2}``).

    XLA's cost/memory analysis is captured from the PARTITIONED module,
    so a tagged entry's flops/bytes — and the MFU/roofline derived from
    them against the single-chip peaks — are PER-DEVICE numbers; the
    tag records the mesh so ledger readers can aggregate (multiply by
    the axis product) instead of misreading a tp=4 step as one chip's
    work. Owners call this once at executable build (the serving engine
    does for every ``serving.*`` entry when ``tp > 1``)."""
    _rec(entry)["mesh"] = {k: int(v) for k, v in axes.items()}


def note_entry_items(entry: str, n: int):
    """Credit ``n`` processed items (tokens, samples) to ``entry`` so
    the ledger can report bytes/token and tokens/s. Host-side integer
    add — call it from the code that already knows the count (the
    serving step loop, generate)."""
    if not perf_enabled():
        return
    _rec(entry)["items"] += int(n)


def install() -> bool:
    """Wrap ``jax._src.compiler.compile_or_get_cached`` (idempotent),
    the one funnel every jit dispatch builds its executable through —
    a fresh XLA compile and a persistent-cache load alike — so each
    executable contributes its analyses to the ledger, attributed via
    the recompile monitor's entrypoint stack. Also registers the
    flight-recorder state provider."""
    if _installed[0]:
        return True
    with _install_lock:
        if _installed[0]:
            return True
        from jax._src import compiler as _jcompiler

        orig = _jcompiler.compile_or_get_cached

        def _compile_captured(*args, **kwargs):
            exe = orig(*args, **kwargs)
            if perf_enabled():
                try:
                    capture_compiled(_rc.current_entry(), exe)
                except Exception:  # capture must never break a compile
                    logger.debug("perf capture failed", exc_info=True)
            return exe

        _jcompiler.compile_or_get_cached = _compile_captured
        from . import tracing as _tracing

        _tracing.register_state_provider("perf", _state_provider)
        _installed[0] = True
        return True


def installed() -> bool:
    return _installed[0]


def reset():
    """Clear the ledger + memory components (tests)."""
    with _lock:
        _entries.clear()
    with _components_lock:
        _components.clear()


# ---------------------------------------------------------------------------
# peaks + roofline
# ---------------------------------------------------------------------------


def _device_kind() -> Optional[str]:
    try:
        import jax

        return jax.devices()[0].device_kind
    except Exception:
        return None


def peak_specs(device_kind: Optional[str] = None) -> dict:
    """Peak FLOP/s and HBM GB/s for the attached device: env overrides
    (``PADDLE_TPU_PEAK_FLOPS`` in FLOP/s, ``PADDLE_TPU_PEAK_HBM_GBPS``
    in GB/s) beat the published per-chip table; unknown kinds — CPU
    included — get honest ``None`` peaks, never a guess."""
    kind = device_kind if device_kind is not None else _device_kind()
    flops = hbm = None
    source = "unknown"
    if kind:
        for prefix, (f, b) in _PEAK_TABLE:
            if kind.startswith(prefix):
                flops, hbm, source = f, b, "table"
                break
    env_f = os.environ.get(PEAK_FLOPS_ENV)
    env_b = os.environ.get(PEAK_HBM_ENV)
    try:
        if env_f:
            flops, source = float(env_f), "env"
        if env_b:
            hbm = float(env_b)
            source = "env"
    except ValueError:
        logger.warning("bad %s/%s value (want a number): %r / %r",
                       PEAK_FLOPS_ENV, PEAK_HBM_ENV, env_f, env_b)
    return {
        "device_kind": kind,
        "peak_flops_per_s": flops,
        "peak_hbm_gbps": hbm,
        "machine_balance_flops_per_byte": (
            flops / (hbm * 1e9) if flops and hbm else None),
        "source": source,
    }


def roofline_class(intensity: Optional[float],
                   peaks: Optional[dict] = None) -> str:
    """``"compute-bound"`` / ``"bandwidth-bound"`` against the machine
    balance, ``"unknown"`` when either the intensity or the peaks are
    absent (CPU's honest answer)."""
    if peaks is None:
        peaks = peak_specs()
    balance = peaks.get("machine_balance_flops_per_byte")
    if intensity is None or balance is None:
        return "unknown"
    return "compute-bound" if intensity >= balance else "bandwidth-bound"


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------


def ledger_entry(entry: str, peaks: Optional[dict] = None,
                 publish: bool = False) -> Optional[dict]:
    """One entry's JSON-ready ledger row, joining the captured static
    analysis with the measured entry timings."""
    rec = _entries.get(entry)
    if rec is None:
        return None
    if peaks is None:
        peaks = peak_specs()
    with _lock:
        recent = list(rec["recent"])
        row = {k: rec[k] for k in (
            "flops", "bytes_accessed", "argument_bytes", "output_bytes",
            "temp_bytes", "generated_code_bytes", "compiles_captured",
            "calls", "total_time_s", "items")}
    mean_t = (sum(recent) / len(recent)) if recent else None
    flops, nbytes = row["flops"], row["bytes_accessed"]
    # mesh-tagged entries (note_entry_mesh): the captured analysis is
    # the partitioned module's, so flops/bytes/MFU below are PER-DEVICE;
    # mesh_flops/mesh_bytes_accessed give the whole-mesh totals
    mesh = rec.get("mesh")
    row["mesh"] = dict(mesh) if mesh else None
    if mesh:
        ndev = 1
        for v in mesh.values():
            ndev *= int(v)
        row["mesh_devices"] = ndev
        row["mesh_flops"] = flops * ndev if flops else None
        row["mesh_bytes_accessed"] = nbytes * ndev if nbytes else None
    row["mean_time_s"] = mean_t
    row["arithmetic_intensity"] = (
        flops / nbytes if flops and nbytes else None)
    row["achieved_flops_per_s"] = (
        flops / mean_t if flops and mean_t else None)
    row["achieved_gbps"] = (
        nbytes / mean_t / 1e9 if nbytes and mean_t else None)
    pf = peaks.get("peak_flops_per_s")
    pb = peaks.get("peak_hbm_gbps")
    row["mfu"] = (row["achieved_flops_per_s"] / pf
                  if row["achieved_flops_per_s"] and pf else None)
    row["hbm_bw_util"] = (row["achieved_gbps"] / pb
                          if row["achieved_gbps"] and pb else None)
    row["roofline"] = roofline_class(row["arithmetic_intensity"], peaks)
    row["bytes_per_item"] = (
        nbytes * row["calls"] / row["items"]
        if nbytes and row["items"] and row["calls"] else None)
    row["items_per_s"] = (
        row["items"] / row["total_time_s"]
        if row["items"] and row["total_time_s"] else None)
    if publish:
        if row["mfu"] is not None:
            mfu_gauge.labels(entry).set(row["mfu"])
        if row["hbm_bw_util"] is not None:
            hbm_bw_util_gauge.labels(entry).set(row["hbm_bw_util"])
    return row


def ledger(prefix: Optional[str] = None) -> Dict[str, dict]:
    """Every captured entry's ledger row (optionally filtered to one
    name prefix, e.g. ``"serving."``). Reading the ledger publishes the
    ``paddle_tpu_mfu`` / ``paddle_tpu_hbm_bw_util`` gauges — scrape
    freshness follows snapshot/stats reads, not the decode hot path."""
    peaks = peak_specs()
    out = {}
    for entry in sorted(_entries):
        if prefix is not None and not entry.startswith(prefix):
            continue
        row = ledger_entry(entry, peaks, publish=True)
        if row is not None:
            out[entry] = row
    return out


# ---------------------------------------------------------------------------
# HBM ledger (live device bytes -> subsystems)
# ---------------------------------------------------------------------------

_components: Dict[str, Callable[[], Optional[dict]]] = {}
_components_lock = threading.Lock()


def register_memory_component(name: str, fn: Callable[[], Optional[dict]]):
    """Register a zero-arg callable returning ``{"bytes": int, ...}``
    (or ``None`` to drop out — weakref-closure friendly, the engine
    pattern) attributed as one subsystem row of the HBM ledger."""
    with _components_lock:
        _components[name] = fn


def unregister_memory_component(name: str):
    with _components_lock:
        _components.pop(name, None)


def hbm_ledger(top_k: int = 8) -> dict:
    """Attribute live device bytes to subsystems:

    - ``device``: PJRT live/peak/limit + headroom (``"unsupported"``
      where ``memory_stats()`` reports nothing — CPU commonly),
    - ``components``: every registered subsystem's own accounting (KV
      pools per format, model weights, optimizer state, ...),
    - ``executables``: top-k captured entries by temp bytes (the
      compiler-owned scratch an OOM usually hides in) with output and
      argument sizes alongside.
    """
    from ..core import memory as _cm

    stats = _cm.device_memory_stats()
    live = stats.get("bytes_in_use")
    peak = stats.get("peak_bytes_in_use")
    limit = stats.get("bytes_limit")
    headroom = _cm.memory_headroom()
    device = {
        "live_bytes": live if live is not None else MEMORY_STATS_UNSUPPORTED,
        "peak_bytes": peak if peak is not None else MEMORY_STATS_UNSUPPORTED,
        "bytes_limit": (limit if limit is not None
                        else MEMORY_STATS_UNSUPPORTED),
        "headroom_bytes": (headroom if headroom is not None
                           else MEMORY_STATS_UNSUPPORTED),
    }
    with _components_lock:
        items = list(_components.items())
    components = {}
    for name, fn in items:
        try:
            c = fn()
        except Exception as e:  # noqa: BLE001 — the ledger must survive
            c = {"error": repr(e)}
        if c is not None:
            components[name] = c
    rows = []
    with _lock:
        for entry, rec in _entries.items():
            if rec["temp_bytes"] is None and rec["output_bytes"] is None:
                continue
            rows.append({
                "entry": entry,
                "temp_bytes": rec["temp_bytes"],
                "output_bytes": rec["output_bytes"],
                "argument_bytes": rec["argument_bytes"],
                "generated_code_bytes": rec["generated_code_bytes"],
            })
    rows.sort(key=lambda r: (r["temp_bytes"] or 0, r["output_bytes"] or 0),
              reverse=True)
    attributed = sum((c.get("bytes") or 0) for c in components.values()
                     if isinstance(c, dict))
    return {
        "device": device,
        "components": components,
        "component_bytes_total": attributed,
        "unattributed_bytes": (live - attributed if live is not None
                               else MEMORY_STATS_UNSUPPORTED),
        "executables": rows[:top_k],
    }


def _state_provider() -> dict:
    """The flight-recorder ``perf`` section: every dump — engine crash,
    pool exhaustion, SIGTERM — carries the ledger + HBM attribution."""
    return {"ledger": ledger(), "hbm": hbm_ledger(),
            "peaks": peak_specs()}


def perf_snapshot() -> dict:
    """The ``observability.snapshot()["perf"]`` section."""
    return {"enabled": perf_enabled(), "ledger": ledger(),
            "hbm": hbm_ledger(), "peaks": peak_specs()}


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

_OOM_MARKERS = (
    "RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED", "Out of memory",
    "out of memory", "OOM", "Allocation failure",
    "failed to allocate", "Failed to allocate", "PoolExhausted",
)


def is_oom_error(exc: BaseException) -> bool:
    """Does this exception look like a device allocation failure
    (XLA RESOURCE_EXHAUSTED, PJRT allocator failure, or the engine's
    own PoolExhaustedError family)?"""
    text = f"{type(exc).__name__}: {exc}"
    return any(m in text for m in _OOM_MARKERS)


def oom_report(top_k: int = 5) -> dict:
    """The forensics payload: HBM ledger + the top-k executables by
    temp bytes (named, so the dump points at the culprit program)."""
    hbm = hbm_ledger(top_k=top_k)
    top = hbm["executables"]
    return {
        "hbm": hbm,
        "peaks": peak_specs(),
        "top_temp_executables": top,
        "suspect": top[0]["entry"] if top else None,
    }


def dump_oom(exc: BaseException, reason: str = "oom",
             top_k: int = 5) -> Optional[str]:
    """Write the OOM forensics flight-recorder dump: the ledger, the
    top-k temp-byte executables, and the active trace (the dump's
    event ring). Returns the dump path (None if the write failed —
    never masks the original error)."""
    from . import tracing as _tracing

    try:
        extra = {"error": repr(exc), **oom_report(top_k=top_k)}
    except Exception:  # noqa: BLE001 — forensics must not crash twice
        extra = {"error": repr(exc)}
    path = _tracing.flight_dump(reason, extra=extra)
    if path is not None:
        _oom_dumps_total.inc()
    return path


# ---------------------------------------------------------------------------
# perf-regression gate (benchmarks/perf_baseline.json)
# ---------------------------------------------------------------------------


def _dig(d: Any, path: str) -> Optional[float]:
    cur = d
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur if isinstance(cur, (int, float)) else None


# metric name -> (artifact file, dotted path). One place defines what
# the gate watches; collect_bench_metrics + the committed baseline
# stay in sync through it.
BENCH_METRIC_SOURCES = {
    "serving.tok_s": ("bench_serving.json", "serving.tok_s"),
    "serving.speedup_vs_sequential": ("bench_serving.json", "speedup"),
    "paged.tok_s": ("bench_paged_kv.json", "capacity_ab.paged.tok_s"),
    "paged.capacity_ratio": ("bench_paged_kv.json",
                             "capacity_ab.capacity_ratio"),
    "paged.int8_capacity_vs_bf16": (
        "bench_paged_kv.json", "kv_format_ab.formats.int8.capacity_vs_bf16"),
    "spec.best_speedup": ("bench_spec_decode.json", "best_speedup"),
    "spec.k8_occ1_tok_s": ("bench_spec_decode.json",
                           "spec_k8_coupled.by_occupancy.1.tok_s"),
    "spec_tree.tok_s_ratio_vs_chain": ("bench_spec_decode.json",
                                       "spec_tree.tok_s_ratio_vs_chain"),
    "spec_tree.parity": ("bench_spec_decode.json", "spec_tree.parity"),
    "router.tok_s": ("bench_router.json", "goodput.tok_s"),
    "router.overhead_pct": ("bench_router.json", "overhead.overhead_pct"),
    "router.fleet_overhead_pct": ("bench_router.json",
                                  "fleet_overhead.overhead_pct"),
    "router.crash_completed_frac": ("bench_router.json",
                                    "crash.completed_frac"),
    "kv_tier.saved_frac_longconv": ("bench_kv_tier.json",
                                    "long_conversation.saved_frac"),
    "kv_tier.readmit_speedup": ("bench_kv_tier.json",
                                "long_conversation.readmit_speedup"),
    "kv_tier.parity": ("bench_kv_tier.json", "parity_all"),
    "tp.tp2_tok_s": ("bench_tp.json", "lanes.tp2.tok_s"),
    "tp.parity": ("bench_tp.json", "parity_all"),
    "tp.weight_hbm_frac_tp2": ("bench_tp.json",
                               "lanes.tp2.weight_bytes_per_device_frac"),
    "train.tok_s_per_chip": ("bench_train.json", "tokens_per_sec_per_chip"),
    "train.mfu": ("bench_train.json", "mfu"),
    "overload.supervisor_overhead_pct": ("bench_overload.json",
                                         "overhead.overhead_pct"),
    "overload.innocent_completed_frac": (
        "bench_overload.json", "poison.innocent_completed_frac"),
}


def collect_bench_metrics(bench_dir: str) -> Dict[str, float]:
    """Flatten the bench artifacts in ``bench_dir`` into the gate's
    metric namespace. Metrics whose artifact (or field) is absent are
    simply omitted — the gate reports them as skipped, never invents a
    number."""
    out: Dict[str, float] = {}
    cache: Dict[str, Optional[dict]] = {}
    for metric, (fname, path) in BENCH_METRIC_SOURCES.items():
        if fname not in cache:
            p = os.path.join(bench_dir, fname)
            try:
                with open(p) as fh:
                    cache[fname] = json.load(fh)
            except (OSError, json.JSONDecodeError):
                cache[fname] = None
        art = cache[fname]
        if art is None:
            continue
        v = _dig(art, path)
        if v is not None:
            out[metric] = float(v)
    return out


def load_baseline(path: str) -> Optional[dict]:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None


def compare_to_baseline(fresh: Dict[str, float],
                        baseline: Optional[dict]) -> dict:
    """The regression verdict. ``baseline["metrics"]`` rows pin
    ``{"value", "rel_tol", "direction"}`` per metric (direction
    ``"higher"`` = bigger is better). A fresh value worse than
    ``value * (1 - rel_tol)`` (or ``* (1 + rel_tol)`` for
    lower-is-better) is a FAILURE; absent fresh metrics are skipped
    (reported, not failed — a lane that didn't run a bench can't
    regress it)."""
    if not baseline or "metrics" not in baseline:
        return {"ok": True, "checked": 0,
                "note": "no baseline (benchmarks/perf_baseline.json "
                        "missing or empty) — gate skipped"}
    failures, checks, skipped = [], [], []
    for name, spec in baseline["metrics"].items():
        base = spec.get("value")
        if base is None:
            continue
        got = fresh.get(name)
        if got is None:
            skipped.append(name)
            continue
        tol = float(spec.get("rel_tol", 0.15))
        higher = spec.get("direction", "higher") == "higher"
        floor = base * (1.0 - tol)
        ceil = base * (1.0 + tol)
        ok = got >= floor if higher else got <= ceil
        row = {"metric": name, "baseline": base, "fresh": got,
               "rel_tol": tol, "direction": "higher" if higher else "lower",
               "bound": floor if higher else ceil,
               "delta_pct": round(100.0 * (got - base) / base, 2) if base
               else None,
               "ok": ok}
        checks.append(row)
        if not ok:
            failures.append(row)
    return {"ok": not failures, "checked": len(checks),
            "skipped": skipped, "failures": failures, "checks": checks}
