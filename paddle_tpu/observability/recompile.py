"""Recompile monitor: attribute XLA compiles to jitted entry points.

jax 0.4.x emits ``jax.monitoring`` events around every trace/compile —
``/jax/core/compile/backend_compile_duration`` fires once per XLA
compilation with its wall seconds, and the compilation-cache events
(``/jax/compilation_cache/...``) mark cache traffic. This module
subscribes listeners once and attributes each compile to the *runtime
entry point* that triggered it: ``jit/api.py`` StaticFunction calls,
``generation.generate``, and the hapi ``Model`` train/eval steps wrap
their dispatch in ``entrypoint(name)``, which pushes the name onto a
thread-local stack the listener reads (compiles happen synchronously on
the dispatching thread).

Retrace detection (reference pain point: silent per-shape program
explosions): an entry point that compiles AFTER it has already completed
a call is retracing — new input shapes/dtypes or an unstable cache key.
Each such event increments ``paddle_tpu_retraces_total`` and logs a
one-line warning (per entry, first occurrence) so a shape regression in
a training loop is visible without a profiler run.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from . import metrics as _m

__all__ = ["install", "installed", "entrypoint", "current_entry",
           "compile_events", "total_compiles", "entry_stats", "reset_entries",
           "reset_warmup", "warmup_scope", "register_entry_location",
           "entry_location"]

logger = logging.getLogger("paddle_tpu.observability")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_UNATTRIBUTED = "<unattributed>"

_tls = threading.local()
_installed = [False]
_install_lock = threading.Lock()

# Bounded flight recorder of compile events (entry, event, duration_s, ts)
_events: deque = deque(maxlen=512)
# Per-entry call/compile bookkeeping for retrace detection
_entries: Dict[str, dict] = {}
_entries_lock = threading.Lock()
# entry name -> "file:line" of the jitted definition, so the retrace
# warning points at the source the static analyzer also reports on
_entry_locations: Dict[str, str] = {}


def register_entry_location(name: str, fn=None,
                            location: Optional[str] = None) -> None:
    """Record where a jitted entry point is defined (``file:line``).
    Owners pass the callable (``StaticFunction``'s wrapped fn, the
    engine's local step/chunk defs) and the analyzer's resolver does the
    rest; an explicit ``location`` string overrides. Best-effort — a
    callable without source never raises."""
    if location is None and fn is not None:
        try:
            from ..analysis.resolver import source_location

            location = source_location(fn)
        except Exception:  # pragma: no cover — resolver must never break
            location = None
    if location:
        _entry_locations[name] = location


def entry_location(name: str) -> Optional[str]:
    return _entry_locations.get(name)

_compiles = _m.counter(
    "paddle_tpu_compiles_total",
    "XLA backend compilations attributed to the triggering entry point",
    ("entry",))
_compile_seconds = _m.histogram(
    "paddle_tpu_compile_seconds",
    "XLA backend compile wall time per entry point", ("entry",))
_retraces = _m.counter(
    "paddle_tpu_retraces_total",
    "compilations that happened AFTER an entry point had already "
    "completed a call (unexpected retrace: shape/dtype churn)", ("entry",))
_jax_events = _m.counter(
    "paddle_tpu_jax_monitoring_events_total",
    "raw jax.monitoring counter events (compilation cache traffic etc.)",
    ("event",))


def current_entry() -> str:
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else _UNATTRIBUTED


class entrypoint:
    """Context manager marking the currently-dispatching entry point so
    compile events attribute to it. Re-entrant; nesting attributes to the
    innermost entry. Completing the ``with`` block counts one call —
    the retrace detector's notion of "this entry is past warmup"."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self.name)
        return self

    def __exit__(self, *exc):
        _tls.stack.pop()
        if exc[0] is None:
            st = _entry_state(self.name)
            st["calls"] += 1
        return False


class warmup_scope:
    """Mark the current thread as deliberately warming executables:
    compiles inside the scope are counted and attributed as usual but
    are NEVER retraces, regardless of the entry's completed-call count.

    ``reset_warmup`` covers the single-engine case (a fresh engine's
    entries start at calls == 0, so their first compiles are warmup by
    construction), but it cannot cover a SECOND in-process engine whose
    entries share names with one that already served calls — e.g. two
    serving replicas both dispatching ``serving.step``. Replica N+1's
    ``engine.warmup()`` runs inside this scope so its expected compiles
    don't trip the retrace alarm the router's zero-retrace invariant
    relies on. Re-entrant; thread-local (compiles run synchronously on
    the dispatching thread)."""

    __slots__ = ()

    def __enter__(self):
        _tls.warmup = getattr(_tls, "warmup", 0) + 1
        return self

    def __exit__(self, *exc):
        _tls.warmup -= 1
        return False


def _in_warmup_scope() -> bool:
    return getattr(_tls, "warmup", 0) > 0


def _entry_state(name: str) -> dict:
    st = _entries.get(name)
    if st is None:
        with _entries_lock:
            st = _entries.setdefault(
                name, {"calls": 0, "compiles": 0, "retraces": 0,
                       "compile_seconds": 0.0, "warned": False})
    return st


def _on_duration(name: str, duration: float, **kwargs):
    if not _m._ENABLED[0] or name != _COMPILE_EVENT:
        return
    try:
        entry = current_entry()
        _compiles.labels(entry).inc()
        _compile_seconds.labels(entry).observe(duration)
        _events.append({"entry": entry, "event": "backend_compile",
                        "duration_s": duration, "ts": time.time()})
        # attribute the compile into the active request trace (compiles
        # run synchronously on the dispatching thread, so the tracing
        # thread-local context is the request that paid for it)
        from . import tracing as _tracing

        _tracing._on_compile(entry, duration)
        st = _entry_state(entry)
        st["compiles"] += 1
        st["compile_seconds"] += duration
        if st["calls"] >= 1 and not _in_warmup_scope():
            st["retraces"] += 1
            _retraces.labels(entry).inc()
            if not st["warned"]:
                st["warned"] = True
                loc = _entry_locations.get(entry)
                logger.warning(
                    "unexpected retrace: entry %r%s recompiled (%.3fs) "
                    "after %d completed call(s) — input shapes/dtypes "
                    "changed or the jit cache key is unstable (compile "
                    "#%d)",
                    entry, f" (defined at {loc})" if loc else "",
                    duration, st["calls"], st["compiles"])
    except Exception:  # a metrics bug must never break a compile
        logger.debug("recompile monitor listener failed", exc_info=True)


def _on_event(name: str, **kwargs):
    if not _m._ENABLED[0] or not name.startswith("/jax/"):
        return
    try:
        _jax_events.labels(name).inc()
    except Exception:
        pass


def install() -> bool:
    """Register the jax.monitoring listeners (idempotent). Returns True
    when running with a jax that exposes the monitoring API."""
    if _installed[0]:
        return True
    with _install_lock:
        if _installed[0]:
            return True
        try:
            from jax import monitoring
        except Exception:
            return False
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed[0] = True
        return True


def installed() -> bool:
    return _installed[0]


def compile_events() -> List[dict]:
    """The bounded flight recorder: most recent compiles, oldest first."""
    return list(_events)


def total_compiles() -> int:
    """Process-wide compile count (all entries) — cheap enough for the
    per-step telemetry delta."""
    return sum(st["compiles"] for st in list(_entries.values()))


def entry_stats() -> Dict[str, dict]:
    with _entries_lock:
        return {k: dict(v) for k, v in _entries.items()}


def reset_warmup(*names: str):
    """Restart retrace warmup for ``names``: the owner just built NEW
    jitted executables for those entries (e.g. a fresh ServingEngine's
    step/prefill closures), so their next compiles are expected warmup,
    not retraces. Compile/retrace totals are kept — only the completed-
    call count (the "past warmup" marker) and the warn latch clear."""
    with _entries_lock:
        for name in names:
            st = _entries.get(name)
            if st is not None:
                st["calls"] = 0
                st["warned"] = False


def reset_entries():
    """Clear attribution state + the event recorder (tests)."""
    with _entries_lock:
        _entries.clear()
    _events.clear()
