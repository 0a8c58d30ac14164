"""The program's own spans, for the readers: the one module of the
benchmark that imports ``paddle_tpu.observability.tracing``.

The program keeps its spans in a bounded ring on the host clock
(``time.perf_counter_ns``), which is the clock ``facts["window"]`` and
``facts["trace"]["host_window"]`` are read on. A reader asks for the
events of one lane that overlap an interval of that clock and is told
whether the ring still reaches back to the interval's start: where it
does not, the reader gives nothing, never a number from a shorter
stretch. Times are read over the measured window outside the profiler's
session (``unprofiled``), counts over the whole window (``windowed``).
Against a program that records no such span (the parent of the PR that
added them) the lanes are empty and the readers give nothing.
"""

from __future__ import annotations

ENGINE, TRAIN = "engine", "train"

_cache = {"recorded": None, "events": [], "capacity": 0}


def _ring():
    """Every event the ring holds, oldest first, and whether none was
    ever evicted. Read once for each state of the ring: after a run the
    engine is stopped and nothing more is recorded."""
    try:
        from paddle_tpu.observability import tracing
    except ImportError:
        return [], True
    info = tracing.summary()
    if _cache["recorded"] != info["events_recorded"]:
        _cache.update(recorded=info["events_recorded"],
                      events=tracing.events(),
                      capacity=info["ring_capacity"])
    return _cache["events"], _cache["recorded"] <= _cache["capacity"]


def lane(lo_s, hi_s, trace=None, cat=None):
    """(events, complete): the events of one trace id (``"engine"``,
    ``"train"``) or one category (``"request"``) that overlap
    ``[lo_s, hi_s)`` of the host clock, by start time, as the program's
    dicts (``name``, ``ts_ns``, ``dur_ns``, ``trace``, ``args``);
    ``complete`` is false where the ring no longer reaches back to
    ``lo_s``."""
    events, nothing_evicted = _ring()
    lo, hi = lo_s * 1e9, hi_s * 1e9
    complete = nothing_evicted or (bool(events) and events[0]["ts_ns"] <= lo)
    out = [e for e in events
           if e["ts_ns"] < hi and e["ts_ns"] + e["dur_ns"] >= lo
           and (trace is None or e["trace"] == trace)
           and (cat is None or e["cat"] == cat)]
    return out, complete


# A profiler session runs its Python tracer, which inflated the host
# phases two to four times (PERF.md section 6, PR 25), and holds the
# interpreter while it starts and stops (0.6 s for the stop, PR 24). A
# time read from a span is therefore read outside the session, this
# margin on either side.
SESSION_MARGIN_S = 1.0


def stretches(facts):
    """The measured window less the profiler's session and its margin,
    as (lo_s, hi_s) parts on the host clock: the whole window in a run
    that took no trace, the window up to the session in a served cell
    (its session ends as the window shuts), both sides of it in a
    training cell."""
    if "window" not in facts:
        return []
    lo, hi = facts["window"]
    red = facts.get("trace")
    if not red or "host_window" not in red:
        return [(lo, hi)]
    t0, t1 = red["host_window"]
    parts = [(lo, min(hi, t0 - SESSION_MARGIN_S)),
             (max(lo, t1 + SESSION_MARGIN_S), hi)]
    return [(a, b) for a, b in parts if b > a]


def unprofiled(facts, min_events, count="engine.iter", **which):
    """The lane's events outside the profiler's session, one
    (events, lo_ns, hi_ns) for each of ``stretches``: the events that
    lie wholly inside the stretch, by start time. None where the ring
    was evicted past the window's start or fewer than ``min_events``
    spans named ``count`` are among them, so that no number comes from
    a shorter stretch than the one it claims."""
    out, n = [], 0
    for lo_s, hi_s in stretches(facts):
        events, complete = lane(lo_s, hi_s, **which)
        if not complete:
            return None
        lo, hi = lo_s * 1e9, hi_s * 1e9
        events = [e for e in events
                  if lo <= e["ts_ns"] and e["ts_ns"] + e["dur_ns"] <= hi]
        n += sum(1 for e in events if e["name"] == count)
        out.append((events, lo, hi))
    return out if n >= min_events else None


def windowed(facts, **which):
    """The lane's events that start inside the measured window (a
    count over the whole window); None where the ring was evicted past
    the window's start."""
    if "window" not in facts:
        return None
    lo_s, hi_s = facts["window"]
    events, complete = lane(lo_s, hi_s, **which)
    if not complete:
        return None
    return [e for e in events if lo_s * 1e9 <= e["ts_ns"] < hi_s * 1e9]


def arg(event, key, default=0):
    return (event.get("args") or {}).get(key, default)
