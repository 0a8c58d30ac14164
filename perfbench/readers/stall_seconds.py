"""Seconds of the measured window, outside the profiler's session, that
the engine's loop lost to iterations (or gaps between two) longer than
the program's own threshold: the sum of ``ms`` over its ``engine.stall``
instants (``part`` ``"all"``). Each stands at the START of the stretch it
names, so it is looked for from the ring's beginning, not from the
window's: a stall that began before a stretch of the window and runs
into it counts with the part inside, as one that runs out of it does.

``part`` ``"blocked"``: of those seconds, the ones in which the engine
alone waited, on the runtime or the device. From each stall go the
thread's own CPU time (``cpu_ms``: it was busy, not blocked; a stall
recorded without it, because another thread ran the iteration before,
counts as busy) and, once, whatever part of it a ``proc.pause`` (the
whole process stood still), a ``proc.gc`` (the collector ran) or an
``xla_compile:*`` (a program was compiled) covers. What is left of
``"all"`` is the host's.

0.0 for a program that watched itself and never stalled, nothing for
one that did not (``span_sum_s.watched``). s."""
from perfbench import trace_reduce
from perfbench.programs import spans
from perfbench.readers import span_sum_s

STALL = "engine.stall"
HOLES = ("proc.pause", "proc.gc", "xla_compile:")


def read(facts, part):
    parts = span_sum_s.watched(facts)
    if parts is None:
        return None
    total = 0.0
    for lo_s, hi_s in parts:
        got = span_sum_s.inside(lo_s, hi_s)
        if got is None:
            return None
        lo, hi = lo_s * 1e9, hi_s * 1e9
        holes = trace_reduce.union(
            [(a, b) for name, a, b, _ in got if name.startswith(HOLES)])
        for e in spans.lane(0.0, hi_s, trace=spans.ENGINE)[0]:
            ms = spans.arg(e, "ms")
            t0, t1 = max(lo, e["ts_ns"]), min(hi, e["ts_ns"] + ms * 1e6)
            if e["name"] != STALL or t1 <= t0:
                continue
            if part == "all":
                total += t1 - t0
                continue
            share = (t1 - t0) / (ms * 1e6)
            idle = (ms - spans.arg(e, "cpu_ms", ms)) * 1e6 * share
            total += max(0.0, idle - (
                (t1 - t0) - trace_reduce.subtract([[t0, t1]], holes)))
    return total / 1e9
