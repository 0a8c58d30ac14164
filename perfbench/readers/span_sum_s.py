"""Seconds of the measured window, outside the profiler's session, that
the spans of one name on one lane covered: a span counts with the part
of it that lies inside a stretch (``spans.stretches``), so one that
straddles the window's edge is neither lost nor counted whole.
``less`` names what is taken off them, once, where it overlaps: the
spans of ANY lane whose name starts with one of its entries. So
``proc_pause_s.*`` leaves out the part of a late wake that a pass of the
collector (``proc.gc``: it holds the interpreter, the beat cannot wake)
or a compile (``xla_compile:*``) explains and reports under its own
name.

The lane is one the program fills only when something went wrong (the
process stood still, the collector ran long), so an empty lane is the
common reading and has to be told from a missing one: the program
writes ``proc.watch`` once, when it starts to watch itself. With that
mark in the ring before the window's start an empty lane reads 0.0;
without it (the parent of the PR that added the lane, tracing off, a
ring that no longer reaches back) the reader gives nothing. s."""
from perfbench import trace_reduce
from perfbench.programs import spans

WATCH = ("proc", "proc.watch")


def watched(facts):
    """The stretches to read, as ``spans.stretches`` gives them, in a
    process that watched itself from before the first of them; None
    where it did not, or where there is no window."""
    parts = spans.stretches(facts)
    if not parts:
        return None
    before, _ = spans.lane(0.0, parts[0][0], trace=WATCH[0])
    if not any(e["name"] == WATCH[1] for e in before):
        return None
    return parts


def inside(lo_s, hi_s, **which):
    """[(name, start_ns, end_ns, event)] of the lane's events clipped
    to the stretch; None where the ring no longer reaches back to its
    start."""
    events, complete = spans.lane(lo_s, hi_s, **which)
    if not complete:
        return None
    lo, hi = lo_s * 1e9, hi_s * 1e9
    return [(e["name"], max(lo, e["ts_ns"]),
             min(hi, e["ts_ns"] + e["dur_ns"]), e) for e in events]


def read(facts, trace, span, less=()):
    parts = watched(facts)
    if parts is None:
        return None
    total = 0.0
    for lo_s, hi_s in parts:
        got = inside(lo_s, hi_s)
        if got is None:
            return None
        holes = trace_reduce.union(
            [(a, b) for name, a, b, _ in got if name.startswith(tuple(less))]
        ) if less else []
        total += trace_reduce.subtract(trace_reduce.union(
            [(a, b) for name, a, b, e in got
             if name == span and e["trace"] == trace]), holes)
    return total / 1e9
