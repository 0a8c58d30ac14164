"""From a profiler trace (``.xplane.pb``) to the numbers the readers
use: device busy time, time by operation and by jitted program,
collective time with no compute running, and the longest idle gaps
with what the host was doing in them.

Read with ``jax.profiler.ProfileData`` alone. A TPU plane is named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed
HLO operation and ``XLA Modules`` one per executed program
(``jit__step(<hash>)``). The host plane ``/host:CPU`` has a line for
each thread with the runtime's spans (and the interpreter's calls when
the Python tracer is on, which it is not in a benchmark run).

Busy time and the window it is divided by are one stretch of one clock.
``run.py`` writes two marks onto the host plane, ``WINDOW_OPEN`` as it
reads the host's ``t0`` and ``WINDOW_SHUT`` as it reads ``t1``; every
device event is clipped to the stretch between them and ``window_s`` is
its length, so a device that never idles reads ``busy_s == window_s``
and never more (the trace itself reaches further: it holds what ran
while ``start_trace`` and ``stop_trace`` were still at work). A trace
without the marks is taken whole: from the first event of any plane to
the end of the last.
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


DEVICE_LINES = ("XLA Ops", "XLA Modules")
WINDOW_OPEN, WINDOW_SHUT = "perfbench.window_open", "perfbench.window_shut"


def load(path):
    """{plane name: {line name: [(name, start_ns, duration_ns), ...]}}:
    of a device plane the two lines the reduction needs, of a host
    plane every thread's spans merged under ``host``."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        host = plane.name.startswith("/host:")
        lines = {}
        for line in plane.lines:
            if host or line.name in DEVICE_LINES:
                lines.setdefault("host" if host else line.name, []).extend(
                    (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events)
        if lines:
            out[plane.name] = lines
    return out


def op_key(event_name):
    """``%multiply_reduce_fusion.12 = f32[..] fusion(..)`` ->
    ``multiply_reduce_fusion``; a custom call (a Pallas kernel) keeps
    the name it was given and gains ``_custom-call``."""
    m = re.match(r"%?([^\s=]+)", event_name)
    base = re.sub(r"\.\d+$", "", m.group(1)) if m else event_name
    if "custom-call(" in event_name and "custom-call" not in base:
        base += "_custom-call"
    return base


def module_key(event_name):
    return re.sub(r"\(\d+\)$", "", event_name)


def union(intervals):
    """Merged, sorted intervals of a list of (start, end)."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged):
    return sum(e - s for s, e in merged)


def subtract(merged, holes):
    """Length of ``merged`` not covered by ``holes`` (both merged)."""
    total, j = 0.0, 0
    for s, e in merged:
        cur = s
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < e:
            if holes[k][0] > cur:
                total += holes[k][0] - cur
            cur = max(cur, holes[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def window(planes):
    """(lo, hi, marked) with lo and hi in the trace's ns: from the start
    of the ``WINDOW_OPEN`` mark to the start of the ``WINDOW_SHUT`` mark
    on the host plane, or, without them (``marked`` false), from the
    first event of any plane to the end of the last. None for a trace
    with no event at all."""
    marks = {name: start for k, v in planes.items() if k.startswith("/host:")
             for name, start, _ in v.get("host", [])
             if name in (WINDOW_OPEN, WINDOW_SHUT)}
    if len(marks) == 2 and marks[WINDOW_OPEN] < marks[WINDOW_SHUT]:
        return marks[WINDOW_OPEN], marks[WINDOW_SHUT], True
    events = [(s, s + d) for lines in planes.values()
              for line in lines.values() for _, s, d in line]
    if not events:
        return None
    return min(s for s, _ in events), max(e for _, e in events), False


def clip(events, lo, hi):
    """The part of every (name, start, duration) inside [lo, hi]; an
    event wholly outside is dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def _host_labels(host_events, gaps):
    """What the host was doing in the middle of each gap (lo, hi): the
    innermost span of any host thread that covers it. The runtime
    traces its own calls and (unless the Python tracer is on) not the
    interpreter, so a gap that no span covers is the interpreter's own
    work, named by the runtime call it led up to."""
    import numpy as np

    def clean(name):
        return re.sub(r"[^A-Za-z0-9_.:<>-]+", "_", name)[:60]

    if not host_events:
        return ["no_host_span_in_trace"] * len(gaps)
    starts = np.array([s for _, s, _ in host_events])
    ends = starts + np.array([d for _, _, d in host_events])
    order = np.argsort(starts)
    out = []
    for lo, hi in gaps:
        mid = (lo + hi) / 2.0
        inside = np.flatnonzero((starts <= mid) & (ends >= mid))
        if len(inside):
            out.append(clean(
                host_events[inside[np.argmax(starts[inside])]][0]))
            continue
        nxt = np.searchsorted(starts[order], mid)
        out.append("python_before_" + (clean(host_events[order[nxt]][0])
                                       if nxt < len(order) else "nothing"))
    return out


def reduce(planes):
    """The reduction, from ``load``'s structure, over the stretch that
    ``window`` gives. Times in seconds; the per-operation and
    per-program sums are averages over the device planes, so that they
    add up to ``busy_s``. An operation that overhangs an end of the
    window counts with the part inside; a program counts, in time and
    in calls, only where it ran wholly inside, so that its mean time is
    that of whole programs."""
    devices = {k: v for k, v in planes.items()
               if k.startswith("/device:TPU:")}
    lo, hi, marked = window(planes) or (0.0, 0.0, False)
    host_events = [ev for k, v in planes.items() if k.startswith("/host:")
                   for ev in v.get("host", [])]
    n = max(1, len(devices))
    busy = busy_whole = exposed = collective = 0.0
    ops, mods, mod_counts, gaps = {}, {}, {}, []
    for lines in devices.values():
        busy_whole += length(union(
            [(s, s + d) for _, s, d in lines.get("XLA Ops", [])]))
        events = clip(lines.get("XLA Ops", []), lo, hi)
        spans = union([(s, s + d) for _, s, d in events])
        busy += length(spans)
        coll = union([(s, s + d) for name, s, d in events
                      if op_key(name).startswith(COLLECTIVES)])
        rest = union([(s, s + d) for name, s, d in events
                      if not op_key(name).startswith(COLLECTIVES)])
        collective += length(coll)
        exposed += subtract(coll, rest)
        for name, _, d in events:
            ops[op_key(name)] = ops.get(op_key(name), 0.0) + d
        for name, s, d in lines.get("XLA Modules", []):
            if s < lo or s + d > hi:
                continue
            k = module_key(name)
            mods[k] = mods.get(k, 0.0) + d
            mod_counts[k] = mod_counts.get(k, 0) + 1
        gaps.extend((b[0] - a[1], a[1], b[0])
                    for a, b in zip(spans, spans[1:]))
    top_gaps = sorted(gaps, reverse=True)[:10]
    labels = _host_labels(host_events, [(lo, hi) for _, lo, hi in top_gaps])
    ns = 1e-9
    return {
        "n_devices": len(devices),
        "window_s": (hi - lo) * ns,
        "window_marked": marked,
        "busy_s": busy * ns / n,
        # what the trace holds beyond the window too (PR 34 was refused
        # on this number read against the window's length)
        "busy_whole_trace_s": busy_whole * ns / n,
        "collective_s": collective * ns / n,
        "collective_exposed_s": exposed * ns / n,
        "op_s": {k: v * ns / n for k, v in ops.items()},
        "module_s": {k: v * ns / n for k, v in mods.items()},
        "module_calls": {k: v / n for k, v in mod_counts.items()},
        "device_ops": [[k, v * ns / n] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[label, g * ns]
                      for label, (g, _, _) in zip(labels, top_gaps)],
    }


def reduce_dir(trace_dir):
    """Reduce the one ``.xplane.pb`` a profiler session left under
    ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} traces under {trace_dir}")
    return reduce(load(found[0]))
