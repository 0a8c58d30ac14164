"""Share of the measured window (outside the profiler's session) in
which the device had nothing queued and the engine's thread was at
work: from every point where the host has just synced with the device
(the end of ``engine.wait``) to the return of the next enqueue (the end
of the next ``prefill_chunk`` or ``engine.dispatch``), cut short where the
engine went idle for want of requests (``engine.idle``). Between those
two points the device certainly waits for the host; past the first
enqueue it has work while the host prepares more, which is not counted.
So this is the part of the device's idle share that the engine's host
phases account for, read from the spans alone (``perfbench/gap_phases.py``
reads the same from a kept trace). A request's ``first_token`` is no
such point: the token is read with the step already enqueued (PR 30), so
the event falls inside ``engine.wait``, whose end covers it. %"""
from perfbench.programs import spans

DRAINED, ENQUEUED, IDLE = 0, 1, 2


def read(facts, min_events=20):
    got = spans.unprofiled(facts, min_events, trace=spans.ENGINE)
    lanes = spans.unprofiled(facts, 0, cat="request")
    if got is None or lanes is None:
        return None
    starved = total = 0
    for (engine, lo, hi), (requests, _, _) in zip(got, lanes):
        marks = []
        for e in engine + requests:
            end = e["ts_ns"] + e["dur_ns"]
            if e["name"] == "engine.wait":
                marks.append((end, DRAINED))
            elif e["name"] in ("engine.dispatch", "prefill_chunk"):
                marks.append((end, ENQUEUED))
            elif e["name"] == "engine.idle":
                marks.append((e["ts_ns"], IDLE))
        since = None
        for t, kind in sorted(marks):
            if kind == DRAINED:
                since = t if since is None else since
            elif since is not None:
                starved += t - since
                since = None
        total += hi - lo
    return 100.0 * starved / total
