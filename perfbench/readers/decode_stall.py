"""Percentile, over consecutive decode steps of the measured window
outside the profiler's session, of the time from the end of one step's
``engine.wait`` to the start of the next iteration's
``engine.dispatch``: the token hand-over, admissions, prefill chunks and
block reservation that a decoding row waits through between two of its
tokens. A pair with an ``engine.idle`` between is left out: the engine
only idles with no row in a slot, so nobody waited through it. ms."""
import bisect

from perfbench import stats
from perfbench.programs import spans


def read(facts, q, min_events=20):
    got = spans.unprofiled(facts, min_events, trace=spans.ENGINE)
    if got is None:
        return None
    stalls = []
    for events, _, _ in got:
        wait_end = {spans.arg(e, "iter", None): e["ts_ns"] + e["dur_ns"]
                    for e in events if e["name"] == "engine.wait"}
        idles = sorted(e["ts_ns"] for e in events
                       if e["name"] == "engine.idle")
        for e in events:
            prev = wait_end.get(spans.arg(e, "iter") - 1)
            if e["name"] != "engine.dispatch" or prev is None:
                continue
            k = bisect.bisect_left(idles, prev)
            if k == len(idles) or idles[k] >= e["ts_ns"]:
                stalls.append(e["ts_ns"] - prev)
    if len(stalls) < min_events:
        return None
    return stats.percentile(stalls, q) / 1e6
