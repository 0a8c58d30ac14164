"""Percentile of the time from a request's admission to a slot (its
first ``admitted`` event) to its first token (``first_token``), on the
program's request lanes, over the requests that did both inside the
measured window and outside the profiler's session: the prefill as the
engine ran it, chunks of other requests and decode steps between its
own chunks included. ms."""
from perfbench import stats
from perfbench.programs import spans


def read(facts, q, min_events=20):
    got = spans.unprofiled(facts, 0, cat="request")
    if got is None:
        return None
    vals = []
    for events, _, _ in got:
        admitted, first = {}, {}
        for e in events:
            if e["name"] == "admitted":
                admitted.setdefault(e["trace"], e["ts_ns"])
            elif e["name"] == "first_token":
                first.setdefault(e["trace"], e["ts_ns"])
        vals += [first[k] - t for k, t in admitted.items() if k in first]
    if len(vals) < min_events:
        return None
    return stats.percentile(vals, q) / 1e6
