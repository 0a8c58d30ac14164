"""A statistic of the durations of one of the program's spans over the
measured window outside the profiler's session, optionally less the
part of each span that a child span of the same iteration took
(``engine.iter`` less ``engine.wait`` is the engine thread's own time in
an iteration, whether the device works meanwhile or not). ``q`` is a
percentile, or ``"mean"`` where the spans are of two kinds (iterations
with and without a prefill chunk) and a median would move with their
mix. ms."""
from perfbench import stats
from perfbench.programs import spans


def read(facts, trace, span, q, less=None, min_events=20):
    got = spans.unprofiled(facts, min_events, count=span, trace=trace)
    if got is None:
        return None
    vals = []
    for events, _, _ in got:
        taken = {spans.arg(e, "iter", None): e["dur_ns"]
                 for e in events if e["name"] == less}
        vals += [e["dur_ns"] - taken.get(spans.arg(e, "iter", None), 0)
                 for e in events if e["name"] == span]
    if q == "mean":
        return sum(vals) / len(vals) / 1e6
    return stats.percentile(vals, q) / 1e6
