"""Prompt tokens prefilled plus output tokens emitted per second of
the window, credited by when they were produced."""
from perfbench import stats


def read(facts, window=None):
    """Over ``facts["window"]``, or over the part of it given."""
    if "requests" not in facts:
        return None
    lo, hi = window or facts["window"]
    sent = [r for r in facts["requests"] if r["times"]]
    prefills = [(r["sent"], r["times"][0], r["prompt_len"]) for r in sent]
    # the first output token ends the prefill; it counts as emitted too
    times = [t for r in sent for t in r["times"]]
    return stats.tokens_in_window(prefills, times, lo, hi) / (hi - lo)


def by_slice(facts, slice_s=3.0):
    """The same rate over consecutive slices of the window, so that a
    run that reads low says on an earlier line whether it stalled once
    or ran slow throughout. Whole tokens/s, for a log line."""
    lo, hi = facts["window"]
    n = max(1, round((hi - lo) / slice_s))
    edges = [lo + (hi - lo) * k / n for k in range(n + 1)]
    return [round(read(facts, (a, b))) for a, b in zip(edges, edges[1:])]
