"""Prompt tokens prefilled plus output tokens emitted per second of
the window, credited by when they were produced."""
from perfbench import stats


def read(facts):
    if "requests" not in facts:
        return None
    lo, hi = facts["window"]
    sent = [r for r in facts["requests"] if r["times"]]
    prefills = [(r["sent"], r["times"][0], r["prompt_len"]) for r in sent]
    # the first output token ends the prefill; it counts as emitted too
    times = [t for r in sent for t in r["times"]]
    return stats.tokens_in_window(prefills, times, lo, hi) / (hi - lo)
