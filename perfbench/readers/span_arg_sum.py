"""A count the program wrote into the args of one of its spans, summed
over the spans of that name that start inside the window."""
from perfbench.programs import spans


def read(facts, trace, span, key):
    events = spans.windowed(facts, trace=trace)
    if events is None:
        return None
    mine = [e for e in events if e["name"] == span]
    if not mine:
        return None
    return sum(spans.arg(e, key) for e in mine)
