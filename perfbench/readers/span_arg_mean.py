"""A count the program wrote into the args of one of its spans, as the
mean over the spans of that name that start inside the window and carry
the arg. Nothing where no span carries it (a program that does not
record it: the parent of the PR that added the arg)."""
from perfbench.programs import spans


def read(facts, trace, span, key):
    events = spans.windowed(facts, trace=trace)
    if events is None:
        return None
    vals = [spans.arg(e, key, None) for e in events if e["name"] == span]
    vals = [v for v in vals if v is not None]
    if not vals:
        return None
    return sum(vals) / len(vals)
