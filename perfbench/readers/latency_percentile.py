"""Percentile of the time to a first token (from the time the request
was due, not sent) or of the gaps between successive tokens, over the
counted requests, in ms."""
from perfbench import stats


def read(facts, what, q):
    recs = [r for r in facts.get("requests", []) if r["counted"] and r["times"]]
    if what == "ttft":
        vals = [r["times"][0] - r["due"] for r in recs]
    else:
        vals = [g for r in recs for g in stats.gaps(r["times"])]
    return stats.percentile(vals, q) * 1e3 if vals else None
