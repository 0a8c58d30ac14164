"""How late the open-loop generator sent, against each request's due
time: a starved generator must not read as a fast server. ms."""
from perfbench import stats


def read(facts, q):
    vals = [r["sent"] - r["due"] for r in facts.get("requests", [])
            if r["counted"] and r["sent"] is not None]
    return stats.percentile(vals, q) * 1e3 if vals else None
