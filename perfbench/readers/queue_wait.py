"""Percentile of the time counted requests waited for a slot, as the
engine's own clock has it (``Request.queue_wait_total_s``). ms."""
from perfbench import stats


def read(facts, q):
    vals = [r["queue_wait_s"] for r in facts.get("requests", [])
            if r["counted"] and r["queue_wait_s"] is not None]
    return stats.percentile(vals, q) * 1e3 if vals else None
