"""Median time of one training step on the host clock, loss on the
host included. ms."""
from perfbench import stats


def read(facts):
    if "step_ends" not in facts:
        return None
    return 1e3 * stats.median_step_s(facts["window"][0], facts["step_ends"])
