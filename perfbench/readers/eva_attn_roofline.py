"""The paged decode kernel's share of its roofline under EVA attention:
the time the chip needs at least to read K and V of every entry (exact
keys of the window, summaries behind it) that the traced decode steps
attended, over the kernel's time in the trace. The entries come from the
client's side: a token that arrived inside the traced window as a
request's j-th (j >= 1) was produced by a query at prompt + j - 1. %"""
import re

from perfbench import ops_bytes, ops_eva


def read(facts, match):
    red = facts.get("trace")
    cfg = facts.get("config") or {}
    if not red or not facts.get("peaks") or "requests" not in facts \
            or "window_size" not in cfg:
        return None
    t_kernel = sum(v for k, v in red["op_s"].items() if re.search(match, k))
    if not t_kernel:
        return None
    lo, hi = red["host_window"]
    entries = sum(
        ops_eva.decode_entries(r["prompt_len"], j, cfg["window_size"],
                               cfg["chunk_size"])
        for r in facts["requests"]
        for j, t in enumerate(r["times"]) if j >= 1 and lo <= t < hi)
    flops, nbytes = ops_eva.attention_cost(cfg, entries)
    least, _ = ops_bytes.roofline_seconds(flops, nbytes, facts["peaks"])
    return 100.0 * least / t_kernel
