"""The paged decode kernel's share of its roofline: the time the chip
needs at least to read K and V of every cached position that the
traced decode steps attended, over the kernel's time in the trace. The
positions come from the client's side: a token that arrived inside the
traced window as a request's j-th (j >= 1) attended prompt + j
positions. %"""
import re

from perfbench import ops_bytes


def read(facts, match):
    red = facts.get("trace")
    if not red or not facts.get("peaks") or "requests" not in facts:
        return None
    t_kernel = sum(v for k, v in red["op_s"].items() if re.search(match, k))
    if not t_kernel:
        return None
    lo, hi = red["host_window"]
    ctx = sum(r["prompt_len"] + j for r in facts["requests"]
              for j, t in enumerate(r["times"]) if j >= 1 and lo <= t < hi)
    flops, nbytes = ops_bytes.decode_attention_cost(facts["config"], ctx)
    least, _ = ops_bytes.roofline_seconds(flops, nbytes, facts["peaks"])
    return 100.0 * least / t_kernel
