"""The grouped expert matmuls' share of their roofline: the least time
for the routed pairs the traced programs computed (three matrices a
pair; a touched expert's weights read once a program and layer;
``ops_mla_moe.expert_cost``) over the kernels' time in the trace. The
pairs and the experts touched are the program's own counts, written
into the args of its ``engine.dispatch`` and ``engine.prefill`` spans
that started inside the traced stretch of the host's clock (a span tells
the counts of the programs READ in its iteration, the ones the iteration
before enqueued: over a traced stretch of seconds the two ends'
iterations are a hundredth of it). Nothing without a trace, peaks, or a
configuration with routed experts, and nothing from a program that
writes no such arg: the parent of the PR that added them. %"""
import re

from perfbench import ops_bytes, ops_mla_moe
from perfbench.programs import spans


def traced(facts):
    """(trace, cfg, {span name: {"pairs", "touched"}}) or None."""
    red, cfg = facts.get("trace"), facts.get("config") or {}
    if not red or not facts.get("peaks") or "moe_intermediate_size" not in cfg \
            or "host_window" not in red:
        return None
    lo, hi = red["host_window"]
    events, complete = spans.lane(lo, hi, trace=spans.ENGINE)
    if not complete:
        return None
    counts = {}
    for e in events:
        pairs = spans.arg(e, "expert_pairs", None)
        if pairs is None or not lo * 1e9 <= e["ts_ns"] < hi * 1e9:
            continue
        c = counts.setdefault(e["name"], {"pairs": 0, "touched": 0})
        c["pairs"] += pairs
        c["touched"] += spans.arg(e, "experts_touched")
    return (red, cfg, counts) if counts else None


def read(facts, match):
    got = traced(facts)
    if got is None:
        return None
    red, cfg, counts = got
    t_kernel = sum(v for k, v in red["op_s"].items() if re.search(match, k))
    if not t_kernel:
        return None
    flops, nbytes = ops_mla_moe.expert_cost(
        cfg, sum(c["pairs"] for c in counts.values()),
        sum(c["touched"] for c in counts.values()))
    least, _ = ops_bytes.roofline_seconds(flops, nbytes, facts["peaks"])
    return 100.0 * least / t_kernel
