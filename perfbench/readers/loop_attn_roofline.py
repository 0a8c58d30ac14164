"""The paged decode kernel's share of its roofline in a looped stack:
the time the chip needs at least to read K and V, in every plane (a pass
of a layer), of every cached position that the traced decode steps
attended, over the kernel's time in the trace. The positions come from
the client's side, as ``decode_attn_roofline`` takes them: a token that
arrived inside the traced window as a request's j-th (j >= 1) attended
prompt + j positions. Nothing without a trace, and nothing for a
configuration that names no passes. %"""
import re

from perfbench import ops_bytes, ops_loop


def traced_decode(facts):
    """(the reduced trace, the configuration, the positions attended by
    each token decoded inside the traced window), or None where a run
    has no trace, no peaks, no requests or a configuration without
    passes."""
    red = facts.get("trace")
    cfg = facts.get("config") or {}
    if not red or not facts.get("peaks") or "requests" not in facts \
            or "total_ut_steps" not in cfg:
        return None
    lo, hi = red["host_window"]
    return red, cfg, [r["prompt_len"] + j for r in facts["requests"]
                      for j, t in enumerate(r["times"])
                      if j >= 1 and lo <= t < hi]


def read(facts, match):
    got = traced_decode(facts)
    if got is None:
        return None
    red, cfg, attended = got
    t_kernel = sum(v for k, v in red["op_s"].items() if re.search(match, k))
    if not t_kernel:
        return None
    flops, nbytes = ops_loop.decode_attention_cost(cfg, sum(attended))
    least, _ = ops_bytes.roofline_seconds(flops, nbytes, facts["peaks"])
    return 100.0 * least / t_kernel
