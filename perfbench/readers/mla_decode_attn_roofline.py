"""Decode attention over a latent (MLA) cache as a share of its
roofline: the least time to read the one vector a layer of every cached
position the traced decode steps attended and to multiply it in the
latent's space (``ops_mla_moe.decode_attention_cost``: 1,152 B and
278 kFLOP a position and layer at DeepSeek-V2's widths), over the decode
rows' kernel time in the trace. The positions come from the client's
side, as ``decode_attn_roofline`` takes them: a token that arrived
inside the traced window as a request's j-th (j >= 1) attended prompt +
j positions. Nothing without a trace, and nothing for a configuration
without a latent cache. %"""
import re

from perfbench import ops_bytes, ops_mla_moe


def attended(facts):
    """Positions attended by each token decoded inside the traced
    window, or None where a run has no trace, no peaks, no requests or
    a configuration without a latent cache."""
    red, cfg = facts.get("trace"), facts.get("config") or {}
    if not red or not facts.get("peaks") or "requests" not in facts \
            or "kv_lora_rank" not in cfg:
        return None
    lo, hi = red["host_window"]
    return [r["prompt_len"] + j for r in facts["requests"]
            for j, t in enumerate(r["times"]) if j >= 1 and lo <= t < hi]


def read(facts, match):
    ctx = attended(facts)
    if ctx is None:
        return None
    t_kernel = sum(v for k, v in facts["trace"]["op_s"].items()
                   if re.search(match, k))
    if not t_kernel:
        return None
    flops, nbytes = ops_mla_moe.decode_attention_cost(facts["config"],
                                                      sum(ctx))
    least, _ = ops_bytes.roofline_seconds(flops, nbytes, facts["peaks"])
    return 100.0 * least / t_kernel
