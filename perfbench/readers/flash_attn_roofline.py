"""The flash attention kernels' share of their roofline over the
traced training steps, forward and backward together. %"""
import re

from perfbench import ops_bytes


def read(facts, match, module):
    red = facts.get("trace")
    if not red or not facts.get("peaks") or "step_ends" not in facts:
        return None
    t_kernel = sum(v for k, v in red["op_s"].items() if re.search(match, k))
    steps = red["module_calls"].get(module)
    if not t_kernel or not steps:
        return None
    tr = facts["traffic"]
    flops, nbytes = ops_bytes.flash_attention_cost(
        facts["config"], int(tr["batch"]), int(tr["seq"]))
    least, _ = ops_bytes.roofline_seconds(flops, nbytes, facts["peaks"])
    return 100.0 * least * steps / t_kernel
