"""Model flops utilization: the flops a step needs (6 x parameters x
tokens plus attention, nothing recomputed) over the median step time,
the chips of the cell and the chip's bf16 peak. %"""
from perfbench import ops_bytes, stats


def read(facts):
    if "step_ends" not in facts or not facts.get("peaks"):
        return None
    step_s = stats.median_step_s(facts["window"][0], facts["step_ends"])
    tr = facts["traffic"]
    flops = ops_bytes.train_step_flops(facts["config"], int(tr["batch"]),
                                       int(tr["seq"]))
    return 100.0 * flops / (step_s * facts["chips"]
                            * facts["peaks"]["bf16_flops_per_s"])
