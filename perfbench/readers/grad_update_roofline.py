"""The share of their roofline that the operations which make the
linear weights' gradients and apply the optimizer's update to them
reach over the traced training steps: the least time the
configuration's linear leaves need for one step (``ops_grad_update``),
times the steps, over the time of the operations that match (on a TPU
XLA runs each leaf's weight-gradient matmul with the whole AdamW update
as its epilogue and names the fusion for the update's last operations;
the embedding's and the norms' updates carry the same name and count
against the share). Nothing where no operation matches. %"""
import re

from perfbench import ops_grad_update


def read(facts, match, module):
    red = facts.get("trace")
    if not red or not facts.get("peaks") or "step_ends" not in facts:
        return None
    t_ops = sum(v for k, v in red["op_s"].items() if re.search(match, k))
    steps = red["module_calls"].get(module)
    if not t_ops or not steps:
        return None
    tr = facts["traffic"]
    least = ops_grad_update.least_seconds(
        facts["config"], int(tr["batch"]), int(tr["seq"]), facts["peaks"])
    return 100.0 * least * steps / t_ops
