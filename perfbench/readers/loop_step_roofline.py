"""A looped stack's decode step as a share of its roofline: the least
time the traced decode steps need (every step reads the layers' weights
once a pass and the head once, and K and V of every attended position in
every plane; ``ops_loop.decode_step_cost``) over the time of the step
program (``module``) in the trace. The steps are the program's whole
runs inside the window; their rows and positions come from the client's
side, as ``loop_attn_roofline`` takes them. Nothing without a trace, and
nothing for a configuration that names no passes. %"""
from perfbench import ops_bytes, ops_loop
from perfbench.readers.loop_attn_roofline import traced_decode


def read(facts, module):
    got = traced_decode(facts)
    if got is None:
        return None
    red, cfg, attended = got
    steps, t_steps = red["module_calls"].get(module), red["module_s"].get(module)
    if not steps or not t_steps:
        return None
    flops, nbytes = ops_loop.decode_step_cost(cfg, steps, len(attended),
                                              sum(attended))
    least, _ = ops_bytes.roofline_seconds(flops, nbytes, facts["peaks"])
    return 100.0 * least / t_steps
