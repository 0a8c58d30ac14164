"""The decode step of a model with a latent cache and a share of routed
experts as a share of its roofline, the whole step: the least time the
traced decode steps need (``ops_mla_moe.decode_step_cost``: a step reads
every layer's unrouted weights and the head once, the weights of the
experts it touched once, and the latent of every attended position)
over the time of the step program (``module``) in the trace. The steps
are the program's whole runs inside the window; their rows and positions
come from the client's side (``mla_decode_attn_roofline.attended``), the
pairs and the experts touched from the ``engine.dispatch`` spans
(``expert_matmul_roofline.traced``). A step that also carried prefill rows counts with
all its time, and of those rows' work only their routed pairs are in the
least, so the share reads low while steps carry rows and cannot pass
100%. %"""
from perfbench import ops_bytes, ops_mla_moe
from perfbench.readers.mla_decode_attn_roofline import attended
from perfbench.readers.expert_matmul_roofline import traced


def read(facts, module, span):
    ctx, got = attended(facts), traced(facts)
    if ctx is None or got is None:
        return None
    red, cfg, counts = got
    steps, t_steps = red["module_calls"].get(module), red["module_s"].get(module)
    mine = counts.get(span)
    if not steps or not t_steps or not mine:
        return None
    flops, nbytes = ops_mla_moe.decode_step_cost(
        cfg, steps, len(ctx), sum(ctx), mine["pairs"], mine["touched"])
    least, _ = ops_bytes.roofline_seconds(flops, nbytes, facts["peaks"])
    return 100.0 * least / t_steps
