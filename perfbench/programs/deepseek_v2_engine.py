"""The system under test for served DeepSeek-V2 cells:
``DeepseekV2ForCausalLM`` (latent attention over a latent cache, one
share of the routed experts) behind ``serving.ServingEngine``, built
from a configuration file in the dtype it states. The only place of the
benchmark that touches these program classes; everything but ``build``
and ``counters`` is ``gpt_engine``'s."""

from __future__ import annotations

# at import, not in ``build``: a program that has no such model (the
# parent of the PR that brought it) fails before it makes any weights
from paddle_tpu.models import DeepseekV2Config, DeepseekV2ForCausalLM

from . import install_weights
from . import gpt_engine
from .gpt_engine import free, request_state, submit  # noqa: F401

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "first_k_dense_replace", "n_shared_experts", "n_group",
              "topk_group", "num_experts_per_tok", "routed_scaling_factor",
              "max_position_embeddings", "rms_norm_eps", "rope_theta",
              "rope_scaling")
ROUTING = ("route_programs", "expert_pairs", "expert_pairs_here",
           "expert_pairs_absent", "experts_touched", "expert_load_max")


def build(config, spec, leaves, chips):
    """A warm-able engine holding ``leaves`` (name -> device array, made
    by the benchmark from the seed, emptied here) as its weights. The
    router scores ``router_experts`` experts; this share holds
    ``n_routed_experts`` of them (``expert_parallel``)."""
    from paddle_tpu import serving

    ep = config["expert_parallel"]
    model = DeepseekV2ForCausalLM(DeepseekV2Config(
        dtype=config["dtype"], n_routed_experts=config["router_experts"],
        ep_rank=ep["rank"], ep_size=ep["size"],
        **{k: config[k] for k in MODEL_KEYS}))
    install_weights(model, spec, leaves)
    scfg = serving.ServingConfig(tp=chips, **config["serving"])
    return serving.ServingEngine(model, scfg)


def counters(engine):
    """``gpt_engine``'s counters from ``stats()``, and the routing's
    from ``counters()``."""
    out = gpt_engine.counters(engine)
    mine = engine.counters()
    out.update({k: mine.get(k, 0) for k in ROUTING})
    return out
