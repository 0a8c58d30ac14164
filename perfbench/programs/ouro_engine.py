"""The system under test for served Ouro cells: ``OuroForCausalLM`` (a
looped stack: its layers run ``total_ut_steps`` times a token) behind
``serving.ServingEngine``, built from a configuration file in the dtype
it states. The only place of the benchmark that touches these program
classes; everything but ``build`` and ``counters`` is ``gpt_engine``'s."""

from __future__ import annotations

from . import install_weights
from . import gpt_engine
from .gpt_engine import free, request_state, submit  # noqa: F401

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings",
              "rms_norm_eps", "rope_theta", "total_ut_steps",
              "early_exit_threshold")


def build(config, spec, leaves, chips):
    """A warm-able engine holding ``leaves`` (name -> device array, made
    by the benchmark from the seed, emptied here) as its weights."""
    from paddle_tpu import serving
    from paddle_tpu.models import OuroConfig, OuroForCausalLM

    model = OuroForCausalLM(OuroConfig(
        dtype=config["dtype"], **{k: config[k] for k in MODEL_KEYS}))
    install_weights(model, spec, leaves)
    scfg = serving.ServingConfig(tp=chips, **config["serving"])
    return serving.ServingEngine(model, scfg)


def counters(engine):
    """``gpt_engine``'s counters from ``stats()``, and the loop's from
    ``counters()``: the passes of every decode step enqueued."""
    out = gpt_engine.counters(engine)
    out["loop_passes"] = engine.counters().get("loop_passes", 0)
    return out

