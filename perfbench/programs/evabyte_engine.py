"""The system under test for served EvaByte cells: ``EvaByteForCausalLM``
behind ``serving.ServingEngine``, built from a configuration file in the
dtype it states. The only place of the benchmark that touches these
program classes; everything but ``build`` and ``counters`` is
``gpt_engine``'s."""

from __future__ import annotations

from . import install_weights
from .gpt_engine import free, request_state, submit  # noqa: F401

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings",
              "rms_norm_eps", "rope_theta", "attention_class", "chunk_size",
              "window_size", "num_pred_heads")
WINDOW_COUNTERS = ("window_rolls", "window_blocks_released",
                   "summary_entries_written")


def build(config, spec, leaves, chips):
    """A warm-able engine holding ``leaves`` (name -> device array, made
    by the benchmark from the seed, emptied here) as its weights."""
    from paddle_tpu import serving
    from paddle_tpu.models import EvaByteConfig, EvaByteForCausalLM

    model = EvaByteForCausalLM(EvaByteConfig(
        dtype=config["dtype"], **{k: config[k] for k in MODEL_KEYS}))
    install_weights(model, spec, leaves)
    scfg = serving.ServingConfig(tp=chips, **config["serving"])
    return serving.ServingEngine(model, scfg)


def counters(engine):
    """``gpt_engine``'s counters from ``stats()``, and the windowed
    layout's from ``counters()``: slots that crossed into a new window,
    exact-key blocks given back, chunk summaries written."""
    from . import gpt_engine

    out = gpt_engine.counters(engine)
    c = engine.counters()
    out.update({k: c.get(k, 0) for k in WINDOW_COUNTERS})
    return out
