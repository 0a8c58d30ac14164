"""The system under test for served GPT cells: ``GPTForCausalLM``
behind ``serving.ServingEngine``, built from a configuration file. The
only place of the benchmark that touches these program classes."""

from __future__ import annotations

import gc

from . import install_weights

MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "intermediate_size",
              "max_position_embeddings", "layer_norm_eps")


def build(config, spec, leaves, chips):
    """A warm-able engine holding ``leaves`` (name -> device array, made
    by the benchmark from the seed, emptied here) as its weights."""
    from paddle_tpu import serving
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    model = GPTForCausalLM(GPTConfig(**{k: config[k] for k in MODEL_KEYS}))
    install_weights(model, spec, leaves)
    scfg = serving.ServingConfig(tp=chips, **config["serving"])
    return serving.ServingEngine(model, scfg)


def submit(engine, prompt, out_len, on_token):
    return engine.submit(prompt, on_token=on_token, max_new_tokens=out_len)


def request_state(req):
    """What the program says of one request: its status, tokens and the
    time it waited for a slot (the program's own host clock)."""
    return {"completed": req.status == "completed",
            "final": req.done, "tokens": list(req.output_tokens),
            "queue_wait_s": req.queue_wait_total_s}


def counters(engine):
    """Engine counters the readers use, from ``stats()`` alone."""
    st = engine.stats()
    steps = st["steps"]
    occ = st["mean_occupancy"] or 0.0
    return {"engine_steps": steps, "slots": st["slots"],
            "slot_steps": occ * steps * st["slots"],
            "preemptions": st["preemptions"],
            "prefix_cache": st.get("prefix_cache")}


def free(engine):
    """Stop the engine, failing whatever is still in flight, and drop
    its weights and pools so that the reference has the chip."""
    engine.stop(abort=True)
    engine.model = None
    for attr in ("_pb", "_pools", "_state"):
        setattr(engine, attr, None)
    gc.collect()
