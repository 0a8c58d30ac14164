"""Operations and bytes that a looped stack (its layers run
``total_ut_steps`` times a token over one set of weights, a K/V plane
for every pass of every layer) needs in a decode step, from shapes
alone. As ``ops_bytes.py``: the least a correct implementation must do,
so a roofline share cannot pass 100%, and nothing here changes with how
the loop is implemented.
"""

from __future__ import annotations

from . import ops_bytes


def passes(cfg):
    """Passes over the layers a token takes (1 for a plain stack)."""
    return int(cfg.get("total_ut_steps", 1))


def decode_attention_cost(cfg, context_tokens, dtype_bytes=2):
    """(flops, bytes) of decode attention over every pass and layer for
    a sum of ``context_tokens`` cached positions attended (one query
    token per row and step): K and V of every cached position are read
    once in every plane, ``total_ut_steps x num_hidden_layers`` of
    them."""
    flops, nbytes = ops_bytes.decode_attention_cost(cfg, context_tokens,
                                                    dtype_bytes)
    return passes(cfg) * flops, passes(cfg) * nbytes


def layer_params(cfg):
    """Parameters of the layers, which a decode step reads once a pass:
    the attention and SwiGLU matrices (``ops_bytes.matmul_params`` less
    the head) and the four norms of each."""
    h = cfg["hidden_size"]
    return ops_bytes.matmul_params(cfg) - h * cfg["vocab_size"] \
        + cfg["num_hidden_layers"] * 4 * h


def decode_step_cost(cfg, steps, rows, context_tokens, dtype_bytes=2):
    """(flops, bytes) of ``steps`` decode steps that decoded ``rows``
    tokens in all over ``context_tokens`` attended positions: every
    step reads the layers' weights once a pass and the output head
    once, and K and V of every attended position in every plane; every
    token multiplies by the layers' matrices once a pass and by the head
    once. The embedding is a lookup of a row a token and is left out."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    t, layers = passes(cfg), layer_params(cfg)
    a_flops, a_bytes = decode_attention_cost(cfg, context_tokens, dtype_bytes)
    flops = rows * 2 * (t * layers + h * v) + a_flops
    return flops, steps * (t * layers + h * v) * dtype_bytes + a_bytes
