"""Operations and bytes that an algorithm needs, from shapes alone.
Roofline shares and MFU divide these by measured time, so they count
the least a correct implementation must do: recomputation, padding and
masked-out work are left out, and a share cannot pass 100%.
"""

from __future__ import annotations


def matmul_params(cfg):
    """Parameters that take part in a matmul for every token: all of a
    Llama-class decoder but the embedding table, which is a lookup."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
    per_layer = 2 * h * h + 2 * h * kv + 3 * h * f
    return cfg["num_hidden_layers"] * per_layer + h * v


def causal_attention_flops(seq, hidden, passes):
    """Flops of the two attention matmuls (QK^T and PV) of one layer on
    one sequence, causal (half the square), times ``passes`` (1 forward;
    3 forward and backward, the backward being twice the forward)."""
    return passes * 2 * seq * seq * hidden


def train_step_flops(cfg, batch, seq):
    """Model flops of one training step: 6 x parameters x tokens plus
    the attention term. Recomputed operations are not counted."""
    dense = 6 * matmul_params(cfg) * batch * seq
    attn = cfg["num_hidden_layers"] * batch * causal_attention_flops(
        seq, cfg["hidden_size"], 3)
    return dense + attn


def flash_attention_cost(cfg, batch, seq, dtype_bytes=2):
    """(flops, bytes) the attention of one training step needs over all
    layers, forward and backward: the matmuls as above, and one pass
    over q, k, v, o in the forward and over q, k, v, o, do, dq, dk, dv
    in the backward."""
    h = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
    flops = cfg["num_hidden_layers"] * batch * causal_attention_flops(
        seq, h, 3)
    per_tok = (2 * h + 2 * kv) + (4 * h + 4 * kv)
    return flops, cfg["num_hidden_layers"] * batch * seq * per_tok * dtype_bytes


def decode_attention_cost(cfg, context_tokens, dtype_bytes=2):
    """(flops, bytes) of decode attention over all layers for a sum of
    ``context_tokens`` cached positions attended (one query token per
    row and step): K and V of every cached position are read once."""
    h = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    kv_width = cfg.get("num_key_value_heads", heads) * (h // heads)
    layers = cfg["num_hidden_layers"]
    flops = layers * context_tokens * 4 * h
    return flops, layers * context_tokens * 2 * kv_width * dtype_bytes


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which roof sets it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_f, t_b), ("flops" if t_f >= t_b else "bytes")
