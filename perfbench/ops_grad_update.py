"""Operations and bytes of a linear weight's gradient and its AdamW
update, from shapes alone: what ``grad_update_roofline`` divides by the
measured time. A leaf ``[in, out]`` trained on ``T`` tokens needs the
contraction ``x^T dy`` (2 x T x in x out flops) and one pass over its
state: the parameter read and written in its own dtype, Adam's two f32
moments read and written, and the two factors ``x [T, in]`` and ``dy
[T, out]`` read once. The gradient itself is counted nowhere: a correct
implementation never has to write it. Whatever implements the leaves
(XLA's fusion of the matmul with the update, or a kernel) is held to the
same count, and each leaf to the larger of its two roofs, so the share
cannot pass 100%.
"""

from __future__ import annotations

from perfbench import ops_bytes

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def linear_leaves(cfg):
    """``(in, out)`` of every linear weight of a Llama-class decoder:
    q, k, v, o, gate, up and down of each layer, and the head where it
    is not tied to the embedding table."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
    layer = [(h, h), (h, kv), (h, kv), (h, h), (h, f), (h, f), (f, h)]
    head = [] if cfg.get("tie_word_embeddings") else [(h, v)]
    return layer * cfg["num_hidden_layers"] + head


def leaf_cost(n_in, n_out, tokens, dtype_bytes=2):
    """(flops, bytes) of one leaf's gradient and update on ``tokens``
    rows."""
    flops = 2 * tokens * n_in * n_out
    state = (2 * dtype_bytes + 4 * 4) * n_in * n_out
    return flops, state + dtype_bytes * tokens * (n_in + n_out)


def least_seconds(cfg, batch, seq, peaks):
    """The least time one step's gradients and updates of the linear
    weights need on the chip: every leaf at the larger of its roofs."""
    width = _BYTES[cfg["dtype"]]
    return sum(
        ops_bytes.roofline_seconds(
            *leaf_cost(n_in, n_out, batch * seq, width), peaks)[0]
        for n_in, n_out in linear_leaves(cfg))
