"""Operations and bytes of a model with a latent (MLA) cache and routed
experts of which one share is held, from shapes and the program's
routing counts alone. As ``ops_bytes.py``: the least a correct
implementation must do, so a roofline share cannot pass 100%, and
nothing here changes with how attention or the expert layer is
implemented (the pool's padding to lane tiles is in no count).
"""

from __future__ import annotations


def latent_width(cfg):
    """Values a cached position holds in a layer: the compressed
    key/value and the rotated key dimensions all heads share."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def decode_attention_cost(cfg, context_tokens, dtype_bytes=2):
    """(flops, bytes) of decode attention over all layers for a sum of
    ``context_tokens`` cached positions attended, one query row a cache
    row: a position's vector is read once a layer, and in the latent's
    space (the absorbed form, the cheaper one for a single row) every
    head multiplies the whole vector for its score and the latent for
    its output: 1,152 B and 278,528 flops a position and layer at
    DeepSeek-V2's widths."""
    rank, width = cfg["kv_lora_rank"], latent_width(cfg)
    per_pos = cfg["num_attention_heads"] * (width + rank) * 2
    n = context_tokens * cfg["num_hidden_layers"]
    return n * per_pos, n * width * dtype_bytes


def expert_params(cfg):
    """Parameters of one routed expert (a SwiGLU)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_cost(cfg, pairs, experts_touched, dtype_bytes=2):
    """(flops, bytes) of the routed experts for ``pairs`` (token,
    expert) pairs computed, in programs that touched
    ``experts_touched`` experts in all (summed over programs and
    layers): a pair multiplies its expert's three matrices, and a
    program reads a touched expert's weights once."""
    return (pairs * 2 * expert_params(cfg),
            experts_touched * expert_params(cfg) * dtype_bytes)


def attention_params(cfg):
    """Parameters of one layer's attention: the two low-rank paths,
    their norms and the output projection."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (h * rq + rq + rq * heads * (dn + dr) + h * (r + dr) + r
            + r * heads * (dn + dv) + heads * dv * h)


def unrouted_params(cfg):
    """Parameters every token multiplies and every program reads whole:
    attention and the two norms of every layer, the dense layers' SwiGLU,
    the expert layers' shared experts and router, the final norm and the
    head. The embedding is a lookup of a row a token and is left out."""
    h = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - dense
    shared = 3 * h * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    router = h * cfg.get("router_experts", cfg["n_routed_experts"])
    return (cfg["num_hidden_layers"] * (attention_params(cfg) + 2 * h)
            + dense * 3 * h * cfg["intermediate_size"]
            + moe * (shared + router) + h + h * cfg["vocab_size"])


def decode_step_cost(cfg, steps, rows, context_tokens, pairs,
                     experts_touched, dtype_bytes=2):
    """(flops, bytes) of ``steps`` decode steps that decoded ``rows``
    tokens in all over ``context_tokens`` attended positions, computed
    ``pairs`` routed pairs and touched ``experts_touched`` experts
    (summed over steps and layers): a step reads the unrouted weights
    once, a touched expert's once, and the latent of every attended
    position; a token multiplies the unrouted weights once."""
    a_flops, a_bytes = decode_attention_cost(cfg, context_tokens, dtype_bytes)
    e_flops, e_bytes = expert_cost(cfg, pairs, experts_touched, dtype_bytes)
    base = unrouted_params(cfg)
    return (rows * 2 * base + a_flops + e_flops,
            steps * base * dtype_bytes + a_bytes + e_bytes)
