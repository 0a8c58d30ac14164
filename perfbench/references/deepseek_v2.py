"""Plain float32 reference of DeepSeek-V2's language model (DeepSeek-AI,
"DeepSeek-V2: A Strong, Economical, and Efficient Mixture-of-Experts
Language Model", arXiv:2405.04434; ``config.json`` of
``deepseek-ai/DeepSeek-V2``): multi-head latent attention (MLA) over a
compressed key/value, a leading dense layer, then expert layers with a
group-limited router, two shared experts and, here, ONE CHIP'S SHARE of
the routed experts. ``jax.numpy`` only, float32, 'highest' matmuls, no
cache, no kernels, nothing of ``paddle_tpu``. Weights come from
``perfbench.weights`` by leaf name; a linear weight is ``[in, out]``.

Per token, hidden ``x`` of H; ``N_w(x) = x / sqrt(mean(x^2) + eps) * w``;
no bias anywhere.

  layer:  x <- x + Attn(N1(x));  x <- x + FFN(N2(x));  final N_f, untied head

  Attn (every layer), h heads, d_n = qk_nope_head_dim, d_r =
  qk_rope_head_dim, d_v = v_head_dim, r_q = q_lora_rank, r = kv_lora_rank:
    c_q = N_q(x W_qa) [r_q];  q = c_q W_qb -> h heads of [q_nope d_n | q_pe d_r]
    [c_kv | k_pe] = x W_kva [r | d_r];  c_kv <- N_kv(c_kv);  k_pe <- RoPE(k_pe),
      one vector for all heads;  q_pe <- RoPE(q_pe)
    [k_nope | v] = c_kv W_kvb -> h heads of [d_n | d_v]
    s = (q_nope_h . k_nope_h + q_pe_h . k_pe) * scale, causal softmax in
      float32, o_h = softmax(s) v_h;  out = concat_h(o_h) W_o
    scale = (d_n + d_r)^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
  RoPE: YaRN over the d_r dimensions (``yarn_inv_freq``), rotate-half on
    the halves ``[:d_r/2]`` and ``[d_r/2:]`` (see ``assumed`` below);
    cos and sin times yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)

  FFN, layers below ``first_k_dense_replace``: SwiGLU of width
    ``intermediate_size``.
  FFN, the others:  y = Shared(x) + sum_{i in top, i held} w_i E_i(x)
    Shared: SwiGLU of width n_shared_experts * moe_intermediate_size;
    E_i: SwiGLU of width moe_intermediate_size
    router (group_limited_greedy): p = softmax(x W_g) over ALL the router's
      experts in float32; a group's score is its largest p (n_group groups
      of consecutive experts); the best ``topk_group`` groups stay, the
      rest are zeroed; ``top`` = the ``num_experts_per_tok`` largest of
      what is left; w_i = p_i * routed_scaling_factor (norm_topk_prob
      false: no renormalising). Dropless: every chosen pair is computed.
    THE SHARE: the chip holds the experts of ``held_experts(cfg)`` (one
      router group of eight in the benchmark's configuration). The sum
      runs over the chosen experts that are held; what the absent ones
      would have added is left out, and that partial result goes on to
      the next layer. ``ep_size`` 1 holds them all: the whole model.

What ``config.json`` does not say, and the configuration lists under
``assumed``: the release applies rotate-half after permuting the rotated
dimensions from interleaved pairs to halves, which on seeded weights is
a fixed permutation of W_qb's and W_kva's rope columns; program and
reference both take the columns as already permuted (halves).

What the harness forces: ``drivers/serve.logit_gaps`` asks for 256 rows
and slices ``out_len`` of them, and this cell's answers reach 1,024
tokens, so ``logit_rows`` ignores ``rows`` and returns every row from
``start`` on (as the Ouro and EvaByte references do).

Memory: the float32 weights are 12.6 GB of a 16 GB chip and a sequence
reaches 17,408 tokens. Nothing here stacks or copies a leaf; a layer is
one jitted program on the layer's own leaves (one compile for each
padded length and layer kind, the lengths in steps of ``LENGTH_STEP``:
compiled afresh for every sequence the check's reference took 565 s of
a run, my chip runs, PR 43); attention runs a group of ``HEAD_GROUP``
heads at a time (its slices of W_qb, W_kvb and W_o, a loop over the
groups), and inside a group a block of ``Q_ROWS`` queries at a time, so
nothing of size positions x positions x heads is kept; the experts are
a loop over the held ids, each on all tokens, weighted by a router
weight that is zero where the token did not choose it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

ATTN_LEAVES = ("input_layernorm.weight", "self_attn.q_a_proj.weight",
               "self_attn.q_a_layernorm.weight", "self_attn.q_b_proj.weight",
               "self_attn.kv_a_proj_with_mqa.weight",
               "self_attn.kv_a_layernorm.weight",
               "self_attn.kv_b_proj.weight", "self_attn.o_proj.weight",
               "post_attention_layernorm.weight")
DENSE_LEAVES = ("mlp.gate_proj.weight", "mlp.up_proj.weight",
                "mlp.down_proj.weight")
MOE_LEAVES = ("mlp.gate.weight", "mlp.experts.gate_proj",
              "mlp.experts.up_proj", "mlp.experts.down_proj",
              "mlp.shared_experts.gate_proj.weight",
              "mlp.shared_experts.up_proj.weight",
              "mlp.shared_experts.down_proj.weight")

# queries scored at once against the whole sequence, and heads at once
Q_ROWS = 256
HEAD_GROUP = 16


def router_experts(cfg):
    """Width of the router: every expert of the deployment, held here or
    not (``router_experts``; a whole model's ``n_routed_experts``)."""
    return int(cfg.get("router_experts", cfg["n_routed_experts"]))


def held_experts(cfg):
    """The ids of the routed experts this share holds: rank ``rank`` of
    ``size`` equal consecutive parts (``expert_parallel``; all of them
    where the configuration names no parts)."""
    ep = cfg.get("expert_parallel") or {"rank": 0, "size": 1}
    n = router_experts(cfg) // int(ep["size"])
    return list(range(int(ep["rank"]) * n, (int(ep["rank"]) + 1) * n))


def is_dense(cfg, i):
    return i < int(cfg["first_k_dense_replace"])


def param_spec(cfg):
    """name -> (shape, mean, std), in a fixed order. Matrices N(0, 0.02);
    norm weights N(1, 0.1) so that a dropped or misplaced norm moves the
    logits; the router N(0, 0.05) (logits of standard deviation 3.6 at
    a hidden size of 5120), so that its softmax over the experts is far
    from flat without one expert taking it all, and a wrong group mask,
    a wrong top or a missing scaling factor changes which experts a token
    gets and by how much;
    the expert matrices stacked over the held experts."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f, fm = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fm * cfg["n_shared_experts"]
    held = len(held_experts(cfg))
    s = 0.02
    spec = {"model.embed_tokens.weight": ((v, h), 0.0, s)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"model.layers.{i}."
        spec[b + "input_layernorm.weight"] = ((h,), 1.0, 0.1)
        spec[b + "self_attn.q_a_proj.weight"] = ((h, rq), 0.0, s)
        spec[b + "self_attn.q_a_layernorm.weight"] = ((rq,), 1.0, 0.1)
        spec[b + "self_attn.q_b_proj.weight"] = ((rq, heads * (dn + dr)),
                                                 0.0, s)
        spec[b + "self_attn.kv_a_proj_with_mqa.weight"] = ((h, r + dr),
                                                           0.0, s)
        spec[b + "self_attn.kv_a_layernorm.weight"] = ((r,), 1.0, 0.1)
        spec[b + "self_attn.kv_b_proj.weight"] = ((r, heads * (dn + dv)),
                                                  0.0, s)
        spec[b + "self_attn.o_proj.weight"] = ((heads * dv, h), 0.0, s)
        spec[b + "post_attention_layernorm.weight"] = ((h,), 1.0, 0.1)
        if is_dense(cfg, i):
            spec[b + "mlp.gate_proj.weight"] = ((h, f), 0.0, s)
            spec[b + "mlp.up_proj.weight"] = ((h, f), 0.0, s)
            spec[b + "mlp.down_proj.weight"] = ((f, h), 0.0, s)
            continue
        spec[b + "mlp.gate.weight"] = ((h, router_experts(cfg)), 0.0, 0.05)
        spec[b + "mlp.experts.gate_proj"] = ((held, h, fm), 0.0, s)
        spec[b + "mlp.experts.up_proj"] = ((held, h, fm), 0.0, s)
        spec[b + "mlp.experts.down_proj"] = ((held, fm, h), 0.0, s)
        spec[b + "mlp.shared_experts.gate_proj.weight"] = ((h, fs), 0.0, s)
        spec[b + "mlp.shared_experts.up_proj.weight"] = ((h, fs), 0.0, s)
        spec[b + "mlp.shared_experts.down_proj.weight"] = ((fs, h), 0.0, s)
    spec["model.norm.weight"] = ((h,), 1.0, 0.1)
    spec["lm_head.weight"] = ((h, v), 0.0, s)
    return spec


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, sc):
    """The ``dim / 2`` inverse frequencies of YaRN: below the correction
    dimension of ``beta_fast`` rotations the unscaled ``theta^(-2i/dim)``,
    above that of ``beta_slow`` the same over ``factor``, between the two
    a linear ramp from the one to the other."""
    def correction_dim(rotations):
        return dim * math.log(sc["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / sc["factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(cfg):
    sc = cfg.get("rope_scaling")
    m = yarn_mscale(sc["factor"], sc["mscale_all_dim"]) if sc else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, cfg):
    """Rotate-half rotary embedding of ``x [S, .., d_r]`` at positions
    0 .. S-1."""
    s, d = x.shape[0], x.shape[-1]
    sc, theta = cfg.get("rope_scaling"), float(cfg["rope_theta"])
    if sc:
        inv = yarn_inv_freq(d, theta, sc)
        mag = yarn_mscale(sc["factor"], sc["mscale"]) \
            / yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    else:
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        mag = 1.0
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang) * mag, jnp.sin(ang) * mag
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(qn, qp, kn, kp, v, scale):
    """Causal softmax attention of one group of heads on ``qn, kn [S, g,
    d_n]``, rotated ``qp [S, g, d_r]`` and ``kp [S, d_r]`` (shared by the
    heads), ``v [S, g, d_v]``; a block of ``Q_ROWS`` queries at a time
    (S a multiple of it, or below it)."""
    s = qn.shape[0]
    rows = min(s, Q_ROWS)

    def one_block(r):
        at = lambda t: jax.lax.dynamic_slice_in_dim(t, r * rows, rows, 0)  # noqa: E731
        sc = (jnp.einsum("qhd,khd->hqk", at(qn), kn)
              + jnp.einsum("qhd,kd->hqk", at(qp), kp)) * scale
        seen = jnp.arange(s)[None, :] <= r * rows + jnp.arange(rows)[:, None]
        sc = jnp.where(seen[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    out = jax.lax.map(one_block, jnp.arange(s // rows))
    return out.reshape((s,) + out.shape[2:])


def attention(y, leaves, cfg, mm):
    """MLA, decompressed, on the normalised ``y [S, H]``: the latent and
    the shared rotated key once, then per group of heads its queries,
    its decompressed keys and values, its attention and its rows of
    W_o."""
    wqa, nq, wqb, wkva, nkv, wkvb, wo = leaves
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    dn, dr, dv, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    s = y.shape[0]
    c_q = _norm(mm(y, wqa), nq, eps)
    kva = mm(y, wkva)
    c_kv, k_pe = _norm(kva[:, :r], nkv, eps), _rope(kva[:, r:], cfg)
    scale, g = softmax_scale(cfg), min(HEAD_GROUP, heads)

    def one_group(i, out):
        cols = lambda w, d: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, i * g * d, g * d, 1)
        q = mm(c_q, cols(wqb, dn + dr)).reshape(s, g, dn + dr)
        kv = mm(c_kv, cols(wkvb, dn + dv)).reshape(s, g, dn + dv)
        o = _attend(q[..., :dn], _rope(q[..., dn:], cfg), kv[..., :dn],
                    k_pe, kv[..., dn:], scale)
        return out + mm(o.reshape(s, g * dv), jax.lax.dynamic_slice_in_dim(
            wo, i * g * dv, g * dv, 0))

    # one group's program, run ``heads / g`` times: a layer compiles in
    # seconds whatever the heads
    return jax.lax.fori_loop(0, heads // g, one_group,
                             jnp.zeros((s, wo.shape[1]), jnp.float32))


def _swiglu(y, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(y, wg)) * mm(y, wu), wd)


def route(y, wg, cfg):
    """``w [S, E]``: a token's weight for every expert of the router,
    zero for those it did not choose (group_limited_greedy). The product
    and the softmax are float32 at 'highest' whatever ``mm`` a control
    passes, as the release casts both to float32."""
    e, groups = wg.shape[1], cfg["n_group"]
    p = jax.nn.softmax(jnp.matmul(y, wg), -1)
    best = p.reshape(-1, groups, e // groups).max(-1)
    kept = jax.lax.top_k(best, cfg["topk_group"])[1]
    stays = jnp.zeros_like(best).at[jnp.arange(p.shape[0])[:, None],
                                    kept].set(1.0)
    left = p * jnp.repeat(stays, e // groups, axis=1)
    top = jax.lax.top_k(left, cfg["num_experts_per_tok"])[1]
    chosen = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None],
                                  top].set(1.0)
    return p * chosen * cfg["routed_scaling_factor"]


def moe(y, leaves, cfg, mm):
    """Shared experts whole, and the chosen experts that are held: a
    loop over the held ids, each expert on every token, weighted by the
    token's router weight for it (zero where it did not choose it)."""
    wg, eg, eu, ed, sg, su, sd = leaves
    w = route(y, wg, cfg)
    first = held_experts(cfg)[0]

    def one_expert(j, out):
        wj = jax.lax.dynamic_slice_in_dim(w, first + j, 1, 1)
        return out + wj * _swiglu(y, eg[j], eu[j], ed[j], mm)

    return jax.lax.fori_loop(0, eg.shape[0], one_expert,
                             _swiglu(y, sg, su, sd, mm))


@partial(jax.jit, static_argnames=("cfg_items", "mm", "dense"))
def _layer(x, leaves, cfg_items, mm, dense):
    """One layer on ``x [S, H]`` float32."""
    cfg = _cfg(cfg_items)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = x + attention(_norm(x, leaves[0], eps), leaves[1:8], cfg, mm)
        y = _norm(x, leaves[8], eps)
        if dense:
            return x + _swiglu(y, *leaves[9:], mm)
        return x + moe(y, leaves[9:], cfg, mm)


@partial(jax.jit, static_argnames=("eps", "mm"))
def _head(x, nw, w, eps, mm):
    with jax.default_matmul_precision("highest"):
        return mm(_norm(x, nw, eps), w)


def _items(cfg):
    """The configuration as something hashable: its numbers, and the two
    nested groups the layer reads."""
    flat = [(k, v) for k, v in cfg.items() if isinstance(v, (int, float))]
    for k in ("rope_scaling", "expert_parallel"):
        if cfg.get(k):
            flat.append((k, tuple(sorted(
                (a, b) for a, b in cfg[k].items()
                if isinstance(b, (int, float))))))
    return tuple(sorted(flat))


def _cfg(items):
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in items}


# a sequence is padded at its end to a multiple of this before the
# layers run (attention is causal and a token's experts are its own, so
# no earlier row changes): the check's six sequences of 2k-17k tokens
# then compile a handful of lengths, not one each
LENGTH_STEP = 4096


def hidden_states(params, ids, cfg, mm=jnp.matmul):
    """The residual stream ``[S, H]`` after the last layer (before the
    final norm) over the whole sequence ``ids [S]``. ``mm`` computes
    every linear layer's product but the router's (a control passes a
    lower-precision one)."""
    items, n = _items(cfg), ids.shape[0]
    if n > Q_ROWS:
        ids = jnp.pad(ids, (0, -n % LENGTH_STEP))
    x = params["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        b, dense = f"model.layers.{i}.", is_dense(cfg, i)
        names = ATTN_LEAVES + (DENSE_LEAVES if dense else MOE_LEAVES)
        x = _layer(x, tuple(params[b + k] for k in names), items, mm, dense)
    return x[:n]


def logits(params, ids, cfg, mm=jnp.matmul):
    """``[S, V]``: every position's logits."""
    return logit_rows(params, ids, 0, None, cfg, mm)


def logit_rows(params, ids, start, rows, cfg, mm=jnp.matmul):
    """Logits ``[len(ids) - start, V]`` at positions ``start`` .. the
    end of ``ids``, which may be padded at its end: attention is causal
    and a token's experts are its own, so padding changes no earlier
    row. ``rows`` is not used: see the module's docstring."""
    del rows
    x = hidden_states(params, ids, cfg, mm)
    return _head(x[int(start):], params["model.norm.weight"],
                 params["lm_head.weight"], cfg["rms_norm_eps"], mm)
