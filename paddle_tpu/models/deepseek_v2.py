"""DeepSeek-V2's language model (DeepSeek-AI, "DeepSeek-V2: A Strong,
Economical, and Efficient Mixture-of-Experts Language Model",
arXiv:2405.04434; ``config.json`` of ``deepseek-ai/DeepSeek-V2``), for
the serving path: multi-head latent attention (MLA) over a latent cache,
a leading dense layer, then expert layers with a group-limited router,
shared experts, and ONE SHARE of the routed experts.

With ``N_w(x) = x / sqrt(mean(x^2) + eps) * w`` and no bias anywhere:

  layer:  x <- x + Attn(N1(x));  x <- x + FFN(N2(x));  final N_f, untied head
  Attn:   c_q = N_q(x W_qa);  q = c_q W_qb -> heads of [q_nope | q_pe]
          [c_kv | k_pe] = x W_kva;  c_kv <- N_kv(c_kv);  k_pe <- RoPE(k_pe),
          one vector for all heads;  q_pe <- RoPE(q_pe)
          [k_nope | v] = c_kv W_kvb -> heads of [d_nope | d_v]
          s = (q_nope_h . k_nope_h + q_pe_h . k_pe) * scale, causal softmax
          in float32, o_h = softmax(s) v_h;  out = concat_h(o_h) W_o
          scale = (d_nope + d_rope)^-0.5 * m^2,  m = 0.1 * mscale_all_dim *
          ln(factor) + 1;  RoPE is YaRN over d_rope (``yarn_inv_freq``),
          rotate-half on the halves
  FFN:    below ``first_k_dense_replace`` a SwiGLU of ``intermediate_size``;
          else  Shared(x) + sum_{i in top, i held} w_i E_i(x)
          (``distributed/moe_serving.py``: router over ALL
          ``n_routed_experts``, dropless, the held experts' part)

CACHE: ``N_kv(c_kv)`` and the rotated ``k_pe``, ``kv_lora_rank +
qk_rope_head_dim`` values a position and layer, nothing per head
(``generation.latent_cache_width``; one pool array a layer).
``generation.latent_cached_attention`` writes it and attends by either
form of the same mathematics: DECOMPRESSED (keys and values of every
head rebuilt from the cached latents) or ABSORBED (``q_nope_h W_uk_h^T``
against the latent itself, the weighted latents through ``W_uv_h``:
multi-query attention with one 576-wide key whose first 512 columns are
its value). On the chip the paged kernel attends absorbed, decode steps
and prefill chunks alike (measured the faster there at every chunk
length, PERF.md section 6, PR 43); in XLA few query rows a cache row
attend absorbed and many (``generation.latent_absorb_below``)
decompressed. The uncached forward is decompressed.

THE SHARE: ``ep_rank`` of ``ep_size`` holds ``n_routed_experts //
ep_size`` consecutive experts (at ``ep_size = n_group``, one router
group, the release's device-limited routing). Attention, the shared
experts and the router are whole on every share. The layer adds the
chosen experts it holds and leaves out the rest; ``ep_size`` 1 is the
whole model. Inference only: raw arrays under the layers' parameters,
nothing taped.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from .llama import LlamaConfig, LlamaMLP

__all__ = ["DeepseekV2Config", "DeepseekV2ForCausalLM", "yarn_inv_freq",
           "yarn_mscale", "mla_softmax_scale"]


def _yarn():
    return {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
            "mscale": 0.707, "mscale_all_dim": 0.707,
            "original_max_position_embeddings": 4096}


@dataclass
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 60
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    n_routed_experts: int = 160
    n_shared_experts: int = 2
    n_group: int = 8
    topk_group: int = 3
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 16.0
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = field(default_factory=_yarn)
    # the share of the routed experts held here
    ep_rank: int = 0
    ep_size: int = 1
    dtype: str = "bfloat16"

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts // self.ep_size

    @staticmethod
    def tiny(**overrides):
        cfg = DeepseekV2Config(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            n_routed_experts=16, n_group=4, topk_group=2,
            num_experts_per_tok=3, max_position_embeddings=256,
            rope_scaling=dict(_yarn(), original_max_position_embeddings=64),
            dtype="float32")
        for k, v in overrides.items():
            setattr(cfg, k, v)
        return cfg


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: dict):
    """YaRN's ``dim / 2`` inverse frequencies: ``theta^(-2i/dim)`` below
    the correction dimension of ``beta_fast`` rotations, the same over
    ``factor`` above that of ``beta_slow``, a linear ramp between."""
    def correction_dim(rotations):
        return dim * math.log(scaling["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / scaling["factor"] * ramp + plain * (1.0 - ramp)


def mla_softmax_scale(config: DeepseekV2Config) -> float:
    sc = config.rope_scaling
    m = yarn_mscale(sc["factor"], sc["mscale_all_dim"]) if sc else 1.0
    return (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5 * m * m


def _rope_tables(config: DeepseekV2Config):
    """cos and sin ``[max_position_embeddings, d_rope / 2]`` float32."""
    d, theta, sc = (config.qk_rope_head_dim, float(config.rope_theta),
                    config.rope_scaling)
    if sc:
        inv = yarn_inv_freq(d, theta, sc)
        mag = yarn_mscale(sc["factor"], sc["mscale"]) \
            / yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    else:
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        mag = 1.0
    ang = jnp.outer(jnp.arange(config.max_position_embeddings,
                               dtype=jnp.float32), inv)
    return jnp.cos(ang) * mag, jnp.sin(ang) * mag


def _rotate(x, cos, sin):
    """Rotate-half of ``x [b, s, .., d]`` by ``cos``/``sin`` [b, s, d/2]
    (float32 tables, the product in x's dtype)."""
    shape = cos.shape[:2] + (1,) * (x.ndim - 3) + cos.shape[-1:]
    c, si = cos.reshape(shape).astype(x.dtype), sin.reshape(shape).astype(
        x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * si, x2 * c + x1 * si], -1)


def _positions(position_offset, b: int, s: int):
    """``[b, s]`` absolute positions of a call's tokens: rows of ``s``
    consecutive ones from a shared or per-row offset."""
    po = position_offset._data if isinstance(position_offset, Tensor) \
        else position_offset
    po = jnp.broadcast_to(jnp.asarray(po, jnp.int32), (b,))
    return po[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]


class DeepseekV2Attention(nn.Layer):
    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        h, heads = config.hidden_size, config.num_attention_heads
        dq = config.qk_nope_head_dim + config.qk_rope_head_dim
        self.q_a_proj = nn.Linear(h, config.q_lora_rank, bias_attr=False)
        self.q_a_layernorm = nn.RMSNorm(config.q_lora_rank,
                                        epsilon=config.rms_norm_eps)
        self.q_b_proj = nn.Linear(config.q_lora_rank, heads * dq,
                                  bias_attr=False)
        self.kv_a_proj_with_mqa = nn.Linear(
            h, config.kv_lora_rank + config.qk_rope_head_dim,
            bias_attr=False)
        self.kv_a_layernorm = nn.RMSNorm(config.kv_lora_rank,
                                         epsilon=config.rms_norm_eps)
        self.kv_b_proj = nn.Linear(
            config.kv_lora_rank,
            heads * (config.qk_nope_head_dim + config.v_head_dim),
            bias_attr=False)
        self.o_proj = nn.Linear(heads * config.v_head_dim, h,
                                bias_attr=False)

    def forward(self, x, cos_tab, sin_tab, kv_cache=None, position_offset=0):
        cfg = self.config
        b, s, _ = x.shape
        heads, dn, dr, dv, rank = (
            cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank)
        q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x)))._data \
            .reshape(b, s, heads, dn + dr)
        kva = self.kv_a_proj_with_mqa(x)
        c_kv = self.kv_a_layernorm(kva[:, :, :rank])._data
        at = _positions(position_offset, b, s)
        at = jnp.minimum(at, cos_tab.shape[0] - 1)
        cos, sin = cos_tab[at], sin_tab[at]                 # [b, s, dr/2]
        q_nope, q_pe = q[..., :dn], _rotate(q[..., dn:], cos, sin)
        k_pe = _rotate(kva._data[:, :, rank:], cos, sin)
        w_kvb = self.kv_b_proj.weight._data.reshape(rank, heads, dn + dv)
        scale = mla_softmax_scale(cfg)
        new_cache = None
        if kv_cache is not None:
            from ..generation import latent_cached_attention

            out, new_cache = latent_cached_attention(
                q_nope, q_pe, jnp.concatenate([c_kv, k_pe], -1), kv_cache,
                position_offset, w_kvb=w_kvb, sm_scale=scale,
                family="deepseek_v2")
        else:
            kv = jnp.einsum("bkr,rhd->bkhd", c_kv, w_kvb.astype(c_kv.dtype))
            sc = (jnp.einsum("bshd,bkhd->bhsk", q_nope, kv[..., :dn],
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshd,bkd->bhsk", q_pe, k_pe,
                               preferred_element_type=jnp.float32)) * scale
            seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
            p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
            out = jnp.einsum("bhsk,bkhd->bshd", p.astype(kv.dtype),
                             kv[..., dn:])
        out = self.o_proj(Tensor(out.reshape(b, s, heads * dv)))
        return out if kv_cache is None else (out, new_cache)


class _Experts(nn.Layer):
    """The held experts' SwiGLU weights, stacked: ``gate_proj``/
    ``up_proj`` [E_held, H, F], ``down_proj`` [E_held, F, H]."""

    def __init__(self, held: int, hidden: int, width: int, dtype: str):
        super().__init__()
        # made in the served dtype one array at a time: a stack is 0.6 GB
        # in float32 at the published widths
        how = dict(dtype=dtype,
                   default_initializer=nn.initializer.Normal(0.0, 0.02))
        self.gate_proj = self.create_parameter((held, hidden, width), **how)
        self.up_proj = self.create_parameter((held, hidden, width), **how)
        self.down_proj = self.create_parameter((held, width, hidden), **how)


class DeepseekV2MoE(nn.Layer):
    """``Shared(x) + sum_{i in top, i held} w_i E_i(x)``; ``forward``
    also returns the call's routing counts
    (``moe_serving.ROUTE_STATS``)."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        if config.n_routed_experts % config.ep_size \
                or config.n_routed_experts % config.n_group:
            raise ValueError(
                f"n_routed_experts ({config.n_routed_experts}) must divide "
                f"into ep_size ({config.ep_size}) shares and n_group "
                f"({config.n_group}) groups")
        h, f = config.hidden_size, config.moe_intermediate_size
        self.gate = nn.Linear(h, config.n_routed_experts, bias_attr=False)
        self.experts = _Experts(config.experts_held, h, f, config.dtype)
        self.shared_experts = _swiglu(h, f * config.n_shared_experts)

    def forward(self, x, live=None):
        from ..distributed.moe_serving import (group_limited_route,
                                               held_expert_ffn)

        cfg = self.config
        b, s, h = x.shape
        flat = x._data.reshape(b * s, h)
        ids, weights = group_limited_route(
            flat, self.gate.weight._data, n_group=cfg.n_group,
            topk_group=cfg.topk_group, top_k=cfg.num_experts_per_tok,
            scale=float(cfg.routed_scaling_factor))
        routed, stats = held_expert_ffn(
            flat, ids, weights, self.experts.gate_proj._data,
            self.experts.up_proj._data, self.experts.down_proj._data,
            first=cfg.ep_rank * cfg.experts_held,
            live=None if live is None else live.reshape(b * s))
        return self.shared_experts(x) + Tensor(routed.reshape(b, s, h)), stats


def _swiglu(hidden: int, width: int):
    """``models/llama.py``'s SwiGLU at another width."""
    return LlamaMLP(LlamaConfig(hidden_size=hidden, intermediate_size=width))


class DeepseekV2DecoderLayer(nn.Layer):
    def __init__(self, config: DeepseekV2Config, index: int):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.self_attn = DeepseekV2Attention(config)
        self.dense = index < config.first_k_dense_replace
        self.mlp = _swiglu(h, config.intermediate_size) if self.dense \
            else DeepseekV2MoE(config)
        self.input_layernorm = nn.RMSNorm(h, epsilon=eps)
        self.post_attention_layernorm = nn.RMSNorm(h, epsilon=eps)

    def forward(self, x, cos_tab, sin_tab, kv_cache=None, position_offset=0,
                live=None):
        """``(x, new_cache or None, routing counts or None)``."""
        y = self.input_layernorm(x)
        new_cache = None
        if kv_cache is not None:
            y, new_cache = self.self_attn(y, cos_tab, sin_tab, kv_cache,
                                          position_offset)
        else:
            y = self.self_attn(y, cos_tab, sin_tab)
        x = x + y
        y, stats = self.post_attention_layernorm(x), None
        if self.dense:
            y = self.mlp(y)
        else:
            y, stats = self.mlp(y, live)
        return x + y, new_cache, stats


class DeepseekV2Model(nn.Layer):
    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        # every layer is cast as it is built: a published-width expert
        # layer in float32 does not fit beside its seeded weights
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size).to(dtype=config.dtype)
        self.layers = nn.LayerList([
            DeepseekV2DecoderLayer(config, i).to(dtype=config.dtype)
            for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(
            config.hidden_size,
            epsilon=config.rms_norm_eps).to(dtype=config.dtype)
        cos_tab, sin_tab = _rope_tables(config)
        self.register_buffer("rope_cos", Tensor(cos_tab), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin_tab), persistable=False)

    def forward(self, input_ids, kv_caches=None, position_offset=0):
        """``(h [b, s, H], new caches or None, routing counts int32 [4]
        summed over the expert layers, the busiest expert's pairs
        too)``."""
        cos_tab, sin_tab = self.rope_cos._data, self.rope_sin._data
        h = self.embed_tokens(input_ids)
        live = None
        if kv_caches is not None and kv_caches[0].get("live") is not None:
            live = kv_caches[0]["live"]
            live = live._data if isinstance(live, Tensor) else live
        new_caches, total = [], None
        for i, layer in enumerate(self.layers):
            cache = None if kv_caches is None else kv_caches[i]
            h, nc, stats = layer(h, cos_tab, sin_tab, cache, position_offset,
                                 live)
            new_caches.append(nc)
            if stats is not None:
                total = stats if total is None else total + stats
        return self.norm(h), (None if kv_caches is None else new_caches), \
            total


class DeepseekV2ForCausalLM(nn.Layer):
    """``forward(ids)`` gives the logits ``[b, s, vocab]``; with
    ``kv_caches`` (``generation.make_paged_kv_pools`` or
    ``make_kv_caches``: one latent array a layer) the logits of the rows
    the head is asked for (``generation.head_rows``) and the new caches,
    the first of which carries the call's routing counts as
    ``"route_stats"`` (``moe_serving.ROUTE_STATS``, summed over the
    expert layers; rows a caller marks dead in ``kv_caches[0]["live"]``
    [b, s] bool are left out of them and of the expert matmuls)."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        self.model = DeepseekV2Model(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False).to(dtype=config.dtype)

    def forward(self, input_ids, attn_mask=None, kv_caches=None,
                position_offset=0):
        if attn_mask is not None:
            raise ValueError(
                "DeepseekV2ForCausalLM attends causally by position alone: "
                "an external attn_mask (ragged left-padded prompts) has no "
                "path over the latent cache")
        h, new_caches, stats = self.model(input_ids, kv_caches,
                                          position_offset)
        if kv_caches is None:
            return self.lm_head(h)
        from ..generation import head_rows

        if stats is not None:
            new_caches[0] = dict(new_caches[0], route_stats=Tensor(stats))
        return self.lm_head(head_rows(h, kv_caches)), new_caches

    def generate(self, input_ids, max_new_tokens: int = 32, **kwargs):
        from ..generation import generate

        return generate(self, input_ids, max_new_tokens=max_new_tokens,
                        **kwargs)
