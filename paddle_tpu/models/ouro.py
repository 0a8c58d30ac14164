"""Ouro: a looped decoder (ByteDance, Ouro 1.4B/2.6B LoopLM, 2025-10;
Zhu et al., "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741). One stack of ``num_hidden_layers`` layers is run
``total_ut_steps`` times over one set of weights; a token's hidden state
after pass ``t`` is what pass ``t + 1`` starts from.

With ``N_w(x) = x / sqrt(mean(x^2) + eps) * w`` and no bias but the
gate's:

  layer i, every pass:  x <- x + N_{i,2}(Attn_i(N_{i,1}(x)))
                        x <- x + N_{i,4}(MLP_i(N_{i,3}(x)))
  stack:  x = E[ids];  for t = 1..T:  x <- layer_L(.. layer_1(x));
          h_t = N_f(x);  g_t = w_g . h_t + b_g;  x <- h_t
  exit:   lambda_t = sigmoid(g_t);  p_t = lambda_t prod_{j<t}(1 - lambda_j)
          for t < T,  p_T = prod_{j<T}(1 - lambda_j);  the exit pass is the
          first t with p_1 + .. + p_t >= q (``early_exit_threshold``), the
          last pass where none is; at q >= 1 it is pass T for every token
  logits = W_head h_exit

``Attn`` and ``MLP`` are ``models/llama.py``'s (q/k/v/o projections,
rotate-half rotary embedding at the token's absolute position in every
pass, SwiGLU). ``config.json`` gives the sizes, the passes and the
threshold. The four norms of a layer (one before and one after each
branch, the second inside the residual branch), the final norm inside
the loop feeding the next pass, the gate as a ``Linear(H, 1)`` with bias
on the normalised state, and one cache plane for every pass and layer
are this module's reading of the release's modelling code as its author
recalls it; the plain reference (``perfbench/references/ouro.py``) and
the configuration file state them again.

Cache: a query of pass ``t``, layer ``i`` attends what pass ``t`` of
layer ``i`` wrote for every position up to its own, so a cached position
holds ``T * L`` planes (``generation.kv_cache_planes``) and every pass
fills its planes whatever the gate says: later tokens attend them.
Served through ``ServingEngine`` on paged pools, a layer's ``T`` planes
are ONE pool array of ``T * num_blocks`` blocks and pass ``t`` reads and
writes through ``block_table + t * num_blocks``; the passes are then a
``lax.fori_loop`` whose body is the ``L`` layers, so the step and the
prefill programs hold ``L`` layer bodies and not ``T * L``
(``fold_loop``; unrolled, the same arithmetic in the same order). A
contiguous cache (``generate``) is a list of ``T * L`` buffers, plane
``t * L + i``, walked unrolled. Both layouts are
``generation.looped_cache_passes``'s to know; this file hands it one
pass. The engine serves ``early_exit_threshold
= 1`` only: rows of one batched step leaving the stack at different
passes, while later tokens still need the planes of the passes they
skipped, is not built.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from .llama import LlamaAttention, LlamaConfig, LlamaMLP, _rope_tables


@dataclass
class OuroConfig(LlamaConfig):
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    dtype: str = "bfloat16"
    # the passes of a paged cached forward as one loop in the program
    # (False: unrolled, T * L layer bodies; the tests hold the two equal
    # to float32 rounding)
    fold_loop: bool = True

    @staticmethod
    def tiny(**overrides):
        cfg = OuroConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         num_key_value_heads=4, max_position_embeddings=256,
                         total_ut_steps=3, dtype="float32")
        for k, v in overrides.items():
            setattr(cfg, k, v)
        return cfg


def exit_distribution(gate):
    """``p [T, ...]`` from the gate's values ``g [T, ...]``: the
    probability of leaving after pass t, the last pass taking what is
    left."""
    lam = jax.nn.sigmoid(gate.astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], 0)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], 0)


def exit_pass(pdf, threshold: float):
    """Index (0-based) of the exit pass for every token: the first t
    whose cumulative exit probability reaches ``threshold``, else the
    last; the last for every token at ``threshold >= 1`` (a saturated
    sigmoid must not round a token out of its later passes)."""
    last = pdf.shape[0] - 1
    if threshold >= 1.0:
        return jnp.full(pdf.shape[1:], last, jnp.int32)
    reached = jnp.cumsum(pdf, axis=0) >= threshold
    return jnp.where(jnp.any(reached, 0), jnp.argmax(reached, 0),
                     last).astype(jnp.int32)


class OuroDecoderLayer(nn.Layer):
    """Llama's attention and SwiGLU, each between two norms: one on the
    branch's input, one on its output before the residual sum."""

    def __init__(self, config: OuroConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        h, eps = config.hidden_size, config.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(h, epsilon=eps)
        self.input_layernorm_2 = nn.RMSNorm(h, epsilon=eps)
        self.post_attention_layernorm = nn.RMSNorm(h, epsilon=eps)
        self.post_attention_layernorm_2 = nn.RMSNorm(h, epsilon=eps)

    def forward(self, x, cos_tab, sin_tab, attn_mask=None, kv_cache=None,
                position_offset=0):
        y = self.input_layernorm(x)
        new_cache = None
        if kv_cache is not None:
            y, new_cache = self.self_attn(y, cos_tab, sin_tab, attn_mask,
                                          kv_cache, position_offset)
        else:
            y = self.self_attn(y, cos_tab, sin_tab, attn_mask)
        x = x + self.input_layernorm_2(y)
        y = self.mlp(self.post_attention_layernorm(x))
        x = x + self.post_attention_layernorm_2(y)
        return x if kv_cache is None else (x, new_cache)


class OuroModel(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        # every layer is cast as it is built: 48 published-width layers
        # in float32 would not fit beside their seeded weights
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size).to(dtype=config.dtype)
        self.layers = nn.LayerList([
            OuroDecoderLayer(config).to(dtype=config.dtype)
            for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(
            config.hidden_size,
            epsilon=config.rms_norm_eps).to(dtype=config.dtype)
        self.early_exit_gate = nn.Linear(
            config.hidden_size, 1).to(dtype=config.dtype)
        cos_tab, sin_tab = _rope_tables(
            config.hidden_size // config.num_attention_heads,
            config.max_position_embeddings, config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos_tab), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin_tab), persistable=False)

    def one_pass(self, h, attn_mask=None, caches=None, position_offset=0):
        """The L layers and the final norm, once: ``h_t`` from what the
        pass before left (``caches``: this pass's L cache dicts)."""
        cos_tab, sin_tab = self.rope_cos._data, self.rope_sin._data
        if caches is None:
            for layer in self.layers:
                h = layer(h, cos_tab, sin_tab, attn_mask)
            return self.norm(h)
        new_caches = []
        for layer, cache in zip(self.layers, caches, strict=True):
            h, nc = layer(h, cos_tab, sin_tab, attn_mask, cache,
                          position_offset)
            new_caches.append(nc)
        return self.norm(h), new_caches

    def forward(self, input_ids, attn_mask=None):
        """Every pass's normalised state, ``T`` of ``[b, s, H]`` (no
        cache)."""
        h = self.embed_tokens(input_ids)
        states = []
        for _ in range(self.config.total_ut_steps):
            h = self.one_pass(h, attn_mask)
            states.append(h)
        return states

    def cached(self, input_ids, attn_mask, kv_caches, position_offset):
        """``h_T`` through the cache, and the caches as they came
        (``generation.looped_cache_passes`` knows their layout)."""
        from ..generation import looped_cache_passes

        cfg = self.config
        # the device trace shows the passes, and a kernel inside one
        # under this name (a decode step's: ouro_pass_q1)
        scope = f"ouro_pass_q{input_ids.shape[1]}"
        return looped_cache_passes(
            lambda h, caches: self.one_pass(h, attn_mask, caches,
                                            position_offset),
            self.embed_tokens(input_ids), kv_caches, cfg.total_ut_steps,
            fold=cfg.fold_loop, scope=scope)


class OuroForCausalLM(nn.Layer):
    """``forward(ids)`` gives the logits ``[b, s, vocab]`` of each
    token's exit pass (pass T at the published threshold of 1); with
    ``return_passes=True`` a dict of every pass's ``hidden`` and
    ``logits`` ``[T, b, s, ..]``, the ``gate`` and the ``exit_pdf``
    ``[T, b, s]``, the ``exit_pass`` ``[b, s]`` and those ``logits``.
    With ``kv_caches`` it gives pass T's logits and the new caches."""

    def __init__(self, config: OuroConfig):
        super().__init__()
        if config.total_ut_steps < 1:
            raise ValueError(
                f"total_ut_steps ({config.total_ut_steps}) must be >= 1")
        self.config = config
        self.ouro = OuroModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False).to(dtype=config.dtype)

    def forward(self, input_ids, attn_mask=None, kv_caches=None,
                position_offset=0, return_passes=False):
        if kv_caches is not None:
            from ..generation import head_rows

            if self.config.early_exit_threshold < 1.0:
                raise ValueError(
                    "the cached forward of a looped stack runs every pass "
                    "for every token (early_exit_threshold 1); a lower "
                    "threshold is applied by the uncached forward alone")
            h, new_caches = self.ouro.cached(input_ids, attn_mask, kv_caches,
                                             position_offset)
            return self.lm_head(head_rows(h, kv_caches)), new_caches
        states = self.ouro(input_ids, attn_mask)
        threshold = float(self.config.early_exit_threshold)
        if not return_passes and threshold >= 1.0:
            return self.lm_head(states[-1])
        from ..ops.manipulation import stack

        hidden = stack(states, axis=0)                      # [T, b, s, H]
        gate = self.ouro.early_exit_gate(hidden)            # [T, b, s, 1]
        logits_all = self.lm_head(hidden)
        # the exit rule reads values and selects: inference only, no op
        # of its own on the dispatch surface
        pdf = exit_distribution(gate._data[..., 0])
        picked = exit_pass(pdf, threshold)
        logits = Tensor(jnp.take_along_axis(
            logits_all._data, picked[None, :, :, None], axis=0)[0])
        if not return_passes:
            return logits
        return {"hidden": hidden, "logits_per_pass": logits_all,
                "gate": Tensor(gate._data[..., 0]), "exit_pdf": Tensor(pdf),
                "exit_pass": Tensor(picked), "logits": logits}

    def generate(self, input_ids, max_new_tokens: int = 32, **kwargs):
        from ..generation import generate

        return generate(self, input_ids, max_new_tokens=max_new_tokens,
                        **kwargs)
