"""EvaByte: a byte-level decoder with EVA chunked linearized attention.

EVA (Zheng et al., ICLR 2023, arXiv:2302.04542) as EvaByte's release
(huggingface.co/EvaByte/EvaByte, 2025-01) made it deterministic: a query
attends the exact keys of its own window of ``window_size`` positions
and, for every window behind it, one learned summary per ``chunk_size``
positions; one softmax runs over the union. Per head, with the learned
vectors ``phi`` and ``mu`` (``adaptive_phi``, ``adaptive_mu_k``) and
``s = head_dim ** -0.5``, the summary of a chunk is

    a_j = softmax_j(s * k_j . phi);  kbar = sum_j a_j k_j + mu;
    vbar = sum_j a_j v_j

taken of rotated keys. Below one window this is causal softmax
attention. The rest is the Llama block this module borrows from
``models/llama.py`` (q/k/v/o projections, rotary tables, SwiGLU), with
RMSNorm scaled by ``1 + w`` (``norm_add_unit_offset``), residual sums
kept in float32 (``fp32_skip_add``) and float32 logits from a head of
``num_pred_heads`` x ``vocab_size`` columns, head ``p`` predicting byte
``t + 1 + p``. ``config.json`` does not give the shapes of ``phi`` and
``mu``, where ``s`` enters the pooling softmax, that ``mu`` joins the
key summary only, or the head's ``[num_pred_heads, vocab]`` layout:
those are this module's reading, stated again by the plain reference
(``perfbench/references/evabyte.py``) and the configuration file.

Served through ``ServingEngine`` on paged pools: a summary is one pool
entry, so a slot's table is [summary blocks | window blocks | the
window's summaries being filled] (``generation.eva_virtual_position``)
and the paged decode kernel reads it as it reads any row. The engine
releases the window's blocks when a slot's position crosses a multiple
of ``window_size``. Serving samples from head 0; multi-byte
self-speculation from the other heads is not built.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.initializer import Constant
from ..ops.dispatch import apply_op
from .llama import (LlamaAttention, LlamaConfig, LlamaMLP, _rope_tables,
                    apply_rotary_pos_emb)


@dataclass
class EvaByteConfig(LlamaConfig):
    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    attention_class: str = "eva"
    chunk_size: int = 16
    window_size: int = 2048
    num_pred_heads: int = 8

    @staticmethod
    def tiny(**overrides):
        cfg = EvaByteConfig(vocab_size=64, hidden_size=64, intermediate_size=128,
                            num_hidden_layers=2, num_attention_heads=4,
                            num_key_value_heads=4, max_position_embeddings=512,
                            chunk_size=4, window_size=16, num_pred_heads=2)
        for k, v in overrides.items():
            setattr(cfg, k, v)
        return cfg


def eva_attention(q, k, v, phi, mu, window: int, chunk: int):
    """EVA attention over one whole sequence with no cache (the dense
    form: scores [s, s + s // chunk] at once, for training-free use and
    the tests; the served path never builds it). Tensors [b, s, h, d],
    already rotated; returns [b, s, h, d] in q's dtype."""
    from ..generation import eva_pool_chunks

    sm_scale = q.shape[-1] ** -0.5

    def _f(qa, ka, va, ph, m):
        b, s, h, d = qa.shape
        n = s // chunk                             # whole chunks
        kbar, vbar = eva_pool_chunks(
            *(t[:, :n * chunk].reshape(b, n, chunk, h, d) for t in (ka, va)),
            ph, m, sm_scale)
        qf = qa.astype(jnp.float32)
        pos = jnp.arange(s)
        exact = (pos[None, :] <= pos[:, None]) \
            & (pos[None, :] // window == pos[:, None] // window)
        behind = (jnp.arange(kbar.shape[1])[None, :] * chunk // window
                  < pos[:, None] // window)
        sc = jnp.concatenate([
            jnp.einsum("bqhd,bmhd->bhqm", qf, kbar),
            jnp.einsum("bqhd,bkhd->bhqk", qf, ka.astype(jnp.float32))],
            -1) * sm_scale
        sc = jnp.where(jnp.concatenate([behind, exact], -1)[None, None], sc,
                       -jnp.inf)
        w = jax.nn.softmax(sc, -1)
        vals = jnp.concatenate([vbar, va.astype(jnp.float32)], 1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, vals).astype(qa.dtype)

    return apply_op("eva_attention", _f, q, k, v, phi, mu)


def _fp32_logits(h, w):
    """``fp32_logits``: float32 operands and a float32 product (the
    TPU's default would round both operands to bfloat16)."""
    with jax.default_matmul_precision("highest"):
        return F.linear(h, w)


class EvaRMSNorm(nn.Layer):
    """RMSNorm scaled by ``1 + weight`` (``norm_add_unit_offset``)."""

    def __init__(self, hidden_size, epsilon):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            (hidden_size,), default_initializer=Constant(0.0))

    def forward(self, x):
        # 1 + w in float32: in bfloat16 the sum keeps 8 bits of w
        return F.rms_norm(x, self.weight.astype("float32") + 1.0,
                          self._epsilon)


class EvaAttention(LlamaAttention):
    """Llama's projections and rotary embedding around EVA attention."""

    def __init__(self, config: EvaByteConfig):
        super().__init__(config)
        shape = (self.num_heads, self.head_dim)
        self.adaptive_phi = self.create_parameter(
            shape, default_initializer=Constant(0.0))
        self.adaptive_mu_k = self.create_parameter(
            shape, default_initializer=Constant(0.0))

    def forward(self, hidden_states, cos_tab, sin_tab, kv_cache=None,
                position_offset=0):
        b, s, _ = hidden_states.shape
        cfg = self.config
        shape = [b, s, self.num_heads, self.head_dim]
        q = self.q_proj(hidden_states).reshape(shape)
        k = self.k_proj(hidden_states).reshape(shape)
        v = self.v_proj(hidden_states).reshape(shape)
        q, k = apply_rotary_pos_emb(q, k, cos_tab, sin_tab, position_offset)
        if kv_cache is None:
            out = eva_attention(q, k, v, self.adaptive_phi, self.adaptive_mu_k,
                                cfg.window_size, cfg.chunk_size)
            return self.o_proj(out.reshape([b, s, -1]))
        from ..generation import (cached_attention, eva_summary_write,
                                  eva_virtual_position, kv_cache_layout)

        paged, quantized = kv_cache_layout(kv_cache) \
            if isinstance(kv_cache, dict) else (False, False)
        if not paged or quantized:
            raise TypeError(
                "an EVA model's cache is the paged pool of the serving "
                "engine in the model's own dtype (window and summary blocks "
                "behind one block table); contiguous and quantized caches "
                "hold no summaries")
        pos = position_offset._data if isinstance(position_offset, Tensor) \
            else position_offset
        # the keys go to the row's virtual position and are read from
        # there; the chunks this write completes are pooled in between
        out, new_cache = cached_attention(
            q, k, v, kv_cache,
            eva_virtual_position(pos, cfg.window_size, cfg.chunk_size),
            family="evabyte",
            after_write=lambda cache: eva_summary_write(
                cache, self.adaptive_phi, self.adaptive_mu_k, pos, s,
                cfg.window_size, cfg.chunk_size, self.head_dim ** -0.5))
        return self.o_proj(out.reshape([b, s, -1])), new_cache


class EvaByteDecoderLayer(nn.Layer):
    def __init__(self, config: EvaByteConfig):
        super().__init__()
        self.self_attn = EvaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = EvaRMSNorm(config.hidden_size,
                                          config.rms_norm_eps)
        self.post_attention_layernorm = EvaRMSNorm(config.hidden_size,
                                                   config.rms_norm_eps)
        self._dtype_name = config.dtype

    def forward(self, x, cos_tab, sin_tab, kv_cache=None, position_offset=0):
        # the residual stream is float32 (fp32_skip_add); each branch
        # computes in the model's dtype
        y = self.input_layernorm(x).astype(self._dtype_name)
        new_cache = None
        if kv_cache is not None:
            y, new_cache = self.self_attn(y, cos_tab, sin_tab, kv_cache,
                                          position_offset)
        else:
            y = self.self_attn(y, cos_tab, sin_tab)
        x = x + y.astype("float32")
        y = self.post_attention_layernorm(x).astype(self._dtype_name)
        x = x + self.mlp(y).astype("float32")
        return x if kv_cache is None else (x, new_cache)


class EvaByteModel(nn.Layer):
    def __init__(self, config: EvaByteConfig):
        super().__init__()
        self.config = config
        # every layer is cast as it is built: twelve published-width
        # layers in float32 would not fit beside their seeded weights
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size).to(dtype=config.dtype)
        self.layers = nn.LayerList([
            EvaByteDecoderLayer(config).to(dtype=config.dtype)
            for _ in range(config.num_hidden_layers)])
        self.norm = EvaRMSNorm(config.hidden_size,
                               config.rms_norm_eps).to(dtype=config.dtype)
        cos_tab, sin_tab = _rope_tables(
            config.hidden_size // config.num_attention_heads,
            config.max_position_embeddings, config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos_tab), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin_tab), persistable=False)

    def forward(self, input_ids, kv_caches=None, position_offset=0):
        h = self.embed_tokens(input_ids).astype("float32")
        cos_tab, sin_tab = self.rope_cos._data, self.rope_sin._data
        if kv_caches is None:
            for layer in self.layers:
                h = layer(h, cos_tab, sin_tab)
            return self.norm(h)
        new_caches = []
        for layer, cache in zip(self.layers, kv_caches, strict=True):
            h, nc = layer(h, cos_tab, sin_tab, cache, position_offset)
            new_caches.append(nc)
        return self.norm(h), new_caches


class EvaByteForCausalLM(nn.Layer):
    """``forward(ids)`` gives every prediction head's logits
    [b, s, num_pred_heads, vocab] in float32; with ``kv_caches`` (the
    serving engine's paged pools) it gives head 0's [b, s, vocab], the
    next byte's, and the new caches."""

    def __init__(self, config: EvaByteConfig):
        super().__init__()
        if config.attention_class != "eva" \
                or config.window_size % config.chunk_size:
            raise ValueError(
                f"EvaByte needs attention_class 'eva' and a window "
                f"({config.window_size}) of whole chunks ({config.chunk_size})")
        self.config = config
        self.evabyte = EvaByteModel(config)
        self.lm_head = nn.Linear(
            config.hidden_size, config.num_pred_heads * config.vocab_size,
            bias_attr=False).to(dtype=config.dtype)

    def forward(self, input_ids, attn_mask=None, kv_caches=None,
                position_offset=0):
        if attn_mask is not None:
            raise ValueError("EVA attention takes no external mask: its "
                             "visibility is the window and the summaries")
        cfg = self.config
        w = self.lm_head.weight.astype("float32")
        if kv_caches is None:
            h = self.evabyte(input_ids)
            b, s, _ = h.shape
            return _fp32_logits(h, w).reshape(
                [b, s, cfg.num_pred_heads, cfg.vocab_size])
        from ..generation import head_rows

        h, new_caches = self.evabyte(input_ids, kv_caches, position_offset)
        return _fp32_logits(head_rows(h, kv_caches),
                            w[:, :cfg.vocab_size]), new_caches
