"""Llama model family — the flagship pretraining model.

Capability parity target: the reference trains Llama/GPT through
PaddleNLP on fleet hybrid parallelism (SURVEY §3.4); the in-framework
pieces it relies on are fused attention kernels
(phi/kernels/gpu/flash_attn_kernel.cu), TP layers (mpu/mp_layers.py),
and SPMD rules (phi/infermeta/spmd_rules/flash_attention.cc). This module
is the TPU-native model built directly on those equivalents:
- attention: nn.functional.scaled_dot_product_attention (XLA-fused) or
  the Pallas flash kernel for long sequences;
- TP/SP/DP: parameters carry mesh placements via ``llama_shard_fn``
  (Megatron layout: qkv/gate column-sharded, o/down row-sharded,
  embeddings vocab-sharded), activations get sequence-dim constraints —
  GSPMD materializes the same collectives fleet would issue;
- rotary embeddings, RMSNorm, SwiGLU as fusable jnp chains.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.functional import repeat_kv
from ..ops import dispatch as _dispatch
from ..ops.dispatch import apply_op


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = False  # Pallas kernel (long-seq path)
    # single [h, (q+2kv)*d] / [h, 2*ffn] matmuls instead of 3/2 separate
    # ones sharing the input (reference: PaddleNLP fuse_attention_qkv /
    # fused_linear config). Opt-in: on v5e at the 134M bench point both
    # measured SLOWER than the unfused layout (qkv 124.7k vs 127.8k
    # tok/s, mlp 127.0k) — XLA already amortizes the shared input read,
    # and the post-matmul slices cost more than the fusion saves; kept
    # for weight-layout parity with fused-checkpoint ecosystems
    fuse_attention_qkv: bool = False
    fuse_mlp: bool = False
    # Mixtral-style MoE decoder: >0 replaces every MLP with a GShard MoE
    # (distributed/moe.py MoELayer) — the in-model door to the reference's
    # incubate MoE surface. Experts are built replicated here; shard them
    # over an 'ep' axis with distributed.auto_shard (ExpertMLP pairing
    # rule) or shard_tensor on experts.w*/b*, and set
    # moe_dispatch_mode='einsum' so GSPMD turns dispatch/combine into
    # all-to-alls (default None: MoELayer picks gather, the fast
    # single-granule path)
    moe_num_experts: int = 0
    moe_topk: int = 2
    moe_capacity_factor: float = 1.25
    moe_dispatch_mode: Optional[str] = None
    dtype: str = "float32"

    @staticmethod
    def llama2_7b(**overrides):
        cfg = LlamaConfig()
        for k, v in overrides.items():
            setattr(cfg, k, v)
        return cfg

    @staticmethod
    def tiny(**overrides):
        cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128)
        for k, v in overrides.items():
            setattr(cfg, k, v)
        return cfg


def _rope_tables(head_dim: int, max_pos: int, theta: float):
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_pos, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [max_pos, head_dim/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rotary_pos_emb(q: Tensor, k: Tensor, cos_tab, sin_tab, position_offset: int = 0):
    """Rotary embedding on [b, s, h, d] tensors (reference:
    incubate fused_rope / PaddleNLP rope; half-split convention).
    ``position_offset`` may be a per-row [b] vector (serving decode:
    every slot sits at its own position) — the tables are then gathered
    per row instead of sliced once."""

    def _rope(x, cos, sin):
        s = x.shape[1]
        if isinstance(position_offset, int):
            c = cos[position_offset:position_offset + s]
            si = sin[position_offset:position_offset + s]
        elif getattr(position_offset, "ndim", 0) == 2:
            # explicit [b, s] position grid (spec-tree bundles: node i
            # occupies cache slot pos+i but its ROTARY position is
            # pos+depth(i) — siblings share a position)
            c = cos[position_offset]   # [b, s, d/2]
            si = sin[position_offset]
        elif getattr(position_offset, "ndim", 0) == 1:
            # per-row offsets [b]: [b, s] position rows, a row past the
            # table's end reading its last row
            if s == 1:   # the decode step: one table row a sequence
                c, si = (tab[position_offset[:, None]] for tab in (cos, sin))
            else:
                # a batch of prefill chunks or verify bundles: ONE slice
                # of s rows a sequence (its start clamped into the
                # table, the rows then shifted back to their places). As
                # a gather of b * s single rows XLA first converts the
                # whole table to the activation dtype: 0.1 ms a program
                # at EvaByte's 32768 x 64 (PERF.md section 6, PR 28)
                def rows(tab):
                    def one(p):
                        start = jnp.clip(p, 0, tab.shape[0] - s)
                        sl = jax.lax.dynamic_slice_in_dim(tab, start, s, 0)
                        return sl[jnp.minimum(
                            jnp.arange(s) + (p - start), s - 1)]
                    return jax.vmap(one)(position_offset)

                c, si = rows(cos), rows(sin)
        else:  # traced offset (jitted decode step)
            c = jax.lax.dynamic_slice_in_dim(cos, position_offset, s, 0)
            si = jax.lax.dynamic_slice_in_dim(sin, position_offset, s, 0)
        # apply the rotation in the activation dtype: the tables are
        # COMPUTED in fp32 (angle precision lives there), but a bf16
        # activation rounds the product to bf16 anyway, so casting the
        # table first costs <=1 ulp while keeping the whole rope fwd AND
        # its transpose in bf16 — fp32 tables made XLA materialize fp32
        # [b,h,s,d] copies in the backward (~10 ms/step on the MoE bench)
        if c.ndim == 3:  # per-row [b, s, d/2]
            c = c[:, :, None, :].astype(x.dtype)
            si = si[:, :, None, :].astype(x.dtype)
        else:
            c = c[None, :, None, :].astype(x.dtype)
            si = si[None, :, None, :].astype(x.dtype)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([
            x1 * c - x2 * si,
            x2 * c + x1 * si,
        ], axis=-1)

    qo = apply_op("rope", lambda x: _rope(x, cos_tab, sin_tab), q)
    ko = apply_op("rope", lambda x: _rope(x, cos_tab, sin_tab), k)
    return qo, ko


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.hidden_size = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        if config.fuse_attention_qkv:
            self.qkv_proj = nn.Linear(
                self.hidden_size,
                (self.num_heads + 2 * self.num_kv_heads) * self.head_dim,
                bias_attr=False)
        else:
            self.q_proj = nn.Linear(self.hidden_size, self.num_heads * self.head_dim, bias_attr=False)
            self.k_proj = nn.Linear(self.hidden_size, self.num_kv_heads * self.head_dim, bias_attr=False)
            self.v_proj = nn.Linear(self.hidden_size, self.num_kv_heads * self.head_dim, bias_attr=False)
        self.o_proj = nn.Linear(self.num_heads * self.head_dim, self.hidden_size, bias_attr=False)

    def forward(self, hidden_states, cos_tab, sin_tab, attn_mask=None, kv_cache=None, position_offset=0):
        b, s, _ = hidden_states.shape
        if self.config.fuse_attention_qkv:
            qkv = self.qkv_proj(hidden_states)
            qd = self.num_heads * self.head_dim
            kvd = self.num_kv_heads * self.head_dim
            q = qkv[:, :, :qd].reshape([b, s, self.num_heads, self.head_dim])
            k = qkv[:, :, qd:qd + kvd].reshape([b, s, self.num_kv_heads, self.head_dim])
            v = qkv[:, :, qd + kvd:].reshape([b, s, self.num_kv_heads, self.head_dim])
        else:
            q = self.q_proj(hidden_states).reshape([b, s, self.num_heads, self.head_dim])
            k = self.k_proj(hidden_states).reshape([b, s, self.num_kv_heads, self.head_dim])
            v = self.v_proj(hidden_states).reshape([b, s, self.num_kv_heads, self.head_dim])
        # under a tp>1 trace, pin [b, s, heads, d] activations to the
        # heads axis so GSPMD keeps column-parallel outputs where the
        # q/k/v weight shards put them (no-op at tp=1)
        from ..distributed.partition import maybe_constrain_heads

        q, k, v = (maybe_constrain_heads(q), maybe_constrain_heads(k),
                   maybe_constrain_heads(v))
        # a spec-tree bundle's [s] node-depth vector rides the cache dict
        # and decouples each node's rotary position from its cache slot
        tree_depth = kv_cache.get("tree_depth") \
            if isinstance(kv_cache, dict) else None
        rope_pos = position_offset
        if tree_depth is not None:
            td = tree_depth._data if isinstance(tree_depth, Tensor) \
                else jnp.asarray(tree_depth)
            po = position_offset._data \
                if isinstance(position_offset, Tensor) \
                else jnp.asarray(position_offset, jnp.int32)
            if po.ndim == 0:
                po = jnp.broadcast_to(po, (b,))
            rope_pos = po[:, None] + td[None, :].astype(jnp.int32)
        q, k = apply_rotary_pos_emb(q, k, cos_tab, sin_tab, rope_pos)

        if isinstance(kv_cache, dict):
            # contiguous buffers or paged pools: generation.py writes
            # the cache and picks what reads it
            from ..generation import cached_attention

            out, new_cache = cached_attention(
                q, k, v, kv_cache, position_offset, family="llama",
                attn_mask=attn_mask,
                flash_prefill=self.config.use_flash_attention)
        else:
            new_cache = None
            if kv_cache is not None:  # the legacy growing (k, v) tuple
                from ..ops.manipulation import concat

                k = concat([kv_cache[0], k], axis=1)
                v = concat([kv_cache[1], v], axis=1)
                new_cache = (k, v)
            # the training/uncached paths expand the heads (the Pallas
            # prefill kernel wants them so)
            if self.num_kv_heads != self.num_heads:
                rep = self.num_heads // self.num_kv_heads
                k = repeat_kv(k, rep)
                v = repeat_kv(v, rep)
            if self.config.use_flash_attention and attn_mask is None:
                from ..pallas_kernels.flash_attention import flash_attention

                out = flash_attention(q, k, v, causal=True)
            else:
                out = F.scaled_dot_product_attention(
                    q, k, v, attn_mask=attn_mask,
                    is_causal=attn_mask is None)
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        out = self.o_proj(out)
        if kv_cache is not None:
            return out, new_cache
        return out


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self._fused = config.fuse_mlp
        self._ffn = config.intermediate_size
        if self._fused:
            self.gate_up_proj = nn.Linear(
                config.hidden_size, 2 * config.intermediate_size, bias_attr=False)
        else:
            self.gate_proj = nn.Linear(config.hidden_size, config.intermediate_size, bias_attr=False)
            self.up_proj = nn.Linear(config.hidden_size, config.intermediate_size, bias_attr=False)
        self.down_proj = nn.Linear(config.intermediate_size, config.hidden_size, bias_attr=False)

    def forward(self, x):
        if self._fused:
            gu = self.gate_up_proj(x)
            gate, up = gu[:, :, :self._ffn], gu[:, :, self._ffn:]
            return self.down_proj(F.silu(gate) * up)
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        if config.moe_num_experts > 0:
            from ..distributed.moe import MoELayer

            self.mlp = MoELayer(
                d_model=config.hidden_size,
                d_hidden=config.intermediate_size,
                num_experts=config.moe_num_experts,
                topk=config.moe_topk,
                capacity_factor=config.moe_capacity_factor,
                activation="silu",
                dispatch_mode=config.moe_dispatch_mode)
        else:
            self.mlp = LlamaMLP(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, hidden_states, cos_tab, sin_tab, attn_mask=None, kv_cache=None,
                position_offset=0):
        residual = hidden_states
        hidden_states = self.input_layernorm(hidden_states)
        new_cache = None
        if kv_cache is not None:
            hidden_states, new_cache = self.self_attn(hidden_states, cos_tab, sin_tab,
                                                      attn_mask, kv_cache, position_offset)
        else:
            hidden_states = self.self_attn(hidden_states, cos_tab, sin_tab, attn_mask)
        hidden_states = residual + hidden_states
        residual = hidden_states
        hidden_states = self.post_attention_layernorm(hidden_states)
        hidden_states = self.mlp(hidden_states)
        out = residual + hidden_states
        if kv_cache is not None:
            return out, new_cache
        return out


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList([LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        head_dim = config.hidden_size // config.num_attention_heads
        cos_tab, sin_tab = _rope_tables(head_dim, config.max_position_embeddings, config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos_tab), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin_tab), persistable=False)

    def forward(self, input_ids, attn_mask=None, kv_caches=None, position_offset=0):
        h = self.embed_tokens(input_ids)
        cos_tab, sin_tab = self.rope_cos._data, self.rope_sin._data
        if kv_caches is not None:
            new_caches = []
            for layer, cache in zip(self.layers, kv_caches, strict=True):
                h, nc = layer(h, cos_tab, sin_tab, attn_mask, cache, position_offset)
                new_caches.append(nc)
            return self.norm(h), new_caches
        for layer in self.layers:
            h = layer(h, cos_tab, sin_tab, attn_mask)
        return self.norm(h)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias_attr=False)

    def forward(self, input_ids, attn_mask=None, kv_caches=None, position_offset=0):
        if kv_caches is not None:
            from ..generation import head_rows

            h, new_caches = self.llama(input_ids, attn_mask, kv_caches, position_offset)
            h = head_rows(h, kv_caches)
        else:
            h = self.llama(input_ids, attn_mask)
        if self.lm_head is None:
            from ..ops.math import matmul

            logits = matmul(h, self.llama.embed_tokens.weight, transpose_y=True)
        else:
            logits = self.lm_head(h)
        if kv_caches is not None:
            return logits, new_caches
        return logits

    def generate(self, input_ids, max_new_tokens: int = 32, **kwargs):
        from ..generation import generate

        return generate(self, input_ids, max_new_tokens=max_new_tokens, **kwargs)

    @classmethod
    def from_huggingface(cls, hf_model_or_state_dict, config: "LlamaConfig | None" = None):
        """Build a LlamaForCausalLM from a HuggingFace transformers Llama
        model (or its state_dict) — the interop door for users bringing
        reference-ecosystem checkpoints (PaddleNLP's Llama loads the same
        HF layout). Accepts the torch module itself or any mapping of
        parameter name -> array-like; weights are converted with
        ``convert_hf_llama_state_dict``."""
        sd = hf_model_or_state_dict
        if hasattr(sd, "state_dict"):
            # scaled-RoPE checkpoints (Llama-3.1 'llama3', 'linear', ...)
            # would load silently with wrong tables — refuse regardless of
            # whether the caller supplies a config. (A bare state_dict
            # carries no config: the caller vouches for default RoPE.)
            if hasattr(sd, "config"):
                scaling = getattr(sd.config, "rope_scaling", None)
                if scaling and scaling.get("rope_type", scaling.get("type")) \
                        not in (None, "default"):
                    raise NotImplementedError(
                        f"rope_scaling={scaling!r} is not supported; only the "
                        "default RoPE tables are derived from the config")
            if config is None and hasattr(sd, "config"):
                h = sd.config
                config = LlamaConfig(
                    vocab_size=h.vocab_size, hidden_size=h.hidden_size,
                    intermediate_size=h.intermediate_size,
                    num_hidden_layers=h.num_hidden_layers,
                    num_attention_heads=h.num_attention_heads,
                    num_key_value_heads=getattr(h, "num_key_value_heads",
                                                h.num_attention_heads),
                    max_position_embeddings=h.max_position_embeddings,
                    rms_norm_eps=h.rms_norm_eps,
                    rope_theta=getattr(h, "rope_theta", 10000.0),
                    tie_word_embeddings=getattr(h, "tie_word_embeddings", False))
            sd = sd.state_dict()
        if config is None:
            raise ValueError("config is required when passing a bare state_dict")
        if config.fuse_attention_qkv or config.fuse_mlp:
            raise NotImplementedError(
                "from_huggingface targets the unfused layout; load unfused, "
                "then concatenate into a fused twin if needed")
        model = cls(config)
        converted = convert_hf_llama_state_dict(sd)
        from .interop import load_converted_state

        # leftover weights (e.g. attention_bias / mlp_bias checkpoints)
        # would be silently dropped — wrong logits with no error; the
        # tied lm_head duplicate is the only benign one
        return load_converted_state(
            model, converted,
            allow_leftover=("lm_head.weight",) if config.tie_word_embeddings
            else ())


def convert_hf_llama_state_dict(sd) -> dict:
    """HF Llama parameter layout -> ours: ``model.`` prefix becomes
    ``llama.``, torch Linear weights [out, in] transpose to [in, out]
    (embedding and norm weights keep their layout), lm_head [vocab, h]
    transposes to [h, vocab]. Values are returned as numpy arrays."""
    import numpy as np

    def to_np(v):
        if hasattr(v, "detach"):  # torch tensor
            v = v.detach().cpu().numpy()
        return np.asarray(v)

    out = {}
    for name, v in sd.items():
        if name.endswith("rotary_emb.inv_freq"):
            continue  # we derive RoPE tables from the config
        arr = to_np(v)
        new = name
        if new.startswith("model."):
            new = "llama." + new[len("model."):]
        is_linear_w = new.endswith("_proj.weight") or new == "lm_head.weight"
        if is_linear_w and arr.ndim == 2:
            arr = arr.T
        out[new] = arr
    return out


def moe_aux_loss(model) -> Optional[Tensor]:
    """Sum of per-layer MoE load-balancing losses from the LAST forward
    (each MoELayer stashes ``aux_loss`` — traced values inside a traced
    step, so read this in the same loss closure; reference:
    moe_layer.py gate.get_loss). None for dense models."""
    total = None
    for layer in model.sublayers(include_self=True):
        aux = getattr(layer, "aux_loss", None)
        if aux is not None:
            total = aux if total is None else total + aux
    if total is None:
        return None
    return total if isinstance(total, Tensor) else Tensor(total)


def moe_pretrain_loss(model, aux_coeff: float = 0.01):
    """loss_fn factory for ShardedTrainStep on an MoE Llama: next-token
    CE + aux_coeff * load-balance loss (reference training recipes add
    the gate loss the same way)."""

    def loss_fn(logits, labels):
        loss = llama_pretrain_loss(logits, labels)
        aux = moe_aux_loss(model)
        if aux is not None:
            loss = loss + aux_coeff * aux
        return loss

    return loss_fn


def llama_pretrain_loss(logits: Tensor, labels: Tensor) -> Tensor:
    """Shifted next-token cross entropy (labels may equal input_ids;
    ignore_index=-100): position t predicts labels[t+1].

    Fused form (custom vjp): loss = logsumexp(logits) - logits[label]
    with labels shifted left and the last position ignore-masked. The
    forward streams the fp32 LSE without materializing an fp32 logits
    copy, and the backward computes d logits = (softmax - onehot) * mask
    / n directly in the logits dtype — the only big residual is the
    logits tensor itself (the autodiff'd form would save an fp32 exp
    buffer: 2 GB at seq 4096, an OOM on one chip). Measured +1.5%
    end-to-end on the 134M bench over the generic one-hot cross_entropy.
    Reference analogue: the fused softmax-CE kernels
    (c_softmax_with_cross_entropy / phi cross_entropy_with_softmax)."""
    from ..ops.dispatch import apply_op

    b, s, v = logits.shape
    lab = labels._data
    if lab.ndim == 3 and lab.shape[-1] == 1:  # (b, s, 1) label convention
        lab = lab[..., 0]

    def _f(lg):
        lab_s = jnp.concatenate(
            [lab[:, 1:], jnp.full((b, 1), -100, lab.dtype)], 1)
        return _fused_shift_ce(lg, lab_s)

    return apply_op("cross_entropy", _f, logits)


@jax.custom_vjp
def _fused_shift_ce(lg, lab_s):
    loss, _ = _fused_shift_ce_fwd(lg, lab_s)
    return loss


def _lse_stream(lg):
    """Row LSE with fp32 accumulation but NO fp32 copy of lg: the
    sub→convert→exp→reduce chain fuses into the reduction loop."""
    m = jnp.max(lg, axis=-1)
    z = jnp.sum(jnp.exp((lg - m[..., None]).astype(jnp.float32)), axis=-1)
    return m.astype(jnp.float32) + jnp.log(z)


def _fused_shift_ce_fwd(lg, lab_s):
    v = lg.shape[-1]
    lse = _lse_stream(lg)
    picked = jnp.take_along_axis(
        lg, jnp.clip(lab_s, 0, v - 1)[..., None].astype(jnp.int32),
        -1)[..., 0]
    mask = lab_s != -100
    n = jnp.maximum(mask.sum(), 1)
    loss = ((lse - picked.astype(jnp.float32)) * mask).sum() / n
    return loss, (lg, lab_s, lse, n)


def _fused_shift_ce_bwd(res, g):
    lg, lab_s, lse, n = res
    v = lg.shape[-1]
    mask = (lab_s != -100)[..., None]
    # softmax recomputed in the LOGITS dtype (bf16): exp(lg - lse)
    p = jnp.exp(lg - lse[..., None].astype(lg.dtype))
    onehot = jax.nn.one_hot(jnp.clip(lab_s, 0, v - 1), v, dtype=lg.dtype)
    scale = (g / n).astype(lg.dtype)
    dlg = (p - onehot) * mask * scale
    return dlg.astype(lg.dtype), None


_fused_shift_ce.defvjp(_fused_shift_ce_fwd, _fused_shift_ce_bwd)


# ---------------------------------------------------------------------------
# Sharding recipe (Megatron layout over a ProcessMesh)
# ---------------------------------------------------------------------------


def llama_shard_fn(mesh, mp_axis: str = "mp"):
    """Returns a shard_fn for distributed.shard_layer: Megatron TP layout.

    Parity: the reference's Llama TP config (ColumnParallelLinear on
    q/k/v/gate/up, RowParallelLinear on o/down, VocabParallelEmbedding) —
    expressed as placements; GSPMD inserts the collectives.
    """
    from ..distributed.api import shard_tensor
    from ..distributed.mesh import Replicate, Shard

    if mp_axis not in mesh.dim_names:
        return lambda name, layer, m: None
    mp_idx = mesh.dim_names.index(mp_axis)

    def placements_for(param_name: str, layer_name: str):
        pl = [Replicate()] * mesh.ndim
        # fused qkv_proj/gate_up_proj column-shard too (matched by the
        # v_proj/up_proj substrings): the concatenated out dim splits per
        # partition; the post-matmul q/k/v (gate/up) slices cross shard
        # boundaries, which GSPMD reshards correctly (use the unfused
        # layout when TP matmul-local slicing matters)
        col = any(k in layer_name for k in ("q_proj", "k_proj", "v_proj", "gate_proj", "up_proj"))
        row = any(k in layer_name for k in ("o_proj", "down_proj"))
        vocab = "embed_tokens" in layer_name or "lm_head" in layer_name
        if col and param_name == "weight":
            pl[mp_idx] = Shard(1)
        elif row and param_name == "weight":
            pl[mp_idx] = Shard(0)
        elif vocab and param_name == "weight":
            # embed: shard vocab rows; lm_head weight [hidden, vocab]: shard cols
            pl[mp_idx] = Shard(1) if "lm_head" in layer_name else Shard(0)
        return pl

    def shard_fn(name, sublayer, m):
        for pname, p in list(sublayer._parameters.items()):
            if p is None:
                continue
            sublayer._parameters[pname] = shard_tensor(p, mesh, placements_for(pname, name))

    return shard_fn
