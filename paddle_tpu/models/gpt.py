"""GPT-2/3-style model (learned positions, pre-LN, GELU MLP).

Capability target: the reference's GPT-3 hybrid-parallel path
(SURVEY §7.2 milestone 4: GPT-3 1.3B TP+PP).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    layer_norm_eps: float = 1e-5
    dropout: float = 0.0

    @property
    def num_key_value_heads(self):
        # no GQA in the GPT family; generation.py sizes KV caches off this
        return self.num_attention_heads

    @staticmethod
    def gpt3_1p3b(**overrides):
        cfg = GPTConfig(hidden_size=2048, num_hidden_layers=24, num_attention_heads=16,
                        intermediate_size=8192, max_position_embeddings=2048)
        for k, v in overrides.items():
            setattr(cfg, k, v)
        return cfg

    @staticmethod
    def tiny(**overrides):
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                        num_attention_heads=4, intermediate_size=128, max_position_embeddings=128)
        for k, v in overrides.items():
            setattr(cfg, k, v)
        return cfg


class GPTBlock(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.attn = nn.MultiHeadAttention(config.hidden_size, config.num_attention_heads,
                                          dropout=config.dropout)
        self.ln_2 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.fc_in = nn.Linear(config.hidden_size, config.intermediate_size)
        self.fc_out = nn.Linear(config.intermediate_size, config.hidden_size)

    def forward(self, x, attn_mask=None, kv_cache=None, position_offset=0):
        h = self.ln_1(x)
        b, s, _ = h.shape
        nh = self.attn.num_heads
        hd = self.attn.head_dim
        q = self.attn.q_proj(h).reshape([b, s, nh, hd])
        k = self.attn.k_proj(h).reshape([b, s, nh, hd])
        v = self.attn.v_proj(h).reshape([b, s, nh, hd])
        # under a tp>1 trace, pin [b, s, heads, d] activations to the
        # heads axis so GSPMD keeps column-parallel outputs where the
        # q/k/v weight shards put them (no-op at tp=1)
        from ..distributed.partition import maybe_constrain_heads

        q, k, v = (maybe_constrain_heads(q), maybe_constrain_heads(k),
                   maybe_constrain_heads(v))
        new_cache = None
        if isinstance(kv_cache, dict):
            # contiguous buffers or paged pools: generation.py writes
            # the cache and picks what reads it
            from ..generation import cached_attention

            a, new_cache = cached_attention(
                q, k, v, kv_cache, position_offset, family="gpt",
                attn_mask=attn_mask)
        elif kv_cache is not None:
            raise TypeError(
                f"GPT kv_cache must be the generation.py static-cache dict, "
                f"got {type(kv_cache).__name__}")
        else:
            a = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None)
        x = x + self.attn.out_proj(a.reshape([b, s, nh * hd]))
        x = x + self.fc_out(F.gelu(self.fc_in(self.ln_2(x)), approximate=True))
        if kv_cache is not None:
            return x, new_cache
        return x


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size)
        self.wpe = nn.Embedding(config.max_position_embeddings, config.hidden_size)
        self.h = nn.LayerList([GPTBlock(config) for _ in range(config.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)

    def forward(self, input_ids, attn_mask=None, kv_caches=None, position_offset=0):
        b, s = input_ids.shape
        # position_offset may be traced (jitted decode step): index wpe
        # with a dynamic starting position; a per-row [b] vector (serving
        # decode: each slot at its own position) gathers [b, s] rows
        td = None
        if kv_caches is not None and isinstance(kv_caches[0], dict):
            # spec-tree bundle: node i's LEARNED position is
            # pos + depth(i), decoupled from its cache slot pos + i
            td = kv_caches[0].get("tree_depth")
        if td is not None:
            tdv = td._data if isinstance(td, Tensor) else jnp.asarray(td)
            po = position_offset._data \
                if isinstance(position_offset, Tensor) \
                else jnp.asarray(position_offset, jnp.int32)
            if po.ndim == 0:
                po = jnp.broadcast_to(po, (b,))
            pos = po[:, None] + tdv[None, :].astype(jnp.int32)
        elif getattr(position_offset, "ndim", 0) == 1:
            pos = position_offset[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        else:
            pos = position_offset + jnp.arange(s, dtype=jnp.int32)
        x = self.wte(input_ids) + self.wpe(Tensor(pos))
        if kv_caches is not None:
            new_caches = []
            for block, cache in zip(self.h, kv_caches, strict=True):
                x, nc = block(x, attn_mask, cache, position_offset)
                new_caches.append(nc)
            return self.ln_f(x), new_caches
        for block in self.h:
            x = block(x, attn_mask)
        return self.ln_f(x)


class GPTForCausalLM(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias_attr=False)

    def forward(self, input_ids, attn_mask=None, kv_caches=None, position_offset=0):
        if kv_caches is not None:
            from ..generation import head_rows

            h, new_caches = self.gpt(input_ids, attn_mask, kv_caches, position_offset)
            return self.lm_head(head_rows(h, kv_caches)), new_caches
        return self.lm_head(self.gpt(input_ids, attn_mask))

    def generate(self, input_ids, max_new_tokens: int = 32, **kwargs):
        from ..generation import generate

        return generate(self, input_ids, max_new_tokens=max_new_tokens, **kwargs)

    @classmethod
    def from_huggingface(cls, hf_model):
        """Build a GPTForCausalLM from a transformers GPT2LMHeadModel —
        the GPT-2 counterpart of the Llama interop door. HF GPT-2 stores
        Conv1D weights in [in, out] (our nn.Linear layout — no
        transpose); the fused c_attn [h, 3h] splits into q/k/v; lm_head
        is tied to wte (we materialize the transpose into our untied
        head)."""
        h = hf_model.config
        if getattr(h, "activation_function", "gelu_new") not in (
                "gelu_new", "gelu_pytorch_tanh"):
            raise NotImplementedError(
                f"activation_function={h.activation_function!r}: this model "
                "uses the tanh-approximate GELU only")
        # attention-math knobs carry no weights, so the shape checks
        # can't catch them — refuse rather than silently mis-load
        if getattr(h, "scale_attn_by_inverse_layer_idx", False) \
                or not getattr(h, "scale_attn_weights", True) \
                or getattr(h, "add_cross_attention", False):
            raise NotImplementedError(
                "non-default attention scaling / cross-attention configs are "
                "not reproduced by this model's fixed 1/sqrt(head_dim) SDPA")
        config = GPTConfig(
            vocab_size=h.vocab_size, hidden_size=h.n_embd,
            num_hidden_layers=h.n_layer, num_attention_heads=h.n_head,
            intermediate_size=h.n_inner or 4 * h.n_embd,
            max_position_embeddings=h.n_positions,
            layer_norm_eps=h.layer_norm_epsilon)
        model = cls(config)

        def to_np(v):
            return v.detach().cpu().numpy()

        sd = hf_model.state_dict()
        out = {
            "gpt.wte.weight": to_np(sd["transformer.wte.weight"]),
            "gpt.wpe.weight": to_np(sd["transformer.wpe.weight"]),
            "gpt.ln_f.weight": to_np(sd["transformer.ln_f.weight"]),
            "gpt.ln_f.bias": to_np(sd["transformer.ln_f.bias"]),
            # present in the state_dict tied or untied; using it (not
            # wte.T) keeps untied checkpoints correct
            "lm_head.weight": to_np(sd["lm_head.weight"]).T,
        }
        hs = config.hidden_size
        for i in range(config.num_hidden_layers):
            src, dst = f"transformer.h.{i}.", f"gpt.h.{i}."
            for ln in ("ln_1", "ln_2"):
                out[dst + ln + ".weight"] = to_np(sd[src + ln + ".weight"])
                out[dst + ln + ".bias"] = to_np(sd[src + ln + ".bias"])
            ca_w = to_np(sd[src + "attn.c_attn.weight"])  # [h, 3h]
            ca_b = to_np(sd[src + "attn.c_attn.bias"])  # [3h]
            for j, name in enumerate(("q_proj", "k_proj", "v_proj")):
                out[dst + f"attn.{name}.weight"] = ca_w[:, j * hs:(j + 1) * hs]
                out[dst + f"attn.{name}.bias"] = ca_b[j * hs:(j + 1) * hs]
            out[dst + "attn.out_proj.weight"] = to_np(sd[src + "attn.c_proj.weight"])
            out[dst + "attn.out_proj.bias"] = to_np(sd[src + "attn.c_proj.bias"])
            out[dst + "fc_in.weight"] = to_np(sd[src + "mlp.c_fc.weight"])
            out[dst + "fc_in.bias"] = to_np(sd[src + "mlp.c_fc.bias"])
            out[dst + "fc_out.weight"] = to_np(sd[src + "mlp.c_proj.weight"])
            out[dst + "fc_out.bias"] = to_np(sd[src + "mlp.c_proj.bias"])

        from .interop import load_converted_state

        return load_converted_state(model, out)
