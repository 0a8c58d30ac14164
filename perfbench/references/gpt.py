"""Plain float32 reference of the GPT-2/3 decoder (Brown et al. 2020):
learned positions, pre-LayerNorm blocks, multi-head causal attention,
tanh-GELU MLP, untied output head. ``jax.numpy`` only, no cache, no
batching, no kernels, nothing of ``paddle_tpu``. Weights come from
``perfbench.weights`` by leaf name; a linear weight is ``[in, out]``.

Departures from the paper: none in the mathematics. 16 heads of 128
(table 2.1 prints 24 for the XL row, which does not divide 2048), an
FFN of 4 x d_model and a vocabulary padded to 50304 are the
configuration file's ``assumed`` sizes.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def param_spec(cfg):
    """name -> (shape, mean, std), in a fixed order. Matrices N(0, 0.02),
    the two projections that write to the residual stream scaled by
    1/sqrt(2 L) as in GPT-2, biases N(0, 0.02) and LayerNorm weights
    N(1, 0.02) so that a dropped bias or scale changes the logits."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    v, p = cfg["vocab_size"], cfg["max_position_embeddings"]
    n = cfg["num_hidden_layers"]
    s, so = 0.02, 0.02 / math.sqrt(2 * n)
    spec = {"gpt.wte.weight": ((v, h), 0.0, s),
            "gpt.wpe.weight": ((p, h), 0.0, s)}
    for i in range(n):
        b = f"gpt.h.{i}."
        for ln in ("ln_1", "ln_2"):
            spec[b + ln + ".weight"] = ((h,), 1.0, s)
            spec[b + ln + ".bias"] = ((h,), 0.0, s)
        for proj in ("q_proj", "k_proj", "v_proj"):
            spec[b + f"attn.{proj}.weight"] = ((h, h), 0.0, s)
            spec[b + f"attn.{proj}.bias"] = ((h,), 0.0, s)
        spec[b + "attn.out_proj.weight"] = ((h, h), 0.0, so)
        spec[b + "attn.out_proj.bias"] = ((h,), 0.0, s)
        spec[b + "fc_in.weight"] = ((h, f), 0.0, s)
        spec[b + "fc_in.bias"] = ((f,), 0.0, s)
        spec[b + "fc_out.weight"] = ((f, h), 0.0, so)
        spec[b + "fc_out.bias"] = ((h,), 0.0, s)
    spec["gpt.ln_f.weight"] = ((h,), 1.0, s)
    spec["gpt.ln_f.bias"] = ((h,), 0.0, s)
    spec["lm_head.weight"] = ((h, v), 0.0, s)
    return spec


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def hidden(params, ids, cfg, mm=jnp.matmul):
    """Final hidden states ``[S, H]`` (after ln_f) of one sequence.
    ``mm`` computes every linear layer's product (a control passes a
    lower-precision one)."""
    n_heads = cfg["num_attention_heads"]
    eps = cfg["layer_norm_eps"]
    s = ids.shape[0]
    x = params["gpt.wte.weight"][ids] + params["gpt.wpe.weight"][:s]
    hd = x.shape[-1] // n_heads
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(cfg["num_hidden_layers"]):
        b = f"gpt.h.{i}."
        g = lambda k: params[b + k]  # noqa: E731
        y = _layer_norm(x, g("ln_1.weight"), g("ln_1.bias"), eps)
        q, k, v = ((mm(y, g(f"attn.{p}.weight")) + g(f"attn.{p}.bias")
                    ).reshape(s, n_heads, hd)
                   for p in ("q_proj", "k_proj", "v_proj"))
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
        x = x + mm(a.reshape(s, -1), g("attn.out_proj.weight")) \
            + g("attn.out_proj.bias")
        y = _layer_norm(x, g("ln_2.weight"), g("ln_2.bias"), eps)
        y = _gelu_tanh(mm(y, g("fc_in.weight")) + g("fc_in.bias"))
        x = x + mm(y, g("fc_out.weight")) + g("fc_out.bias")
    return _layer_norm(x, params["gpt.ln_f.weight"],
                       params["gpt.ln_f.bias"], eps)


@partial(jax.jit, static_argnames=("cfg_items", "rows", "mm"))
def _logit_rows(params, ids, start, cfg_items, rows, mm):
    with jax.default_matmul_precision("highest"):
        h = hidden(params, ids, dict(cfg_items), mm)
        h = jax.lax.dynamic_slice_in_dim(h, start, rows, 0)
        return mm(h, params["lm_head.weight"])


def logit_rows(params, ids, start, rows, cfg, mm=jnp.matmul):
    """Logits ``[rows, V]`` at positions start .. start+rows-1 of the
    sequence ``ids`` (float32 weights, 'highest' matmuls). ``ids`` may
    be padded at its end: attention is causal, so padding changes no
    earlier row."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float))))
    return _logit_rows(params, ids, start, items, rows, mm)
