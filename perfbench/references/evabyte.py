"""Plain float32 reference of EvaByte: a Llama-style byte decoder whose
attention is EVA, chunked linearized attention (Zheng et al., ICLR 2023,
arXiv:2302.04542), as EvaByte's release made it deterministic (learned
``phi`` and ``mu`` in place of sampled random features;
huggingface.co/EvaByte/EvaByte, 2025-01). ``jax.numpy`` only, float32,
'highest' matmuls, no cache, no kernels, nothing of ``paddle_tpu``.
Weights come from ``perfbench.weights`` by leaf name; a linear weight is
``[in, out]``.

One layer, with H hidden, h heads of d, chunk c, window W, s = d**-0.5,
no bias anywhere:

  x <- x + Attn(N(x));  x <- x + Wd(silu(Wg N(x)) * Wu N(x))
  N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)      (norm_add_unit_offset)
  q_i, k_i, v_i = per-head slices of Wq y_i, Wk y_i, Wv y_i; rotary
    embedding (rotate-half, theta, absolute position i) on q and k
    before anything below
  chunk m = positions c*m .. c*m+c-1; window g(i) = i // W
  summary of chunk m, per head: a_j = softmax over j in chunk m of
    (s * k_j . phi);  kbar_m = sum_j a_j k_j + mu;  vbar_m = sum_j a_j v_j
  query i scores the exact keys of its window, s * q_i . k_j for
    g(j) = g(i), j <= i, and the summaries of every chunk of every
    earlier window, s * q_i . kbar_m for (c*m) // W < g(i); one softmax
    over the union; o_i = those weights on [v_j ; vbar_m]; then Wo
  head: final N, logits = x W_head, W_head [H, P*V], viewed [P, V]; head
    p predicts byte t+1+p

Below W positions this is causal softmax attention. A summary is used
only once its window lies behind the query, so pooling a chunk when it
fills (the served program) or all chunks at once (here) is the same
mathematics.

``config.json`` does not give, and the configuration file lists under
``assumed``: ``phi`` and ``mu`` are one vector of d per head and layer;
``s`` scales the pooling softmax's scores as it does attention's; ``mu``
is added to the key summary only; summaries are taken of rotated keys;
the head's columns are laid out ``[num_pred_heads, vocab]``; the
residual stream stays float32 between blocks (``fp32_skip_add``: here
everything is float32 anyway).

What the harness forces: ``drivers/serve.logit_gaps`` asks for 256 rows
and then slices ``out_len`` of them, so an answer over 256 tokens would
come up short. ``logit_rows`` therefore ignores ``rows`` and returns
every row from ``start`` to the end of the padded sequence (320 logits a
row cost nothing): every served token is compared, none fewer.

Memory: the float32 weights of the 12-layer cut are 9.7 GB of a 16 GB
chip, so a layer is one jitted program called layer by layer on the
layer's own leaves (one compile for each padded length, no stacked
copy), attention runs 512 queries at a time against their window
(scores [h, 512, W + summaries]) and the MLP in row blocks of W.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("input_layernorm.weight", "self_attn.q_proj.weight",
                "self_attn.k_proj.weight", "self_attn.v_proj.weight",
                "self_attn.o_proj.weight", "self_attn.adaptive_phi",
                "self_attn.adaptive_mu_k", "post_attention_layernorm.weight",
                "mlp.gate_proj.weight", "mlp.up_proj.weight",
                "mlp.down_proj.weight")


# queries scored at once: [h, Q_ROWS, window + summaries] float32 is
# 0.2 GB at the published sizes
Q_ROWS = 512


def param_spec(cfg):
    """name -> (shape, mean, std), in a fixed order. Matrices N(0, 0.02),
    the two projections that write to the residual stream scaled by
    1/sqrt(2 L). Norm offsets N(0, 0.1), ``phi`` N(0, 1) and ``mu``
    N(0, 0.5): spread, so that a unit offset left out, a uniform chunk
    mean in place of the ``phi`` softmax or a dropped ``mu`` moves the
    logits (s * k . phi then spreads by about a unit over a chunk)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    heads = cfg["num_attention_heads"]
    d, n = h // heads, cfg["num_hidden_layers"]
    s, so = 0.02, 0.02 / math.sqrt(2 * n)
    spec = {"evabyte.embed_tokens.weight": ((cfg["vocab_size"], h), 0.0, s)}
    for i in range(n):
        b = f"evabyte.layers.{i}."
        spec[b + "input_layernorm.weight"] = ((h,), 0.0, 0.1)
        for proj in ("q_proj", "k_proj", "v_proj"):
            spec[b + f"self_attn.{proj}.weight"] = ((h, h), 0.0, s)
        spec[b + "self_attn.o_proj.weight"] = ((h, h), 0.0, so)
        spec[b + "self_attn.adaptive_phi"] = ((heads, d), 0.0, 1.0)
        spec[b + "self_attn.adaptive_mu_k"] = ((heads, d), 0.0, 0.5)
        spec[b + "post_attention_layernorm.weight"] = ((h,), 0.0, 0.1)
        spec[b + "mlp.gate_proj.weight"] = ((h, f), 0.0, s)
        spec[b + "mlp.up_proj.weight"] = ((h, f), 0.0, s)
        spec[b + "mlp.down_proj.weight"] = ((f, h), 0.0, so)
    spec["evabyte.norm.weight"] = ((h,), 0.0, 0.1)
    spec["lm_head.weight"] = (
        (h, cfg["num_pred_heads"] * cfg["vocab_size"]), 0.0, s)
    return spec


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + w)


def _rope(x, theta):
    """Rotate-half rotary embedding of ``x [S, h, d]`` at positions
    0 .. S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def eva_attention(q, k, v, phi, mu, window, chunk):
    """The attention equations above on rotated ``q, k, v [S, h, d]``
    with S a multiple of ``window``; a block of queries at a time
    against their window's keys and every summary behind it."""
    s, h, d = q.shape
    scale = d ** -0.5
    kc = k.reshape(s // chunk, chunk, h, d)
    vc = v.reshape(s // chunk, chunk, h, d)
    a = jax.nn.softmax(scale * jnp.einsum("mchd,hd->mch", kc, phi), axis=1)
    kbar = jnp.einsum("mch,mchd->mhd", a, kc) + mu
    vbar = jnp.einsum("mch,mchd->mhd", a, vc)
    n_sum, rows = s // chunk, min(window, Q_ROWS)

    def one_block(r):
        """``rows`` queries from position ``r * rows``, all of one window."""
        at = r * rows // window * window          # where their window starts
        qw = jax.lax.dynamic_slice_in_dim(q, r * rows, rows, 0)
        kw, vw = (jax.lax.dynamic_slice_in_dim(t, at, window, 0)
                  for t in (k, v))
        exact = jnp.einsum("qhd,khd->hqk", qw, kw) * scale
        seen = at + jnp.arange(window)[None, :] \
            <= r * rows + jnp.arange(rows)[:, None]
        exact = jnp.where(seen[None], exact, -jnp.inf)
        summ = jnp.einsum("qhd,mhd->hqm", qw, kbar) * scale
        behind = jnp.arange(n_sum) * chunk < at
        summ = jnp.where(behind[None, None, :], summ, -jnp.inf)
        w = jax.nn.softmax(jnp.concatenate([summ, exact], -1), -1)
        return jnp.einsum("hqm,mhd->qhd", w[..., :n_sum], vbar) \
            + jnp.einsum("hqk,khd->qhd", w[..., n_sum:], vw)

    out = jax.lax.map(one_block, jnp.arange(s // rows))
    return out.reshape(s, h, d)


@partial(jax.jit, static_argnames=("cfg_items", "mm"))
def _layer(x, leaves, cfg_items, mm):
    """One block on ``x [S, H]`` float32, S a multiple of the window."""
    cfg = dict(cfg_items)
    (ln1, wq, wk, wv, wo, phi, mu, ln2, wg, wu, wd) = leaves
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    s, hid = x.shape
    with jax.default_matmul_precision("highest"):
        y = _norm(x, ln1, eps)
        q, k, v = (mm(y, w).reshape(s, heads, hid // heads)
                   for w in (wq, wk, wv))
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        o = eva_attention(q, k, v, phi, mu, window, chunk)
        x = x + mm(o.reshape(s, hid), wo)

        def mlp(rows):
            y = _norm(rows, ln2, eps)
            return rows + mm(jax.nn.silu(mm(y, wg)) * mm(y, wu), wd)

        return jax.lax.map(mlp, x.reshape(s // window, window, hid)
                           ).reshape(s, hid)


@partial(jax.jit, static_argnames=("cfg_items", "mm"))
def _head(x, ln, w, cfg_items, mm):
    with jax.default_matmul_precision("highest"):
        return mm(_norm(x, ln, dict(cfg_items)["rms_norm_eps"]), w)


def _items(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float))))


def all_head_logits(params, ids, cfg, mm=jnp.matmul):
    """Logits ``[S, num_pred_heads, V]`` of every position of ``ids``
    and every prediction head. ``mm`` computes every linear layer's
    product (a control passes a lower-precision one)."""
    items, window = _items(cfg), cfg["window_size"]
    n = ids.shape[0]
    padded = -(-n // window) * window
    # padding comes last and attention is causal: no earlier row changes
    buf = jnp.zeros(padded, jnp.int32).at[:n].set(ids)
    x = params["evabyte.embed_tokens.weight"][buf]
    for i in range(cfg["num_hidden_layers"]):
        b = f"evabyte.layers.{i}."
        x = _layer(x, tuple(params[b + k] for k in LAYER_LEAVES), items, mm)
    logits = _head(x, params["evabyte.norm.weight"], params["lm_head.weight"],
                   items, mm)
    return logits[:n].reshape(n, cfg["num_pred_heads"], cfg["vocab_size"])


def logit_rows(params, ids, start, rows, cfg, mm=jnp.matmul):
    """Head 0's logits ``[len(ids) - start, V]`` (the next byte's) at
    positions ``start`` .. the end of ``ids``, which may be padded at its
    end. ``rows`` is not used: see the module's docstring."""
    del rows
    return all_head_logits(params, ids, cfg, mm)[int(start):, 0]
