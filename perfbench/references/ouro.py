"""Plain float32 reference of Ouro, a looped decoder (ByteDance, Ouro
1.4B/2.6B LoopLM, 2025-10; Zhu et al., "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741): one stack of L layers run T
times (``total_ut_steps``) over one set of weights. ``jax.numpy`` only,
float32, 'highest' matmuls, no cache, no kernels, nothing of
``paddle_tpu``. Weights come from ``perfbench.weights`` by leaf name; a
linear weight is ``[in, out]``.

With H hidden, h heads of d, N_w(x) = x / sqrt(mean(x^2) + eps) * w, and
no bias but the gate's:

  layer i, the same weights in every pass:
    a = Attn_i(N_{i,1}(x));  x <- x + N_{i,2}(a)
    m = Wd_i(silu(Wg_i y) * Wu_i y), y = N_{i,3}(x);  x <- x + N_{i,4}(m)
  Attn: q, k, v = per-head slices of Wq y, Wk y, Wv y; rotary embedding
    (rotate-half, theta, the token's absolute position, the same in every
    pass) on q and k; causal softmax(q . k / sqrt(d)) v; then Wo
  stack: x = E[ids];  for t = 1..T:  x <- layer_L(.. layer_1(x));
    h_t = N_f(x);  g_t = w_g . h_t + b_g;  x <- h_t
  exit: lambda_t = sigmoid(g_t);  p_t = lambda_t prod_{j<t} (1 - lambda_j)
    for t < T,  p_T = prod_{j<T} (1 - lambda_j);  the exit pass is the
    first t with p_1 + .. + p_t >= q (``early_exit_threshold``), the last
    where none is
  logits = W_head h_exit

At the published q = 1 the exit pass is T for every token: here that is
a rule and not a comparison, since a sigmoid that saturates to exactly 1
in float32 would otherwise round a token out of its later passes. There
is no cache here; in a program that has one, a query of pass t, layer i
attends what pass t of layer i wrote, T * L planes a position, none
shared between passes.

``config.json`` gives the sizes, T and q. It does not give, and the
configuration file lists under ``assumed``: the placement of the four
norms of a layer (one before and one after each branch, the second
inside the residual branch); that the final norm sits inside the loop
and its output feeds the next pass; the gate's form (a Linear(H, 1) with
bias on the normalised state); one cache plane per pass and layer; the
absence of attention biases and of q/k norms. These follow the release's
modelling code and the paper as this file's author recalls them; there
was no network to check them against.

What the harness forces: ``drivers/serve.logit_gaps`` asks for 256 rows
and then slices ``out_len`` of them, and this cell's answers reach 448
tokens. ``logit_rows`` therefore ignores ``rows`` and returns every row
from ``start`` to the end of the padded sequence, as the EvaByte
reference does: every served token is compared, none fewer.

Memory: the float32 weights are 10.7 GB of a 16 GB chip and the check
makes them whole, so nothing here stacks or copies a leaf: a layer is
one jitted program called layer by layer and pass by pass on the layer's
own leaves (one compile for each padded length), and attention scores a
block of ``Q_ROWS`` queries at a time.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("input_layernorm.weight", "self_attn.q_proj.weight",
                "self_attn.k_proj.weight", "self_attn.v_proj.weight",
                "self_attn.o_proj.weight", "input_layernorm_2.weight",
                "post_attention_layernorm.weight", "mlp.gate_proj.weight",
                "mlp.up_proj.weight", "mlp.down_proj.weight",
                "post_attention_layernorm_2.weight")

# queries scored at once against the whole sequence
Q_ROWS = 256


def param_spec(cfg):
    """name -> (shape, mean, std), in a fixed order. Matrices N(0, 0.02);
    norm weights N(1, 0.1) and the gate N(0, 1), spread so that a
    dropped post-norm, a final norm left outside the loop, a pass left
    out or a cache plane shared between passes moves the logits by far
    more than any tolerance."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    s = 0.02
    spec = {"ouro.embed_tokens.weight": ((v, h), 0.0, s)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"ouro.layers.{i}."
        spec[b + "input_layernorm.weight"] = ((h,), 1.0, 0.1)
        for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
            spec[b + f"self_attn.{proj}.weight"] = ((h, h), 0.0, s)
        spec[b + "input_layernorm_2.weight"] = ((h,), 1.0, 0.1)
        spec[b + "post_attention_layernorm.weight"] = ((h,), 1.0, 0.1)
        spec[b + "mlp.gate_proj.weight"] = ((h, f), 0.0, s)
        spec[b + "mlp.up_proj.weight"] = ((h, f), 0.0, s)
        spec[b + "mlp.down_proj.weight"] = ((f, h), 0.0, s)
        spec[b + "post_attention_layernorm_2.weight"] = ((h,), 1.0, 0.1)
    spec["ouro.norm.weight"] = ((h,), 1.0, 0.1)
    spec["ouro.early_exit_gate.weight"] = ((h, 1), 0.0, 1.0)
    spec["ouro.early_exit_gate.bias"] = ((1,), 0.0, 1.0)
    spec["lm_head.weight"] = ((h, v), 0.0, s)
    return spec


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _rope(x, theta):
    """Rotate-half rotary embedding of ``x [S, h, d]`` at positions
    0 .. S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v):
    """Causal softmax attention on rotated ``q, k, v [S, h, d]``, S a
    multiple of ``Q_ROWS`` or below it; a block of queries at a time."""
    s, h, d = q.shape
    rows = min(s, Q_ROWS)

    def one_block(r):
        qb = jax.lax.dynamic_slice_in_dim(q, r * rows, rows, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        seen = jnp.arange(s)[None, :] <= r * rows + jnp.arange(rows)[:, None]
        sc = jnp.where(seen[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    return jax.lax.map(one_block, jnp.arange(s // rows)).reshape(s, h, d)


@partial(jax.jit, static_argnames=("cfg_items", "mm"))
def _layer(x, leaves, cfg_items, mm):
    """One layer, once, on ``x [S, H]`` float32."""
    cfg = dict(cfg_items)
    (n1, wq, wk, wv, wo, n2, n3, wg, wu, wd, n4) = leaves
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    s, hid = x.shape
    with jax.default_matmul_precision("highest"):
        y = _norm(x, n1, eps)
        q, k, v = (mm(y, w).reshape(s, heads, hid // heads)
                   for w in (wq, wk, wv))
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        a = mm(causal_attention(q, k, v).reshape(s, hid), wo)
        x = x + _norm(a, n2, eps)
        y = _norm(x, n3, eps)
        m = mm(jax.nn.silu(mm(y, wg)) * mm(y, wu), wd)
        return x + _norm(m, n4, eps)


@partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, eps):
    return _norm(x, w, eps)


@partial(jax.jit, static_argnames=("mm",))
def _linear(x, w, mm):
    with jax.default_matmul_precision("highest"):
        return mm(x, w)


def _items(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float))))


def pass_states(params, ids, cfg, mm=jnp.matmul):
    """``h_1 .. h_T``, each ``[S, H]``: the normalised state after every
    pass over the whole sequence ``ids [S]``. ``mm`` computes every
    linear layer's product (a control passes a lower-precision one)."""
    items = _items(cfg)
    x = params["ouro.embed_tokens.weight"][ids]
    states = []
    for _ in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_hidden_layers"]):
            b = f"ouro.layers.{i}."
            x = _layer(x, tuple(params[b + k] for k in LAYER_LEAVES), items,
                       mm)
        x = _final_norm(x, params["ouro.norm.weight"], cfg["rms_norm_eps"])
        states.append(x)
    return states


def exit_distribution(gate):
    """``p [T, S]`` from the gate's values ``g [T, S]``."""
    lam = jax.nn.sigmoid(gate)
    t = gate.shape[0]
    stay, out = jnp.ones_like(gate[0]), []
    for j in range(t - 1):
        out.append(lam[j] * stay)
        stay = stay * (1.0 - lam[j])
    return jnp.stack(out + [stay])


def exit_pass(pdf, q):
    """0-based exit pass ``[S]``: the first t whose cumulative exit
    probability reaches ``q``, the last where none does, and the last
    for every token at ``q >= 1``."""
    last = pdf.shape[0] - 1
    if q >= 1:
        return jnp.full(pdf.shape[1:], last, jnp.int32)
    reached = jnp.cumsum(pdf, 0) >= q
    return jnp.where(reached.any(0), reached.argmax(0), last).astype(jnp.int32)


def all_passes(params, ids, cfg, q=None, mm=jnp.matmul):
    """Every pass of one sequence, for the tests: ``logits [T, S, V]``,
    ``gate`` and ``exit_pdf [T, S]``, ``exit_pass [S]`` at the threshold
    ``q`` (the configuration's where None) and the logits ``[S, V]`` of
    each token's exit pass."""
    q = cfg["early_exit_threshold"] if q is None else q
    h = jnp.stack(pass_states(params, ids, cfg, mm))
    gate = _linear(h, params["ouro.early_exit_gate.weight"], mm)[..., 0] \
        + params["ouro.early_exit_gate.bias"][0]
    logits = _linear(h, params["lm_head.weight"], mm)
    pdf = exit_distribution(gate)
    at = exit_pass(pdf, q)
    return {"logits_per_pass": logits, "gate": gate, "exit_pdf": pdf,
            "exit_pass": at,
            "logits": jnp.take_along_axis(logits, at[None, :, None], 0)[0]}


def logit_rows(params, ids, start, rows, cfg, mm=jnp.matmul):
    """Pass T's logits ``[len(ids) - start, V]`` (the exit pass at the
    published threshold of 1) at positions ``start`` .. the end of
    ``ids``, which may be padded at its end: attention is causal, so
    padding changes no earlier row. ``rows`` is not used: see the
    module's docstring."""
    del rows
    if cfg["early_exit_threshold"] < 1:
        raise ValueError("logit_rows is the served comparison, at the "
                         "published early_exit_threshold of 1; use "
                         "all_passes for a lower one")
    h = pass_states(params, ids, cfg, mm)[-1]
    return _linear(h[int(start):], params["lm_head.weight"], mm)
