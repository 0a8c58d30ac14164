"""Plain float32 reference of the Llama/Mistral decoder and of its
training step: RMSNorm, rotary positions (half-split), grouped-query
causal attention, SwiGLU MLP, untied head, next-token cross entropy
averaged over all predicted positions, AdamW with decoupled decay.
``jax.numpy`` only; nothing of ``paddle_tpu``. A linear weight is
``[in, out]``.

Mistral-7B's sliding window of 4096 equals full causal attention at
the sequence lengths the cells use (<= 4096), so no window is applied.

To fit beside nothing else on one chip at 7B widths, the follower
walks the model layer by layer with ``jax.vjp`` (a layer's forward is
recomputed in its backward), takes the batch in blocks of rows, runs
attention one kv group at a time, and keeps the first step's gradient
in place of Adam's two moments, which it rebuilds per leaf from the
gradient history. The arithmetic is the textbook's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def param_spec(cfg):
    """name -> (shape, mean, std): matrices N(0, 0.02), norm weights
    N(1, 0.02)."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    s = 0.02
    spec = {"llama.embed_tokens.weight": ((v, h), 0.0, s)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"llama.layers.{i}."
        spec[b + "self_attn.q_proj.weight"] = ((h, h), 0.0, s)
        spec[b + "self_attn.k_proj.weight"] = ((h, kv), 0.0, s)
        spec[b + "self_attn.v_proj.weight"] = ((h, kv), 0.0, s)
        spec[b + "self_attn.o_proj.weight"] = ((h, h), 0.0, s)
        spec[b + "mlp.gate_proj.weight"] = ((h, f), 0.0, s)
        spec[b + "mlp.up_proj.weight"] = ((h, f), 0.0, s)
        spec[b + "mlp.down_proj.weight"] = ((f, h), 0.0, s)
        spec[b + "input_layernorm.weight"] = ((h,), 1.0, s)
        spec[b + "post_attention_layernorm.weight"] = ((h,), 1.0, s)
    spec["llama.norm.weight"] = ((h,), 1.0, s)
    spec["lm_head.weight"] = ((h, v), 0.0, s)
    return spec


LAYER_KEYS = ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
              "self_attn.v_proj.weight", "self_attn.o_proj.weight",
              "mlp.gate_proj.weight", "mlp.up_proj.weight",
              "mlp.down_proj.weight", "input_layernorm.weight",
              "post_attention_layernorm.weight")


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _rope(x, theta):
    """x ``[B, S, heads, d]``; rotate the two halves of d."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(lp, x, cfg, mm):
    """One decoder layer on ``x [B, S, H]``; ``lp`` maps LAYER_KEYS."""
    b, s, h = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = h // nh, cfg["rms_norm_eps"]
    y = _rms_norm(x, lp["input_layernorm.weight"], eps)
    q = mm(y, lp["self_attn.q_proj.weight"]).reshape(b, s, nkv, nh // nkv, hd)
    k = mm(y, lp["self_attn.k_proj.weight"]).reshape(b, s, nkv, hd)
    v = mm(y, lp["self_attn.v_proj.weight"]).reshape(b, s, nkv, hd)
    q = _rope(q.reshape(b, s, nh, hd), cfg["rope_theta"]).reshape(q.shape)
    k = _rope(k, cfg["rope_theta"])
    causal = jnp.tril(jnp.ones((s, s), bool))

    def group(qkv):  # one kv head and the query heads it serves
        qg, kg, vg = qkv  # [B,S,rep,d], [B,S,d], [B,S,d]
        sc = jnp.einsum("bqrd,bkd->brqk", qg, kg) / math.sqrt(hd)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        return jnp.einsum("brqk,bkd->bqrd", jax.nn.softmax(sc, -1), vg)

    a = jax.lax.map(group, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                            jnp.moveaxis(v, 2, 0)))  # [nkv,B,S,rep,d]
    a = jnp.moveaxis(a, 0, 2).reshape(b, s, h)
    x = x + mm(a, lp["self_attn.o_proj.weight"])
    y = _rms_norm(x, lp["post_attention_layernorm.weight"], eps)
    y = jax.nn.silu(mm(y, lp["mlp.gate_proj.weight"])) \
        * mm(y, lp["mlp.up_proj.weight"])
    return x + mm(y, lp["mlp.down_proj.weight"])


def head_loss(hp, x, labels, inv_n, cfg, mm):
    """Sum over this block's predicted positions of the cross entropy,
    times ``inv_n`` (one over the whole batch's count). Position t
    predicts token t+1; a row's last position predicts nothing."""
    y = _rms_norm(x, hp["llama.norm.weight"], cfg["rms_norm_eps"])
    logits = mm(y, hp["lm_head.weight"])[:, :-1]
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[:, 1:, None], -1)[..., 0]
    return jnp.sum(lse - picked) * inv_n


def _matmul(x, w):
    return jnp.matmul(x, w)


class Follower:
    """Follows the program's first steps in float32: ``loss_and_grads``
    and ``adamw`` are all a check needs."""

    def __init__(self, cfg, params, hyper, rows_per_block=1, mm=None):
        self.cfg = {k: v for k, v in cfg.items()
                    if isinstance(v, (int, float))}
        self.params = params  # name -> float32 array
        self.hyper = hyper    # lr, beta1, beta2, epsilon, weight_decay
        self.rows = rows_per_block
        mm = mm or _matmul
        cfg_ = self.cfg

        def prec(f):
            def g(*a):
                with jax.default_matmul_precision("highest"):
                    return f(*a)
            return jax.jit(g)

        self._layer = prec(lambda lp, x: layer(lp, x, cfg_, mm))
        self._layer_vjp = prec(
            lambda lp, x, dy: jax.vjp(
                lambda lp_, x_: layer(lp_, x_, cfg_, mm), lp, x)[1](dy))
        self._head = prec(jax.value_and_grad(
            lambda hp, x, lab, inv_n: head_loss(hp, x, lab, inv_n, cfg_, mm),
            argnums=(0, 1)))
        self._head_fwd = prec(
            lambda hp, x, lab, inv_n: head_loss(hp, x, lab, inv_n, cfg_, mm))

    def _lp(self, i):
        return {k: self.params[f"llama.layers.{i}.{k}"] for k in LAYER_KEYS}

    def _hp(self):
        return {k: self.params[k] for k in ("llama.norm.weight",
                                            "lm_head.weight")}

    def loss_and_grads(self, ids, want_grads=True):
        """``ids [B, S]`` int32 (labels are the inputs, shifted inside).
        Returns (loss, name -> gradient) with the loss averaged over all
        B x (S-1) predicted positions."""
        n_layers = self.cfg["num_hidden_layers"]
        b, s = ids.shape
        inv_n = jnp.float32(1.0 / (b * (s - 1)))
        emb = self.params["llama.embed_tokens.weight"]
        loss, grads = 0.0, {}

        def acc(name, g):
            grads[name] = grads[name] + g if name in grads else g

        for r in range(0, b, self.rows):
            blk = jnp.asarray(ids[r:r + self.rows])
            xs = [emb[blk]]
            for i in range(n_layers):
                xs.append(self._layer(self._lp(i), xs[-1]))
            if not want_grads:
                loss += float(self._head_fwd(self._hp(), xs[-1], blk, inv_n))
                continue
            part, (ghp, dx) = self._head(self._hp(), xs[-1], blk, inv_n)
            loss += float(part)
            for k, g in ghp.items():
                acc(k, g)
            for i in reversed(range(n_layers)):
                glp, dx = self._layer_vjp(self._lp(i), xs[i], dx)
                for k, g in glp.items():
                    acc(f"llama.layers.{i}.{k}", g)
                xs.pop()
            acc("llama.embed_tokens.weight",
                jnp.zeros_like(emb).at[blk].add(dx))
        return loss, grads

    def adamw(self, history):
        """Apply the update that follows ``len(history)`` gradients
        (name -> gradient, oldest first) to the parameters, in place.
        The moments are rebuilt per leaf from the history, which is the
        same arithmetic as carrying them and needs no room for them."""
        hy = self.hyper
        t = len(history)

        @jax.jit
        def upd(p, *gs):
            m = v = jnp.zeros_like(p)
            for g in gs:
                m = hy["beta1"] * m + (1 - hy["beta1"]) * g
                v = hy["beta2"] * v + (1 - hy["beta2"]) * jnp.square(g)
            mhat = m / (1 - hy["beta1"] ** t)
            vhat = v / (1 - hy["beta2"] ** t)
            p = p * (1.0 - hy["lr"] * hy["weight_decay"])
            return p - hy["lr"] * mhat / (jnp.sqrt(vhat) + hy["epsilon"])

        for k in list(self.params):
            self.params[k] = upd(self.params[k], *(h[k] for h in history))
