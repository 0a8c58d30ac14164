"""Functional (pure) optimizer kernels for whole-program training steps.

The eager optimizers (optimizer.py) mutate per-parameter state host-side;
for pjit/GSPMD training the entire step must be one compiled program, so
these pure init/update pairs mirror the same update rules over pytrees.
The split mirrors the reference's dual structure: eager optimizer ops vs
static-graph optimizer passes (reference: python/paddle/optimizer/
optimizer.py _append_optimize_op dygraph-vs-static branches).

State layout note: state pytrees mirror the param pytree, so ZeRO-style
optimizer-state sharding = sharding the state pytree over the 'dp'/
'sharding' mesh axis (reference semantics: DygraphShardingOptimizer).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class FunctionalOptimizer(NamedTuple):
    init: Callable  # params -> state
    update: Callable  # (grads, state, params, lr) -> (new_params, new_state)


def _tree_f32_zeros(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _path_name(path):
    """Parameter name from tree-path entries (DictKey.key for dict trees;
    keystr-ish fallback for others) — shared by the decay-mask lookups."""
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def sgd(weight_decay: float = 0.0) -> FunctionalOptimizer:
    def init(params):
        return {}

    def update(grads, state, params, lr):
        def upd(p, g):
            if g is None:
                return p
            g = g.astype(p.dtype)
            if weight_decay:
                g = g + weight_decay * p
            return p - lr.astype(p.dtype) * g

        return jax.tree.map(upd, params, grads), state

    return FunctionalOptimizer(init, update)


def momentum(mu: float = 0.9, weight_decay: float = 0.0, use_nesterov: bool = False) -> FunctionalOptimizer:
    def init(params):
        return {"velocity": _tree_f32_zeros(params)}

    def update(grads, state, params, lr):
        p_flat, treedef = jax.tree.flatten(params)
        g_flat = treedef.flatten_up_to(grads)
        v_flat = treedef.flatten_up_to(state["velocity"])
        new_p, new_v = [], []
        for p, g, v in zip(p_flat, g_flat, v_flat):
            if g is None:
                new_p.append(p)
                new_v.append(v)
                continue
            g32 = g.astype(jnp.float32)
            if weight_decay:
                g32 = g32 + weight_decay * p.astype(jnp.float32)
            v_n = mu * v + g32
            step = (g32 + mu * v_n) if use_nesterov else v_n
            new_p.append((p.astype(jnp.float32) - lr * step).astype(p.dtype))
            new_v.append(v_n)
        return treedef.unflatten(new_p), {"velocity": treedef.unflatten(new_v)}

    return FunctionalOptimizer(init, update)


def adamw(beta1: float = 0.9, beta2: float = 0.999, epsilon: float = 1e-8,
          weight_decay: float = 0.01, decay_mask_fn: Optional[Callable] = None) -> FunctionalOptimizer:
    """AdamW with fp32 master state (bf16 params supported). decay_mask_fn:
    param-name predicate (parity: apply_decay_param_fun)."""

    def init(params):
        return {
            "m": _tree_f32_zeros(params),
            "v": _tree_f32_zeros(params),
            "t": jnp.zeros((), jnp.float32),
        }

    def update(grads, state, params, lr):
        t = state["t"] + 1.0
        p_flat_path, treedef = jax.tree_util.tree_flatten_with_path(params)
        g_flat = treedef.flatten_up_to(grads)
        m_flat = treedef.flatten_up_to(state["m"])
        v_flat = treedef.flatten_up_to(state["v"])
        new_p, new_m, new_v = [], [], []
        for (path, p), g, m, v in zip(p_flat_path, g_flat, m_flat, v_flat):
            if g is None:
                new_p.append(p)
                new_m.append(m)
                new_v.append(v)
                continue
            g32 = g.astype(jnp.float32)
            p32 = p.astype(jnp.float32)
            wd = weight_decay
            if decay_mask_fn is not None and not decay_mask_fn(_path_name(path)):
                wd = 0.0
            p32 = p32 * (1.0 - lr * wd)
            m_n = beta1 * m + (1 - beta1) * g32
            v_n = beta2 * v + (1 - beta2) * jnp.square(g32)
            mhat = m_n / (1 - beta1**t)
            vhat = v_n / (1 - beta2**t)
            new_p.append((p32 - lr * mhat / (jnp.sqrt(vhat) + epsilon)).astype(p.dtype))
            new_m.append(m_n)
            new_v.append(v_n)
        return treedef.unflatten(new_p), {"m": treedef.unflatten(new_m),
                                          "v": treedef.unflatten(new_v), "t": t}

    return FunctionalOptimizer(init, update)


def adam(beta1: float = 0.9, beta2: float = 0.999, epsilon: float = 1e-8,
         weight_decay: float = 0.0) -> FunctionalOptimizer:
    base = adamw(beta1, beta2, epsilon, weight_decay=0.0)

    def update(grads, state, params, lr):
        if weight_decay:
            grads = jax.tree.map(
                lambda g, p: None if g is None else g + weight_decay * p.astype(g.dtype), grads, params)
        return base.update(grads, state, params, lr)

    return FunctionalOptimizer(base.init, update)


def clip_by_global_norm(grads, clip_norm: float):
    """Pure global-norm clip over a grad pytree (parity:
    ClipGradByGlobalNorm inside compiled steps)."""
    leaves = [g for g in jax.tree.leaves(grads) if g is not None]
    total = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
    gnorm = jnp.sqrt(total)
    scale = clip_norm / jnp.maximum(gnorm, clip_norm)
    return jax.tree.map(lambda g: None if g is None else (g.astype(jnp.float32) * scale).astype(g.dtype), grads), gnorm


def from_eager(opt) -> FunctionalOptimizer:
    """Map an eager Optimizer instance to its functional twin."""
    from . import optimizer as eager

    if isinstance(opt, eager.AdamW):
        return adamw(opt._beta1, opt._beta2, opt._epsilon, opt._wd,
                     decay_mask_fn=opt._apply_decay_param_fun)
    if isinstance(opt, eager.Adam):
        return adam(opt._beta1, opt._beta2, opt._epsilon, opt._weight_decay)
    if isinstance(opt, eager.Momentum):
        return momentum(opt._momentum, opt._weight_decay, opt._use_nesterov)
    if isinstance(opt, eager.SGD):
        return sgd(opt._weight_decay)
    raise NotImplementedError(f"no functional twin for {type(opt).__name__}")
