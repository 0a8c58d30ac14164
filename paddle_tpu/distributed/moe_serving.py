"""A dropless expert layer for the serving step: one chip's share of an
expert-parallel deployment.

``moe.py`` beside this file is the trainer's layer: top-2 with a
capacity factor, tokens over it dropped. A served token is never
dropped, and a serving chip holds some of the experts: the router scores
ALL of the deployment's experts, the layer computes the chosen (token,
expert) pairs whose expert it holds, and what the absent experts would
have added is left out (on a deployment their chips add it; no code here
stands in for them or their traffic).

Shapes are static whatever the routing: a batch of R rows choosing k
experts each is ``R * k`` pairs, sorted by held expert with the pairs of
absent experts (and of rows that carry nothing) behind them, and ONE
grouped matmul a projection over the experts' stacked weights walks the
held ones alone (``jax.experimental.pallas.ops.tpu.megablox`` on the
chip, whose grid is as long as the groups' tiles; ``jax.lax.ragged_dot``
elsewhere). Nothing retraces as the load shifts.

Raw arrays in and out: inference only, under ``no_grad``, no op of its
own on the dispatch surface.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["group_limited_route", "held_expert_ffn", "ROUTE_STATS"]

# what ``held_expert_ffn`` counts of one call, in this order
ROUTE_STATS = ("pairs_here", "pairs", "experts_touched", "load_max")

# rows of a grouped matmul's tile on the chip: the pairs are padded to a
# multiple of it
_TILE_M = 128


def group_limited_route(x, w_gate, *, n_group: int, topk_group: int,
                        top_k: int, scale: float):
    """DeepSeek-V2's ``group_limited_greedy`` router on ``x`` [R, H]:
    ``p = softmax(x W_g)`` over all E experts, in float32 on float32
    copies of both at full precision; a group's score is its largest
    ``p`` (``n_group`` groups of consecutive experts); the best
    ``topk_group`` groups stay; the ``top_k`` largest ``p`` of what
    stays are the token's experts, each weighted ``p * scale`` (no
    renormalising). Returns ``(ids [R, top_k] int32, weights [R, top_k]
    float32)``."""
    rows, e = x.shape[0], w_gate.shape[-1]
    p = jax.nn.softmax(jnp.matmul(
        x.astype(jnp.float32), w_gate.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    best = p.reshape(rows, n_group, e // n_group).max(-1)
    kept = jax.lax.top_k(best, topk_group)[1]
    stays = jnp.zeros((rows, n_group), bool).at[
        jnp.arange(rows)[:, None], kept].set(True)
    left = jnp.where(jnp.repeat(stays, e // n_group, axis=1), p, 0.0)
    weights, ids = jax.lax.top_k(left, top_k)
    return ids.astype(jnp.int32), weights * scale


def _grouped_matmul(xs, w, sizes):
    """``xs[rows of group g] @ w[g]`` for the groups of ``sizes``, rows
    beyond their sum left to the caller's mask."""
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        k, n = w.shape[1], w.shape[2]
        # whole expert columns a tile where they fit: the weights of a
        # touched expert stream in few, long transfers
        tiling = (_TILE_M, min(k, 512), min(n, 1536 if k <= 2048 else 1024))
        return gmm(xs, w, sizes, preferred_element_type=xs.dtype,
                   tiling=tiling)
    return jax.lax.ragged_dot(xs, w, sizes)


def held_expert_ffn(x, ids, weights, w_gate, w_up, w_down, *, first: int,
                    live=None):
    """The routed part of an expert layer on this share: ``sum_i w_i
    E_i(x)`` over each row's chosen experts ``ids`` [R, k] that are HELD
    here, experts ``first .. first + E_held - 1`` with stacked SwiGLU
    weights ``w_gate``/``w_up`` [E_held, H, F] and ``w_down`` [E_held,
    F, H]. Dropless: every held pair is computed. ``live`` [R] bool
    marks the rows that carry a token (a dead row's pairs are neither
    computed nor counted). Returns ``(y [R, H] in x's dtype, stats
    int32 [4])``, the stats as ``ROUTE_STATS`` names them: the held
    pairs computed, all the live rows' pairs, the held experts with a
    pair, and the pairs of the busiest one."""
    rows, k = ids.shape
    held = w_gate.shape[0]
    local = ids - first
    here = (local >= 0) & (local < held)
    if live is not None:
        here &= live[:, None]
    n = rows * k
    pad = -n % _TILE_M
    key = jnp.pad(jnp.where(here, local, held).reshape(n), (0, pad),
                  constant_values=held)
    order = jnp.argsort(key, stable=True)           # held pairs first
    sizes = jnp.zeros(held + 1, jnp.int32).at[key].add(1)[:held]
    tok = jnp.minimum(order, n - 1) // k
    xs = x[tok]
    with jax.named_scope("moe_experts"):
        a = jax.nn.silu(_grouped_matmul(xs, w_gate, sizes)) \
            * _grouped_matmul(xs, w_up, sizes)
        ys = _grouped_matmul(a, w_down, sizes)
    # rows beyond the groups hold whatever the kernel left there
    n_here = sizes.sum()
    ys = jnp.where((jnp.arange(n + pad) < n_here)[:, None], ys, 0)
    w_sorted = jnp.pad(weights.reshape(n), (0, pad))[order]
    ys = ys.astype(jnp.float32) * w_sorted[:, None]
    back = jnp.argsort(order)[:n]                   # a pair's sorted place
    y = ys[back].reshape(rows, k, -1).sum(1).astype(x.dtype)
    n_live = rows if live is None else live.sum()
    stats = jnp.stack([n_here, n_live * k, (sizes > 0).sum(), sizes.max()])
    return y, stats.astype(jnp.int32)
