"""Drives a training step: the first steps from the seed (which the
reference follows), then the measured window on the same object."""

from __future__ import annotations

import math
import statistics
import time

from .. import traffic as traffic_mod
from .. import weights


def _norms(tree):
    import jax.numpy as jnp

    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def change_norms_fn(spec, dtype):
    """jit(params, salts) -> per-leaf norm of how far each parameter is
    from its seeded start, which is made again inside the program
    instead of kept."""
    import jax
    import jax.numpy as jnp

    names = list(spec)

    def f(params, salts):
        return _norms({
            k: params[k].astype(jnp.float32) - weights.leaf(
                tuple(spec[k][0]), salts[i], spec[k][1], spec[k][2],
                dtype).astype(jnp.float32)
            for i, k in enumerate(names)})

    return jax.jit(f)


def first_steps(trainer, spec, spec_traffic, seed, vocab, dtype, beta1):
    """Drive the trainer through its first steps and read what the
    check compares: each loss, the first gradient's norm by leaf (from
    Adam's first moment after one step) and each parameter's change
    after two updates."""
    import jax
    import jax.numpy as jnp

    salts = jnp.asarray(weights.salts(seed, len(spec)))
    grad_norms = jax.jit(lambda m: {k: v / (1.0 - beta1)
                                    for k, v in _norms(m).items()})
    change = change_norms_fn(spec, dtype)
    losses, got = [], {}
    for i in range(int(spec_traffic["follow_steps"])):
        losses.append(trainer.step(
            traffic_mod.train_batch(spec_traffic, seed, i, vocab)))
        if i == 0:
            got["grad_norms"] = {k: float(v) for k, v in
                                 grad_norms(trainer.first_moment()).items()}
        if i == 1:
            got["change_norms"] = {k: float(v) for k, v in
                                   change(trainer.params(), salts).items()}
    got["losses"] = losses
    return got


def window(trainer, spec_traffic, seed, vocab, seconds, first_step, tracer):
    """Steps until ``seconds`` have passed; every step ends with its
    loss on the host. Returns (end time of each step, losses, t0)."""
    ends, losses = [], []
    tracer.window_start()
    t0 = time.perf_counter()
    i = first_step
    while True:
        tracer.between_steps(time.perf_counter() - t0)
        losses.append(trainer.step(
            traffic_mod.train_batch(spec_traffic, seed, i, vocab)))
        now = time.perf_counter()
        ends.append(now)
        i += 1
        if now - t0 >= seconds:
            break
    return ends, losses, t0


def follow(ref, cfg, hyper, spec, spec_traffic, seed, vocab, dtype, mm=None):
    """The reference's (or, with ``mm``, a control's) first steps:
    float32 on the served weights, two updates and three losses."""
    import jax
    import jax.numpy as jnp

    params = weights.make(spec, seed, dtype, upcast=jnp.float32)
    fol = ref.Follower(cfg, params, hyper,
                       rows_per_block=int(spec_traffic["reference_rows_per_block"]),
                       mm=mm)
    batch = lambda i: traffic_mod.train_batch(spec_traffic, seed, i, vocab)  # noqa: E731
    out = {"losses": []}
    l1, g1 = fol.loss_and_grads(batch(0))
    out["grad_norms"] = {k: float(v) for k, v in jax.jit(_norms)(g1).items()}
    fol.adamw([g1])
    l2, g2 = fol.loss_and_grads(batch(1))
    fol.adamw([g1, g2])
    del g1, g2
    salts = jnp.asarray(weights.salts(seed, len(spec)))
    out["change_norms"] = {k: float(v) for k, v in change_norms_fn(
        spec, dtype)(fol.params, salts).items()}
    l3, _ = fol.loss_and_grads(batch(2), want_grads=False)
    out["losses"] = [l1, l2, l3]
    return out


def worst_leaf_gap(got, want):
    """Largest gap between the two norms of one leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero). Returns (gap, leaf)."""
    floor = statistics.median(want.values())
    gap, leaf = max((abs(got[k] - want[k]) / max(want[k], floor), k)
                    for k in want)
    return gap, leaf


def compare(got, want):
    """The numbers the check holds against their limits."""
    vals = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"]), 1):
        vals[f"loss_rel_gap_step{i}"] = (abs(a - b) / abs(b),
                                         f"program {a:.6f} reference {b:.6f}")
    g, leaf = worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    vals["first_grad_norm_gap"] = (g, f"worst leaf {leaf}")
    g, leaf = worst_leaf_gap(got["change_norms"], want["change_norms"])
    vals["param_change_norm_gap"] = (g, f"after 2 updates, worst leaf {leaf}")
    return vals


def nonfinite(losses):
    return sum(1 for x in losses if not math.isfinite(x))
