"""One-off probe: NCHW vs NHWC conv stack timing on the real chip.

Representative ResNet-50 shapes (batch 256, bf16, fwd+bwd through a
bottleneck-like stack + BN + ReLU). Decides the layout for the vision
path (reference analogue: paddle/fluid/imperative/layout_autotune.cc
picks layouts dynamically; we measure once and bake the result in).
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np


def _sync(r):
    # a scalar fetch: the host cannot have the value before the device does
    leaf = jax.tree_util.tree_leaves(r)[0]
    return float(jnp.ravel(leaf)[0].astype(jnp.float32))


def timeit(f, *args, n=20, warmup=3):
    for _ in range(warmup):
        r = f(*args)
    _sync(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = f(*args)
    _sync(r)
    return (time.perf_counter() - t0) / n


def make_stack(layout, wlayout):
    # stage-2-like: 28x28 feature maps, C=128/512 bottleneck x3
    dn = (layout, wlayout, layout)

    def block(x, ws):
        w1, w2, w3 = ws
        for w, st in ((w1, 1), (w2, 1), (w3, 1)):
            x = jax.lax.conv_general_dilated(
                x, w, (st, st), "SAME", dimension_numbers=dn)
            # BN-ish: normalize over all but channel axis, relu
            ch = 1 if layout == "NCHW" else 3
            axes = tuple(i for i in range(4) if i != ch)
            xf = x.astype(jnp.float32)
            m = jnp.mean(xf, axis=axes, keepdims=True)
            v = jnp.var(xf, axis=axes, keepdims=True)
            x = jnp.maximum((xf - m) * jax.lax.rsqrt(v + 1e-5),
                            0.0).astype(jnp.bfloat16)
        return x

    def loss(x, ws):
        return jnp.sum(block(x, ws).astype(jnp.float32))

    return jax.jit(jax.grad(loss, argnums=1)), block


def run(layout, wlayout):
    rng = np.random.RandomState(0)
    B, C, H = 256, 128, 28
    if layout == "NCHW":
        x = jnp.asarray(rng.randn(B, C, H, H), jnp.bfloat16)
    else:
        x = jnp.asarray(rng.randn(B, H, H, C), jnp.bfloat16)

    def w(kh, kw, ci, co):
        if wlayout == "OIHW":
            return jnp.asarray(rng.randn(co, ci, kh, kw) * 0.05, jnp.bfloat16)
        return jnp.asarray(rng.randn(kh, kw, ci, co) * 0.05, jnp.bfloat16)

    ws = (w(1, 1, C, C), w(3, 3, C, C), w(1, 1, C, C))
    g, _ = make_stack(layout, wlayout)
    dt = timeit(g, x, ws)
    flops = 2 * B * H * H * (C * C + 9 * C * C + C * C) * 3  # fwd
    print(f"{layout}/{wlayout}: {dt*1e3:.2f} ms  (~{3*flops/dt/1e12:.1f} TF/s fwd+bwd)")
    return dt


if __name__ == "__main__":
    print("devices:", jax.devices())
    run("NCHW", "OIHW")
    run("NHWC", "OIHW")
    run("NHWC", "HWIO")
