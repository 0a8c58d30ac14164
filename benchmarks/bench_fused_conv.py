"""Fused conv+BN+ReLU microbench: Pallas kernels vs the XLA-fused path.

Run on a real TPU chip (`python benchmarks/bench_fused_conv.py`).
Prints one JSON line per ResNet-50 hot shape with:

- ``eval``: inference epilogue kernel (conv+scale/shift+relu, one HBM
  write) vs the XLA composition conv -> BN(frozen stats) -> relu.
- ``train``: fwd+bwd of conv+BN with batch stats (the Pallas path
  computes stats in the conv epilogue and, in the chained variant,
  consumes the upstream normalize+relu as a VMEM prologue) vs the XLA
  composition, both through jax.value_and_grad.
- ``bytes_saved_mb``: per-block HBM savings from the committed round-5
  byte audit (benchmarks/resnet_byte_audit.json).

Timing: the same chained-scan differencing as bench_flash_attention.py
(see that module's docstring); iteration outputs feed back into the
inputs via a scalar
epsilon so the scan can be neither parallelized nor elided.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.pallas_kernels.fused_conv import (_xla_conv, bn_apply,
                                                  conv_stats, conv_stats_pre,
                                                  fused_conv_bn_eval)

ON_TPU = jax.default_backend() == "tpu"
# ResNet-50 hot NHWC shapes (batch matches the flagship bench point);
# CPU fallback uses tiny shapes in interpret mode — correctness smoke
# only, the timings are meaningless off-chip.
BATCH = 256 if ON_TPU else 4
SHAPES = [
    # (tag, H=W, C_in, C_out, k)
    ("l1.conv2 3x3", 56, 64, 64, 3),
    ("l2.conv2 3x3", 28, 128, 128, 3),
    ("l3.conv2 3x3", 14, 256, 256, 3),
    ("l4.conv2 3x3", 7, 512, 512, 3),
    ("l1.conv1 1x1", 56, 256, 64, 1),
    ("l3.conv3 1x1", 14, 256, 1024, 1),
    ("l4.conv1 1x1", 7, 2048, 512, 1),
] if ON_TPU else [
    ("3x3 smoke", 8, 16, 16, 3),
    ("1x1 smoke", 8, 32, 16, 1),
]
DTYPE = jnp.bfloat16 if ON_TPU else jnp.float32


def bench(fn, *args, iters=10):
    """Chained-scan differencing; fn returns a pytree — its leaves' means
    perturb the carried inputs so iterations are serially dependent."""

    def chained(n):
        @jax.jit
        def run(args):
            def body(carry, _):
                out = fn(*carry)
                leaves = jax.tree.leaves(out)
                eps = sum(jnp.mean(l.astype(jnp.float32)) for l in leaves) * 1e-6
                new = tuple(a + eps.astype(a.dtype) for a in carry)
                return new, ()

            carry, _ = jax.lax.scan(body, tuple(args), None, length=n)
            return carry[0]

        _ = np.asarray(jax.device_get(run(args)))[0].ravel()[0]  # compile+warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            _ = np.asarray(jax.device_get(run(args)))[0].ravel()[0]
            best = min(best, time.perf_counter() - t0)
        return best

    t1 = chained(1)
    tk = chained(iters + 1)
    return max(tk - t1, 1e-9) / iters


def _audit_savings():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "resnet_byte_audit.json")
    try:
        with open(path) as f:
            audit = json.load(f)
    except OSError:
        return {}, None
    per_shape = {}
    for b in audit["blocks"]:
        key = (b["conv"], b["out_spatial"], b["in_channels"], b["out_channels"])
        per_shape.setdefault(key, 0)
        per_shape[key] += b["fused_train_fwd_bytes_saved"]
    return per_shape, audit["per_block_activation_model"]


def main():
    rng = np.random.RandomState(0)
    savings, agg = _audit_savings()

    for tag, hw, c, k, ksz in SHAPES:
        x = jnp.asarray(rng.randn(BATCH, hw, hw, c), DTYPE)
        w = jnp.asarray(rng.randn(k, c, ksz, ksz) * 0.05, DTYPE)
        scale = jnp.asarray(rng.rand(k) + 0.5, jnp.float32)
        shift = jnp.asarray(rng.randn(k), jnp.float32)
        gamma = jnp.asarray(rng.rand(k) + 0.5, jnp.float32)
        beta = jnp.asarray(rng.randn(k), jnp.float32)
        # upstream-unit tensors for the chained (prologue) variant
        m_p = jnp.asarray(rng.randn(c) * 0.1, jnp.float32)
        v_p = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
        gp = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
        bp = jnp.asarray(rng.randn(c), jnp.float32)

        # --- inference epilogue ---
        def eval_fused(x, w):
            return fused_conv_bn_eval(x, w, scale, shift, True)

        def eval_xla(x, w):
            y = _xla_conv(x, w) * scale + shift
            return jnp.maximum(y, 0.0).astype(x.dtype)

        t_eval_fused = bench(eval_fused, x, w)
        t_eval_xla = bench(eval_xla, x, w)

        # --- training fwd+bwd (loss = sum of normalized output) ---
        def train_fused(x, w):
            def loss(x, w):
                co, m, v = conv_stats(x, w)
                return jnp.sum(bn_apply(co, m, v, gamma, beta, 1e-5)
                               .astype(jnp.float32))

            return jax.value_and_grad(loss, (0, 1))(x, w)

        def train_chained(x, w):
            def loss(x, w):
                co, m, v = conv_stats_pre(x, m_p, v_p, gp, bp, w, True, 1e-5)
                return jnp.sum(bn_apply(co, m, v, gamma, beta, 1e-5)
                               .astype(jnp.float32))

            return jax.value_and_grad(loss, (0, 1))(x, w)

        def train_xla(x, w):
            def loss(x, w):
                co = _xla_conv(x, w).astype(jnp.float32)
                m, v = co.mean((0, 1, 2)), co.var((0, 1, 2))
                y = (co - m) * jax.lax.rsqrt(v + 1e-5) * gamma + beta
                return jnp.sum(y)

            return jax.value_and_grad(loss, (0, 1))(x, w)

        t_train_fused = bench(train_fused, x, w)
        t_train_chained = bench(train_chained, x, w)
        t_train_xla = bench(train_xla, x, w)

        key = (f"{ksz}x{ksz}/s1", hw, c, k)
        print(json.dumps({
            "shape": tag, "batch": BATCH, "hw": hw, "cin": c, "cout": k,
            "dtype": str(DTYPE.__name__),
            "eval_ms": {"pallas_fused": round(t_eval_fused * 1e3, 3),
                        "xla": round(t_eval_xla * 1e3, 3),
                        "speedup": round(t_eval_xla / t_eval_fused, 3)},
            "train_ms": {"pallas_fused": round(t_train_fused * 1e3, 3),
                         "pallas_chained": round(t_train_chained * 1e3, 3),
                         "xla": round(t_train_xla * 1e3, 3),
                         "speedup": round(t_train_xla / t_train_fused, 3),
                         "speedup_chained": round(t_train_xla / t_train_chained, 3)},
            "audit_train_fwd_bytes_saved_mb":
                round(savings.get(key, 0) / 2**20, 1) if savings else None,
        }), flush=True)

    if agg:
        print(json.dumps({"resnet50_audit_aggregate": agg}), flush=True)
    if not ON_TPU:
        print(json.dumps({"note": "CPU interpret-mode run: correctness smoke "
                                  "only, timings are not meaningful"}),
              flush=True)


if __name__ == "__main__":
    main()
