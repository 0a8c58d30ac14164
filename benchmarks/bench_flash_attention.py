"""Flash-attention microbench: Pallas kernel vs XLA dense attention.

Run on a real TPU chip (`python benchmarks/bench_flash_attention.py`).
Prints one JSON line per sequence length with fwd/bwd times for the
Pallas flash kernel and the XLA dense reference.

Timing method: K data-chained iterations inside ONE jitted scan, synced
by a host transfer, minus the same measurement at K=1 — per-iteration
time = (T_K - T_1) / (K - 1). Chaining forces serial execution, and
differencing cancels the dispatch, transfer and scan overhead, which
at these kernel times (milliseconds) is not small beside the kernel.

Reference analogue: the perf harnesses in test/legacy_test/benchmark.py;
kernel parity: phi/kernels/gpu/flash_attn_kernel.cu / flash_attn_grad_kernel.cu.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.pallas_kernels.flash_attention import _flash


def xla_attn(q, k, v, scale):
    s_ = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * scale
    n = q.shape[1]
    mask = jnp.tril(jnp.ones((n, n), bool))
    s_ = jnp.where(mask, s_, -1e30)
    p = jax.nn.softmax(s_, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)


def bench(fn, *args, iters=10):
    """Chained-scan differencing (see module docstring). ``fn`` returns
    either an array (fwd) or a (dq, dk, dv) tuple (grad); each iteration
    feeds an epsilon of the output back into the inputs so the scan
    cannot be parallelized or elided."""

    def chained(n):
        @jax.jit
        def run(args):
            def body(carry, _):
                out = fn(*carry)
                outs = out if isinstance(out, tuple) else (out,) * len(carry)
                new = tuple(a + o.astype(a.dtype) * 1e-6
                            for a, o in zip(carry, outs))
                return new, ()
            carry, _ = jax.lax.scan(body, tuple(args), None, length=n)
            return carry[0]

        _ = np.asarray(run(args)[0, 0])  # compile + warm
        best = float("inf")
        for _ in range(3):  # best-of-3: the transfer round trip is noisy
            t0 = time.perf_counter()
            _ = np.asarray(run(args)[0, 0])  # host transfer = real sync
            best = min(best, time.perf_counter() - t0)
        return best

    t1 = chained(1)
    tk = chained(iters + 1)
    return max(tk - t1, 1e-9) / iters


def main():
    d = 64
    for s, bh in ((1024, 192), (2048, 96), (4096, 32)):
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)
        do = jnp.asarray(rng.randn(bh, s, d), jnp.bfloat16)
        scale = 1.0 / math.sqrt(d)

        # 512x512 blocks: the production default flash_attention() uses
        bq = bk = min(512, s)
        flash_f = jax.jit(lambda q, k, v: _flash(q, k, v, None, True, scale, bq, bk))
        xla_f = jax.jit(lambda q, k, v: xla_attn(q, k, v, scale))
        flash_g = jax.jit(jax.grad(
            lambda q, k, v: (_flash(q, k, v, None, True, scale, bq, bk) * do).sum(),
            argnums=(0, 1, 2)))
        xla_g = jax.jit(jax.grad(
            lambda q, k, v: (xla_attn(q, k, v, scale) * do).sum(), argnums=(0, 1, 2)))

        err = float(jnp.abs(flash_f(q, k, v).astype(jnp.float32)
                            - xla_f(q, k, v).astype(jnp.float32)).max())
        row = {
            "seq": s, "bh": bh, "head_dim": d, "max_abs_err": round(err, 4),
            "fwd_flash_ms": round(bench(flash_f, q, k, v) * 1e3, 2),
            "fwd_xla_ms": round(bench(xla_f, q, k, v) * 1e3, 2),
            "bwd_flash_ms": round(bench(flash_g, q, k, v) * 1e3, 2),
            "bwd_xla_ms": round(bench(xla_g, q, k, v) * 1e3, 2),
        }
        row["speedup_fwd"] = round(row["fwd_xla_ms"] / row["fwd_flash_ms"], 2)
        row["speedup_bwd"] = round(row["bwd_xla_ms"] / row["bwd_flash_ms"], 2)
        print(json.dumps(row))


if __name__ == "__main__":
    main()
