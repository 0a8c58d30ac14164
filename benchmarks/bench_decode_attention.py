"""Flash-decode A/B lane: Pallas kernel vs XLA fallback on the decode step.

The serving decode hot loop's attention, isolated: single-query
attention over a static [B, max_len, kv_heads, d] KV cache at three
cache occupancies (25/50/100% — per-row positions, the continuous-
batching steady state) and two GQA ratios (1x and 4x), timed three ways:

- ``kernel``:   pallas_kernels.decode_attention.flash_decode_attention
                (one pass a row, GQA-native, bounded by each row's length);
- ``fallback``: the post-PR XLA path — grouped-einsum SDPA over the
                masked cache (nn.functional.grouped_query_sdpa form),
                no repeat_kv materialization;
- ``legacy``:   the pre-PR XLA path — repeat_kv-expanded K/V + dense
                masked SDPA (what every decode step used to pay).

All three are jitted on raw jnp arrays, warmed, and timed best-of-N
with block_until_ready. Parity (kernel vs fallback) is asserted per
config.

Artifact: ``benchmarks/bench_decode.json`` — per-config ms + speedups +
max parity error; ``tests/run_shards.py`` folds it into
``telemetry_lane.json`` as the ``decode_bench`` block for both lanes.

Lane semantics: on CPU the Pallas kernel runs in the INTERPRETER, so
this lane records interpret-mode parity only (timings are reported but
the speedup acceptance is not applied — the interpreter is orders of
magnitude off). On TPU (`--platform=tpu` chip lane) the acceptance is
kernel >= 1.3x over the fallback on the GQA-4x config at <= 50%
occupancy.
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

from paddle_tpu.pallas_kernels.decode_attention import flash_decode_attention

HERE = os.path.dirname(os.path.abspath(__file__))

ON_TPU = jax.default_backend() == "tpu"
# CPU shapes keep the interpreted kernel tractable; chip shapes are the
# serving regime (Llama-70B-style head geometry, 2k cache)
if ON_TPU:
    B, KV, D, MAX_LEN, Q_LEN, BLOCK_K = 8, 2, 128, 2048, 1, 256
else:
    B, KV, D, MAX_LEN, Q_LEN, BLOCK_K = 4, 2, 64, 512, 1, 64

GQA_RATIOS = (1, 4)
OCCUPANCIES = (0.25, 0.5, 1.0)
ACCEPT_SPEEDUP = 1.3  # TPU lane: kernel vs fallback, GQA 4x, occ <= 0.5


def _mask_for(pos, q_len, max_len):
    """The update_static_kv_cache per-row additive mask the XLA paths pay."""
    kpos = jnp.arange(max_len)
    qpos = pos[:, None] + jnp.arange(q_len)
    m = (kpos[None, None, :] <= qpos[:, :, None]) \
        & (kpos[None, None, :] < (pos[:, None, None] + q_len))
    return jnp.where(m[:, None], 0.0, -1e30).astype(jnp.float32)


def _grouped_sdpa(q, kc, vc, mask):
    b, s, H, d = q.shape
    kv = kc.shape[2]
    g = H // kv
    qt = jnp.swapaxes(q, 1, 2).reshape(b, kv, g, s, d)
    kt = jnp.swapaxes(kc, 1, 2)
    vt = jnp.swapaxes(vc, 1, 2)
    scores = jnp.einsum("bkgqd,bktd->bkgqt", qt, kt) / math.sqrt(d)
    scores = scores + mask[:, :, None]
    p = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(vt.dtype)
    out = jnp.einsum("bkgqt,bktd->bkgqd", p, vt)
    return jnp.swapaxes(out.reshape(b, H, s, d), 1, 2)


def _legacy_sdpa(q, kc, vc, mask):
    b, s, H, d = q.shape
    g = H // kc.shape[2]
    ke = jnp.repeat(kc, g, axis=2)  # the old HBM-materialized expansion
    ve = jnp.repeat(vc, g, axis=2)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(ke, 1, 2)
    vt = jnp.swapaxes(ve, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / math.sqrt(d)
    scores = scores + mask
    p = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(vt.dtype)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)


def _time(fn, *args, iters=30, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3  # ms


def run_config(gqa, occ, dtype):
    H = KV * gqa
    rng = np.random.RandomState(hash((gqa, int(occ * 100))) % (2 ** 31))
    q = jnp.asarray(rng.randn(B, Q_LEN, H, D), dtype)
    kc = jnp.asarray(rng.randn(B, MAX_LEN, KV, D), dtype)
    vc = jnp.asarray(rng.randn(B, MAX_LEN, KV, D), dtype)
    pos = jnp.asarray(np.full(B, int(occ * MAX_LEN) - Q_LEN, np.int32))

    kern = jax.jit(lambda q, k, v, p: flash_decode_attention(
        q, k, v, p, block_k=BLOCK_K))
    fall = jax.jit(lambda q, k, v, p: _grouped_sdpa(
        q, k, v, _mask_for(p, Q_LEN, MAX_LEN)))
    legacy = jax.jit(lambda q, k, v, p: _legacy_sdpa(
        q, k, v, _mask_for(p, Q_LEN, MAX_LEN)))

    out_k = np.asarray(kern(q, kc, vc, pos), np.float32)
    out_f = np.asarray(fall(q, kc, vc, pos), np.float32)
    max_err = float(np.abs(out_k - out_f).max())

    kernel_ms = _time(kern, q, kc, vc, pos)
    fallback_ms = _time(fall, q, kc, vc, pos)
    legacy_ms = _time(legacy, q, kc, vc, pos)
    tol = 5e-5 if dtype == "float32" else 3e-2
    return {
        "gqa": gqa,
        "occupancy": occ,
        "kernel_ms": round(kernel_ms, 4),
        "fallback_ms": round(fallback_ms, 4),
        "legacy_repeat_kv_ms": round(legacy_ms, 4),
        "kernel_vs_fallback": round(fallback_ms / kernel_ms, 2),
        "fallback_vs_legacy": round(legacy_ms / fallback_ms, 2),
        "max_err": max_err,
        "parity": bool(max_err < tol),
    }


def run_format_config(gqa, occ, dtype):
    """Quantized-cache columns at one config: the dequant-prologue
    kernel per format vs the float kernel on the SAME (dequantized)
    values — per-format ms, max-abs-err, and the KV byte accounting
    that drives the capacity story (bf16 2 bytes/value vs 1 byte +
    4/d scale tax)."""
    from paddle_tpu.quantization import intx

    H = KV * gqa
    rng = np.random.RandomState(77)
    q = jnp.asarray(rng.randn(B, Q_LEN, H, D), dtype)
    kc = jnp.asarray(rng.randn(B, MAX_LEN, KV, D), dtype)
    vc = jnp.asarray(rng.randn(B, MAX_LEN, KV, D), dtype)
    pos = jnp.asarray(np.full(B, int(occ * MAX_LEN) - Q_LEN, np.int32))

    base = jax.jit(lambda q, k, v, p: flash_decode_attention(
        q, k, v, p, block_k=BLOCK_K))
    base_ms = _time(base, q, kc, vc, pos)
    out_base = np.asarray(base(q, kc, vc, pos), np.float32)
    rows = {"bf16" if dtype == "bfloat16" else "float32": {
        "kernel_ms": round(base_ms, 4),
        "kv_bytes_per_value": jnp.dtype(dtype).itemsize,
        "max_abs_err_vs_float": 0.0}}
    formats = ["int8"] + (["fp8"] if intx.fp8_available() else [])
    for fmt in formats:
        ks = intx.absmax_along(kc, -1)
        vs = intx.absmax_along(vc, -1)
        kq = intx.pack_absmax(kc, ks[..., None], fmt)
        vq = intx.pack_absmax(vc, vs[..., None], fmt)
        kern = jax.jit(lambda q, k, v, ks, vs, p: flash_decode_attention(
            q, k, v, p, block_k=BLOCK_K, k_scale=ks, v_scale=vs))
        out_q = np.asarray(kern(q, kq, vq, ks, vs, pos), np.float32)
        rows[fmt] = {
            "kernel_ms": round(_time(kern, q, kq, vq, ks, vs, pos), 4),
            # 1 byte/value + f32 scale amortized over the head_dim
            "kv_bytes_per_value": round(1 + 4 / D, 4),
            "max_abs_err_vs_float": float(np.abs(out_q - out_base).max()),
        }
        rows[fmt]["kv_bytes_vs_bf16"] = round(
            2 / rows[fmt]["kv_bytes_per_value"], 3)
    return {"gqa": gqa, "occupancy": occ, "formats": rows}


def main():
    dtype = "bfloat16" if ON_TPU else "float32"
    rows = [run_config(g, o, dtype) for g in GQA_RATIOS for o in OCCUPANCIES]
    fmt_rows = [run_format_config(4, 0.5, dtype)]

    parity_ok = all(r["parity"] for r in rows)
    accept_rows = [r for r in rows if r["gqa"] == 4 and r["occupancy"] <= 0.5]
    speedup_ok = all(r["kernel_vs_fallback"] >= ACCEPT_SPEEDUP
                     for r in accept_rows)
    result = {
        "bench": "flash_decode_vs_xla",
        "platform": jax.default_backend(),
        "dtype": dtype,
        "shapes": {"batch": B, "kv_heads": KV, "head_dim": D,
                   "max_len": MAX_LEN, "q_len": Q_LEN, "block_k": BLOCK_K},
        "configs": rows,
        "quantized_kv": fmt_rows,
        "parity": parity_ok,
        "speedup_target": ACCEPT_SPEEDUP,
        "speedup_ok": speedup_ok,
        # CPU: the kernel runs in the Pallas INTERPRETER — timings are
        # recorded for the curious but only parity gates the lane; the
        # >=1.3x acceptance applies on the TPU lane
        "mode": "compiled" if ON_TPU else "interpret (parity only)",
    }
    path = os.path.join(HERE, "bench_decode.json")
    with open(path, "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result, indent=1))
    print(f"[bench_decode_attention] artifact -> {path}")

    ok = parity_ok and (speedup_ok or not ON_TPU)
    if not ok:
        print("[bench_decode_attention] ACCEPTANCE FAILED", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
