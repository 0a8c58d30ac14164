"""Attention over a latent (MLA) pool at DeepSeek-V2's widths, one
layer, on the chip: the absorbed form through the paged kernel at
several chunk lengths and head groupings, and the decompressed form
in XLA at two chunk lengths, each at two context lengths. (PR 43 also
ran a Pallas chunk kernel for the decompressed form here, since
removed: PERF.md section 6.) Prints one
JSON line a variant with the time a call and a (query row, position)
pair. Not part of a benchmark run; PERF.md records what it printed.

    chiprun -- python3 benchmarks/mla_attention_micro.py
"""
import json
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from paddle_tpu import generation  # noqa: E402
from paddle_tpu.pallas_kernels import decode_attention as da  # noqa: E402

H, DN, DR, DV, RANK, W, BS = 128, 128, 64, 128, 512, 640, 16
NB, POOL = 1088, 20000
SCALE = 0.114721


def timed(fn, *args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    fn(*args)
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    key = jax.random.PRNGKey(0)
    pool = (jax.random.normal(key, (POOL, BS, W), jnp.float32)
            * 0.3).astype(jnp.bfloat16)
    w_kvb = (jax.random.normal(key, (RANK, H, DN + DV), jnp.float32)
             * 0.02).astype(jnp.bfloat16)
    for ctx in (4096, 12288):
        for b, s, rows in ((64, 1, None), (8, 32, 8192), (8, 32, 2048),
                           (8, 32, 1024), (4, 64, 8192), (4, 64, 2048),
                           (4, 64, 1024), (2, 128, 2048), (1, 256, 2048),
                           (1, 256, 1024), (1, 256, 512)):
            bt = (jnp.arange(b * NB, dtype=jnp.int32).reshape(b, NB)
                  % (POOL - 1)) + 1
            pos = jnp.full((b,), ctx - s, jnp.int32)
            q = (jax.random.normal(key, (b, s, H, W), jnp.float32)
                 ).astype(jnp.bfloat16)
            f = jax.jit(lambda q, pool, bt, pos, rows=rows:
                        da.latent_paged_flash_decode_attention(
                            q, pool, bt, pos, sm_scale=SCALE, v_width=RANK,
                            max_rows=rows))
            try:
                t = timed(f, q, pool, bt, pos)
            except Exception as e:  # noqa: BLE001
                print(json.dumps({"form": "absorbed", "ctx": ctx, "b": b,
                                  "s": s, "rows": rows,
                                  "error": repr(e)[:200]}), flush=True)
                continue
            pairs = b * s * ctx
            print(json.dumps({
                "form": "absorbed", "ctx": ctx, "b": b, "s": s, "rows": rows,
                "ms": t * 1e3, "ns_per_pair": t * 1e9 / pairs,
                "mxu_share": pairs * H * (W + RANK) * 2 / t / 197e12}),
                flush=True)
        for s in (256, 1024):
            b = 1
            bt = (jnp.arange(b * NB, dtype=jnp.int32).reshape(b, NB)
                  % (POOL - 1)) + 1
            pos = jnp.full((b,), ctx - s, jnp.int32)
            qn = jax.random.normal(key, (b, s, H, DN), jnp.float32
                                   ).astype(jnp.bfloat16)
            qp = jax.random.normal(key, (b, s, H, DR), jnp.float32
                                   ).astype(jnp.bfloat16)
            f = jax.jit(lambda qn, qp, pool, bt, pos:
                        generation._latent_decompressed(
                            qn, qp, pool, bt, pos, w_kvb, SCALE))
            try:
                t = timed(f, qn, qp, pool, bt, pos)
            except Exception as e:  # noqa: BLE001
                print(json.dumps({"form": "decompressed", "ctx": ctx,
                                  "s": s, "error": repr(e)[:200]}),
                      flush=True)
                continue
            pairs = b * s * ctx
            print(json.dumps({
                "form": "decompressed", "ctx": ctx,
                "b": b, "s": s, "ms": t * 1e3,
                "ns_per_pair": t * 1e9 / pairs,
                "mxu_share": pairs * H * ((DN + DR + DV) * 2
                                          + RANK * (DN + DV) * 2 / s)
                / t / 197e12}), flush=True)


if __name__ == "__main__":
    main()
