"""Paged-KV serving lane: concurrent capacity + prefix-sharing A/B.

Two workloads against the SAME KV HBM budget:

1. **Long-tail capacity** — the tentpole claim. A budget of KV
   token-slots that a layout reserving ``max_len`` a slot would spend on
   ``S_c`` slots (capacity bounded by WORST-CASE length: at most ``S_c``
   requests at once, whatever their lengths) is spent as a block pool
   (``S_c * max_len / block_size`` blocks) fronted by 4x the slots —
   capacity bounded by TOKENS IN FLIGHT, preemption-by-recompute keeps
   oversubscription safe.

   A long-tail request mix (mostly short, a few near-max_len) drains
   through the engine; the bench measures MEAN ACTIVE REQUESTS
   (concurrency actually sustained), wall time, and tok/s, and asserts
   per-request bit-parity with ``generation.generate`` plus the
   one-step-compile invariant while it runs. (``capacity_ratio`` and
   its verdict were measured against the per-slot-buffer engine this
   lane once ran beside it; that engine is gone, a fresh artifact has
   neither key, and the committed ``bench_paged_kv.json`` holds the
   last CPU reading, which is what the CPU gate's pin reads.)

2. **Shared-prefix prefill savings** — 12 requests sharing a 64-token
   system prompt. After the first request populates the prefix cache,
   every follower adopts the shared blocks instead of recomputing them;
   the bench asserts the measured prefill-work saving is proportional
   to the shared fraction of the prompt (within 10%).

Artifact: ``benchmarks/bench_paged_kv.json``; ``tests/run_shards.py``
folds it into ``telemetry_lane.json`` as the ``paged_kv_bench`` block
(both lanes). CPU numbers size the structural win on the dev box; the
chip lane reruns this on TPU (where the paged flash-decode kernel is
compiled instead of interpreted/gathered).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import generation, serving
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import recompile

HERE = os.path.dirname(os.path.abspath(__file__))

MODEL_KW = dict(hidden_size=128, intermediate_size=256,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, vocab_size=1024,
                max_position_embeddings=256)

MAX_LEN = 128
BLOCK_SIZE = 16
CONTIG_SLOTS = 4                      # the HBM budget: 4 * 128 tokens
PAGED_SLOTS = 16                      # 4x the slots on the SAME budget
NUM_BLOCKS = CONTIG_SLOTS * MAX_LEN // BLOCK_SIZE + 1  # + dump block

# long-tail mix: (prompt_len, max_new_tokens) — 18 short, 6 long
LONG_TAIL = ([(6, 10), (9, 8), (14, 12), (7, 16), (11, 9), (5, 14)] * 3
             + [(48, 40), (64, 48), (40, 32), (56, 44), (60, 36), (44, 48)])

SYS_PROMPT_LEN = 64
SHARED_TAILS = 12
TAIL_LEN = 8

# ---- quantized-KV format lane (bf16 vs int8 vs fp8) ----------------------
# head_dim 64 — the serving geometry class. Capacity accounting is per
# CACHED TOKEN: bf16 stores 2 bytes/value, int8/fp8 store 1 byte/value
# + 4 bytes/head per token of f32 absmax scale, so the fixed-byte-budget
# multiplier is 2d / (d + 4) = 1.88x at d=64 (the scale tax shrinks as
# d grows; at d=16 it would only be 1.6x — head_dim matters).
FMT_MODEL_KW = dict(hidden_size=256, intermediate_size=256,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, vocab_size=1024,
                    max_position_embeddings=256)
FMT_MIX = ([(6, 8), (10, 6), (8, 10), (12, 8), (7, 6), (9, 8)]
           + [(40, 24), (48, 20), (36, 16), (44, 12)])
FMT_SLOTS = 12
# budget chosen so the POOL (not the slot count) binds concurrency on
# this mix: the bf16 lane runs pool-starved (preemption/queueing), the
# int8 lane's ~1.88x extra blocks convert directly into active requests
FMT_BF16_BLOCKS = 12          # the byte budget, expressed in bf16 blocks


def make_requests(cfg, mix, seed):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, cfg.vocab_size, n).astype(np.int32),
             dict(max_new_tokens=m, do_sample=bool(i % 3 == 1),
                  top_k=8 if i % 3 == 1 else 0, seed=i))
            for i, (n, m) in enumerate(mix)]


def drain(engine, workload):
    reqs = [engine.submit(p, **params) for p, params in workload]
    t0 = time.perf_counter()
    engine.run_until_idle(max_steps=100_000)
    return reqs, time.perf_counter() - t0


def check_parity(model, reqs, workload):
    for req, (p, params) in zip(reqs, workload):
        ref = generation.generate(model, p[None], **params).numpy()[0, len(p):]
        got = np.asarray(req.result(timeout=1.0))
        if not (len(got) == len(ref) and np.array_equal(got, ref)):
            return False
    return True


def run_capacity_lane(model, cfg):
    workload = make_requests(cfg, LONG_TAIL, seed=7)
    gen_tokens = sum(params["max_new_tokens"] for _, params in workload)
    eng = serving.ServingEngine(
        model, max_len=MAX_LEN, max_queue_depth=len(workload),
        max_slots=PAGED_SLOTS, block_size=BLOCK_SIZE, num_blocks=NUM_BLOCKS,
        prefix_caching=False)
    drain(eng, workload)  # warmup: compile every executable
    base_steps, base_occ = eng._steps, eng._occupancy_integral
    step_before = recompile.entry_stats().get(
        "serving.step", {"compiles": 0, "retraces": 0})
    reqs, wall = drain(eng, workload)
    step_after = recompile.entry_stats().get(
        "serving.step", {"compiles": 0, "retraces": 0})
    steps = eng._steps - base_steps
    mean_active = (eng._occupancy_integral - base_occ) / max(1, steps)
    paged = {
        "max_slots": eng.config.max_slots,
        "kv_token_budget": (NUM_BLOCKS - 1) * BLOCK_SIZE,
        "completed": sum(r.status == "completed" for r in reqs),
        "requests": len(workload),
        "mean_active_requests": round(mean_active, 2),
        "decode_steps": steps,
        "wall_s": round(wall, 3),
        "tok_s": round(gen_tokens / wall, 1),
        "parity": check_parity(model, reqs, workload),
        "step_compiles_measured":
            step_after["compiles"] - step_before["compiles"],
        "step_retraces_measured":
            step_after["retraces"] - step_before["retraces"],
        "num_blocks": NUM_BLOCKS - 1,
        "preemptions": eng._preempt_count,
        "kv_blocks_high_watermark": eng.pool.stats()["high_watermark"],
    }
    return {
        "kv_token_budget": CONTIG_SLOTS * MAX_LEN,
        "block_size": BLOCK_SIZE,
        "generated_tokens": gen_tokens,
        # the most a max_len-a-slot layout holds of this budget at once
        "worst_case_length_slots": CONTIG_SLOTS,
        "paged": paged,
    }


def run_shared_prefix_lane(model, cfg):
    from paddle_tpu.serving import metrics as sm

    rng = np.random.RandomState(11)
    sys_prompt = rng.randint(1, cfg.vocab_size, SYS_PROMPT_LEN).astype(np.int32)
    prompts = [np.concatenate(
        [sys_prompt, rng.randint(1, cfg.vocab_size, TAIL_LEN).astype(np.int32)])
        for _ in range(SHARED_TAILS)]
    eng = serving.ServingEngine(model, max_slots=4, max_len=MAX_LEN,
                                block_size=BLOCK_SIZE, prefill_chunk=32,
                                max_queue_depth=SHARED_TAILS)
    computed0 = sm.tokens_total.labels("prompt").value()
    cached0 = sm.tokens_total.labels("prompt_cached").value()
    # the first request populates the prefix cache...
    first = eng.submit(prompts[0], max_new_tokens=8)
    eng.run_until_idle()
    # ...every follower adopts the shared system-prompt blocks
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts[1:]]
    eng.run_until_idle(max_steps=100_000)
    computed = sm.tokens_total.labels("prompt").value() - computed0
    cached = sm.tokens_total.labels("prompt_cached").value() - cached0
    parity = check_parity(
        model, [first] + reqs,
        [(p, dict(max_new_tokens=8)) for p in prompts])
    total_prompt = sum(len(p) for p in prompts)
    followers = SHARED_TAILS - 1
    # shareable per follower: the system prompt's FULL blocks
    shareable = (SYS_PROMPT_LEN // BLOCK_SIZE) * BLOCK_SIZE * followers
    savings = cached / max(1e-9, shareable)
    chunk = recompile.entry_stats().get("serving.prefill_chunk",
                                        {"compiles": 0, "retraces": 0})
    return {
        "requests": SHARED_TAILS,
        "system_prompt_tokens": SYS_PROMPT_LEN,
        "tail_tokens": TAIL_LEN,
        "prompt_tokens_total": total_prompt,
        "prompt_tokens_computed": int(computed),
        "prompt_tokens_cached": int(cached),
        "shared_fraction": round(SYS_PROMPT_LEN
                                 / (SYS_PROMPT_LEN + TAIL_LEN), 3),
        "savings_vs_shareable": round(savings, 3),
        "prefix_cache": eng.stats()["prefix_cache"],
        "cow_forks": eng.pool.stats()["cow_forks"],
        "parity": parity,
        "prefill_chunk_retraces": chunk["retraces"],
    }


def _kernel_format_err(cfg, fmt):
    """Max-abs attention error of the quantized read path vs bf16-class
    float caches at the lane's geometry — the per-format numerics column
    (fast, kernel-level, no engine)."""
    import jax.numpy as jnp

    rng = np.random.RandomState(99)
    KV = cfg.num_key_value_heads
    d = cfg.hidden_size // cfg.num_attention_heads
    H = cfg.num_attention_heads
    q = jnp.asarray(rng.randn(4, 1, H, d), jnp.float32)
    kc = jnp.asarray(rng.randn(4, 128, KV, d), jnp.float32)
    vc = jnp.asarray(rng.randn(4, 128, KV, d), jnp.float32)
    pos = jnp.asarray([32, 64, 96, 127], jnp.int32)
    from paddle_tpu.generation import (dequantize_kv_buffer,
                                       kv_cache_write_quant,
                                       make_kv_caches)
    from paddle_tpu.nn import functional as F

    def _attend(k, v):
        # the XLA grouped fallback — format-independent oracle
        import paddle_tpu as pt

        kpos = np.arange(128)
        m = (kpos[None, None] <= np.asarray(pos)[:, None, None])
        mask = jnp.asarray(np.where(m[:, None], 0.0, -1e30), jnp.float32)
        return F.grouped_query_sdpa(
            pt.to_tensor(q), pt.to_tensor(k), pt.to_tensor(v),
            attn_mask=pt.to_tensor(mask)).numpy()

    ref = _attend(kc, vc)
    caches = make_kv_caches(cfg, 4, 128, jnp.float32, fmt)
    ck, cks = kv_cache_write_quant(caches[0]["k"], caches[0]["ks"], kc, 0,
                                   fmt)
    cv, cvs = kv_cache_write_quant(caches[0]["v"], caches[0]["vs"], vc, 0,
                                   fmt)
    kd = dequantize_kv_buffer(ck, cks, jnp.float32)._data
    vd = dequantize_kv_buffer(cv, cvs, jnp.float32)._data
    got = _attend(kd, vd)
    return float(np.abs(got - ref).max())


def run_format_lane():
    """bf16 vs int8 (vs fp8) at ONE fixed KV byte budget: the pool each
    format affords (host-side accounting — bytes per cached token incl.
    scale overhead, at the canonical bf16 compute dtype), the measured
    concurrency/throughput of a long-tail drain through that pool, and
    the per-format numerics error. Acceptance: int8 holds >= 1.8x the
    tokens (and therefore concurrent requests at a token-bound mix) of
    bf16 on the same bytes."""
    import jax.numpy as jnp

    from paddle_tpu.generation import kv_cache_bytes_per_token
    from paddle_tpu.quantization import intx

    paddle.seed(3)
    cfg = LlamaConfig.tiny(**FMT_MODEL_KW)
    model = LlamaForCausalLM(cfg)
    workload = make_requests(cfg, FMT_MIX, seed=23)
    gen_tokens = sum(params["max_new_tokens"] for _, params in workload)

    bpt_bf16 = kv_cache_bytes_per_token(cfg, "bf16", jnp.bfloat16)
    budget_bytes = FMT_BF16_BLOCKS * BLOCK_SIZE * bpt_bf16
    formats = ["bf16", "int8"] + (["fp8"] if intx.fp8_available() else [])
    lanes = {}
    for fmt in formats:
        bpt = (bpt_bf16 if fmt == "bf16"
               else kv_cache_bytes_per_token(cfg, fmt))
        blocks = int(budget_bytes // (bpt * BLOCK_SIZE))
        eng = serving.ServingEngine(
            model, max_slots=FMT_SLOTS, max_len=128,
            block_size=BLOCK_SIZE, num_blocks=blocks + 1,
            prefix_caching=False, kv_format=fmt,
            max_queue_depth=len(workload))
        drain(eng, workload)  # warmup: compile every executable
        base_steps, base_occ = eng._steps, eng._occupancy_integral
        reqs, wall = drain(eng, workload)
        steps = eng._steps - base_steps
        mean_active = (eng._occupancy_integral - base_occ) / max(1, steps)
        # parity spot-check on 4 requests vs generate at the SAME format
        parity = True
        for req, (p, params) in list(zip(reqs, workload))[:4]:
            ref = generation.generate(
                model, p[None], kv_format=fmt,
                **params).numpy()[0, len(p):]
            got = np.asarray(req.result(timeout=5.0))
            parity = parity and np.array_equal(got, ref)
        lanes[fmt] = {
            "bytes_per_token": bpt,
            "blocks_at_budget": blocks,
            "capacity_tokens_at_budget": blocks * BLOCK_SIZE,
            "completed": sum(r.status == "completed" for r in reqs),
            "mean_active_requests": round(mean_active, 2),
            "wall_s": round(wall, 3),
            "tok_s": round(gen_tokens / wall, 1),
            "preemptions": eng._preempt_count,
            "parity": parity,
            "max_abs_err_vs_bf16": (
                0.0 if fmt == "bf16" else
                round(_kernel_format_err(cfg, fmt), 5)),
        }
    for fmt in formats[1:]:
        lanes[fmt]["capacity_vs_bf16"] = round(
            lanes[fmt]["capacity_tokens_at_budget"]
            / lanes["bf16"]["capacity_tokens_at_budget"], 3)
        lanes[fmt]["mean_active_vs_bf16"] = round(
            lanes[fmt]["mean_active_requests"]
            / max(1e-9, lanes["bf16"]["mean_active_requests"]), 2)
        lanes[fmt]["tok_s_vs_bf16"] = round(
            lanes[fmt]["tok_s"] / max(1e-9, lanes["bf16"]["tok_s"]), 2)
    return {
        "model": {"family": "llama", **FMT_MODEL_KW},
        "head_dim": cfg.hidden_size // cfg.num_attention_heads,
        "kv_byte_budget": budget_bytes,
        "block_size": BLOCK_SIZE,
        "slots": FMT_SLOTS,
        "requests": len(workload),
        "formats": lanes,
    }


def main():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(**MODEL_KW)
    model = LlamaForCausalLM(cfg)

    capacity = run_capacity_lane(model, cfg)
    shared = run_shared_prefix_lane(model, cfg)
    formats = run_format_lane()

    verdicts = {
        "prefix_savings_proportional": shared["savings_vs_shareable"] >= 0.9,
        "parity": capacity["paged"]["parity"] and shared["parity"],
        "one_step_compile": (
            capacity["paged"]["step_compiles_measured"] == 0
            and capacity["paged"]["step_retraces_measured"] == 0),
        # the quantized-KV acceptance: int8 >= 1.8x tokens (and thus
        # token-bound concurrency) at a FIXED byte budget, with every
        # format's engine bit-matching generate at the same format
        "int8_capacity_ge_1_8x":
            formats["formats"]["int8"]["capacity_vs_bf16"] >= 1.8,
        "format_parity": all(l["parity"]
                             for l in formats["formats"].values()),
    }
    result = {
        "bench": "paged_kv",
        "platform": jax.default_backend(),
        "model": {"family": "llama", **MODEL_KW},
        "capacity_ab": capacity,
        "shared_prefix": shared,
        "kv_format_ab": formats,
        "verdicts": verdicts,
    }
    path = os.path.join(HERE, "bench_paged_kv.json")
    with open(path, "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result, indent=1))
    print(f"[bench_paged_kv] artifact -> {path}")
    ok = all(verdicts.values())
    if not ok:
        print("[bench_paged_kv] ACCEPTANCE FAILED", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
