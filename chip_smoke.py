#!/usr/bin/env python
"""One command that proves the system starts on the chip.

    python chip_smoke.py

drives the two normal entry points once each at published widths, with
seeded random bf16 weights, and checks what comes out:

- serve: ``GPTForCausalLM(GPTConfig.gpt3_1p3b())`` (all 24 layers)
  -> ``serving.ServingEngine`` in its default paged mode, every kernel
  at its default dispatch -> ``warmup()`` -> ``start()`` -> 8 requests
  (short, multi-chunk, shared-prefix, greedy and sampled) read through
  ``stream()``/``result()`` -> ``stop()``. Before it, on one chip, the
  case ``batched_prefill_chunk``: the ``[P, C]`` prefill program against
  its rows run one at a time, and the program alone timed at each width.
- train: ``LlamaForCausalLM(LlamaConfig.llama2_7b(...))`` at 7B widths
  with flash attention, depth cut to TRAIN_LAYERS -> ``ShardedTrainStep``
  with ``llama_pretrain_loss`` and AdamW, TRAIN_STEPS steps at seq 4096
  on one repeated batch.

On a host with several chips the same phases shard over all of them
(``ServingConfig(tp=n)``; a dp x mp mesh with ``llama_shard_fn``, XLA
attention at seq 2048: see ``phase_train``), every device must hold its
share, and a third phase runs ring attention, a ``dist.spmd``
(shard_map) surface, on the real devices.

Each phase runs in a child process of its own so that it starts with an
empty HBM and its ``peak_bytes_in_use`` is its own; this parent never
imports jax (a process that has touched jax holds the chip). The
children share the persistent compile cache
(``paddle_tpu/core/compile_cache.py``).

Stdout is two lines, each one JSON object. The first is the report:
versions, compile cache, and per phase its wall time, compiles, kernel
counters, requests or losses, and peak bytes. The last is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``
with exactly those keys and the device as jax reports it. Any failed
check, or no TPU, is a non-zero exit with neither line. Progress goes to
stderr. ``--rehearse-on-cpu`` walks the same code at toy sizes with the
kernels interpreted, for debugging this script where there is no chip;
it reports ``"platform": "cpu"`` and is never what a bare run does.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from importlib import metadata

SEED = 20260926
DEADLINE_S = 1150  # the contract allows 1200 s, compilation included

# Train depth: the only cut to llama2-7b. AdamW keeps fp32 m and v, so a
# bf16 parameter costs 10 bytes resident (+2 transient for its gradient).
# XLA's own memory analysis of this step compiled for v5e (argument +
# temp bytes, batch 1 x seq 4096): 3 layers 10.5 GiB, 4 layers 13.1 GiB,
# 5 layers 15.0 GiB, of the chip's 15.75 GiB. Four keeps a sixth of the
# chip free for whatever the step grows by next; it ran on the chip with
# peak_bytes_in_use 10.8 GB (PJRT's peak leaves out the program's
# temporaries).
TRAIN_LAYERS = 4
TRAIN_STEPS = 5

CHIP = dict(
    gpt={},  # GPTConfig.gpt3_1p3b() as published
    slots=16, max_len=2048,
    prompts=(5, 20, 200, 333, 900), prefix=96, new_tokens=(32, 48, 64),
    llama=dict(num_hidden_layers=TRAIN_LAYERS),
    seq=4096, tree=(4, 2, 2),
    # the EVA cell's decode step: rows, heads, head_dim, window, block
    eva=(20, 32, 128, 2048, 16),
    # batched_prefill_chunk: program widths timed, a row's context, runs
    chunk_rows=(1, 4, 8, 16), chunk_ctx=1024, chunk_reps=20,
)
REHEARSAL = dict(
    gpt=dict(hidden_size=512, num_hidden_layers=2, num_attention_heads=4,
             intermediate_size=1024, vocab_size=512,
             max_position_embeddings=256),
    slots=4, max_len=256,
    prompts=(5, 20, 70, 90, 120), prefix=32, new_tokens=(8, 12, 16),
    llama=dict(hidden_size=512, intermediate_size=1024, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=4, vocab_size=512,
               max_position_embeddings=256),
    seq=256, tree=(2, 2),
    eva=(3, 4, 32, 64, 4),
    chunk_rows=(1, 2, 4), chunk_ctx=128, chunk_reps=2,
)

# Kernel-vs-XLA tolerance on attention outputs, |out - ref| <= ATOL +
# RTOL * |ref|. The reference runs the XLA path on f32 copies of the
# same bf16 values at "highest" matmul precision, so the difference is
# the kernel's own rounding, and bf16 rounding is relative (2^-9, 0.2%):
# RTOL covers the output's cast to bf16 with room for the order of the
# float32 sums; ATOL covers the softmax weights' cast to bf16 before the PV
# matmul, an error that scales with max |v| (about 4 for these normal
# pools), not with the output. Both sides dequantize int8 identically.
KERNEL_ATOL = 1e-2
KERNEL_RTOL = 1e-2


def log(msg):
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def check(cond, what):
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {what}")
    log(f"ok: {what}")


# ---------------------------------------------------------------------------
# child side: everything below runs in a process that owns the chip
# ---------------------------------------------------------------------------


def _start(rehearse):
    """Refuse to run anywhere but on a TPU (before any model exists),
    then place the compile cache. Returns (what every phase reports
    about its process, size table)."""
    import jax

    dev = jax.devices()[0]
    want = "cpu" if rehearse else "tpu"
    if dev.platform != want:
        raise SystemExit(
            f"chip_smoke: jax found platform {dev.platform!r}, need {want!r}"
            + ("" if rehearse else " (no fallback; --rehearse-on-cpu is the "
               "explicit toy-size walk-through)"))
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.core.native import native_status

    common = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "compile_cache_dir": enable_compile_cache(),
        # the C++ runtime built from csrc/, or the Python fallbacks
        "native_runtime": native_status(),
    }
    return common, (REHEARSAL if rehearse else CHIP)


def _memory():
    """Per-device peak bytes from PJRT. A real chip reports them; the
    observability stack's "unsupported" marker must not appear there."""
    import jax

    from paddle_tpu.observability.perf import MEMORY_STATS_UNSUPPORTED

    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        out.append(stats["peak_bytes_in_use"] if stats
                   else MEMORY_STATS_UNSUPPORTED)
    if jax.devices()[0].platform == "tpu":
        check(MEMORY_STATS_UNSUPPORTED not in out,
              f"PJRT reports peak_bytes_in_use on every device ({out})")
    return out


def _resident_bytes(arrays, peaks, rehearse):
    """Bytes of ``arrays`` (weights, KV pools) resident on each device,
    from their addressable shards. Sharded state must not sit on device
    0 alone: every device holds the same share, and its PJRT peak is at
    least that share. (A peak far above it is reported, not failed:
    models are built whole on device 0 before they are sharded.)"""
    import jax

    per_dev = {d.id: 0 for d in jax.devices()}
    for a in arrays:
        for shard in a.addressable_shards:
            per_dev[shard.device.id] += shard.data.nbytes
    shares = list(per_dev.values())
    check(min(shares) > 0 and max(shares) - min(shares) <= 0.01 * max(shares),
          f"every device holds an equal share of the sharded state "
          f"({shares} bytes)")
    check(rehearse or all(p >= s for p, s in zip(peaks, shares)),
          f"every device's peak covers its share (peaks {peaks})")
    return shares


def _counter(snapshot, name):
    fam = snapshot["metrics"].get(name)
    return {"/".join(s["labels"].values()): int(s["value"])
            for s in (fam["samples"] if fam else []) if s["value"]}


def _compile_summary():
    from paddle_tpu import observability

    snap = observability.snapshot()
    events = _counter(snap, "paddle_tpu_jax_monitoring_events_total")
    requests = events.get("/jax/compilation_cache/compile_requests_use_cache", 0)
    hits = events.get("/jax/compilation_cache/cache_hits", 0)
    stats = observability.recompile.entry_stats().values()
    return {
        # executables built, whether XLA compiled them or the persistent
        # cache supplied them; backend_compiles is the first kind only
        "executables": sum(s["compiles"] for s in stats),
        "seconds": round(sum(s["compile_seconds"] for s in stats), 2),
        "cache_requests": requests, "cache_hits": hits,
        "backend_compiles": requests - hits,
        "retraces": sum(_counter(snap, "paddle_tpu_retraces_total").values()),
    }


def _kernel_parity(sizes, heads, head_dim, num_blocks, block_size, chunk):
    """paged_flash_decode_attention against the XLA path
    (generation.gather_paged_kv + SDPA) on seeded pools at the engine's
    own shapes: the decode step, one prefill chunk, an int8 pool, an
    ancestor-masked tree bundle, and the decode step and chunk again at
    GQA 32/8. The seeded lengths are ragged across the kernel's cell
    boundary."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu.nn.functional as F
    from paddle_tpu import generation
    from paddle_tpu.pallas_kernels.decode_attention import (
        _blocks_per_cell, paged_flash_decode_attention, spec_tree_width)
    from paddle_tpu.quantization import intx

    slots, max_len = sizes["slots"], sizes["max_len"]
    nb = max_len // block_size
    rng = np.random.RandomState(SEED)
    gqa_heads, gqa_kv = 32, 8
    kp, vp, kp_gqa, vp_gqa = (
        jnp.asarray(rng.randn(num_blocks, block_size, kv, head_dim),
                    jnp.bfloat16) for kv in (heads, heads, gqa_kv, gqa_kv))
    # every slot owns nb distinct physical blocks, in shuffled order;
    # block 0 is the engine's dump block
    bt = jnp.asarray(1 + rng.permutation(slots * nb).reshape(slots, nb),
                     jnp.int32)

    def reference(q, k_full, v_full, visible):
        group = q.shape[2] // k_full.shape[2]
        with jax.default_matmul_precision("highest"):
            out = F.scaled_dot_product_attention(
                q.astype(jnp.float32),
                jnp.repeat(k_full.astype(jnp.float32), group, axis=2),
                jnp.repeat(v_full.astype(jnp.float32), group, axis=2),
                attn_mask=visible[:, None])
        return np.asarray(out._data)

    worst = {}
    tree_w = spec_tree_width(sizes["tree"])
    cases = {"decode": (slots, 1), "prefill_chunk": (1, chunk),
             "int8_decode": (slots, 1), "tree_bundle": (slots, tree_w),
             "gqa_decode": (slots, 1), "gqa_prefill_chunk": (1, chunk)}
    for name, (b, q_len) in cases.items():
        gqa = name.startswith("gqa_")
        q_heads = gqa_heads if gqa else heads
        q = jnp.asarray(rng.randn(b, q_len, q_heads, head_dim), jnp.bfloat16)
        # a bundle that straddles the kernel's cell boundary, lengths
        # one short of a cell and one cell exactly, the edges (empty
        # cache, full cache, block boundaries) and random interiors
        cell = block_size * _blocks_per_cell(
            block_size, nb, gqa_kv if gqa else heads, head_dim, jnp.bfloat16,
            q_len * (gqa_heads // gqa_kv if gqa else 1))
        edges = [cell - q_len + 1, 0, max_len - q_len, block_size - 1,
                 block_size, cell - q_len - 1, cell - q_len]
        pos = np.clip((edges + list(rng.randint(0, max_len - q_len, slots))
                       )[:b], 0, max_len - q_len).astype(np.int32)
        t = np.arange(max_len)[None, None, :]
        qpos = pos[:, None, None] + np.arange(q_len)[None, :, None]
        visible = t <= qpos
        pools, kwargs = ((kp_gqa, vp_gqa) if gqa else (kp, vp)), {}
        k_full, v_full = (generation.gather_paged_kv(p, bt[:b])._data
                          for p in pools)
        if name == "int8_decode":
            scales = [intx.absmax_along(p, axis=-1).astype(jnp.float32)
                      for p in pools]
            pools = [intx.pack_absmax(p, s[..., None], "int8")
                     for p, s in zip(pools, scales)]
            kwargs = dict(k_scale=scales[0], v_scale=scales[1])
            k_full, v_full = (
                generation.gather_paged_kv_dequant(p, s, bt[:b])._data
                for p, s in zip(pools, scales))
        if name == "tree_bundle":
            # a random forest over the bundle: node i sees itself and
            # some earlier nodes, never a later one
            anc = np.tril(rng.rand(b, q_len, q_len) < 0.5)
            anc |= np.eye(q_len, dtype=bool)[None]
            kwargs = dict(ancestor_mask=jnp.asarray(anc))
            in_bundle = (t >= pos[:, None, None]) \
                & (t < pos[:, None, None] + q_len)
            idx = np.clip(t - pos[:, None, None], 0, q_len - 1)
            visible = (t < pos[:, None, None]) | (
                in_bundle & np.take_along_axis(
                    anc, np.broadcast_to(idx, (b, q_len, max_len)), axis=2))
        out = paged_flash_decode_attention(
            q, *pools, bt[:b], jnp.asarray(pos), **kwargs)
        out = np.asarray(out, np.float32)
        ref = reference(q, k_full, v_full, jnp.asarray(visible))
        check(np.isfinite(out).all(), f"kernel {name}: finite")
        diff = np.abs(out - ref)
        ratio = float((diff / (KERNEL_ATOL + KERNEL_RTOL * np.abs(ref))).max())
        worst[name] = round(float(diff.max()), 5)
        check(ratio <= 1,
              f"kernel {name} agrees with the XLA path (max |diff| "
              f"{worst[name]}, {ratio:.2f} of the tolerance)")
    return worst


def _eva_kernel_parity(sizes):
    """The same kernel on the rows of an EVA model (chunked linearized
    attention, models/evabyte.py): 32 heads of 128, 20 rows two windows
    behind, so a row's table is [the summary blocks of two windows | the
    window's blocks] and its length the summaries plus its place in the
    window (``generation.eva_virtual_position``). Against the XLA path
    over the same table."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu.nn.functional as F
    from paddle_tpu import generation
    from paddle_tpu.pallas_kernels.decode_attention import \
        paged_flash_decode_attention

    rows, heads, d, window, bs = sizes["eva"]
    chunk = 16
    nb = (3 * window // chunk + window) // bs      # three windows' summaries
    rng = np.random.RandomState(SEED + 1)
    kp, vp = (jnp.asarray(rng.randn(1 + rows * nb, bs, heads, d), jnp.bfloat16)
              for _ in range(2))
    bt = jnp.asarray(1 + rng.permutation(rows * nb).reshape(rows, nb),
                     jnp.int32)
    q = jnp.asarray(rng.randn(rows, 1, heads, d), jnp.bfloat16)
    # the window's first and last positions, then anywhere in it
    pos = 2 * window + np.asarray(
        ([0, window - 1] + list(rng.randint(0, window, rows)))[:rows])
    vpos = generation.eva_virtual_position(pos, window, chunk)
    check(int(vpos.min()) == 2 * window // chunk
          and int(vpos.max()) < nb * bs - window // chunk,
          "EVA rows read two windows of summaries and their own window")
    out = np.asarray(paged_flash_decode_attention(
        q, kp, vp, bt, jnp.asarray(vpos, jnp.int32)), np.float32)
    visible = jnp.asarray(np.arange(nb * bs)[None, None, :]
                          <= vpos[:, None, None])
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(F.scaled_dot_product_attention(
            q.astype(jnp.float32),
            *(generation.gather_paged_kv(p, bt)._data.astype(jnp.float32)
              for p in (kp, vp)), attn_mask=visible[:, None])._data)
    diff = np.abs(out - ref)
    ratio = float((diff / (KERNEL_ATOL + KERNEL_RTOL * np.abs(ref))).max())
    check(np.isfinite(out).all() and ratio <= 1,
          f"kernel eva_decode agrees with the XLA path (max |diff| "
          f"{diff.max():.5f}, {ratio:.2f} of the tolerance)")
    return round(float(diff.max()), 5)


# The rows of the case ``batched_prefill_chunk``, iteration by
# iteration: (pos0, valid) for a live row, None for a row that carries
# nothing. Mid-prompt chunks at different offsets, padded last chunks
# (valid 7, 20 and 1), a row that sits an iteration out, rows that start
# late and a row that never carries anything; the first P columns are
# used.
CHUNK_SCHEDULE = (
    ((0, 32), (0, 32), (0, 32), (0, 32), (0, 32), (0, 32), None, None),
    ((32, 32), (32, 32), (32, 32), (32, 32), (32, 7), None, (0, 32), None),
    ((64, 32), (64, 1), None, (64, 20), None, (32, 32), (32, 32), None),
)


def _batched_prefill_chunk(model, cfg, scfg, sizes):
    """The ``[P, C]`` prefill program against the same rows run one at
    a time through the ``[1, C]`` program, on the model the engine will
    serve: the largest gap between the logits either way selects from,
    the pools they leave behind compared block for block, and the
    program alone timed at each ``chunk_rows`` with one row live and
    with every row live. The program is the engine's ``_chunk`` less its
    select: ``run`` over ``bt``, ``valid`` and ``head_idx``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import generation
    from paddle_tpu.serving.engine import prefill_batch_rows

    C, bs = scfg.prefill_chunk, scfg.block_size
    dtype = next(iter(model.parameters()))._data.dtype
    P = prefill_batch_rows(C, dtype, sizes["slots"])
    widths = sizes["chunk_rows"]
    nb = sizes["chunk_ctx"] // bs               # table width of a row
    n_blocks = 1 + max(widths) * nb
    run = generation.make_cached_runner(model)
    pb = {**{k: v._data for k, v in model.named_parameters()},
          **{k: v._data for k, v in model.named_buffers()}}
    rng = np.random.RandomState(SEED + 28)

    def fwd(pb, pools, bt, ids, pos0, valid, last_idx):
        caches = [dict(c, bt=bt, valid=valid) for c in pools]
        caches[0]["head_idx"] = last_idx
        logits, newc = run(pb, ids, caches, pos0)
        return logits[:, 0].astype(jnp.float32), [
            {"k": c["k"], "v": c["v"]} for c in newc]

    fwd = jax.jit(fwd, donate_argnums=(1,))

    def pools():
        return generation.make_paged_kv_pools(cfg, n_blocks, bs, dtype)

    def args(rows, width):
        """Host arguments of one ``[width, C]`` program: ``rows`` maps a
        row of the program to (table row, tokens, pos0, valid)."""
        bt = np.zeros((width, nb), np.int32)
        ids = np.zeros((width, C), np.int32)
        pos0, valid, last = (np.zeros(width, np.int32) for _ in range(3))
        for r, (owner, toks, p0, n) in rows.items():
            bt[r] = 1 + owner * nb + np.arange(nb)
            ids[r, :n] = toks[:n]
            pos0[r], valid[r], last[r] = p0, n, n - 1
        return bt, ids, pos0, valid, last

    # the same rows, batched and one at a time
    batched, single, gap = pools(), pools(), 0.0
    for it in CHUNK_SCHEDULE:
        live = {r: (r, rng.randint(1, cfg.vocab_size, C), *cell)
                for r, cell in enumerate(it[:P]) if cell is not None}
        lg, batched = fwd(pb, batched, *args(live, P))
        lg = np.asarray(lg)
        for r, row in live.items():
            one, single = fwd(pb, single, *args({0: row}, 1))
            gap = max(gap, float(np.abs(np.asarray(one)[0] - lg[r]).max()))
    # block 0 is the dump block: every padded tail and dead row wrote it
    diffs = [jnp.abs(a[n][1:].astype(jnp.float32)
                     - b[n][1:].astype(jnp.float32)).max()
             for a, b in zip(batched, single) for n in ("k", "v")]
    pool_gap = float(jnp.max(jnp.stack(diffs)))
    written = float(jnp.abs(batched[0]["k"][1:].astype(jnp.float32)).max())
    del batched, single

    # the program alone at each width
    timings = {}
    reps = sizes["chunk_reps"]
    toks = rng.randint(1, cfg.vocab_size, C)
    for width in widths:
        cur = pools()
        span = (nb * bs - C) // C // max(width - 1, 1) * C
        cases = {"one_live": {0: (0, toks, nb * bs - C, C)},
                 "all_live": {r: (r, toks, r * span, C)
                              for r in range(width)}}
        for name, rows in cases.items():
            a = args(rows, width)
            for _ in range(3):
                lg, cur = fwd(pb, cur, *a)
            jax.block_until_ready(lg)
            t0 = time.perf_counter()
            for _ in range(reps):
                lg, cur = fwd(pb, cur, *a)
            jax.block_until_ready(lg)
            timings[f"{width}x{C}.{name}_ms"] = round(
                (time.perf_counter() - t0) / reps * 1e3, 3)
        del cur
    log(f"batched_prefill_chunk: P={P}, logit gap {gap:.5f}, pool gap "
        f"{pool_gap:.5f} (largest entry {written:.2f}); program alone, ms: "
        f"{timings}")
    check(written > 0 and gap <= 0.1 and pool_gap <= 0.1,
          f"[{P}, {C}] prefill program agrees with its rows run one at a "
          f"time (logits {gap:.5f}, pools {pool_gap:.5f})")
    return {"rows": P, "chunk": C, "logit_max_abs_diff": gap,
            "pool_max_abs_diff": pool_gap, "program_alone": timings}


def phase_serve(rehearse):
    t_phase = time.perf_counter()
    common, sizes = _start(rehearse)
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import observability, serving
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    n_dev = common["device"]["count"]
    cfg = GPTConfig.gpt3_1p3b(**sizes["gpt"])
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    scfg = serving.ServingConfig(max_slots=sizes["slots"],
                                 max_len=sizes["max_len"], tp=n_dev)
    parity = _kernel_parity(sizes, cfg.num_attention_heads, head_dim,
                            scfg.default_num_blocks(), scfg.block_size,
                            scfg.prefill_chunk)
    parity["eva_decode"] = _eva_kernel_parity(sizes)

    paddle.seed(SEED)
    model = GPTForCausalLM(cfg)
    model.to(dtype="bfloat16")
    # before the engine takes its pool: the case brings two of its own
    batched_chunk = _batched_prefill_chunk(model, cfg, scfg, sizes) \
        if n_dev == 1 else None
    engine = serving.ServingEngine(model, scfg)
    t0 = time.perf_counter()
    warm = engine.warmup()
    warmup_s = time.perf_counter() - t0
    log(f"warmup: {warm['compiles']} executables in {warmup_s:.1f}s")
    after_warmup = _compile_summary()

    rng = np.random.RandomState(SEED)
    vocab = cfg.vocab_size

    def prompt(n):
        return rng.randint(1, vocab, n).astype(np.int32)

    n_short, n_mid, n_long = sizes["new_tokens"]
    p_a, p_b, p_c, p_d, p_e = sizes["prompts"]
    shared = prompt(sizes["prefix"])
    engine.start()
    t0 = time.perf_counter()
    reqs = [
        engine.submit(prompt(p_a), max_new_tokens=n_short),
        engine.submit(prompt(p_b), max_new_tokens=n_mid, do_sample=True,
                      temperature=0.8, top_k=40, seed=1),
        engine.submit(prompt(p_c), max_new_tokens=n_long),
        engine.submit(prompt(p_d), max_new_tokens=n_short, do_sample=True,
                      top_p=0.9, seed=2),
        engine.submit(prompt(p_e), max_new_tokens=n_long),
        engine.submit(np.concatenate([shared, prompt(17)]),
                      max_new_tokens=n_short),
    ]
    # the first prefix-sharer streams to its end before the second is
    # submitted, so the second finds the shared blocks in the cache
    streamed = list(reqs[5].stream(timeout=300))
    reqs.append(engine.submit(np.concatenate([shared, prompt(40)]),
                              max_new_tokens=n_short))
    reqs.append(engine.submit(prompt(p_b), max_new_tokens=n_mid,
                              do_sample=True, temperature=1.2, seed=3))
    outputs = [r.result(timeout=300) for r in reqs]
    traffic_s = time.perf_counter() - t0
    stats = engine.stats()
    engine.stop()

    check(streamed == outputs[5], "stream() and result() agree")
    for r, out in zip(reqs, outputs):
        check(r.status == "completed"
              and len(out) == r.params.max_new_tokens
              and all(0 <= t < vocab for t in out),
              f"request {r.id}: completed with {len(out)} of "
              f"{r.params.max_new_tokens} tokens, all in the vocabulary")
    snap = observability.snapshot()
    hits = _counter(snap, "paddle_tpu_flash_decode_hits_total")
    fallbacks = _counter(snap, "paddle_tpu_flash_decode_fallbacks_total")
    if n_dev == 1:
        check(hits.get("gpt_paged", 0) > 0 and not fallbacks,
              f"paged flash-decode kernel dispatched (hits {hits}) with no "
              f"fallback ({fallbacks})")
    else:
        # pallas_call cannot be partitioned by GSPMD: under tp>1 the
        # dispatcher declines, and says so
        check(set(fallbacks) == {"paged_tp_sharded"},
              f"tp={n_dev}: every decline is counted as tp_sharded "
              f"({fallbacks})")
    after_traffic = _compile_summary()
    check(after_traffic["executables"] == after_warmup["executables"]
          and after_traffic["retraces"] == 0,
          f"no compile and no retrace after warmup ({after_traffic})")
    row = stats["perf"]["ledger"].get("serving.step", {})
    check(row.get("flops") or row.get("bytes_accessed"),
          f"perf ledger has a cost row for serving.step ({row.get('flops')} "
          f"flops, {row.get('bytes_accessed')} bytes)")
    memory = _memory()
    shares = None
    if n_dev > 1:
        arrays = list(engine._pb.values()) + [
            a for pool in engine._pools for a in pool.values()]
        shares = _resident_bytes(arrays, memory, rehearse)
    return {
        **common,
        "wall_s": round(time.perf_counter() - t_phase, 1),
        "warmup_s": round(warmup_s, 1), "traffic_s": round(traffic_s, 2),
        "compile": after_traffic, "kernel_max_abs_diff": parity,
        "batched_prefill_chunk": batched_chunk,
        "flash_decode_hits": hits, "flash_decode_fallbacks": fallbacks,
        "requests": len(reqs), "tokens": sum(map(len, outputs)),
        "prefix_cache": stats["prefix_cache"],
        # XLA's static cost model for one decode step (the ledger's
        # rates divide by host wall time and are not device metrics)
        "ledger_serving_step": {k: row.get(k) for k in (
            "flops", "bytes_accessed", "temp_bytes")},
        "peak_bytes_in_use": memory, "shard_bytes": shares,
    }


def phase_train(rehearse):
    t_phase = time.perf_counter()
    common, sizes = _start(rehearse)
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.engine import ShardedTrainStep
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   llama_pretrain_loss, llama_shard_fn)

    n_dev = common["device"]["count"]
    mp = 2 if n_dev % 2 == 0 else 1
    dp = n_dev // mp
    # GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    # shard_map"): a multi-device jit refuses to lower the flash kernel
    # (seen on four v5e chips, ROADMAP S6c). Until the model wraps it,
    # several chips train with XLA attention, whose s^2 scores fit at
    # half the sequence.
    flash = n_dev == 1
    seq = sizes["seq"] if flash else sizes["seq"] // 2
    cfg = LlamaConfig.llama2_7b(use_flash_attention=flash, dtype="bfloat16",
                                **sizes["llama"])
    paddle.seed(SEED)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    # RoPE tables stay fp32: position phases lose too much in bf16
    for table in (model.llama.rope_cos, model.llama.rope_sin):
        table._data = table._data.astype(np.float32)
    mesh = dist.ProcessMesh(np.arange(n_dev).reshape(dp, mp), ["dp", "mp"])
    if mp > 1:
        dist.shard_layer(model, mesh, llama_shard_fn(mesh, mp_axis="mp"))
    # 3e-4 is the published Llama 2 7B peak learning rate
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())
    step = ShardedTrainStep(model, llama_pretrain_loss, opt, mesh,
                            dp_axis="dp" if dp > 1 else None,
                            shard_optimizer_states=dp > 1)
    rng = np.random.RandomState(SEED)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (dp, seq)).astype(np.int32))

    losses, step_s = [], []
    for i in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        losses.append(float(step.step(ids, ids)))  # float(): device sync
        step_s.append(round(time.perf_counter() - t0, 3))
        log(f"step {i}: loss {losses[-1]:.4f} in {step_s[-1]}s")
    check(all(np.isfinite(losses)), f"every loss is finite ({losses})")
    check(losses[-1] < losses[0],
          f"loss fell on the repeated batch ({losses[0]:.4f} -> "
          f"{losses[-1]:.4f})")
    n_kernels = step.lowered_text(ids, ids).count("tpu_custom_call")
    if flash and not rehearse:  # on CPU the kernels are interpreted
        check(n_kernels >= 3 * cfg.num_hidden_layers,
              f"the step holds the Pallas flash kernels ({n_kernels} "
              f"tpu_custom_call: forward, dK/dV and dQ per layer)")
    memory = _memory()
    shares = None
    if n_dev > 1:
        shares = _resident_bytes(list(step.params.values()), memory, rehearse)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    return {
        **common,
        "wall_s": round(time.perf_counter() - t_phase, 1),
        "layers": cfg.num_hidden_layers, "params_m": round(n_params / 1e6),
        "mesh": {"dp": dp, "mp": mp}, "batch": dp, "seq": seq,
        "flash_attention": flash,
        "steps": TRAIN_STEPS, "losses": [round(x, 4) for x in losses],
        "step_s": step_s, "compile": _compile_summary(),
        "tpu_custom_calls": n_kernels,
        "peak_bytes_in_use": memory, "shard_bytes": shares,
    }


def phase_spmd(rehearse):
    """Ring attention under ``dist.spmd`` (a shard_map program with a
    collective-permute KV rotation) on every local device, against
    plain causal attention."""
    t_phase = time.perf_counter()
    common, sizes = _start(rehearse)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed.sequence_parallel import ring_attention

    n_dev = common["device"]["count"]
    rng = np.random.RandomState(SEED)
    b, s, h, d = 1, 1024 * n_dev, 8, 128
    q, k, v = (paddle.to_tensor(jnp.asarray(rng.randn(b, s, h, d),
                                            jnp.bfloat16)) for _ in range(3))
    group = dist.new_group(axis_name="sp")
    out = dist.spmd(
        lambda q, k, v: ring_attention(q, k, v, group=group, causal=True),
        {"sp": n_dev}, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"))(q, k, v)
    out = np.asarray(out._data, np.float32)
    with jax.default_matmul_precision("highest"):
        ref = F.scaled_dot_product_attention(
            *(t._data.astype(jnp.float32) for t in (q, k, v)), is_causal=True)
    diff = float(np.abs(out - np.asarray(ref._data)).max())
    check(np.isfinite(out).all()
          and diff <= KERNEL_ATOL + KERNEL_RTOL * np.abs(out).max(),
          f"ring attention over {n_dev} devices agrees with causal SDPA "
          f"(max |diff| {diff:.5f})")
    return {**common, "wall_s": round(time.perf_counter() - t_phase, 1),
            "seq": s, "max_abs_diff": round(diff, 5)}


PHASES = {"serve": phase_serve, "train": phase_train, "spmd": phase_spmd}


# ---------------------------------------------------------------------------
# parent side: no jax in this process
# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="toy sizes, kernels interpreted, platform cpu")
    ap.add_argument("--phase", choices=PHASES, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:  # child: run one phase in this process
        print(json.dumps(PHASES[args.phase](args.rehearse_on_cpu)))
        return 0

    t_start = time.monotonic()
    env = dict(os.environ)
    flags = []
    if args.rehearse_on_cpu:
        flags = ["--rehearse-on-cpu"]
        # the CPU backend only dispatches the decode kernel when asked
        env.update(JAX_PLATFORMS="cpu", PADDLE_TPU_FLASH_DECODE="1")
    results, failed = {}, []
    todo = ["serve", "train"]
    while todo:
        phase = todo.pop(0)
        log(f"--- phase {phase} ---")
        left = DEADLINE_S - (time.monotonic() - t_start)
        # run() kills the child when the time is up, so nothing this
        # script started outlives it
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--phase", phase]
            + flags, env=env, stdout=subprocess.PIPE, text=True,
            timeout=max(left, 1))
        if proc.returncode != 0:
            # the other phases still run: their checks are worth having
            log(f"phase {phase} exited with code {proc.returncode}")
            failed.append(phase)
            continue
        results[phase] = json.loads(proc.stdout.strip().splitlines()[-1])
        if phase == "serve" and results[phase]["device"]["count"] > 1:
            todo.append("spmd")
    if failed:
        log(f"FAILED phases: {failed}")
        return 1

    devices = [r.pop("device") for r in results.values()]
    caches = {r.pop("compile_cache_dir") for r in results.values()}
    check(all(d == devices[0] for d in devices) and len(caches) == 1,
          "every phase saw the same devices and the same compile cache")
    print(json.dumps({
        "rehearsal": args.rehearse_on_cpu,
        "versions": {p: metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu")},
        "compile_cache_dir": caches.pop(),
        "wall_s": round(time.monotonic() - t_start, 1), **results,
    }))
    # the result line: these keys and no others
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
