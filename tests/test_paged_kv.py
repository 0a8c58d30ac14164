"""Paged KV cache: block allocator, prefix sharing (COW), chunked
prefill, and the paged serving engine.

Oracles:
- ALLOCATOR INVARIANTS: alloc/free/refcount bookkeeping is exact;
  exhaustion and double-free are loud, typed errors; fragmentation and
  sharing are accounted.
- OUTPUT PARITY: every request decoded through the PAGED engine —
  including multi-chunk prompts, prefix-shared prompts, COW forks, and
  preemption-by-recompute — produces exactly the tokens
  ``generation.generate`` produces for the same prompt + seed/params.
- ONE EXECUTABLE: the paged decode step compiles exactly once across
  ≥3 mixed-length request waves (block tables are traced data, never
  shape), and the single chunk-prefill executable replaces every
  per-bucket prefill program.
- PAGED KERNEL: the block-table Pallas kernel (interpret mode on CPU)
  is bit-identical to the contiguous flash-decode kernel over the same
  logical cache.
"""

import json
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import generation, serving
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.observability import recompile
from paddle_tpu.serving.block_pool import (BlockPool, BlockPoolError,
                                           PoolExhaustedError, PrefixCache)

SEED = 1234


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(max_position_embeddings=256)
    return LlamaForCausalLM(cfg), cfg


def _prompt(rng, cfg, n):
    return rng.randint(1, cfg.vocab_size, n).astype("int32")


def _ref(model, prompt, **params):
    return generation.generate(
        model, prompt[None], **params).numpy()[0, len(prompt):]


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------


class TestBlockPool:
    def test_alloc_free_refcount(self):
        pool = BlockPool(num_blocks=5, block_size=4)
        assert pool.usable_blocks == 4 and pool.free_blocks == 4
        a = pool.alloc(2)
        assert len(a) == 2 and 0 not in a  # dump block never allocated
        assert pool.used_blocks == 2
        pool.incref(a[0])
        assert pool.ref(a[0]) == 2
        assert not pool.decref(a[0])      # still referenced
        assert pool.decref(a[0])          # now freed
        assert pool.decref(a[1])
        assert pool.free_blocks == 4 and pool.used_blocks == 0

    def test_exhaustion_is_all_or_nothing(self):
        pool = BlockPool(num_blocks=4, block_size=4)
        pool.alloc(2)
        with pytest.raises(PoolExhaustedError, match="exhausted"):
            pool.alloc(2)  # only 1 free
        assert pool.free_blocks == 1  # the failed alloc took nothing

    def test_double_free_and_bad_ids_raise(self):
        pool = BlockPool(num_blocks=4, block_size=4)
        (b,) = pool.alloc(1)
        pool.decref(b)
        with pytest.raises(BlockPoolError, match="double free|not allocated"):
            pool.decref(b)
        with pytest.raises(BlockPoolError, match="dump block"):
            pool.decref(0)  # the reserved dump block is untouchable
        with pytest.raises(BlockPoolError, match="bad block id"):
            pool.incref(99)

    def test_fragmentation_and_sharing_accounting(self):
        pool = BlockPool(num_blocks=6, block_size=8)
        a = pool.alloc(3)
        pool.incref(a[1])
        st = pool.stats()
        assert st["in_use"] == 3 and st["free"] == 2
        assert st["shared"] == 1
        assert st["high_watermark"] == 3
        assert st["utilization"] == pytest.approx(3 / 5)
        pool.decref(a[2])
        assert pool.stats()["high_watermark"] == 3  # watermark sticks
        assert pool.stats()["alloc_total"] == 3
        assert pool.stats()["free_total"] == 1


class TestPrefixCache:
    def test_match_full_and_partial_prefixes(self):
        pool = BlockPool(num_blocks=10, block_size=4)
        cache = PrefixCache(pool)
        toks = np.arange(100, 110, dtype=np.int32)  # 10 tokens
        blocks = pool.alloc(3)                      # covers 4+4+2
        cache.insert(toks, 10, blocks)
        assert len(cache) == 3
        # identical prompt: full + full + partial tail (capped at L-1=9
        # -> the 10-token tail entry is not reusable, stop at 8)
        covered, got = cache.match(toks, limit=9)
        assert covered == 8 and got == blocks[:2]
        for b in got:
            pool.decref(b)
        # longer prompt sharing the first 10 tokens reuses the partial
        longer = np.concatenate([toks, np.arange(5, dtype=np.int32)])
        covered, got = cache.match(longer, limit=14)
        assert covered == 10 and got == blocks
        # divergent tokens: no match beyond the diverging block
        div = toks.copy()
        div[5] = 7
        covered, got = cache.match(div, limit=9)
        assert covered == 4 and got == blocks[:1]

    def test_insert_is_first_writer_wins(self):
        pool = BlockPool(num_blocks=10, block_size=4)
        cache = PrefixCache(pool)
        toks = np.arange(8, dtype=np.int32)
        b1 = pool.alloc(2)
        assert cache.insert(toks, 8, b1) == 2
        b2 = pool.alloc(2)
        assert cache.insert(toks, 8, b2) == 0  # duplicates rejected
        assert pool.ref(b1[0]) == 2 and pool.ref(b2[0]) == 1

    def test_lru_eviction_skips_referenced_blocks(self):
        pool = BlockPool(num_blocks=8, block_size=4)
        cache = PrefixCache(pool)
        t1 = np.arange(4, dtype=np.int32)
        t2 = np.arange(50, 54, dtype=np.int32)
        (b1,) = pool.alloc(1)
        (b2,) = pool.alloc(1)
        cache.insert(t1, 4, [b1])
        cache.insert(t2, 4, [b2])
        pool.decref(b1)
        pool.decref(b2)      # both now cache-only
        pool.incref(b1)      # ...but a request re-adopts b1
        assert cache.evict(2) == 1  # only b2 is reclaimable
        assert pool.ref(b1) == 2 and len(cache) == 1


# ---------------------------------------------------------------------------
# config validation (satellite: same actionable error shape as max_len)
# ---------------------------------------------------------------------------


class TestConfigValidation:
    def test_block_size_must_divide_max_len(self):
        with pytest.raises(ValueError, match="block_size .* must divide "
                                             "max_len"):
            serving.ServingConfig(max_len=100, block_size=16)

    @pytest.mark.parametrize("kv_mode", ["virtual", "contiguous"])
    def test_bad_kv_mode_and_num_blocks(self, kv_mode):
        # "paged" is the one layout; the field stays while configuration
        # files name it, and any other value is refused with a sentence
        with pytest.raises(ValueError, match="kv_mode must be 'paged'"):
            serving.ServingConfig(kv_mode=kv_mode)
        with pytest.raises(ValueError, match="num_blocks"):
            serving.ServingConfig(num_blocks=1)
        with pytest.raises(ValueError, match="prefill_chunk"):
            serving.ServingConfig(prefill_chunk=0)

    def test_max_len_vs_model_still_validates(self, tiny_model):
        model, _ = tiny_model
        with pytest.raises(ValueError, match="max_position_embeddings"):
            serving.ServingEngine(model, max_slots=1, max_len=512)

    def test_request_too_big_for_pool_is_a_clear_error(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=128,
                                    num_blocks=4)  # 3 usable blocks
        with pytest.raises(ValueError, match="KV blocks"):
            eng.submit(np.arange(1, 60, dtype="int32"), max_new_tokens=30)


# ---------------------------------------------------------------------------
# end-to-end parity (the tentpole acceptance)
# ---------------------------------------------------------------------------


class TestPagedParity:
    def test_mixed_sampling_and_multichunk_prompts_match_generate(
            self, tiny_model):
        """Greedy + top-k + top-p requests, prompts spanning one to
        several prefill chunks, all bit-identical to generate()."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=3, max_len=128,
                                    prefill_chunk=32)
        rng = np.random.RandomState(SEED)
        specs = [
            dict(max_new_tokens=6),
            dict(max_new_tokens=8, do_sample=True, temperature=0.8,
                 top_k=8, seed=5),
            dict(max_new_tokens=5, do_sample=True, top_p=0.9, seed=9),
            dict(max_new_tokens=7),  # 3-chunk prompt below
            dict(max_new_tokens=10, do_sample=True, temperature=1.2,
                 top_k=12, top_p=0.95, seed=3),
        ]
        prompts = [_prompt(rng, cfg, n) for n in (5, 33, 17, 70, 100)]
        reqs = [eng.submit(p, **s) for p, s in zip(prompts, specs)]
        eng.run_until_idle()
        for req, p, s in zip(reqs, prompts, specs):
            assert req.status == serving.RequestStatus.COMPLETED
            got = np.asarray(req.result(timeout=1.0))
            np.testing.assert_array_equal(got, _ref(model, p, **s))
        assert eng.pool.stats()["in_use"] >= 0  # all request refs dropped
        assert eng.busy_slots() == 0

    def test_gpt_paged_parity(self):
        """Per-row positions through LEARNED embeddings + paged pools."""
        paddle.seed(1)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        eng = serving.ServingEngine(model, max_slots=2, max_len=48,
                                    block_size=8, prefill_chunk=16)
        rng = np.random.RandomState(3)
        prompts = [_prompt(rng, cfg, n) for n in (4, 21)]
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_idle()
        for req, p in zip(reqs, prompts):
            got = np.asarray(req.result(timeout=1.0))
            np.testing.assert_array_equal(
                got, _ref(model, p, max_new_tokens=5))


# ---------------------------------------------------------------------------
# prefix sharing + copy-on-write
# ---------------------------------------------------------------------------


class TestPrefixSharing:
    def test_shared_system_prompt_prefills_once(self, tiny_model):
        """N requests sharing a 64-token system prompt: every request
        after the first adopts the shared blocks (prefix-cache hits,
        prompt_cached token accounting) and still matches generate()."""
        from paddle_tpu.serving import metrics as sm

        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=128,
                                    block_size=16, prefill_chunk=32)
        rng = np.random.RandomState(SEED + 2)
        sys_prompt = _prompt(rng, cfg, 64)
        tails = [_prompt(rng, cfg, n) for n in (9, 21, 4)]
        prompts = [np.concatenate([sys_prompt, t]) for t in tails]
        cached_before = sm.tokens_total.labels("prompt_cached").value()
        # warm the cache with the first request (registration happens at
        # prefill completion — same-wave admissions can't share yet)
        first = eng.submit(prompts[0], max_new_tokens=5)
        eng.run_until_idle()
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts[1:]]
        eng.run_until_idle()
        for req, p in zip([first] + reqs, prompts):
            np.testing.assert_array_equal(
                np.asarray(req.result(timeout=1.0)),
                _ref(model, p, max_new_tokens=5))
        st = eng.stats()
        # 64 shared tokens = 4 full blocks; requests 2 and 3 both adopt
        # them (8 block hits) without recomputing those tokens
        assert st["prefix_cache"]["hits"] >= 8
        cached = sm.tokens_total.labels("prompt_cached").value() \
            - cached_before
        assert cached >= 2 * 64
        assert eng.pool.stats()["cow_forks"] >= 1

    def test_identical_prompt_reuses_nearly_everything(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=128,
                                    block_size=16)
        rng = np.random.RandomState(SEED + 3)
        p = _prompt(rng, cfg, 48)  # 3 full blocks
        r1 = eng.submit(p, max_new_tokens=4)
        eng.run_until_idle()
        hits_before = eng.prefix_cache.hits
        r2 = eng.submit(p, max_new_tokens=4)
        eng.run_until_idle()
        # the repeat matches 2 of 3 blocks (the last is re-selected for
        # its logits: match is capped at L-1 tokens)
        assert eng.prefix_cache.hits - hits_before >= 2
        ref = _ref(model, p, max_new_tokens=4)
        assert r1.result(1.0) == r2.result(1.0) == list(ref)

    def test_cow_forks_on_divergent_write_keep_cache_pristine(
            self, tiny_model):
        """Two same-prompt sampled requests with different seeds diverge
        from the first generated token. Their decode writes fork the
        shared tail block; the cached pristine block keeps serving
        later identical prompts."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=128,
                                    block_size=16)
        rng = np.random.RandomState(SEED + 4)
        p = _prompt(rng, cfg, 40)  # partial tail block (40 = 2.5 blocks)
        forks_before = eng.pool.stats()["cow_forks"]
        specs = [dict(max_new_tokens=6, do_sample=True, top_k=16, seed=11),
                 dict(max_new_tokens=6, do_sample=True, top_k=16, seed=99)]
        reqs = [eng.submit(p, **s) for s in specs]
        eng.run_until_idle()
        outs = []
        for req, s in zip(reqs, specs):
            got = np.asarray(req.result(timeout=1.0))
            np.testing.assert_array_equal(got, _ref(model, p, **s))
            outs.append(list(got))
        assert outs[0] != outs[1]  # genuinely divergent continuations
        assert eng.pool.stats()["cow_forks"] > forks_before
        # a third identical prompt still reuses the pristine prefix
        r3 = eng.submit(p, max_new_tokens=4)
        eng.run_until_idle()
        np.testing.assert_array_equal(
            np.asarray(r3.result(timeout=1.0)),
            _ref(model, p, max_new_tokens=4))


# ---------------------------------------------------------------------------
# preemption by recompute (oversubscribed pool)
# ---------------------------------------------------------------------------


class TestPreemption:
    def test_oversubscribed_pool_preempts_and_stays_bit_identical(
            self, tiny_model):
        """A pool sized far below worst case forces preemption; every
        request (incl. a sampled one — the PRNG chain is replayed)
        still completes bit-identical to generate(), and nothing is
        re-delivered."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=3, max_len=128,
                                    num_blocks=13)  # 12 usable << 3*8
        rng = np.random.RandomState(SEED + 5)
        specs = [dict(max_new_tokens=30),
                 dict(max_new_tokens=30, do_sample=True, top_k=8,
                      temperature=0.9, seed=7),
                 dict(max_new_tokens=30)]
        prompts = [_prompt(rng, cfg, n) for n in (40, 55, 33)]
        reqs = [eng.submit(p, **s) for p, s in zip(prompts, specs)]
        eng.run_until_idle(max_steps=5000)
        for req, p, s in zip(reqs, prompts, specs):
            assert req.status == serving.RequestStatus.COMPLETED
            got = np.asarray(req.result(timeout=1.0))
            np.testing.assert_array_equal(got, _ref(model, p, **s))
            assert len(got) == 30  # no duplicates, no gaps
        assert eng._preempt_count >= 1
        assert eng.stats()["preemptions"] == eng._preempt_count

    def test_resume_state_survives_admission_backoff(self, tiny_model):
        """Regression: a preempted request whose re-admission is
        deferred (not enough free blocks on the first try) must keep
        its resume state — losing it re-delivered tokens."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=128,
                                    prefix_caching=False)
        rng = np.random.RandomState(SEED + 6)
        pa = _prompt(rng, cfg, 40)
        pb = _prompt(rng, cfg, 55)
        ra = eng.submit(pa, max_new_tokens=40)
        rb = eng.submit(pb, max_new_tokens=30)
        while len(rb.output_tokens) < 16:
            eng.step()
        with eng._step_lock:
            eng._preempt(rb.slot)
        assert rb._resume is not None
        eng.run_until_idle(max_steps=5000)
        np.testing.assert_array_equal(
            np.asarray(ra.result(timeout=1.0)),
            _ref(model, pa, max_new_tokens=40))
        np.testing.assert_array_equal(
            np.asarray(rb.result(timeout=1.0)),
            _ref(model, pb, max_new_tokens=30))


# ---------------------------------------------------------------------------
# one-compile invariant
# ---------------------------------------------------------------------------


class TestOneCompile:
    def test_one_step_compile_zero_retraces_across_waves(self, tiny_model):
        """≥3 waves of mixed-length requests through the PAGED engine:
        exactly one ``serving.step`` compile, zero retraces — block
        tables, occupancy, sharing, and chunk counts are all traced
        data. The single ``serving.prefill_chunk`` executable likewise
        compiles once (vs one per bucket before)."""
        model, cfg = tiny_model
        before = recompile.entry_stats().get("serving.step",
                                             {"compiles": 0, "retraces": 0})
        eng = serving.ServingEngine(model, max_slots=2, max_len=128,
                                    max_queue_depth=32, prefill_chunk=32)
        rng = np.random.RandomState(SEED + 7)
        for wave in range(3):
            reqs = [eng.submit(_prompt(rng, cfg, 3 + 11 * ((wave + i) % 7)),
                               max_new_tokens=2 + (wave + i) % 3,
                               do_sample=bool(i % 2), seed=i, top_k=5)
                    for i in range(5)]
            eng.run_until_idle()
            assert all(r.status == serving.RequestStatus.COMPLETED
                       for r in reqs)
        after = recompile.entry_stats()["serving.step"]
        assert after["compiles"] - before["compiles"] == 1
        assert after["retraces"] - before["retraces"] == 0
        for entry in eng._chunk_entries + eng._fused_entries:
            assert recompile.entry_stats()[entry]["retraces"] == 0, entry
        cow = recompile.entry_stats().get("serving.cow")
        if cow is not None:
            assert cow["retraces"] == 0


# ---------------------------------------------------------------------------
# observability: /stats, /healthz, block gauges
# ---------------------------------------------------------------------------


class TestObservability:
    def test_stats_and_healthz_carry_block_pool_state(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=128)
        rng = np.random.RandomState(SEED + 8)
        long_req = eng.submit(_prompt(rng, cfg, 40), max_new_tokens=40)
        for _ in range(4):
            eng.step()
        assert not long_req.done
        st = eng.stats()
        assert st["kv_mode"] == "paged"
        kv = st["kv_blocks"]
        assert kv["in_use"] >= 3 and kv["usable"] == 16
        assert kv["internal_fragmentation_tokens"] >= 0
        assert st["prefix_cache"]["misses"] >= 1
        # per-request block counts
        recs = st["requests"]
        assert len(recs) == 1 and recs[0]["kv_blocks"] >= 3
        assert recs[0]["phase"] == "decode"
        assert recs[0]["tokens_in_cache"] > 40

        port = serving.start_serving_http_server(eng, port=0)
        try:
            health = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10).read())
            assert health["status"] == "ok"
            assert health["kv_blocks_total"] == 16
            assert health["kv_blocks_in_use"] >= 3
            assert 0.0 <= health["kv_block_utilization"] <= 1.0
            stats = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=10).read())
            assert stats["kv_blocks"]["block_size"] == 16
        finally:
            serving.stop_serving_http_server()
            eng.stop()
        eng.run_until_idle()

    def test_block_gauges_scrape(self, tiny_model):
        from paddle_tpu import observability as obs

        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64)
        rng = np.random.RandomState(SEED + 9)
        req = eng.submit(_prompt(rng, cfg, 4), max_new_tokens=3)
        eng.run_until_idle()
        assert req.status == serving.RequestStatus.COMPLETED
        text = obs.prometheus_text()
        for name in ("paddle_tpu_kv_blocks_total",
                     "paddle_tpu_kv_blocks_in_use",
                     "paddle_tpu_kv_blocks_shared",
                     "paddle_tpu_prefix_cache_hits_total",
                     "paddle_tpu_prefix_cache_misses_total"):
            assert name in text, name


# ---------------------------------------------------------------------------
# the paged Pallas kernel (interpret mode on the CPU lane)
# ---------------------------------------------------------------------------


class TestPagedKernel:
    def test_paged_kernel_matches_contiguous_kernel(self):
        """Gathering through the block table inside the index map is
        bit-identical to the contiguous kernel over the materialized
        cache (same block split => same online-softmax partials)."""
        from paddle_tpu.pallas_kernels.decode_attention import (
            flash_decode_attention, paged_flash_decode_attention)

        rng = np.random.RandomState(0)
        B, q_len, KV, d, bs, nb, N = 3, 1, 2, 8, 16, 4, 14
        kp = rng.randn(N, bs, KV, d).astype(np.float32)
        vp = rng.randn(N, bs, KV, d).astype(np.float32)
        q = rng.randn(B, q_len, 4, d).astype(np.float32)
        bt = np.array([[1, 2, 3, 0], [4, 5, 6, 7], [8, 9, 10, 11]],
                      np.int32)
        pos = np.array([5, 37, 63], np.int32)  # 1 / 3 / 4 blocks deep
        out = paged_flash_decode_attention(q, kp, vp, bt, pos)
        kc = kp[bt.reshape(-1)].reshape(B, nb * bs, KV, d)
        vc = vp[bt.reshape(-1)].reshape(B, nb * bs, KV, d)
        ref = flash_decode_attention(q, kc, vc, pos, block_k=bs)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_paged_kernel_chunk_bundle(self):
        """q_len > 1 (a chunked-prefill bundle) through the paged
        kernel vs an f64 oracle over the gathered cache."""
        from paddle_tpu.pallas_kernels.decode_attention import \
            paged_flash_decode_attention

        rng = np.random.RandomState(1)
        B, q_len, H, KV, d, bs, nb, N = 2, 8, 4, 2, 8, 8, 4, 10
        kp = rng.randn(N, bs, KV, d).astype(np.float32)
        vp = rng.randn(N, bs, KV, d).astype(np.float32)
        q = rng.randn(B, q_len, H, d).astype(np.float32)
        bt = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
        pos = np.array([3, 17], np.int32)
        out = np.asarray(paged_flash_decode_attention(q, kp, vp, bt, pos))
        kc = kp[bt.reshape(-1)].reshape(B, nb * bs, KV, d).astype(np.float64)
        vc = vp[bt.reshape(-1)].reshape(B, nb * bs, KV, d).astype(np.float64)
        g = H // KV
        for b in range(B):
            for i in range(q_len):
                L = int(pos[b]) + i + 1
                for h in range(H):
                    kk, vv = kc[b, :L, h // g], vc[b, :L, h // g]
                    s = kk @ q[b, i, h].astype(np.float64) / np.sqrt(d)
                    p = np.exp(s - s.max())
                    expect = (p / p.sum()) @ vv
                    np.testing.assert_allclose(out[b, i, h], expect,
                                               rtol=5e-4, atol=5e-4)

    def test_engine_parity_with_paged_kernel_on(self, tiny_model,
                                                monkeypatch):
        """Engine e2e with PADDLE_TPU_FLASH_DECODE=1: decode and chunk
        prefill run the paged kernel (interpret), tokens still match
        kernel-on generate()."""
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "1")
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64,
                                    block_size=16, prefill_chunk=16)
        rng = np.random.RandomState(SEED + 10)
        prompts = [_prompt(rng, cfg, n) for n in (5, 21)]
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run_until_idle()
        for req, p in zip(reqs, prompts):
            got = np.asarray(req.result(timeout=1.0))
            np.testing.assert_array_equal(
                got, _ref(model, p, max_new_tokens=4))


# ---------------------------------------------------------------------------
# the one-pass kernel against the XLA gather path
# ---------------------------------------------------------------------------

# (heads, kv_heads): the serving cells' MHA and Llama-style GQA
LAYOUTS = {"mha16": (16, 16), "gqa32_8": (32, 8)}
# bundle -> q_len; tree29 is the [4, 2, 2] draft tree with its mask
BUNDLES = {"decode": 1, "spec5": 5, "chunk32": 32, "tree29": 29}
ONE_PASS_CASES = (
    [(h, b, "f32") for h in LAYOUTS for b in BUNDLES]
    + [(h, b, f) for h in LAYOUTS for b in ("decode", "spec5")
       for f in ("bf16", "int8", "fp8")])


def _one_pass_problem(layout, bundle, fmt, seed=0):
    """Pools, a table and positions that put every edge of the cell
    loop into one batch: lengths 1 (or the bundle), cell - 1, cell,
    cell + 1, two cells + 3 and the table's full width; a table that is
    not monotone; two rows that share their prefix blocks."""
    import jax.numpy as jnp
    from paddle_tpu.pallas_kernels import decode_attention as fd
    from paddle_tpu.quantization import intx

    H, KV = LAYOUTS[layout]
    q_len = BUNDLES[bundle]
    d, bs, nb = 8, 16, 72
    store = {"f32": jnp.float32, "bf16": jnp.bfloat16,
             "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}[fmt]
    cell = bs * fd._blocks_per_cell(bs, nb, KV, d, store,
                                    q_len * (H // KV))
    assert 2 * cell + 3 < nb * bs, "the table must span over two cells"
    lens = np.array([q_len, cell - 1, cell, cell + 1, 2 * cell + 3,
                     nb * bs], np.int32)
    B = len(lens)
    rng = np.random.RandomState(seed)
    bt = 1 + rng.permutation(B * nb).reshape(B, nb).astype(np.int32)
    bt[3, :cell // bs] = bt[2, :cell // bs]     # a shared prefix
    N = B * nb + 1
    qdt = jnp.bfloat16 if fmt == "bf16" else jnp.float32
    q = jnp.asarray(rng.randn(B, q_len, H, d), qdt)
    kp, vp = (jnp.asarray(rng.randn(N, bs, KV, d), qdt) for _ in range(2))
    kwargs = {}
    if fmt in ("int8", "fp8"):
        scales = [intx.absmax_along(p, axis=-1) for p in (kp, vp)]
        kp, vp = (intx.pack_absmax(p, s[..., None], fmt)
                  for p, s in zip((kp, vp), scales))
        full = [generation.gather_paged_kv_dequant(p, s, bt)._data
                for p, s in zip((kp, vp), scales)]
        kwargs = dict(k_scale=scales[0], v_scale=scales[1])
    else:
        full = [generation.gather_paged_kv(p, bt)._data for p in (kp, vp)]
    pos = lens - q_len
    t = np.arange(nb * bs)[None, None, :]
    visible = t <= pos[:, None, None] + np.arange(q_len)[None, :, None]
    if bundle == "tree29":
        anc = np.asarray(generation.spec_tree_plan([4, 2, 2])["anc"], bool)
        assert anc.shape == (q_len, q_len)
        kwargs["ancestor_mask"] = np.broadcast_to(anc, (B, q_len, q_len))
        idx = np.clip(t - pos[:, None, None], 0, q_len - 1)
        in_bundle = (t >= pos[:, None, None]) \
            & (t < pos[:, None, None] + q_len)
        visible = (t < pos[:, None, None]) | (in_bundle & np.take_along_axis(
            np.broadcast_to(anc, (B, q_len, q_len)),
            np.broadcast_to(idx, (B, q_len, nb * bs)), axis=2))
    return (q, kp, vp, bt, pos, kwargs), (full, visible), cell


def _xla_gather_attention(q, k_full, v_full, visible):
    """The path the kernel replaces: the gathered cache, kv heads
    repeated, a masked softmax in float32."""
    import jax
    import jax.numpy as jnp

    g = q.shape[2] // k_full.shape[2]
    k, v = (jnp.repeat(a.astype(jnp.float32), g, axis=2)
            for a in (k_full, v_full))
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bqhd,bthd->bhqt", q.astype(jnp.float32), k) \
            / np.sqrt(q.shape[-1])
        s = jnp.where(jnp.asarray(visible)[:, None], s, -1e30)
        return np.asarray(jnp.einsum("bhqt,bthd->bqhd",
                                     jax.nn.softmax(s, axis=-1), v))


class TestOnePassKernel:
    @pytest.mark.parametrize("layout,bundle,fmt", ONE_PASS_CASES)
    def test_matches_the_xla_gather_path(self, layout, bundle, fmt):
        from paddle_tpu.pallas_kernels.decode_attention import \
            paged_flash_decode_attention

        (q, kp, vp, bt, pos, kwargs), (full, visible), _ = \
            _one_pass_problem(layout, bundle, fmt)
        out = np.asarray(paged_flash_decode_attention(
            q, kp, vp, bt, pos, **kwargs), np.float32)
        ref = _xla_gather_attention(q, *full, visible)
        # float32 everywhere but the bf16 lane, whose probabilities go
        # into the second matmul in bf16 (the documented tolerance)
        tol = 2e-2 if fmt == "bf16" else 2e-5
        np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)

    def test_a_dead_slot_returns_zeros_and_leaves_its_neighbours(self):
        """A row with nothing to attend (length 0) between two live
        rows: zeros out, and the chain of fetches goes on past it."""
        from paddle_tpu.pallas_kernels.decode_attention import \
            paged_flash_decode_attention

        (q, kp, vp, bt, pos, _), (full, visible), _ = _one_pass_problem(
            "mha16", "decode", "f32", seed=3)
        pos = pos.copy()
        pos[[0, 3]] = -1                     # lens = pos + q_len = 0
        out = np.asarray(paged_flash_decode_attention(q, kp, vp, bt, pos))
        assert not out[[0, 3]].any()
        ref = _xla_gather_attention(q, *full, visible)
        live = [1, 2, 4, 5]
        np.testing.assert_allclose(out[live], ref[live], atol=2e-5,
                                   rtol=2e-5)

    def test_one_pallas_call_no_partials_no_merge(self):
        """The call's jaxpr holds exactly one ``pallas_call``, none of
        its outputs has an axis as wide as the table, and no reduction
        follows it: the whole softmax is inside the kernel."""
        import jax
        from paddle_tpu.pallas_kernels.decode_attention import \
            paged_flash_decode_attention

        (q, kp, vp, bt, pos, _), _, _ = _one_pass_problem(
            "gqa32_8", "spec5", "f32")
        nb = bt.shape[1]

        def flat(jaxpr):
            for eqn in jaxpr.eqns:
                subs = [v for v in eqn.params.values()
                        if hasattr(v, "eqns") or hasattr(v, "jaxpr")]
                if eqn.primitive.name != "pallas_call" and subs:
                    for sub in subs:
                        yield from flat(getattr(sub, "jaxpr", sub))
                else:
                    yield eqn

        eqns = list(flat(jax.make_jaxpr(
            lambda *a: paged_flash_decode_attention(*a))(
                q, kp, vp, bt, pos).jaxpr))
        calls = [i for i, e in enumerate(eqns)
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 1
        call = eqns[calls[0]]
        assert [v.aval.shape for v in call.outvars] \
            == [(q.shape[0], 8, 5 * 4, q.shape[-1])]
        assert all(nb not in v.aval.shape for v in call.outvars)
        after = {e.primitive.name for e in eqns[calls[0] + 1:]}
        assert not {n for n in after if n.startswith("reduce")
                    or n in ("exp", "argmax", "cumsum", "dot_general")}, after

    def test_cell_size_is_a_pure_function_of_the_shapes(self, monkeypatch):
        import inspect
        import jax.numpy as jnp
        from paddle_tpu.pallas_kernels import decode_attention as fd

        assert list(inspect.signature(fd._blocks_per_cell).parameters) == [
            "block_size", "nb", "kv_heads", "d", "kv_dtype", "gq"]
        shapes = {"gpt_step": (16, 128, 16, 128, jnp.bfloat16, 1),
                  "gpt_chunk": (16, 128, 16, 128, jnp.bfloat16, 32),
                  "gqa_int8": (16, 128, 8, 128, jnp.int8, 4),
                  "window256": (16, 128, 8, 128, jnp.bfloat16, 1024),
                  "tiny_table": (8, 4, 2, 8, jnp.float32, 1)}
        first = {k: fd._blocks_per_cell(*s) for k, s in shapes.items()}
        # neither the environment nor a ServingConfig reaches it
        for key in ("PADDLE_TPU_FLASH_DECODE", "PADDLE_TPU_DECODE_CELL",
                    "PADDLE_TPU_DECODE_BLOCK_K"):
            monkeypatch.setenv(key, "64")
        serving.ServingConfig(max_slots=2, max_len=64, block_size=16)
        assert {k: fd._blocks_per_cell(*s)
                for k, s in shapes.items()} == first
        code = fd._blocks_per_cell.__code__
        assert not {"os", "environ", "getenv"} & set(code.co_names)
        for k, (bs, nb, *_) in shapes.items():
            positions = first[k] * bs
            assert positions == nb * bs or 128 <= positions <= 512, k
        # a wide bundle's score tiles leave less room for the stream
        assert first["window256"] <= first["gpt_step"]


# ---------------------------------------------------------------------------
# one prefill program an iteration: the chunks of several slots as rows
# ---------------------------------------------------------------------------

# (prefill_chunk, dtype, max_slots) -> P: 256 rows of a bf16 matmul ride
# one pass over its weights, a float32 weight streams twice the bytes
ROWS_RULE = {(32, "bfloat16", 16): 8, (256, "bfloat16", 20): 1,
             (32, "bfloat16", 2): 2, (32, "float32", 64): 16,
             (16, "bfloat16", 3): 2, (512, "bfloat16", 4): 1,
             (16, "float32", 5): 4}


def _one_row_engine(monkeypatch, model, **kw):
    """The same engine with every chunk a program of its own: the
    measured constant of the rule pushed under one chunk."""
    from paddle_tpu.serving import engine as engine_mod

    with monkeypatch.context() as m:
        m.setattr(engine_mod, "_WEIGHT_PASS_ROWS_BF16", 1)
        eng = serving.ServingEngine(model, **kw)
    assert eng._chunk_rows == 1
    return eng


def _schedule(lengths, C, P):
    """The prefill programs of prompts admitted together, slot order
    the admission order, an iteration a tuple ``(rows, programs,
    fill)``: every prefilling slot's next chunk, P rows a program; the
    rows the last program has left go to further chunks, the earliest
    slot first; a lone chunk is a program of one row."""
    left = [-(-n // C) for n in lengths]
    out = []
    while any(left):
        live = [i for i, k in enumerate(left) if k]
        programs = -(-len(live) // P)
        spare = programs * P - len(live) if P > 1 else 0
        fill = 0
        for i in live:
            left[i] -= 1
        for i in live:
            take = min(spare - fill, left[i])
            left[i] -= take
            fill += take
        out.append((len(live) + fill, programs, fill))
    return out


def _serve_all(eng, prompts, specs):
    reqs = [eng.submit(p, **s) for p, s in zip(prompts, specs)]
    eng.run_until_idle(max_steps=5000)
    assert all(r.status == serving.RequestStatus.COMPLETED for r in reqs)
    return [list(r.output_tokens) for r in reqs]


class TestBatchedPrefill:
    KW = dict(max_slots=5, max_len=128, block_size=16, prefill_chunk=16)
    # one iteration's program holds a mid-prompt chunk, a last chunk
    # that fills the program's width, a padded last chunk, ...
    LENGTHS = (37, 16, 5, 48, 70)

    @pytest.mark.parametrize("shape,want", ROWS_RULE.items(),
                             ids=[f"{c}-{d}-{b}" for c, d, b in ROWS_RULE])
    def test_rows_of_the_program_come_from_the_shapes(self, shape, want):
        from paddle_tpu.serving.engine import prefill_batch_rows

        assert prefill_batch_rows(*shape) == want

    @pytest.mark.parametrize("s", [1, 8])
    def test_per_row_rope_reads_each_row_at_its_own_offset(self, s):
        """Offsets a row, one of them so near the table's end that its
        slice has to start earlier than the row does (a verify bundle
        or a chunk after a prefix hit at ``max_len``): every position
        inside the table gets its own table row."""
        import jax.numpy as jnp

        from paddle_tpu.models.llama import (_rope_tables,
                                             apply_rotary_pos_emb)

        n, d = 64, 8
        cos, sin = _rope_tables(d, n, 10000.0)
        rng = np.random.RandomState(SEED + 31)
        pos = np.array([0, 13, n - s, n - 3], np.int32)
        x = rng.randn(len(pos), s, 2, d).astype(np.float32)
        q, _ = apply_rotary_pos_emb(paddle.to_tensor(x), paddle.to_tensor(x),
                                    cos, sin, jnp.asarray(pos))
        for b, p in enumerate(pos):
            live = min(s, n - int(p))       # positions inside the table
            one, _ = apply_rotary_pos_emb(
                paddle.to_tensor(x[b:b + 1, :live]),
                paddle.to_tensor(x[b:b + 1, :live]), cos, sin, int(p))
            np.testing.assert_allclose(np.asarray(q._data)[b, :live],
                                       np.asarray(one._data)[0], atol=1e-6)

    @pytest.mark.parametrize("n", [2, 4, 5], ids=["two", "P", "P_plus_1"])
    def test_simultaneous_prompts_match_generate_and_one_row_programs(
            self, tiny_model, monkeypatch, n):
        """Greedy and sampled rows in one program give the tokens that
        ``generate`` gives each prompt alone, and that the engine gives
        with every chunk a program of its own; rows past the live ones
        carry nothing."""
        model, cfg = tiny_model
        rng = np.random.RandomState(SEED + 20)
        prompts = [_prompt(rng, cfg, L) for L in self.LENGTHS[:n]]
        specs = [dict(max_new_tokens=5 + i) if i % 2 == 0 else
                 dict(max_new_tokens=5 + i, do_sample=True, top_k=8,
                      temperature=0.8, seed=30 + i) for i in range(n)]
        eng = serving.ServingEngine(model, **self.KW)
        assert eng._chunk_rows == 4
        got = _serve_all(eng, prompts, specs)
        for g, p, s in zip(got, prompts, specs):
            assert g == list(_ref(model, p, **s))
        assert got == _serve_all(
            _one_row_engine(monkeypatch, model, **self.KW), prompts, specs)
        c = eng.counters()
        chunks = sum(-(-L // 16) for L in self.LENGTHS[:n])
        assert c["prefill_rows"] == chunks
        # the rows of an iteration share programs of four, and the rows
        # the last program has left carry further chunks: (3, 1) chunks
        # are one program, (3, 1, 1, 3) two, (3, 1, 1, 3, 5) four
        plan = _schedule(self.LENGTHS[:n], 16, 4)
        assert sum(r for r, _, _ in plan) == chunks
        assert c["prefill_programs"] == sum(p for _, p, _ in plan) \
            == {2: 1, 4: 2, 5: 4}[n]
        assert c["prefill_fill_rows"] == sum(f for _, _, f in plan) \
            == {2: 2, 4: 2, 5: 5}[n]
        assert eng.pool.used_blocks == len(eng.prefix_cache)

    @pytest.mark.parametrize("fuses", [True, False], ids=["fuses", "twin"])
    def test_a_lone_chunk_rides_the_one_row_form_of_the_program(
            self, tiny_model, fuses):
        """Two prompts of eight chunks and of one: the first iteration
        is one [4, C] program (the long prompt's first three chunks
        beside the short one's only chunk), the second carries its next
        four as [4, C] rows of the short one's decode step, and the
        lone last chunk, the short one done by then, a [1, C] program
        on an engine that keeps that form (one that does not fuse: the
        twin), the [4, C] program on one that fuses."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        assert eng._row_widths == [4]
        eng._fuses, eng._row_widths = fuses, [4] if fuses else [1, 4]
        seen = []
        real, step = eng._chunk_fn, eng._step_fn
        eng._chunk_fn = lambda pb, pools, state, rows: (
            seen.append(("chunk", rows.shape[0]))
            or real(pb, pools, state, rows))
        eng._step_fn = lambda *a: (
            len(a) == 7 and seen.append(("step", a[6].shape[0]))
            or step(*a))
        rng = np.random.RandomState(SEED + 29)
        prompts = [_prompt(rng, cfg, L) for L in (120, 9)]
        got = _serve_all(eng, prompts, [dict(max_new_tokens=3)] * 2)
        assert seen == ([("chunk", 4), ("step", 4), ("chunk", 4)] if fuses
                        else [("chunk", 4), ("chunk", 4), ("chunk", 1)])
        assert _schedule((120, 9), 16, 4) \
            == [(4, 1, 2), (4, 1, 3), (1, 1, 0)]
        for g, p in zip(got, prompts):
            assert g == list(_ref(model, p, max_new_tokens=3))
        c = eng.counters()
        assert (c["prefill_rows"], c["prefill_programs"],
                c["prefill_fill_rows"]) == (9, 3, 5)

    def test_gpt_rows_match_generate(self):
        paddle.seed(3)
        cfg = GPTConfig.tiny(max_position_embeddings=128)
        model = GPTForCausalLM(cfg)
        rng = np.random.RandomState(SEED + 21)
        prompts = [_prompt(rng, cfg, L) for L in (21, 8, 40)]
        specs = [dict(max_new_tokens=4)] * 3
        eng = serving.ServingEngine(model, max_slots=4, max_len=128,
                                    block_size=8, prefill_chunk=8)
        assert eng._chunk_rows == 4
        for g, p in zip(_serve_all(eng, prompts, specs), prompts):
            assert g == list(_ref(model, p, max_new_tokens=4))

    def test_pool_exhaustion_on_one_row_preempts_that_slot_alone(
            self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        rng = np.random.RandomState(SEED + 22)
        prompts = [_prompt(rng, cfg, L) for L in (40, 50, 45)]
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.step()
        real, fired = eng._reserve_write, []

        def reserve(slot, start, end, **kw):
            if slot == 1 and not fired:
                fired.append(slot)
                raise PoolExhaustedError("no block for this row")
            return real(slot, start, end, **kw)

        eng._reserve_write = reserve
        before = eng.counters()
        eng.step()
        after = eng.counters()
        assert fired and after["preemptions"] - before["preemptions"] == 1
        assert reqs[1].slot is None and reqs[1].preempt_count == 1
        # the other two rode the iteration's one program (the first
        # step gave slot 0 its second chunk in a spare row, so this is
        # its last), and a row it had left went to the later one's last
        assert after["prefill_rows"] - before["prefill_rows"] == 3
        assert after["prefill_fill_rows"] - before["prefill_fill_rows"] == 1
        assert after["prefill_programs"] - before["prefill_programs"] == 1
        assert [eng._jobs[r.slot] for r in (reqs[0], reqs[2])] == [None] * 2
        assert all(eng._decoding[r.slot] for r in (reqs[0], reqs[2]))
        eng.run_until_idle()
        for r, p in zip(reqs, prompts):
            assert list(r.output_tokens) == list(
                _ref(model, p, max_new_tokens=4))

    def test_a_row_whose_slot_a_later_reservation_preempts_carries_nothing(
            self, tiny_model):
        """Row 1's reservation takes the blocks of row 0, claimed a
        moment before: row 0 is taken out of the program (its blocks
        may be row 1's by now) and recomputed from the queue."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        rng = np.random.RandomState(SEED + 23)
        prompts = [_prompt(rng, cfg, L) for L in (40, 50)]
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.step()
        real, fired = eng._reserve_write, []

        def reserve(slot, start, end, **kw):
            if slot == 1 and not fired:
                fired.append(slot)
                eng._preempt(0)     # what _reclaim_alloc does under pressure
            return real(slot, start, end, **kw)

        eng._reserve_write = reserve
        before = eng.counters()
        eng.step()
        after = eng.counters()
        assert fired and reqs[0].preempt_count == 1
        # row 1 and, in the rows left, its two further chunks; row 0,
        # preempted, neither rides nor is given a spare row
        assert after["prefill_rows"] - before["prefill_rows"] == 3
        assert after["prefill_fill_rows"] - before["prefill_fill_rows"] == 2
        eng.run_until_idle()
        for r, p in zip(reqs, prompts):
            assert list(r.output_tokens) == list(
                _ref(model, p, max_new_tokens=4))

    @pytest.mark.parametrize("one_row", [True, False],
                             ids=["P1", "P4"])
    def test_a_program_goes_out_as_soon_as_its_rows_are_claimed(
            self, tiny_model, monkeypatch, one_row):
        """The device gets a program before the host reserves the next
        program's blocks: at P = 1 every chunk before the next slot's
        reservation, at P = 4 the first four slots' before the
        fifth's."""
        from paddle_tpu.serving import engine as engine_mod

        model, cfg = tiny_model
        eng = _one_row_engine(monkeypatch, model, **self.KW) if one_row \
            else serving.ServingEngine(model, **self.KW)
        rng = np.random.RandomState(SEED + 32)
        prompts = [_prompt(rng, cfg, 40) for _ in range(5)]
        reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
        events = []
        reserve, enqueue = eng._reserve_write, eng._enqueue_chunks

        def reserving(slot, start, end, **kw):
            events.append(slot)
            return reserve(slot, start, end, **kw)

        def enqueuing(packed):
            valid = packed[:, -len(engine_mod._ROW_COLUMNS):][:, 1]
            events.append(("program", int((valid > 0).sum())))
            return enqueue(packed)

        eng._reserve_write, eng._enqueue_chunks = reserving, enqueuing
        eng.step()
        one, four = ("program", 1), ("program", 4)
        # (at P = 4 the second program's three spare rows go to slot
        # 0's two further chunks and slot 1's next, reserved after every
        # slot's first chunk)
        assert events == ([0, one, 1, one, 2, one, 3, one, 4, one]
                          if one_row else [0, 1, 2, 3, four, 4, 0, 0, 1, four,
                                           0])   # slot 0's first decode write
        eng.run_until_idle()
        for r, p in zip(reqs, prompts):
            assert list(r.output_tokens) == list(
                _ref(model, p, max_new_tokens=3))

    def test_a_row_preempted_after_its_program_went_out_is_recomputed(
            self, tiny_model):
        """The fifth slot's reservation, made with the first program
        already out, preempts a row of that program: the row's chunk is
        not booked, and the request is served from the queue's front."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        rng = np.random.RandomState(SEED + 33)
        prompts = [_prompt(rng, cfg, 40) for _ in range(5)]
        reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
        real, fired = eng._reserve_write, []

        def reserve(slot, start, end, **kw):
            if slot == 4 and not fired:
                fired.append(slot)
                eng._preempt(1)     # what _reclaim_alloc does under pressure
            return real(slot, start, end, **kw)

        eng._reserve_write = reserve
        eng.step()
        assert fired and reqs[1].preempt_count == 1 and reqs[1].slot is None
        # five first chunks, and the second program's three spare rows:
        # two end slot 0's prompt, one goes to slot 2 (slot 1 is gone)
        c = eng.counters()
        assert (c["prefill_rows"], c["prefill_fill_rows"]) == (8, 3)
        assert eng._jobs[reqs[0].slot] is None
        assert [eng._jobs[r.slot].done for r in reqs[2:]] == [32, 16, 16]
        eng.run_until_idle()
        for r, p in zip(reqs, prompts):
            assert list(r.output_tokens) == list(
                _ref(model, p, max_new_tokens=3))

    def test_cancel_and_deadline_between_chunks_free_their_rows_alone(
            self, tiny_model):
        import time

        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        rng = np.random.RandomState(SEED + 24)
        prompts = [_prompt(rng, cfg, L) for L in (60, 60, 60)]
        reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
        eng.step()
        eng.cancel(reqs[0])
        reqs[2].deadline_ts = time.perf_counter() - 1.0
        before = eng.counters()
        eng.step()
        assert reqs[0].status == serving.RequestStatus.CANCELLED
        assert reqs[2].status == serving.RequestStatus.EXPIRED
        assert "prefill" in reqs[2].error
        # the one slot left takes its next chunk and, in the rows the
        # other two no longer claim, its last two
        after = eng.counters()
        assert after["prefill_rows"] - before["prefill_rows"] == 3
        assert after["prefill_fill_rows"] - before["prefill_fill_rows"] == 2
        eng.run_until_idle()
        assert list(reqs[1].output_tokens) == list(
            _ref(model, prompts[1], max_new_tokens=3))
        assert eng.pool.used_blocks == len(eng.prefix_cache)

    def test_a_prefix_hit_and_a_cow_fork_on_a_row_of_a_batch(
            self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        rng = np.random.RandomState(SEED + 25)
        base = _prompt(rng, cfg, 40)        # two blocks and half a block
        first = eng.submit(base, max_new_tokens=3)
        eng.run_until_idle()
        # beside two fresh prompts: one that adopts base's blocks and
        # writes into the shared half block (a fork), one that adopts
        # the two whole blocks
        prompts = [_prompt(rng, cfg, 23),
                   np.concatenate([base, _prompt(rng, cfg, 9)]),
                   np.concatenate([base[:32], _prompt(rng, cfg, 20)]),
                   _prompt(rng, cfg, 35)]
        hits, forks = eng.prefix_cache.hits, eng.pool.stats()["cow_forks"]
        before = eng.counters()
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.step()
        after = eng.counters()
        assert after["prefill_rows"] - before["prefill_rows"] == 4
        assert after["prefill_programs"] - before["prefill_programs"] == 1
        assert eng.prefix_cache.hits - hits == 3 + 2
        assert eng.pool.stats()["cow_forks"] - forks >= 1
        assert after["prefix_hit_tokens"] - before["prefix_hit_tokens"] \
            == 40 + 32
        eng.run_until_idle()
        for r, p in zip([first, *reqs], [base, *prompts]):
            assert list(r.output_tokens) == list(
                _ref(model, p, max_new_tokens=len(r.output_tokens)))

    def test_a_windowed_engine_rolls_a_window_inside_a_batch(
            self, monkeypatch):
        """An EVA layout at a tiny chunk: rows of one program sit in
        different windows, and one of them rolls its window in the
        iteration that the others only write."""
        from paddle_tpu.models import EvaByteConfig, EvaByteForCausalLM

        paddle.seed(0)
        cfg = EvaByteConfig.tiny()     # window 16 in chunks of 4
        model = EvaByteForCausalLM(cfg)
        kw = dict(max_slots=4, max_len=128, block_size=4, prefill_chunk=8,
                  prefix_caching=False)
        rng = np.random.RandomState(SEED + 26)
        prompts = [_prompt(rng, cfg, L) for L in (37, 12, 53, 22)]
        specs = [dict(max_new_tokens=6)] * 4
        eng = serving.ServingEngine(model, **kw)
        assert eng._chunk_rows == 4 and eng._layout is not None
        reqs = [eng.submit(p, **s) for p, s in zip(prompts, specs)]
        rolls, rows = [], []
        while True:
            before = eng.counters()
            if not eng.step():
                break
            after = eng.counters()
            rolls.append(after["window_rolls"] - before["window_rolls"])
            rows.append(after["prefill_rows"] - before["prefill_rows"])
        # the third iteration writes positions 16..23 of three prompts:
        # each rolls, in a program that another row does not ride
        assert rows[:3] == [4, 4, 3] and rolls[:3] == [0, 0, 3]
        got = [list(r.output_tokens) for r in reqs]
        assert all(len(g) == 6 for g in got)
        assert got == _serve_all(_one_row_engine(monkeypatch, model, **kw),
                                 prompts, specs)
        for p, g in zip(prompts, got):
            ids = np.concatenate([p, np.asarray(g, np.int32)])
            lg = model(paddle.to_tensor(ids[None]))._data[0, :, 0]
            assert np.asarray(lg.argmax(-1))[len(p) - 1:-1].tolist() == g
        assert eng.pool.free_blocks == eng.pool.usable_blocks

    def test_int8_pool_rows_match_one_row_programs(self, tiny_model,
                                                   monkeypatch):
        model, cfg = tiny_model
        kw = dict(self.KW, kv_format="int8")
        rng = np.random.RandomState(SEED + 27)
        prompts = [_prompt(rng, cfg, L) for L in (37, 16, 5)]
        specs = [dict(max_new_tokens=6), dict(max_new_tokens=6),
                 dict(max_new_tokens=6, do_sample=True, top_k=8, seed=5)]
        eng = serving.ServingEngine(model, **kw)
        assert eng._chunk_rows == 4 and "ks" in eng._pools[0]
        assert _serve_all(eng, prompts, specs) == _serve_all(
            _one_row_engine(monkeypatch, model, **kw), prompts, specs)

    def test_the_program_compiles_once_whatever_the_live_rows(
            self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_queue_depth=32, **self.KW)
        eng.warmup()
        names = ("serving.prefill_chunk[4]", "serving.step+chunk[4]")
        assert list(names) == eng._chunk_entries + eng._fused_entries
        stats0 = {n: dict(recompile.entry_stats()[n]) for n in names}
        total0 = recompile.total_compiles()
        rng = np.random.RandomState(SEED + 28)
        for n in (1, 2, 3, 4, 5, 9):
            reqs = [eng.submit(_prompt(rng, cfg, 5 + 13 * i),
                               max_new_tokens=2, do_sample=bool(i % 2),
                               seed=i, top_k=4) for i in range(n)]
            eng.run_until_idle()
            assert all(r.status == serving.RequestStatus.COMPLETED
                       for r in reqs)
        # the [4, C] rows as a prefill program and in the step's (an
        # engine that fuses keeps no [1, C] form): both compiled by
        # warmup(), neither again
        for n in names:
            stats1 = recompile.entry_stats()[n]
            assert stats1["calls"] > stats0[n]["calls"]
            assert stats1["compiles"] == stats0[n]["compiles"]
            assert stats1["retraces"] == stats0[n]["retraces"]
        assert recompile.total_compiles() == total0


# ---------------------------------------------------------------------------
# the rows a prefill program has left carry the earliest slot's next chunks
# ---------------------------------------------------------------------------


def _program_rows(eng, seen, rode=None):
    """Record every prefill program's live rows as ``(slot, pos0,
    is_last)`` in ``seen``, one list a program, be it a prefill program
    or the decode step that carried the rows (``rode``, where given:
    True for each of those, False for the others)."""
    from paddle_tpu.serving import engine as engine_mod

    enqueue, step = eng._enqueue_chunks, eng._enqueue_step

    def note(packed, in_step):
        cols = packed[:, -len(engine_mod._ROW_COLUMNS):]
        seen.append([(int(c[2]), int(c[0]), bool(c[3]))
                     for c in cols if c[1] > 0])
        if rode is not None:
            rode.append(in_step)

    def enqueuing(packed):
        note(packed, False)
        return enqueue(packed)

    def stepping(bt, any_sampling, active, packed=None):
        if packed is not None:
            note(packed, True)
        return step(bt, any_sampling, active, packed)

    eng._enqueue_chunks, eng._enqueue_step = enqueuing, stepping


def _fill_family(family):
    paddle.seed(7)
    if family == "gpt":
        return GPTForCausalLM(GPTConfig.tiny(max_position_embeddings=128)), {}
    model = LlamaForCausalLM(LlamaConfig.tiny(
        num_key_value_heads=2, max_position_embeddings=128))
    return model, ({"kv_format": "int8"} if family.endswith("int8") else {})


class TestSpareRows:
    KW = dict(max_slots=4, max_len=128, block_size=16, prefill_chunk=16)

    @pytest.mark.parametrize("kernel", ["0", "1"], ids=["xla", "kernel"])
    @pytest.mark.parametrize("family", ["gpt", "llama_gqa",
                                        "llama_gqa_int8"])
    def test_several_chunks_a_program_match_one_chunk_an_iteration(
            self, monkeypatch, family, kernel):
        """A prompt alone in the engine rides ``[4, C]`` four chunks a
        program, each row reading what the rows before it wrote in the
        same program; greedy and sampled it gives the tokens of the same
        prompt prefilled one chunk an iteration, and (float pools) the
        same keys and values in its blocks."""
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", kernel)
        model, extra = _fill_family(family)
        kw = dict(self.KW, **extra)
        rng = np.random.RandomState(SEED + 40)
        vocab = model.config.vocab_size
        prompts = [rng.randint(1, vocab, n).astype("int32")
                   for n in (101, 77)]    # 7 chunks (one padded), 5
        specs = [dict(max_new_tokens=5),
                 dict(max_new_tokens=5, do_sample=True, top_k=8,
                      temperature=0.9, seed=11)]
        eng = serving.ServingEngine(model, **kw)
        one = _one_row_engine(monkeypatch, model, **kw)
        assert eng._chunk_rows == 4
        for p, s in zip(prompts, specs):
            reqs = [e.submit(p, **s) for e in (eng, one)]
            before = eng.counters()
            iters = {}
            for e in (eng, one):        # each alone, in its slot 0
                iters[e] = 0
                while not e._decoding[0]:
                    e.step()
                    iters[e] += 1
            after = eng.counters()
            chunks = -(-len(p) // 16)
            assert (iters[eng], iters[one]) == (-(-chunks // 4), chunks)
            assert after["prefill_rows"] - before["prefill_rows"] == chunks
            assert after["prefill_fill_rows"] - before["prefill_fill_rows"] \
                == chunks - iters[eng]
            if not extra:
                blocks = eng._slot_blocks[0][:len(p) // 16]
                assert blocks == one._slot_blocks[0][:len(p) // 16]
                for a, b in zip(eng._pools, one._pools):
                    for name in ("k", "v"):
                        np.testing.assert_allclose(
                            np.asarray(a[name])[blocks],
                            np.asarray(b[name])[blocks], atol=2e-5)
            eng.run_until_idle()
            one.run_until_idle()
            got = [list(r.output_tokens) for r in reqs]
            assert got[0] == got[1] and len(got[0]) == 5
            if not extra:       # (an int8 pool has no generate twin)
                assert got[0] == list(_ref(model, p, **s))
        assert eng.pool.used_blocks == len(eng.prefix_cache)

    def test_spare_rows_go_to_the_earliest_admitted_slot_then_the_next(
            self, tiny_model):
        """Admission order, not slot order: B (slot 1) was admitted
        before C, which took the slot a finished request left (slot 0:
        X's second token was in flight through the second iteration, and
        its slot was free from the third). The program's two spare rows:
        B's one further chunk, its last, then C's next."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **dict(self.KW, prefill_chunk=8))
        assert eng._chunk_rows == 4
        rng = np.random.RandomState(SEED + 41)
        x, b, c = (_prompt(rng, cfg, n) for n in (5, 72, 60))
        rx = eng.submit(x, max_new_tokens=2)
        rb = eng.submit(b, max_new_tokens=4)
        seen = []
        _program_rows(eng, seen)
        eng.step()
        # X's only chunk, B's first, and B's next two in the spare rows
        assert seen == [[(0, 0, True), (1, 0, False), (1, 8, False),
                         (1, 16, False)]]
        eng.step()
        assert seen[1] == [(1, 24, False), (1, 32, False), (1, 40, False),
                           (1, 48, False)]
        assert rx.status == serving.RequestStatus.COMPLETED
        rc = eng.submit(c, max_new_tokens=4)
        eng.step()
        assert (rc.slot, rb.slot) == (0, 1)
        assert eng._slot_seq[1] < eng._slot_seq[0]
        assert seen[2] == [(0, 0, False), (1, 56, False), (1, 64, True),
                           (0, 8, False)]
        c1 = eng.counters()
        assert (c1["prefill_rows"], c1["prefill_programs"],
                c1["prefill_fill_rows"]) == (12, 3, 7)
        eng.run_until_idle()
        for r, p in ((rx, x), (rb, b), (rc, c)):
            assert list(r.output_tokens) == list(
                _ref(model, p, max_new_tokens=len(r.output_tokens)))
        # every chunk of C rode exactly once, in order, the final one last
        mine = [row for rows in seen[1:] for row in rows if row[0] == 0]
        assert [pos for _, pos, _ in mine] == list(range(0, len(c), 8))
        assert [last for _, _, last in mine] == [False] * 7 + [True]

    def test_a_fill_cut_short_by_the_pool_preempts_nobody(self, tiny_model):
        """A spare row is one its slot can do without: where the pool
        cannot give its blocks the slot's share of the rows ends there,
        nobody is preempted, and the prompt goes on next iteration."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        rng = np.random.RandomState(SEED + 42)
        prompts = [_prompt(rng, cfg, n) for n in (100, 90)]
        reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
        real, asked = eng._reserve_write, []

        def reserve(slot, start, end, **kw):
            asked.append((slot, start, kw))
            if kw.get("allow_preempt") is False and (slot, start) == (0, 32):
                raise PoolExhaustedError("no block for a spare row")
            return real(slot, start, end, **kw)

        eng._reserve_write = reserve
        seen = []
        _program_rows(eng, seen)
        eng.step()
        # slot 0 got one spare row and was refused its second; the row
        # went to slot 1, the next in admission order
        assert seen == [[(0, 0, False), (1, 0, False), (0, 16, False),
                         (1, 16, False)]]
        assert [a for a in asked if a[2]] == [
            (slot, start, {"allow_preempt": False})
            for slot, start in ((0, 16), (0, 32), (1, 16))]
        c = eng.counters()
        assert (c["preemptions"], c["prefill_fill_rows"]) == (0, 2)
        assert all(r.preempt_count == 0 and r.slot is not None for r in reqs)
        assert [eng._jobs[i].done for i in (0, 1)] == [32, 32]
        eng.run_until_idle()
        for r, p in zip(reqs, prompts):
            assert list(r.output_tokens) == list(
                _ref(model, p, max_new_tokens=3))

    def test_a_write_the_slot_can_do_without_never_preempts(self, tiny_model):
        """``_reserve_write(allow_preempt=False)`` on an exhausted pool raises
        and leaves every slot its blocks; the same write with the
        default takes them from the latest-admitted other request."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, prefix_caching=False,
                                    num_blocks=5, **self.KW)   # 4 usable
        rng = np.random.RandomState(SEED + 43)
        reqs = [eng.submit(_prompt(rng, cfg, 30), max_new_tokens=20)
                for _ in range(2)]
        eng.step()      # two blocks each: the pool is full
        assert eng.pool.free_blocks == 0 and all(eng._decoding[:2])
        with pytest.raises(PoolExhaustedError):
            eng._reserve_write(0, 32, 33, allow_preempt=False)
        assert eng.counters()["preemptions"] == 0
        assert [len(b) for b in eng._slot_blocks[:2]] == [2, 2]
        eng._reserve_write(0, 32, 33)
        assert eng.counters()["preemptions"] == 1
        assert reqs[1].slot is None and len(eng._slot_blocks[0]) == 3

    @pytest.mark.parametrize("how", ["cancel", "deadline"])
    def test_cancel_and_deadline_between_iterations_stop_a_filled_prompt(
            self, tiny_model, how):
        import time

        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        rng = np.random.RandomState(SEED + 44)
        prompts = [_prompt(rng, cfg, n) for n in (120, 120)]
        reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
        eng.step()      # a first chunk each, and two spare rows to slot 0
        assert [eng._jobs[i].done for i in (0, 1)] == [48, 16]
        if how == "cancel":
            eng.cancel(reqs[0])
        else:
            reqs[0].deadline_ts = time.perf_counter() - 1.0
        before = eng.counters()
        eng.step()
        assert reqs[0].status == (serving.RequestStatus.CANCELLED
                                  if how == "cancel"
                                  else serving.RequestStatus.EXPIRED)
        # the slot left alone takes the whole program
        after = eng.counters()
        assert after["prefill_rows"] - before["prefill_rows"] == 4
        assert after["prefill_fill_rows"] - before["prefill_fill_rows"] == 3
        assert eng._jobs[1].done == 80 and eng._slot_req[0] is None
        eng.run_until_idle()
        assert list(reqs[1].output_tokens) == list(
            _ref(model, prompts[1], max_new_tokens=3))
        assert eng.pool.used_blocks == len(eng.prefix_cache)

    def test_a_windowed_slot_never_has_two_rows_in_a_program(self):
        """An EVA engine at ``[2, 32]``: a slot alone, or beside one
        other, advances one chunk an iteration whatever rows are left,
        because rolling its window gives blocks back that an earlier row
        of the same program would still read."""
        from paddle_tpu.models import EvaByteConfig, EvaByteForCausalLM

        paddle.seed(0)
        cfg = EvaByteConfig.tiny(chunk_size=16, window_size=64)
        eng = serving.ServingEngine(
            EvaByteForCausalLM(cfg), max_slots=2, max_len=320, block_size=4,
            prefill_chunk=32, prefix_caching=False)
        assert eng._chunk_rows == 2 and eng._layout is not None
        seen = []
        _program_rows(eng, seen)
        rng = np.random.RandomState(SEED + 45)
        lone = eng.submit(_prompt(rng, cfg, 150), max_new_tokens=3)
        eng.run_until_idle()
        assert [len(rows) for rows in seen] == [1] * 5
        del seen[:]
        pair = [eng.submit(_prompt(rng, cfg, n), max_new_tokens=3)
                for n in (150, 70)]
        eng.run_until_idle()
        assert all(len({slot for slot, _, _ in rows}) == len(rows)
                   for rows in seen)
        assert [len(rows) for rows in seen] == [2, 2, 2, 1, 1]
        c = eng.counters()
        assert c["prefill_fill_rows"] == 0 and c["window_rolls"] >= 4
        assert all(len(r.output_tokens) == 3 for r in [lone, *pair])


# ---------------------------------------------------------------------------
# the decode step and the iteration's last prefill rows in ONE program
# ---------------------------------------------------------------------------


FUSED_KW = dict(max_slots=6, max_len=128, block_size=16, prefill_chunk=8)
FAMILIES = {"llama": {}, "llama_gqa_int8": {"kv_format": "int8"}, "gpt": {}}


def _first_eos(ref, k):
    """What a request gives whose end-of-sequence token is ``ref[k]``."""
    ref = list(ref)
    return ref[:ref.index(ref[k]) + 1]


def _mixed_traffic(eng, model, cfg, refs):
    """One run through everything a step that carries prefill rows
    meets, the same requests whatever the engine's order of programs: a
    greedy runner; five prompts admitted behind it at once (sampled and
    greedy, one that ends on a token's value, one that adopts the
    runner's blocks and forks the half block it shares); a late prompt
    whose slot the reservation of a decode row preempts while its rows
    are claimed; one cancelled with a step in flight. Returns ``{name:
    (request, the tokens it should have)}``, the programs' rows, which
    of them rode a step, and the first program after the late prompt.
    ``refs`` keeps ``generate``'s tokens by name from run to run."""
    rng = np.random.RandomState(SEED + 50)
    seen, rode = [], []
    _program_rows(eng, seen, rode)

    def sampled(i):
        return dict(do_sample=True, top_k=8, temperature=0.8, seed=60 + i)

    out = {}

    def submit(name, prompt, cut=None, **spec):
        if name not in refs:
            refs[name] = list(_ref(model, prompt, **spec))
        ref = refs[name]
        if cut is not None:
            spec["eos_token_id"] = ref[cut]
            ref = _first_eos(ref, cut)
        out[name] = (eng.submit(prompt, **spec), ref)

    a = _prompt(rng, cfg, 20)
    submit("a", a, max_new_tokens=70)
    eng.step()
    eng.step()
    assert eng._decoding[0]
    submit("b", _prompt(rng, cfg, 11), max_new_tokens=9, **sampled(1))
    submit("c", _prompt(rng, cfg, 45), max_new_tokens=7, **sampled(2))
    submit("d", _prompt(rng, cfg, 9), cut=3, max_new_tokens=12)
    submit("e", _prompt(rng, cfg, 30), max_new_tokens=6, **sampled(3))
    submit("f", np.concatenate([a, _prompt(rng, cfg, 7)]), max_new_tokens=5)
    n = 0
    while not all(out[k][0].done for k in "bd"):
        assert eng.step()
        n += 1
        assert n < 200
    # a late prompt of six chunks; as the next decode row is reserved,
    # with rows of its claimed (or just out, in the twin), its slot is
    # preempted
    g_from = len(seen)
    submit("g", _prompt(rng, cfg, 45), max_new_tokens=4, **sampled(4))
    real, fired = eng._reserve_write, []

    def reserve(slot, start, end, **kw):
        victim = out["g"][0].slot
        if end - start == 1 and not fired and victim is not None \
                and eng._jobs[victim] is not None:
            fired.append(victim)
            eng._preempt(victim)    # what _reclaim_alloc does under pressure
        return real(slot, start, end, **kw)

    eng._reserve_write = reserve
    while not fired:
        assert eng.step()
    eng._reserve_write = real
    # one more, cancelled with a step in flight and its prompt under way
    submit("h", _prompt(rng, cfg, 50), max_new_tokens=8)
    eng.step()
    eng.step()
    assert eng._ahead is not None
    eng.cancel(out["h"][0])
    eng.run_until_idle(max_steps=5000)
    return out, seen, rode, g_from


@pytest.fixture(scope="module", params=FAMILIES, ids=list(FAMILIES))
def mixed(request):
    """The mixed traffic once through an engine that fuses and once
    through its twin that keeps the parent's pair of programs an
    iteration, in the parent's order (nothing is held for the step)."""
    model, more = _fill_family(request.param)
    cfg = model.config
    runs, refs = {}, {}
    for fuses in (True, False):
        eng = serving.ServingEngine(model, **FUSED_KW, **more)
        assert eng._fuses and eng._chunk_rows == 4
        eng._fuses = fuses
        runs[fuses] = (eng, *_mixed_traffic(eng, model, cfg, refs))
    return runs, request.param


class TestFusedStep:
    def test_every_request_has_generates_tokens_in_either_order(self, mixed):
        """Greedy and sampled, through prefix hit, fork, preemption,
        end of sequence and cancel: the tokens are ``generate``'s
        (int8 pools: the twin's), whichever program carried the rows."""
        runs, family = mixed
        for fuses, (eng, out, *_) in runs.items():
            for name, (req, ref) in out.items():
                if name == "h":
                    assert req.status == serving.RequestStatus.CANCELLED
                    continue
                assert req.status == serving.RequestStatus.COMPLETED, name
                if "int8" not in family:
                    assert list(req.output_tokens) == ref, (fuses, name)
            assert eng.pool.used_blocks == len(eng.prefix_cache)
            assert not eng.in_flight and eng.busy_slots() == 0
        fused, pair = runs[True][1], runs[False][1]
        for name in fused:
            got, want = (list(o[name][0].output_tokens)
                         for o in (fused, pair))
            if name == "h":     # cancelled at another token of its stream
                n = min(len(got), len(want))
                got, want = got[:n], want[:n]
            assert got == want, name

    def test_the_traffic_met_what_it_was_built_to_meet(self, mixed):
        runs, _ = mixed
        eng, out, seen, rode, _ = runs[True]
        c = eng.counters()
        assert c["steps_fused"] == sum(rode) >= 4
        assert c["prefix_hit_tokens"] >= 20
        assert eng.pool.stats()["cow_forks"] >= 1
        assert c["preemptions"] == 1 == out["g"][0].preempt_count
        assert c["dead_rows"] >= 1          # d ended on a token's value
        # two chunks of one slot in one program that rode a step
        assert any(r and len({slot for slot, _, _ in rows}) < len(rows)
                   for rows, r in zip(seen, rode))
        # an iteration of two programs: four rows out at once, the
        # fifth slot's and the spare rows with the step
        k = rode.index(True)
        assert not rode[k - 1] and len(seen[k - 1]) == 4
        assert {slot for slot, _, _ in seen[k - 1]} == {1, 2, 3, 4}
        # (f, in slot 5, starts behind the twenty tokens it adopted)
        assert seen[k][0] == (5, 20, True) and len(seen[k]) == 4
        # the twin never carried a row in its step
        eng, _, _, rode2, _ = runs[False]
        assert eng.counters()["steps_fused"] == 0 == sum(rode2)

    def test_a_prompt_that_ends_in_the_steps_program_decodes_from_the_next(
            self, tiny_model):
        """The slot is no decode row of the step its last chunk rides:
        the program writes its state row, the step after reads it."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **FUSED_KW)
        rng = np.random.RandomState(SEED + 51)
        runner = eng.submit(_prompt(rng, cfg, 6), max_new_tokens=12)
        eng.step()
        late = eng.submit(_prompt(rng, cfg, 13), max_new_tokens=4)
        masks, real = [], eng._enqueue_step

        def stepping(bt, any_sampling, active, packed=None):
            masks.append((active.copy(), packed is not None))
            return real(bt, any_sampling, active, packed)

        eng._enqueue_step = stepping
        eng.step()
        # its two chunks rode the runner's step, and it was no row of it
        assert masks == [(masks[0][0], True)]
        assert masks[0][0].tolist() == [True] + [False] * 5
        assert eng._decoding[late.slot] and eng._slot_due[late.slot] == 1
        assert len(eng._parked_tokens) == 1
        eng.step()
        assert masks[1][0].tolist() == [True, True] + [False] * 4
        assert not masks[1][1] and len(late.output_tokens) == 1
        eng.run_until_idle()
        for r in (runner, late):
            assert list(r.output_tokens) == list(_ref(
                model, r.prompt, max_new_tokens=r.params.max_new_tokens))

    def test_a_preempted_slots_claimed_row_carries_nothing(self, mixed):
        """The decode row's reservation preempted the slot whose rows
        were held for the step: the program that went out next has no
        row of that slot, and the request ran again from the queue."""
        runs, _ = mixed

        def first_chunks(fuses):
            _, out, seen, _, g_from = runs[fuses]
            g = out["g"][0]
            assert g.preempt_count == 1
            return sum((slot, pos) == (g.slot, 0)
                       for rows in seen[g_from:] for slot, pos, _ in rows)

        # its first chunk rode once: the rows held when it was preempted
        # never went out (the twin, whose program was out, ran it twice)
        assert (first_chunks(True), first_chunks(False)) == (1, 2)

    def test_empty_rows_and_inactive_rows_write_the_dump_block_alone(
            self, tiny_model, width=4):
        """The step's program with no row carrying a chunk (``valid``
        0, a zeroed table row) and every decode row inactive (a zeroed
        table): every block but the dump block is as it was."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **FUSED_KW)
        req = eng.submit(_prompt(np.random.RandomState(SEED + 52), cfg, 21),
                         max_new_tokens=3)
        eng.run_until_idle()
        assert req.status == serving.RequestStatus.COMPLETED
        before = [{k: np.asarray(v) for k, v in c.items()}
                  for c in eng._pools]
        assert any(np.abs(c["k"][1:]).sum() > 0 for c in before)
        B, nb = eng.config.max_slots, eng._bt.shape[1]
        eng._enqueue_step(np.zeros((B, nb), np.int32), np.asarray(False),
                          np.zeros(B, bool), eng._chunk_args((), width))
        for was, now in zip(before, eng._pools):
            for k in was:
                np.testing.assert_array_equal(was[k][1:],
                                              np.asarray(now[k])[1:])

    def test_warmup_leaves_no_compile_for_a_run_through_every_trace(
            self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **FUSED_KW)
        info = eng.warmup()
        assert {"serving.step", "serving.step+chunk[4]",
                "serving.prefill_chunk[4]"} <= set(info["entries"])
        before = recompile.total_compiles()
        stats0 = {k: dict(v) for k, v in recompile.entry_stats().items()}
        entries = []
        real = eng._enqueue_step

        def stepping(*a):
            out = real(*a)
            entries.append(out[2])
            return out

        eng._enqueue_step = stepping
        rng = np.random.RandomState(SEED + 53)
        reqs = [eng.submit(_prompt(rng, cfg, 5), max_new_tokens=14)]
        eng.step()
        reqs += [eng.submit(_prompt(rng, cfg, n), max_new_tokens=3)
                 for n in (30, 12)]
        for _ in range(4):
            eng.step()
        reqs.append(eng.submit(_prompt(rng, cfg, 7), max_new_tokens=3))
        eng.run_until_idle()
        # (the lone chunk of the last prompt rode the [4, C] rows too)
        assert set(entries) == {"serving.step", "serving.step+chunk[4]"}
        assert recompile.total_compiles() == before
        for name, st in recompile.entry_stats().items():
            assert st["retraces"] == stats0.get(name, st)["retraces"], name
        for r in reqs:
            assert list(r.output_tokens) == list(_ref(
                model, r.prompt, max_new_tokens=r.params.max_new_tokens))
