"""Flash-decode attention (pallas_kernels/decode_attention.py).

Oracles:
- KERNEL PARITY: the one-pass GQA kernel must match a float64 dense SDPA
  over each row's valid cache prefix — across q_len {1, 4}, GQA ratios
  {1, 2, 4}, ragged per-row positions including the pos=0 and
  pos=max_len-q_len edge rows, fp32 at exact-class tolerance and bf16 at
  the documented tolerance.
- FALLBACK EXACTNESS: the grouped-einsum XLA fallback
  (nn.functional.grouped_query_sdpa) must be bit-identical to the old
  repeat_kv + scaled_dot_product_attention path it replaced.
- DISPATCH: PADDLE_TPU_FLASH_DECODE flips the kernel on/off with
  identical generated tokens either way (llama AND gpt), hit/fallback
  counters fire with the right reasons, and the serving engine keeps its
  one-step-compile-across-waves invariant with the kernel enabled.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import generation, serving
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.models.llama import repeat_kv
from paddle_tpu.nn import functional as F
from paddle_tpu.observability import recompile
from paddle_tpu.pallas_kernels import decode_attention as fd
from paddle_tpu.pallas_kernels.decode_attention import flash_decode_attention

# documented bf16 tolerance: bf16 q/k/v streams with fp32 statistics and
# accumulation land within ~1e-2 of the f64 oracle on these shapes
BF16_ATOL = 2e-2


def _oracle(q, kc, vc, pos):
    """Dense f64 SDPA over each row's valid prefix (the pre-kernel
    semantics: query i of row b attends cache positions <= pos[b] + i)."""
    B, qlen, H, d = q.shape
    KV = kc.shape[2]
    g = H // KV
    ke = np.repeat(np.asarray(kc, np.float64), g, axis=2)
    ve = np.repeat(np.asarray(vc, np.float64), g, axis=2)
    qa = np.asarray(q, np.float64)
    out = np.zeros(qa.shape, np.float64)
    for b in range(B):
        for i in range(qlen):
            L = int(pos[b]) + i + 1
            for h in range(H):
                s = (ke[b, :L, h] @ qa[b, i, h]) / np.sqrt(d)
                p = np.exp(s - s.max())
                out[b, i, h] = (p / p.sum()) @ ve[b, :L, h]
    return out


def _rand_qkv(rng, B, qlen, KV, g, d, max_len, dtype=np.float32):
    q = rng.randn(B, qlen, KV * g, d).astype(dtype)
    kc = rng.randn(B, max_len, KV, d).astype(dtype)
    vc = rng.randn(B, max_len, KV, d).astype(dtype)
    return q, kc, vc


class TestKernelParity:
    @pytest.mark.parametrize("group", [1, 2, 4])
    @pytest.mark.parametrize("q_len", [1, 4])
    def test_fp32_parity_ragged_positions(self, group, q_len):
        """block_k=16 over max_len=48 is a three-block cell fetched only
        as far as each row's length; rows pin the pos=0 and
        pos=max_len-q_len edges plus a mid-cache position."""
        rng = np.random.RandomState(group * 10 + q_len)
        B, KV, d, max_len = 3, 2, 16, 48
        q, kc, vc = _rand_qkv(rng, B, q_len, KV, group, d, max_len)
        pos = np.array([0, 17, max_len - q_len], np.int32)
        out = np.asarray(flash_decode_attention(q, kc, vc, pos, block_k=16))
        np.testing.assert_allclose(out, _oracle(q, kc, vc, pos),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16_documented_tolerance(self):
        import jax.numpy as jnp

        rng = np.random.RandomState(7)
        B, q_len, KV, g, d, max_len = 3, 1, 2, 4, 16, 32
        q, kc, vc = _rand_qkv(rng, B, q_len, KV, g, d, max_len)
        qb, kb, vb = (jnp.asarray(a, jnp.bfloat16) for a in (q, kc, vc))
        pos = np.array([0, 9, max_len - q_len], np.int32)
        out = np.asarray(flash_decode_attention(qb, kb, vb, pos,
                                                block_k=16),
                         dtype=np.float32)
        # oracle on the bf16-rounded inputs (the kernel's actual operands)
        ref = _oracle(np.asarray(qb, np.float32), np.asarray(kb, np.float32),
                      np.asarray(vb, np.float32), pos)
        np.testing.assert_allclose(out, ref, atol=BF16_ATOL, rtol=BF16_ATOL)

    def test_scalar_position_broadcasts(self):
        rng = np.random.RandomState(11)
        q, kc, vc = _rand_qkv(rng, 2, 1, 2, 2, 8, 32)
        out = np.asarray(flash_decode_attention(q, kc, vc, 5, block_k=8))
        ref = _oracle(q, kc, vc, np.full(2, 5, np.int32))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_right_pad_garbage_is_masked(self):
        """Cache contents beyond pos + q_len (stale tokens from freed
        requests) must not reach the output — per-row length masking,
        including the boundary block's element-wise tail."""
        rng = np.random.RandomState(13)
        q, kc, vc = _rand_qkv(rng, 3, 1, 2, 2, 8, 48)
        pos = np.array([0, 17, 30], np.int32)
        clean = np.asarray(flash_decode_attention(q, kc, vc, pos, block_k=16))
        kg, vg = kc.copy(), vc.copy()
        for b in range(3):
            kg[b, pos[b] + 1:] = 1e6
            vg[b, pos[b] + 1:] = -1e6
        dirty = np.asarray(flash_decode_attention(q, kg, vg, pos, block_k=16))
        assert np.isfinite(dirty).all()
        np.testing.assert_array_equal(clean, dirty)

    def test_dead_slot_row(self):
        """A dead slot (the serving engine pins freed slots to pos 0)
        attends exactly its own step token — finite output equal to the
        single-position oracle, and no effect on live rows."""
        rng = np.random.RandomState(17)
        q, kc, vc = _rand_qkv(rng, 2, 1, 2, 2, 8, 32)
        pos = np.array([0, 20], np.int32)
        out = np.asarray(flash_decode_attention(q, kc, vc, pos, block_k=8))
        ref = _oracle(q, kc, vc, pos)
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestOnePassCells:
    """The contiguous entry is the paged kernel over an identity table:
    a cache of several cells, row lengths on every side of the cell
    boundaries, against the f64 oracle."""

    @pytest.mark.parametrize("group", [1, 4])
    @pytest.mark.parametrize("q_len", [1, 4])
    def test_lengths_across_cell_boundaries(self, group, q_len):
        import jax.numpy as jnp

        rng = np.random.RandomState(group * 100 + q_len)
        KV, d, block_k, max_len = 2, 16, 64, 1280
        cell = block_k * fd._blocks_per_cell(
            block_k, max_len // block_k, KV, d, jnp.float32, q_len * group)
        assert 2 * cell + 3 < max_len
        lens = np.array([q_len, cell - 1, cell, cell + 1, 2 * cell + 3,
                         max_len], np.int32)
        q, kc, vc = _rand_qkv(rng, len(lens), q_len, KV, group, d, max_len)
        pos = lens - q_len
        out = np.asarray(flash_decode_attention(q, kc, vc, pos,
                                                block_k=block_k))
        np.testing.assert_allclose(out, _oracle(q, kc, vc, pos),
                                   atol=2e-5, rtol=2e-5)

    def test_a_row_of_length_zero_returns_zeros(self):
        rng = np.random.RandomState(23)
        q, kc, vc = _rand_qkv(rng, 3, 1, 2, 2, 8, 64)
        pos = np.array([9, -1, 40], np.int32)   # lens = pos + 1
        out = np.asarray(flash_decode_attention(q, kc, vc, pos, block_k=16))
        assert not out[1].any()
        ref = _oracle(q, kc, vc, np.array([9, 0, 40]))
        np.testing.assert_allclose(out[[0, 2]], ref[[0, 2]], atol=2e-5,
                                   rtol=2e-5)


    def test_the_layers_of_a_program_share_one_trace_of_the_kernel(self):
        """Calls of one set of shapes share one ``pallas_call`` object,
        whose jit traces the kernel once: a 24-layer program traced it
        24 times, a third of ``warmup()``'s seconds an executable."""
        import jax

        rng = np.random.RandomState(29)
        q, kc, vc = _rand_qkv(rng, 2, 1, 2, 2, 8, 64)
        pos = np.array([9, 40], np.int32)
        traced = []
        real = fd._decode_kernel

        def counting(*a, **kw):
            traced.append(1)
            return real(*a, **kw)

        def three_layers(q, kc, vc, pos):
            return [flash_decode_attention(q * s, kc, vc, pos, block_k=16)
                    for s in (1.0, 2.0, 3.0)]

        fd._decode_call.cache_clear()
        fd._decode_kernel = counting
        try:
            outs = jax.jit(three_layers)(q, kc, vc, pos)
        finally:
            fd._decode_kernel = real
            fd._decode_call.cache_clear()
        assert len(traced) == 1
        for s, out in zip((1.0, 2.0, 3.0), outs):
            np.testing.assert_allclose(
                np.asarray(out), _oracle(q * s, kc, vc, pos), atol=2e-5,
                rtol=2e-5)


class TestGroupedFallback:
    def _mask(self, B, s, max_len, pos):
        kpos = np.arange(max_len)
        qpos = pos + np.arange(s)
        m = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < pos + s)
        return np.where(m[None, None], 0.0, -1e30).astype(np.float32)

    # The grouped einsum is the same math per query head, but XLA lowers
    # the [b, kv, g, s, t] contraction with different reduction groupings
    # than the repeated [b, h, s, t] one — last-ulp reassociation noise
    # (measured 1.8e-7 abs on these shapes), not a semantic delta. The
    # regression is pinned at ulp-class tolerance; token-level decode
    # parity (TestModelDispatch) is asserted EXACTLY.
    ULP_TOL = dict(atol=1e-6, rtol=1e-5)

    def test_identical_to_repeat_kv_path(self):
        """The regression oracle for the de-bloated XLA fallback: the
        grouped einsum must reproduce the old repeat_kv + SDPA decode
        path (ulp-class tolerance — see ULP_TOL note)."""
        rng = np.random.RandomState(19)
        B, s, KV, g, d, max_len = 2, 1, 2, 4, 16, 24
        q = rng.randn(B, s, KV * g, d).astype(np.float32)
        k = rng.randn(B, max_len, KV, d).astype(np.float32)
        v = rng.randn(B, max_len, KV, d).astype(np.float32)
        mask = self._mask(B, s, max_len, 10)
        old = F.scaled_dot_product_attention(
            paddle.Tensor(q), repeat_kv(paddle.Tensor(k), g),
            repeat_kv(paddle.Tensor(v), g), attn_mask=paddle.Tensor(mask))
        new = F.grouped_query_sdpa(paddle.Tensor(q), paddle.Tensor(k),
                                   paddle.Tensor(v),
                                   attn_mask=paddle.Tensor(mask))
        np.testing.assert_allclose(old.numpy(), new.numpy(), **self.ULP_TOL)

    def test_bool_and_per_head_masks(self):
        rng = np.random.RandomState(23)
        B, s, KV, g, d, T = 2, 3, 2, 2, 8, 12
        q = rng.randn(B, s, KV * g, d).astype(np.float32)
        k = rng.randn(B, T, KV, d).astype(np.float32)
        v = rng.randn(B, T, KV, d).astype(np.float32)
        bool_mask = rng.rand(B, 1, s, T) > 0.3
        bool_mask[..., 0] = True  # keep every row attendable
        old = F.scaled_dot_product_attention(
            paddle.Tensor(q), repeat_kv(paddle.Tensor(k), g),
            repeat_kv(paddle.Tensor(v), g), attn_mask=paddle.Tensor(bool_mask))
        new = F.grouped_query_sdpa(paddle.Tensor(q), paddle.Tensor(k),
                                   paddle.Tensor(v),
                                   attn_mask=paddle.Tensor(bool_mask))
        np.testing.assert_allclose(old.numpy(), new.numpy(), **self.ULP_TOL)
        per_head = np.where(rng.rand(B, KV * g, s, T) > 0.3, 0.0,
                            -1e30).astype(np.float32)
        per_head[..., 0] = 0.0
        old = F.scaled_dot_product_attention(
            paddle.Tensor(q), repeat_kv(paddle.Tensor(k), g),
            repeat_kv(paddle.Tensor(v), g), attn_mask=paddle.Tensor(per_head))
        new = F.grouped_query_sdpa(paddle.Tensor(q), paddle.Tensor(k),
                                   paddle.Tensor(v),
                                   attn_mask=paddle.Tensor(per_head))
        np.testing.assert_allclose(old.numpy(), new.numpy(), **self.ULP_TOL)


@pytest.fixture(scope="module")
def tiny_llama():
    # module-scoped: the dispatch tests flip the env flag, which is part
    # of generate's jit cache key — sharing the model shares executables
    # across tests instead of recompiling per test
    paddle.seed(0)
    cfg = LlamaConfig.tiny()  # 4 heads over 2 kv heads: GQA 2x
    return LlamaForCausalLM(cfg), cfg


class TestModelDispatch:
    def _gen_all_modes(self, model, p, **kw):
        scan = generation.generate(model, p, max_new_tokens=6, **kw).numpy()
        py = generation.generate(model, p, max_new_tokens=6,
                                 loop_mode="python", **kw).numpy()
        samp = generation.generate(model, p, max_new_tokens=6,
                                   do_sample=True, temperature=0.9, top_k=8,
                                   seed=3, **kw).numpy()
        return scan, py, samp

    def test_llama_generate_parity_on_vs_off(self, tiny_llama, monkeypatch):
        model, cfg = tiny_llama
        rng = np.random.RandomState(29)
        p = rng.randint(1, cfg.vocab_size, (2, 9)).astype("int32")
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "0")
        off = self._gen_all_modes(model, p)
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "1")
        on = self._gen_all_modes(model, p)
        for a, b in zip(off, on):
            np.testing.assert_array_equal(a, b)

    def test_gpt_generate_parity_on_vs_off(self, monkeypatch):
        """GPT (learned positions, no GQA): the dispatch in gpt.py is
        loop-mode-agnostic, so scan + sampled cover it (llama sweeps the
        full mode surface above)."""
        paddle.seed(1)
        model = GPTForCausalLM(GPTConfig.tiny())
        rng = np.random.RandomState(31)
        p = rng.randint(1, 256, (2, 5)).astype("int32")
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "0")
        off = generation.generate(model, p, max_new_tokens=6).numpy()
        off_s = generation.generate(model, p, max_new_tokens=6,
                                    do_sample=True, top_k=8, seed=3).numpy()
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "1")
        on = generation.generate(model, p, max_new_tokens=6).numpy()
        on_s = generation.generate(model, p, max_new_tokens=6,
                                   do_sample=True, top_k=8, seed=3).numpy()
        np.testing.assert_array_equal(off, on)
        np.testing.assert_array_equal(off_s, on_s)

    def test_ragged_prompts_fall_back_with_reason(self, tiny_llama,
                                                  monkeypatch):
        """Ragged left-padded prompts bring their own attention mask —
        the dispatch must fall back (reason external_mask) and still
        decode identically to the kernel-off path."""
        model, cfg = tiny_llama
        rng = np.random.RandomState(37)
        prompts = [rng.randint(1, cfg.vocab_size, n).tolist() for n in (4, 8)]
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "0")
        off = generation.generate(model, prompts, max_new_tokens=5,
                                  pad_token_id=0).numpy()
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "1")
        before = fd._fd_fallbacks.labels("external_mask").value()
        on = generation.generate(model, prompts, max_new_tokens=5,
                                 pad_token_id=0).numpy()
        np.testing.assert_array_equal(off, on)
        assert fd._fd_fallbacks.labels("external_mask").value() > before

    def test_counters_hits_and_disabled(self, tiny_llama, monkeypatch):
        model, cfg = tiny_llama
        rng = np.random.RandomState(41)
        # fresh (B, S) per flag state: the counters fire at TRACE time
        # (python-side dispatch), so cached executables would not count
        p = rng.randint(1, cfg.vocab_size, (1, 3)).astype("int32")
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "1")
        h0 = fd._fd_hits.labels("llama").value()
        generation.generate(model, p, max_new_tokens=3)
        assert fd._fd_hits.labels("llama").value() > h0
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "0")
        d0 = fd._fd_fallbacks.labels("disabled").value()
        generation.generate(model, p, max_new_tokens=4)
        assert fd._fd_fallbacks.labels("disabled").value() > d0

    def test_grad_mode_falls_back(self, tiny_llama, monkeypatch):
        """With autograd recording, the forward-only kernel must refuse
        (reason grad_mode) and the XLA path must run fine."""
        model, cfg = tiny_llama
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "1")
        caches = [{"k": paddle.Tensor(c["k"]), "v": paddle.Tensor(c["v"])}
                  for c in generation.make_kv_caches(cfg, 1, 16, "float32")]
        ids = paddle.Tensor(np.array([[5]], np.int32))
        g0 = fd._fd_fallbacks.labels("grad_mode").value()
        logits, _ = model(ids, kv_caches=caches, position_offset=3)
        assert np.isfinite(logits.numpy()).all()
        assert fd._fd_fallbacks.labels("grad_mode").value() > g0


class TestServingE2E:
    def test_mixed_waves_match_generate_with_kernel_on(self, tiny_llama,
                                                       monkeypatch):
        """The acceptance oracle: with the kernel enabled end to end,
        mixed greedy/sampled waves through the engine stay bit-identical
        to standalone generate(), and enabling the kernel adds exactly
        ONE executable to serving.step across all waves (no per-wave
        retraces) — the recompile-monitor satellite check."""
        model, cfg = tiny_llama
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "1")
        before = recompile.entry_stats().get("serving.step",
                                             {"compiles": 0, "retraces": 0})
        eng = serving.ServingEngine(model, max_slots=2, max_len=64,
                                    max_queue_depth=16)
        rng = np.random.RandomState(43)
        for wave in range(3):
            # per-wave FRESH prompts/seeds over a FIXED (S, N, params)
            # grid: waves still mix greedy/sampled and refill slots, but
            # the generate() oracle executables compile once in wave 0
            # and are reused after (keeps this acceptance test cheap)
            specs = [dict(max_new_tokens=3 + i % 3, do_sample=bool(i % 2),
                          top_k=6, seed=wave * 10 + i) for i in range(4)]
            prompts = [rng.randint(1, cfg.vocab_size,
                                   3 + i % 4).astype("int32")
                       for i in range(4)]
            reqs = [eng.submit(p, **s) for p, s in zip(prompts, specs)]
            eng.run_until_idle()
            for r, p, s in zip(reqs, prompts, specs):
                assert r.status == serving.RequestStatus.COMPLETED
                got = np.asarray(r.result(timeout=1.0))
                ref = generation.generate(model, p[None],
                                          **s).numpy()[0, len(p):]
                np.testing.assert_array_equal(got, ref)
        after = recompile.entry_stats()["serving.step"]
        assert after["compiles"] - before["compiles"] == 1
        assert after["retraces"] - before["retraces"] == 0

    def test_dead_slots_pin_positions_to_zero(self, tiny_llama):
        """Freed slots must sit at pos 0 (one KV block of flash-decode
        cost) while the pool keeps stepping for live requests."""
        model, cfg = tiny_llama
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        rng = np.random.RandomState(47)
        long_req = eng.submit(rng.randint(1, cfg.vocab_size, 5), max_new_tokens=20)
        short_req = eng.submit(rng.randint(1, cfg.vocab_size, 4), max_new_tokens=2)
        while not short_req.done:
            eng.step()
        assert not long_req.done
        eng.step()  # one more pool step with slot 1 dead
        pos = np.asarray(eng._state["pos"])
        free = [i for i, r in enumerate(eng._slot_req) if r is None]
        assert free and all(pos[i] == 0 for i in free)
        eng.run_until_idle()
        assert long_req.status == serving.RequestStatus.COMPLETED
