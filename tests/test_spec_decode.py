"""Speculative decoding: the draft+verify lane on the paged serving
engine and the offline ``generate(draft_model=...)`` oracle.

Oracles:
- BIT-PARITY: speculative output — greedy AND sampled — is exactly the
  non-speculative output for the same prompt/seed/params, for ANY draft
  model (the common-noise coupling makes the draft a pure throughput
  knob: a random draft is the worst case and must still be exact).
- ACCEPT RATE: a draft that is functionally the target (self-draft, or
  a truncated draft under an identity-extended target) accepts every
  proposal — the coupling and the draft-KV bookkeeping leak nothing.
- ONE EXECUTABLE EACH: the draft and verify programs compile exactly
  once across ≥3 request waves with ragged accept-length patterns
  (accept lengths, bundle widths, block tables are all traced data).
- LIFECYCLE: preemption mid-speculation resumes bit-identically; EOS
  inside an accepted run truncates delivery; mixed spec/non-spec slots
  share the pool; config errors are loud and actionable.
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
from paddle_tpu.observability import tracing
from paddle_tpu.pallas_kernels.decode_attention import MAX_SPEC_K

SEED = 20250805


def zero_tail_layers(model, keep: int):
    """Make decoder layers >= ``keep`` exact identities: in a pre-norm
    residual block, zeroing the attention output projection and the MLP
    down/out projection leaves x + 0 + 0 = x bitwise, so the model IS
    its first ``keep`` layers. ``truncated_draft(model, keep)`` is then
    functionally identical to the target — a deterministic 100%-accept
    configuration for the coupling tests."""
    for name, p in model.state_dict().items():
        for i in range(keep, model.config.num_hidden_layers):
            if (f"layers.{i}.self_attn.o_proj" in name
                    or f"layers.{i}.mlp.down_proj" in name
                    or f"h.{i}.attn.out_proj" in name
                    or f"h.{i}.fc_out" in name):
                p._data = p._data * 0.0


@pytest.fixture(scope="module")
def llama_pair():
    """Random 2-layer llama target + INDEPENDENT random 1-layer draft:
    the adversarial pair (accepts are rare, rejection paths dominate)."""
    paddle.seed(0)
    cfg = LlamaConfig.tiny(max_position_embeddings=256)
    target = LlamaForCausalLM(cfg)
    paddle.seed(99)
    draft = LlamaForCausalLM(
        LlamaConfig.tiny(num_hidden_layers=1, max_position_embeddings=256))
    return target, draft, cfg


@pytest.fixture(scope="module")
def coupled_pair():
    """Identity-extended 4-layer target + truncated 2-layer draft:
    functionally identical models (bitwise equal logits), so every
    draft should be accepted."""
    paddle.seed(3)
    cfg = LlamaConfig.tiny(num_hidden_layers=4, max_position_embeddings=256)
    target = LlamaForCausalLM(cfg)
    zero_tail_layers(target, 2)
    draft = generation.truncated_draft(target, 2)
    return target, draft, cfg


@pytest.fixture(scope="module")
def gpt_pair():
    paddle.seed(5)
    cfg = GPTConfig.tiny(max_position_embeddings=256)
    target = GPTForCausalLM(cfg)
    draft = generation.truncated_draft(target, 1)
    return target, draft, cfg


def _prompt(rng, cfg, n):
    return rng.randint(1, cfg.vocab_size, n).astype("int32")


def _ref(model, prompt, **params):
    return generation.generate(model, prompt[None], **params).numpy()[
        0, len(prompt):]


# ---------------------------------------------------------------------------
# offline oracle: generate(draft_model=...)
# ---------------------------------------------------------------------------


class TestOfflineOracle:
    def test_greedy_parity_llama(self, llama_pair):
        target, draft, cfg = llama_pair
        rng = np.random.RandomState(SEED)
        ids = _prompt(rng, cfg, 9)[None]
        ref = generation.generate(target, ids, max_new_tokens=17).numpy()
        out = generation.generate(target, ids, max_new_tokens=17,
                                  draft_model=draft, spec_k=4).numpy()
        assert np.array_equal(out, ref)

    def test_greedy_parity_gpt(self, gpt_pair):
        target, draft, cfg = gpt_pair
        rng = np.random.RandomState(SEED + 1)
        ids = _prompt(rng, cfg, 6)[None]
        ref = generation.generate(target, ids, max_new_tokens=13).numpy()
        out = generation.generate(target, ids, max_new_tokens=13,
                                  draft_model=draft, spec_k=3).numpy()
        assert np.array_equal(out, ref)

    def test_greedy_parity_batched_ragged_accepts(self, llama_pair):
        """B=2 rows accept at different rates each round (per-row
        position bump) — greedy output is key-independent and must be
        bit-identical at any batch size."""
        target, draft, cfg = llama_pair
        rng = np.random.RandomState(SEED + 2)
        ids = _prompt(rng, cfg, 12).reshape(2, 6)
        ref = generation.generate(target, ids, max_new_tokens=9).numpy()
        out = generation.generate(target, ids, max_new_tokens=9,
                                  draft_model=draft, spec_k=3).numpy()
        assert np.array_equal(out, ref)

    def test_sampled_b1_parity(self, llama_pair):
        """B=1 sampled: the speculative chain walks the exact
        key-per-token split walk, so sampled output is bit-identical to
        plain generate too (top-k and top-p-only rows both)."""
        target, draft, cfg = llama_pair
        rng = np.random.RandomState(SEED + 3)
        ids = _prompt(rng, cfg, 8)[None]
        for kw in (dict(do_sample=True, temperature=0.8, top_k=7, seed=11),
                   dict(do_sample=True, top_p=0.9, seed=12)):
            ref = generation.generate(target, ids, max_new_tokens=14,
                                      **kw).numpy()
            out = generation.generate(target, ids, max_new_tokens=14,
                                      draft_model=draft, spec_k=4,
                                      **kw).numpy()
            assert np.array_equal(out, ref), kw

    def test_eos_posthoc_mask_matches_scan_mode(self, llama_pair):
        target, draft, cfg = llama_pair
        rng = np.random.RandomState(SEED + 4)
        ids = _prompt(rng, cfg, 7)[None]
        base = generation.generate(target, ids, max_new_tokens=12).numpy()
        eos = int(base[0, 7 + 3])  # force an early EOS hit
        ref = generation.generate(target, ids, max_new_tokens=12,
                                  eos_token_id=eos).numpy()
        out = generation.generate(target, ids, max_new_tokens=12,
                                  eos_token_id=eos, draft_model=draft,
                                  spec_k=4).numpy()
        assert np.array_equal(out, ref)

    def test_validation_errors(self, llama_pair):
        target, draft, cfg = llama_pair
        rng = np.random.RandomState(SEED + 5)
        ids = _prompt(rng, cfg, 5)[None]
        paddle.seed(1)
        alien = LlamaForCausalLM(LlamaConfig.tiny(
            vocab_size=cfg.vocab_size * 2, max_position_embeddings=256))
        with pytest.raises(ValueError, match="vocab mismatch"):
            generation.generate(target, ids, max_new_tokens=4,
                                draft_model=alien)
        with pytest.raises(ValueError, match="stream"):
            generation.generate(target, ids, max_new_tokens=4,
                                draft_model=draft, stream=True)
        with pytest.raises(ValueError, match="ragged"):
            generation.generate(target, [[3, 4], [5, 6, 7]],
                                max_new_tokens=4, pad_token_id=0,
                                draft_model=draft)

    def test_truncated_draft_shares_weights_and_vocab(self, llama_pair):
        target, _, cfg = llama_pair
        d = generation.truncated_draft(target, 1)
        assert d.config.num_hidden_layers == 1
        assert d.config.vocab_size == cfg.vocab_size
        got = d.llama.layers[0].self_attn.q_proj.weight.numpy()
        want = target.llama.layers[0].self_attn.q_proj.weight.numpy()
        assert np.array_equal(got, want)
        with pytest.raises(ValueError, match="num_layers"):
            generation.truncated_draft(target, 99)


# ---------------------------------------------------------------------------
# serving engine: bit-parity
# ---------------------------------------------------------------------------


class TestEngineParity:
    def test_greedy_and_sampled_parity_llama(self, llama_pair):
        """Random (worst-case) draft on the paged spec engine: every
        request — greedy, top-k, top-p-only — bit-matches standalone
        generate; the draft only ever changes round counts."""
        target, draft, cfg = llama_pair
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=3,
                                    max_len=128, spec_k=4)
        rng = np.random.RandomState(SEED + 6)
        cases = [
            (_prompt(rng, cfg, 5), dict(max_new_tokens=12)),
            (_prompt(rng, cfg, 37), dict(max_new_tokens=9, do_sample=True,
                                         temperature=0.8, top_k=8, seed=3)),
            (_prompt(rng, cfg, 9), dict(max_new_tokens=15, do_sample=True,
                                        top_p=0.9, seed=4)),
            (_prompt(rng, cfg, 14), dict(max_new_tokens=20)),
        ]
        reqs = [eng.submit(p, **kw) for p, kw in cases]
        eng.run_until_idle()
        for (p, kw), r in zip(cases, reqs):
            assert r.status == serving.RequestStatus.COMPLETED
            assert np.array_equal(r.result(timeout=5), _ref(target, p, **kw))

    def test_greedy_parity_gpt(self, gpt_pair):
        target, draft, cfg = gpt_pair
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=2,
                                    max_len=96, spec_k=4)
        rng = np.random.RandomState(SEED + 7)
        cases = [(_prompt(rng, cfg, 6), dict(max_new_tokens=14)),
                 (_prompt(rng, cfg, 11), dict(max_new_tokens=10,
                                              do_sample=True, top_k=5,
                                              seed=8))]
        reqs = [eng.submit(p, **kw) for p, kw in cases]
        eng.run_until_idle()
        for (p, kw), r in zip(cases, reqs):
            assert np.array_equal(r.result(timeout=5), _ref(target, p, **kw))

    def test_sampled_replay_parity(self, llama_pair):
        """Same request on a fresh engine replays bit-identically (the
        chain is a pure function of seed + emitted count)."""
        target, draft, cfg = llama_pair
        rng = np.random.RandomState(SEED + 8)
        p = _prompt(rng, cfg, 8)
        outs = []
        for _ in range(2):
            eng = serving.ServingEngine(target, draft_model=draft,
                                        max_slots=2, max_len=128, spec_k=3)
            r = eng.submit(p, max_new_tokens=11, do_sample=True,
                           temperature=1.1, top_k=12, seed=21)
            eng.run_until_idle()
            outs.append(r.result(timeout=5))
        assert outs[0] == outs[1]

    def test_mixed_spec_and_nonspec_slots(self, coupled_pair):
        """Opted-out rows (spec_k=0) ride the verify bundle at width 1;
        spec rows draft beside them. Everyone's output is exact, and
        draft accounting only ever charges the spec rows."""
        target, draft, cfg = coupled_pair
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=3,
                                    max_len=128, spec_k=4)
        rng = np.random.RandomState(SEED + 9)
        p_spec = _prompt(rng, cfg, 7)
        p_out = _prompt(rng, cfg, 5)
        p_small = _prompt(rng, cfg, 9)
        r_spec = eng.submit(p_spec, max_new_tokens=12)
        r_out = eng.submit(p_out, max_new_tokens=12, spec_k=0)
        r_small = eng.submit(p_small, max_new_tokens=12, spec_k=2)
        eng.run_until_idle()
        assert np.array_equal(r_spec.result(5),
                              _ref(target, p_spec, max_new_tokens=12))
        assert np.array_equal(r_out.result(5),
                              _ref(target, p_out, max_new_tokens=12))
        assert np.array_equal(r_small.result(5),
                              _ref(target, p_small, max_new_tokens=12))
        assert r_out.spec_drafted == 0
        assert r_spec.spec_drafted > 0
        # per-request k cap honored: width-2 drafts only
        assert r_small.spec_drafted > 0
        assert r_small.spec_accepted <= r_small.spec_drafted

    def test_eos_inside_accepted_run_truncates(self, coupled_pair):
        """EOS landing mid-bundle (the coupled draft accepts everything,
        so multi-token rounds are guaranteed): delivery stops at EOS,
        nothing after it leaks, parity with generate's early-exit
        semantics."""
        target, draft, cfg = coupled_pair
        rng = np.random.RandomState(SEED + 10)
        p = _prompt(rng, cfg, 6)
        base = _ref(target, p, max_new_tokens=16)
        eos = int(base[5])  # mid-chain token becomes EOS
        ref = _ref(target, p, max_new_tokens=16, eos_token_id=eos)
        stop = int(np.argmax(ref == eos)) + 1 if eos in ref else len(ref)
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=2,
                                    max_len=128, spec_k=4)
        r = eng.submit(p, max_new_tokens=16, eos_token_id=eos)
        eng.run_until_idle()
        got = r.result(timeout=5)
        assert got == list(ref[:stop])
        assert r.status == serving.RequestStatus.COMPLETED

    def test_plain_engine_unchanged_without_draft(self, llama_pair):
        """No draft_model -> no spec machinery: the engine has no spec
        attrs in play and stats say disabled."""
        target, _, cfg = llama_pair
        eng = serving.ServingEngine(target, max_slots=2, max_len=128)
        assert eng.spec is False
        assert eng.stats()["spec"] == {"enabled": False}


# ---------------------------------------------------------------------------
# accept rate: the coupling is airtight
# ---------------------------------------------------------------------------


class TestAcceptRate:
    def test_self_draft_accepts_everything(self, llama_pair):
        """draft == target object: every proposal must be accepted,
        greedy AND sampled — any rejection is a leak in the draft-KV
        bookkeeping (e.g. the full-accept hole) or the key coupling."""
        target, _, cfg = llama_pair
        eng = serving.ServingEngine(target, draft_model=target, max_slots=2,
                                    max_len=128, spec_k=4)
        rng = np.random.RandomState(SEED + 11)
        r1 = eng.submit(_prompt(rng, cfg, 7), max_new_tokens=16)
        r2 = eng.submit(_prompt(rng, cfg, 9), max_new_tokens=12,
                        do_sample=True, temperature=0.9, top_k=8, seed=5)
        eng.run_until_idle()
        st = eng.stats()["spec"]
        assert st["accept_rate"] == 1.0
        assert st["drafted_tokens"] == st["accepted_tokens"] > 0
        assert r1.spec_accepted == r1.spec_drafted
        assert r2.spec_accepted == r2.spec_drafted

    def test_coupled_truncated_draft_accepts_everything(self, coupled_pair):
        """Identity-extended target + truncated draft: functionally one
        model in two sizes — accept rate 1.0 through the REAL two-model
        path (separate pools, separate params)."""
        target, draft, cfg = coupled_pair
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=1,
                                    max_len=128, spec_k=4)
        rng = np.random.RandomState(SEED + 12)
        r = eng.submit(_prompt(rng, cfg, 7), max_new_tokens=16)
        eng.run_until_idle()
        st = eng.stats()["spec"]
        assert st["accept_rate"] == 1.0
        assert st["accept_len"]["p50"] == 4.0
        # 16 tokens in ceil(16 / 5) = 4 rounds, not 16 steps
        assert st["rounds"] < 16
        assert r.status == serving.RequestStatus.COMPLETED


# ---------------------------------------------------------------------------
# preemption during speculation
# ---------------------------------------------------------------------------


class TestPreemption:
    def test_preempt_mid_speculation_resumes_bit_identical(self, llama_pair):
        """Oversubscribed pool forces preemption while rounds are
        multi-token wide; the resumed request replays its chain from
        emitted-token count alone and finishes bit-identical (greedy and
        sampled both), with zero re-delivery."""
        target, draft, cfg = llama_pair
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=2,
                                    max_len=64, block_size=8, num_blocks=10,
                                    spec_k=3)
        rng = np.random.RandomState(SEED + 13)
        pa = _prompt(rng, cfg, 10)
        pb = _prompt(rng, cfg, 12)
        ra = eng.submit(pa, max_new_tokens=30, do_sample=True, top_k=5,
                        seed=7)
        rb = eng.submit(pb, max_new_tokens=30)
        eng.run_until_idle()
        assert eng._preempt_count > 0, "pool was sized to force preemption"
        assert np.array_equal(
            ra.result(5), _ref(target, pa, max_new_tokens=30,
                               do_sample=True, top_k=5, seed=7))
        assert np.array_equal(
            rb.result(5), _ref(target, pb, max_new_tokens=30))
        preempted = ra if ra.preempt_count else rb
        assert preempted.preempt_count > 0
        assert len(preempted.output_tokens) == 30  # nothing re-delivered


# ---------------------------------------------------------------------------
# one-compile invariant
# ---------------------------------------------------------------------------


class TestOneCompile:
    def test_draft_and_verify_compile_once_across_waves(self, llama_pair):
        """3 waves of mixed spec/non-spec, greedy/sampled, ragged-length
        requests: the draft and verify executables each compile EXACTLY
        once and never retrace — accept lengths, bundle widths, block
        tables, and occupancy are all traced data. The plain decode step
        is never even traced on a spec engine."""
        target, draft, cfg = llama_pair
        stats0 = recompile.entry_stats()
        before = {n: stats0.get(n, {"compiles": 0, "retraces": 0})
                  for n in ("serving.spec_draft", "serving.spec_verify",
                            "serving.step")}
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=2,
                                    max_len=128, max_queue_depth=32,
                                    prefill_chunk=32, spec_k=3)
        rng = np.random.RandomState(SEED + 14)
        for wave in range(3):
            reqs = [eng.submit(_prompt(rng, cfg, 3 + 11 * ((wave + i) % 7)),
                               max_new_tokens=2 + (wave + i) % 5,
                               do_sample=bool(i % 2), seed=i, top_k=5,
                               spec_k=None if i % 3 else 0)
                    for i in range(5)]
            eng.run_until_idle()
            assert all(r.status == serving.RequestStatus.COMPLETED
                       for r in reqs)
        stats1 = recompile.entry_stats()
        for name in ("serving.spec_draft", "serving.spec_verify"):
            after = stats1[name]
            assert after["compiles"] - before[name]["compiles"] == 1, name
            assert after["retraces"] - before[name]["retraces"] == 0, name
        step = stats1.get("serving.step", {"compiles": 0})
        assert step["compiles"] - before["serving.step"]["compiles"] == 0
        chunk = stats1["serving.prefill_chunk"]
        assert chunk["retraces"] == 0


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


class TestValidation:
    def test_spec_k_bounds(self):
        with pytest.raises(ValueError, match="MAX_PAGED_Q_LEN"):
            serving.ServingConfig(spec_k=MAX_SPEC_K + 1)
        serving.ServingConfig(spec_k=MAX_SPEC_K)  # boundary OK

    def test_draft_with_zero_k_is_rejected(self, llama_pair):
        target, draft, _ = llama_pair
        with pytest.raises(ValueError, match="spec_k"):
            serving.ServingEngine(target, draft_model=draft, spec_k=0,
                                  max_len=128)

    def test_vocab_mismatch_is_actionable(self, llama_pair):
        target, _, cfg = llama_pair
        paddle.seed(2)
        alien = LlamaForCausalLM(LlamaConfig.tiny(
            vocab_size=cfg.vocab_size * 2, max_position_embeddings=256))
        with pytest.raises(ValueError, match="truncated_draft"):
            serving.ServingEngine(target, draft_model=alien, max_len=128)

    def test_draft_position_table_too_short(self, llama_pair):
        target, _, cfg = llama_pair
        paddle.seed(4)
        short = LlamaForCausalLM(LlamaConfig.tiny(
            num_hidden_layers=1, max_position_embeddings=64))
        with pytest.raises(ValueError, match="DRAFT model's"):
            serving.ServingEngine(target, draft_model=short, max_len=128)


# ---------------------------------------------------------------------------
# observability: metrics, /stats, /debug/requests, trace lane
# ---------------------------------------------------------------------------


class TestObservability:
    def test_metrics_stats_http_and_trace(self, coupled_pair):
        target, draft, cfg = coupled_pair
        from paddle_tpu.serving import metrics as sm

        drafted0 = sm.spec_drafted_tokens.value()
        accepted0 = sm.spec_accepted_tokens.value()
        rejected0 = sm.spec_rejected_tokens.value()
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=2,
                                    max_len=128, spec_k=4)
        rng = np.random.RandomState(SEED + 15)
        r = eng.submit(_prompt(rng, cfg, 7), max_new_tokens=13)
        r2 = eng.submit(_prompt(rng, cfg, 5), max_new_tokens=6, spec_k=0)
        eng.run_until_idle()
        drafted = sm.spec_drafted_tokens.value() - drafted0
        accepted = sm.spec_accepted_tokens.value() - accepted0
        rejected = sm.spec_rejected_tokens.value() - rejected0
        assert drafted == accepted + rejected > 0
        assert drafted == r.spec_drafted + r2.spec_drafted

        st = eng.stats()["spec"]
        assert st["enabled"] and st["k"] == 4
        assert st["accept_len"]["count"] > 0
        assert 0.0 <= st["accept_rate"] <= 1.0

        # the accepted-k instants and the engine-lane spans ride the
        # PR-7 trace; the verify-path preflight instant fired at init
        counts = tracing.span_counts()
        assert counts.get("spec_accept", 0) > 0
        assert counts.get("serving.spec_draft", 0) > 0
        assert counts.get("serving.spec_verify", 0) > 0
        assert counts.get("spec_verify_path", 0) > 0
        ev = tracing.events(trace=r.id, name="spec_accept")
        assert ev and {"drafted", "accepted", "emitted"} <= set(
            ev[0]["args"])

        row = r.debug_row()
        assert row["spec_drafted"] == r.spec_drafted
        assert row["spec_accept_rate"] == 1.0  # coupled draft
        assert r2.debug_row()["spec_k"] == 0

        port = serving.start_serving_http_server(eng, port=0)
        try:
            stats = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=10).read())
            assert stats["spec"]["enabled"] is True
            assert stats["spec"]["accept_rate"] == 1.0
            body = json.dumps({
                "prompt": _prompt(rng, cfg, 4).tolist(),
                "max_new_tokens": 6, "spec_k": 2}).encode()
            resp = json.loads(urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/generate", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=30).read())
            assert resp["status"] == "completed"
            assert resp["spec_drafted"] >= resp["spec_accepted"] >= 0
            dbg = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/requests",
                timeout=10).read())
            recent = {row["request_id"]: row for row in dbg["recent"]}
            assert recent[r.id]["spec_accepted"] == r.spec_accepted
        finally:
            serving.stop_serving_http_server()
            eng.stop()

    def test_scheduler_counts_spec_opt_outs(self, llama_pair):
        target, draft, cfg = llama_pair
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=1,
                                    max_len=128, spec_k=2)
        rng = np.random.RandomState(SEED + 16)
        # fill the single slot, then queue one opt-out + one default
        reqs = [eng.submit(_prompt(rng, cfg, 5), max_new_tokens=4),
                eng.submit(_prompt(rng, cfg, 5), max_new_tokens=4,
                           spec_k=0),
                eng.submit(_prompt(rng, cfg, 5), max_new_tokens=4)]
        eng.step()
        assert eng.scheduler.depth_spec_opted_out() == 1
        assert eng.stats()["spec"]["queue_spec_opted_out"] == 1
        eng.run_until_idle()
        assert all(r.status == serving.RequestStatus.COMPLETED
                   for r in reqs)
