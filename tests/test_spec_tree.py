"""Tree speculative decoding: multi-candidate draft trees verified in
one paged flash-decode call (``spec_tree`` on generate() and the
serving engine).

Oracles:
- KERNEL: the q_len>1 bundle cell with an ancestor mask matches a dense
  f64 SDPA with visibility = past-KV OR ancestor; a causal
  lower-triangular ancestor mask reproduces the default (chain) path
  BITWISE, so the chain lane never pays for the tree operand.
- BIT-PARITY: tree-speculative output — greedy AND sampled — is exactly
  the non-speculative output for the same prompt/seed/params (llama AND
  gpt). All depth-t tree nodes verify with the chain's t-th subkey and
  the draft's branch-0 proposals reuse the exact chain key (siblings
  fold_in their BFS index), so the accepted root-to-leaf path IS a
  chain-lane walk: the tree only changes round counts.
- ONE EXECUTABLE EACH: tree draft/verify compile exactly once across 3
  ragged waves of mixed tree/opt-out/depth-clamped requests, and a
  chain engine in the same process keeps its own executables without
  cross-retracing.
- LIFECYCLE: preemption mid-tree resumes bit-identically (replay is a
  pure function of seed + emitted count, same as the chain lane); EOS
  inside an accepted path truncates delivery; config errors are loud.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import generation, serving
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.observability import recompile, tracing
from paddle_tpu.pallas_kernels.decode_attention import (
    MAX_PAGED_Q_LEN, spec_tree_width, spec_verify_eligibility)

SEED = 20250807


@pytest.fixture(scope="module")
def llama_pair():
    """Random 2-layer target + INDEPENDENT random 1-layer draft: the
    adversarial pair (deep accepts are rare, rollback paths dominate)."""
    paddle.seed(0)
    cfg = LlamaConfig.tiny(max_position_embeddings=256)
    target = LlamaForCausalLM(cfg)
    paddle.seed(99)
    draft = LlamaForCausalLM(
        LlamaConfig.tiny(num_hidden_layers=1, max_position_embeddings=256))
    return target, draft, cfg


@pytest.fixture(scope="module")
def coupled_pair():
    """Identity-extended target + truncated draft: functionally one
    model, so greedy accepts the full branch-0 path every round."""
    paddle.seed(3)
    cfg = LlamaConfig.tiny(num_hidden_layers=4, max_position_embeddings=256)
    target = LlamaForCausalLM(cfg)
    for name, p in target.state_dict().items():
        for i in range(2, cfg.num_hidden_layers):
            if (f"layers.{i}.self_attn.o_proj" in name
                    or f"layers.{i}.mlp.down_proj" in name):
                p._data = p._data * 0.0
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
# the flattened tree plan
# ---------------------------------------------------------------------------


class TestTreePlan:
    def test_width_and_offsets(self):
        assert spec_tree_width([4, 2, 2]) == 29
        plan = generation.spec_tree_plan([4, 2, 2])
        assert plan["nodes"] == 29 and plan["depth"] == 3
        assert list(plan["offsets"]) == [0, 1, 5, 13, 29]

    def test_ancestor_closure(self):
        """anc[i] is exactly i's root-to-self path; parent/depth/anc_idx
        agree with each other on every node."""
        plan = generation.spec_tree_plan([3, 2])
        parent = np.asarray(plan["parent"])
        depth = np.asarray(plan["depth_vec"])
        anc = np.asarray(plan["anc"])
        anc_idx = np.asarray(plan["anc_idx"])
        w = int(plan["nodes"])
        for i in range(w):
            path, j = [], i
            while True:
                path.append(j)
                if j == 0:
                    break
                j = int(parent[j])
            assert depth[i] == len(path) - 1
            expect = np.zeros(w, bool)
            expect[path] = True
            np.testing.assert_array_equal(anc[i], expect)
            # anc_idx row: ancestor at depth t (self-padded past depth i)
            for t, node in enumerate(anc_idx[i]):
                want = [p for p in path if depth[p] == t]
                assert node == (want[0] if want else i)


# ---------------------------------------------------------------------------
# kernel: the in-bundle ancestor mask
# ---------------------------------------------------------------------------


class TestKernelTreeMask:
    def test_causal_ancestor_mask_is_bitwise_default(self):
        """A lower-triangular ancestor mask reproduces the maskless
        (chain) bundle path bit-for-bit — same visibility, same
        summation order."""
        from paddle_tpu.pallas_kernels.decode_attention import \
            paged_flash_decode_attention

        rng = np.random.RandomState(0)
        B, q_len, H, KV, d, bs, nb, N = 2, 5, 4, 2, 8, 8, 4, 10
        kp = rng.randn(N, bs, KV, d).astype(np.float32)
        vp = rng.randn(N, bs, KV, d).astype(np.float32)
        q = rng.randn(B, q_len, H, d).astype(np.float32)
        bt = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
        pos = np.array([3, 17], np.int32)
        base = np.asarray(paged_flash_decode_attention(q, kp, vp, bt, pos))
        causal = np.broadcast_to(np.tril(np.ones((q_len, q_len), bool)),
                                 (B, q_len, q_len))
        out = np.asarray(paged_flash_decode_attention(
            q, kp, vp, bt, pos, ancestor_mask=causal))
        np.testing.assert_array_equal(out, base)

    def test_tree_mask_matches_f64_oracle(self):
        """A real [4,2]-tree ancestor mask vs dense f64 SDPA with
        visibility = past-KV OR ancestor-or-self."""
        from paddle_tpu.pallas_kernels.decode_attention import \
            paged_flash_decode_attention

        plan = generation.spec_tree_plan([4, 2])
        w = int(plan["nodes"])  # 13
        anc = np.asarray(plan["anc"])
        rng = np.random.RandomState(1)
        B, H, KV, d, bs, nb, N = 2, 4, 2, 8, 8, 5, 12
        kp = rng.randn(N, bs, KV, d).astype(np.float32)
        vp = rng.randn(N, bs, KV, d).astype(np.float32)
        q = rng.randn(B, w, H, d).astype(np.float32)
        bt = np.array([[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]], np.int32)
        pos = np.array([4, 19], np.int32)
        mask = np.broadcast_to(anc, (B, w, w))
        out = np.asarray(paged_flash_decode_attention(
            q, kp, vp, bt, pos, ancestor_mask=mask))
        kc = kp[bt.reshape(-1)].reshape(B, nb * bs, KV, d).astype(np.float64)
        vc = vp[bt.reshape(-1)].reshape(B, nb * bs, KV, d).astype(np.float64)
        g = H // KV
        for b in range(B):
            p0 = int(pos[b])
            for i in range(w):
                vis = np.zeros(nb * bs, bool)
                vis[:p0] = True                      # all past KV
                vis[p0:p0 + w] = anc[i]              # in-bundle ancestry
                for h in range(H):
                    kk = kc[b, vis, h // g]
                    vv = vc[b, vis, h // g]
                    s = kk @ q[b, i, h].astype(np.float64) / np.sqrt(d)
                    e = np.exp(s - s.max())
                    expect = (e / e.sum()) @ vv
                    np.testing.assert_allclose(out[b, i, h], expect,
                                               rtol=2e-5, atol=2e-5)

    def test_eligibility_tree_reasons(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "0")
        ok, reason = spec_verify_eligibility(0, 'float32',
                                             spec_tree=[2, 2])
        assert (ok, reason) == (False, "disabled")
        monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE", "1")
        ok, reason = spec_verify_eligibility(0, 'float32',
                                             spec_tree=[2, 2])
        assert (ok, reason) == (True, None)
        # width past the kernel's query window
        deep = [2] * 9  # 1 + 2 + ... + 512 nodes
        assert spec_tree_width(deep) > MAX_PAGED_Q_LEN
        ok, reason = spec_verify_eligibility(0, 'float32', spec_tree=deep)
        assert (ok, reason) == (False, "q_len")


# ---------------------------------------------------------------------------
# offline oracle: generate(spec_tree=...)
# ---------------------------------------------------------------------------


class TestOfflineTreeOracle:
    def test_greedy_parity_llama_batched(self, llama_pair):
        target, draft, cfg = llama_pair
        rng = np.random.RandomState(SEED)
        ids = _prompt(rng, cfg, 12).reshape(2, 6)
        ref = generation.generate(target, ids, max_new_tokens=11).numpy()
        out = generation.generate(target, ids, max_new_tokens=11,
                                  draft_model=draft,
                                  spec_tree=[2, 2]).numpy()
        assert np.array_equal(out, ref)

    def test_greedy_parity_gpt(self, gpt_pair):
        target, draft, cfg = gpt_pair
        rng = np.random.RandomState(SEED + 1)
        ids = _prompt(rng, cfg, 6)[None]
        ref = generation.generate(target, ids, max_new_tokens=10).numpy()
        out = generation.generate(target, ids, max_new_tokens=10,
                                  draft_model=draft,
                                  spec_tree=[3, 2]).numpy()
        assert np.array_equal(out, ref)

    def test_sampled_parity_both_families(self, llama_pair, gpt_pair):
        """Sampled B=1: every depth-t node verifies with the chain's
        t-th subkey, so the accepted path replays the chain's key walk
        exactly — bit-parity holds for top-k AND top-p-only rows."""
        for pair, tree in ((llama_pair, [2, 2]), (gpt_pair, [4, 2])):
            target, draft, cfg = pair
            rng = np.random.RandomState(SEED + 2)
            ids = _prompt(rng, cfg, 8)[None]
            for kw in (dict(do_sample=True, temperature=0.8, top_k=7,
                            seed=11),
                       dict(do_sample=True, top_p=0.9, seed=12)):
                ref = generation.generate(target, ids, max_new_tokens=12,
                                          **kw).numpy()
                out = generation.generate(target, ids, max_new_tokens=12,
                                          draft_model=draft, spec_tree=tree,
                                          **kw).numpy()
                assert np.array_equal(out, ref), (tree, kw)

    def test_spec_tree_requires_draft_model(self, llama_pair):
        target, _, cfg = llama_pair
        rng = np.random.RandomState(SEED + 3)
        ids = _prompt(rng, cfg, 5)[None]
        with pytest.raises(ValueError, match="draft_model"):
            generation.generate(target, ids, max_new_tokens=4,
                                spec_tree=[2, 2])
        with pytest.raises(ValueError, match="branching"):
            generation.spec_tree_plan([2, 0])


# ---------------------------------------------------------------------------
# serving engine: bit-parity + lifecycle
# ---------------------------------------------------------------------------


class TestEngineTreeParity:
    def test_greedy_and_sampled_parity_llama(self, llama_pair):
        """Adversarial draft on the paged tree engine: greedy, top-k,
        top-p-only, per-request opt-out and depth clamp — every request
        bit-matches standalone generate."""
        target, draft, cfg = llama_pair
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=3,
                                    max_len=128, spec_tree=[2, 2])
        rng = np.random.RandomState(SEED + 4)
        cases = [
            (_prompt(rng, cfg, 5), dict(max_new_tokens=12)),
            (_prompt(rng, cfg, 37), dict(max_new_tokens=9, do_sample=True,
                                         temperature=0.8, top_k=8, seed=3)),
            (_prompt(rng, cfg, 9), dict(max_new_tokens=15, do_sample=True,
                                        top_p=0.9, seed=4)),
            (_prompt(rng, cfg, 7), dict(max_new_tokens=10, spec_k=0)),
            (_prompt(rng, cfg, 6), dict(max_new_tokens=10, spec_k=1)),
        ]
        reqs = [eng.submit(p, **kw) for p, kw in cases]
        eng.run_until_idle()
        for (p, kw), r in zip(cases, reqs):
            assert r.status == serving.RequestStatus.COMPLETED
            kw = {k: v for k, v in kw.items() if k != "spec_k"}
            assert np.array_equal(r.result(timeout=5),
                                  _ref(target, p, **kw)), kw
        st = eng.stats()["spec"]
        assert st["mode"] == "tree"
        assert st["tree"]["factors"] == [2, 2]
        assert st["tree"]["nodes"] == 7

    def test_greedy_and_sampled_parity_gpt(self, gpt_pair):
        target, draft, cfg = gpt_pair
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=2,
                                    max_len=96, spec_tree=[3, 2])
        rng = np.random.RandomState(SEED + 5)
        cases = [(_prompt(rng, cfg, 6), dict(max_new_tokens=12)),
                 (_prompt(rng, cfg, 11), dict(max_new_tokens=9,
                                              do_sample=True, top_k=5,
                                              seed=8))]
        reqs = [eng.submit(p, **kw) for p, kw in cases]
        eng.run_until_idle()
        for (p, kw), r in zip(cases, reqs):
            assert np.array_equal(r.result(timeout=5), _ref(target, p, **kw))

    def test_coupled_draft_accepts_full_depth(self, coupled_pair):
        """Functionally-identical draft, greedy: branch 0 is the chain,
        so every round commits the full depth-D path — the accept-depth
        digest pins at D and rounds collapse by D+1."""
        target, draft, cfg = coupled_pair
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=1,
                                    max_len=128, spec_tree=[2, 2])
        rng = np.random.RandomState(SEED + 6)
        p = _prompt(rng, cfg, 7)
        r = eng.submit(p, max_new_tokens=16)
        eng.run_until_idle()
        assert np.array_equal(r.result(5), _ref(target, p,
                                                max_new_tokens=16))
        st = eng.stats()["spec"]
        assert st["accept_len"]["p50"] == 2.0  # depth D = 2 every round
        assert st["tree"]["mean_accepted_path_len"] == 3.0
        assert st["rounds"] < 16

    def test_eos_inside_accepted_path_truncates(self, coupled_pair):
        """EOS landing mid-path (full-depth accepts guarantee
        multi-token rounds): delivery stops at EOS, nothing after it
        leaks, parity with generate's early-exit semantics."""
        target, draft, cfg = coupled_pair
        rng = np.random.RandomState(SEED + 7)
        p = _prompt(rng, cfg, 6)
        base = _ref(target, p, max_new_tokens=16)
        eos = int(base[5])
        ref = _ref(target, p, max_new_tokens=16, eos_token_id=eos)
        stop = int(np.argmax(ref == eos)) + 1 if eos in ref else len(ref)
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=2,
                                    max_len=128, spec_tree=[2, 2])
        r = eng.submit(p, max_new_tokens=16, eos_token_id=eos)
        eng.run_until_idle()
        assert r.result(timeout=5) == list(ref[:stop])
        assert r.status == serving.RequestStatus.COMPLETED

    def test_preempt_mid_tree_resumes_bit_identical(self, llama_pair):
        """Oversubscribed pool preempts mid-speculation; the resumed
        request replays from emitted-token count alone and finishes
        bit-identical (greedy and sampled), zero re-delivery."""
        target, draft, cfg = llama_pair
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=2,
                                    max_len=64, block_size=8, num_blocks=10,
                                    spec_tree=[2, 2])
        rng = np.random.RandomState(SEED + 8)
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
        assert len(preempted.output_tokens) == 30


# ---------------------------------------------------------------------------
# one-compile invariant: mixed tree/chain/non-spec pools
# ---------------------------------------------------------------------------


class TestOneCompile:
    def test_tree_engine_compiles_once_beside_chain_engine(self,
                                                           llama_pair):
        """A chain engine serves a wave, then a tree engine serves 3
        ragged waves of mixed tree/opt-out/depth-clamped requests: the
        tree engine adds EXACTLY one compile to each spec entry and
        never retraces — accept depths, per-row widths, block tables
        are all traced data. serving.step never compiles on either."""
        target, draft, cfg = llama_pair
        rng = np.random.RandomState(SEED + 9)
        chain = serving.ServingEngine(target, draft_model=draft,
                                      max_slots=2, max_len=128, spec_k=3)
        r = chain.submit(_prompt(rng, cfg, 5), max_new_tokens=4)
        chain.run_until_idle()
        assert r.status == serving.RequestStatus.COMPLETED
        stats0 = recompile.entry_stats()
        before = {n: stats0.get(n, {"compiles": 0, "retraces": 0})
                  for n in ("serving.spec_draft", "serving.spec_verify",
                            "serving.step")}
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=2,
                                    max_len=128, max_queue_depth=32,
                                    prefill_chunk=32, spec_tree=[2, 2])
        for wave in range(3):
            reqs = [eng.submit(_prompt(rng, cfg, 3 + 11 * ((wave + i) % 7)),
                               max_new_tokens=2 + (wave + i) % 5,
                               do_sample=bool(i % 2), seed=i, top_k=5,
                               spec_k=(None, 0, 1)[i % 3])
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
        # chain engine still serves without a new compile of its own
        r = chain.submit(_prompt(rng, cfg, 6), max_new_tokens=3)
        chain.run_until_idle()
        assert r.status == serving.RequestStatus.COMPLETED
        stats2 = recompile.entry_stats()
        assert stats2["serving.spec_verify"]["compiles"] \
            == stats1["serving.spec_verify"]["compiles"]


# ---------------------------------------------------------------------------
# config validation + telemetry
# ---------------------------------------------------------------------------


class TestValidationAndTelemetry:
    def test_spec_tree_config_validation(self):
        with pytest.raises(ValueError, match="branching"):
            serving.ServingConfig(spec_tree=[2, 0, 2])
        with pytest.raises(ValueError, match="spec_tree"):
            serving.ServingConfig(spec_tree=[])
        with pytest.raises(ValueError, match="MAX_PAGED_Q_LEN"):
            serving.ServingConfig(spec_tree=[2] * 9)
        with pytest.raises(ValueError, match="mutually exclusive"):
            serving.ServingConfig(spec_k=3, spec_tree=[2, 2])
        cfg = serving.ServingConfig(spec_tree=[4, 2, 2])
        assert cfg.spec_tree == (4, 2, 2)

    def test_tree_metrics_and_trace(self, coupled_pair):
        from paddle_tpu.serving import metrics as sm

        target, draft, cfg = coupled_pair
        drafted0 = sm.spec_tree_nodes_drafted.value()
        accepted0 = sm.spec_tree_nodes_accepted.value()
        eng = serving.ServingEngine(target, draft_model=draft, max_slots=2,
                                    max_len=128, spec_tree=[2, 2])
        rng = np.random.RandomState(SEED + 10)
        r = eng.submit(_prompt(rng, cfg, 7), max_new_tokens=12)
        eng.run_until_idle()
        assert r.status == serving.RequestStatus.COMPLETED
        drafted = sm.spec_tree_nodes_drafted.value() - drafted0
        accepted = sm.spec_tree_nodes_accepted.value() - accepted0
        assert drafted > 0
        assert drafted == r.spec_drafted  # 6 nodes per round
        assert accepted == r.spec_accepted
        from paddle_tpu import observability as obs
        text = obs.prometheus_text()
        assert "paddle_tpu_serving_spec_accept_depth" in text
        assert "paddle_tpu_serving_spec_tree_nodes_drafted_total" in text
        # tree shape rides the engine-lane spans
        counts = tracing.span_counts()
        assert counts.get("serving.spec_draft", 0) > 0
        assert counts.get("serving.spec_verify", 0) > 0
        ev = tracing.events(trace=r.id, name="spec_accept")
        assert ev and {"drafted", "accepted", "emitted"} <= set(
            ev[0]["args"])
