"""Continuous-batching serving engine (paddle_tpu/serving/).

Oracles:
- OUTPUT PARITY: every request decoded through the slot-batched engine
  must produce exactly the tokens ``generation.generate`` produces for
  the same prompt + sampling seed/params (the engine's per-slot key
  chain and traced-param sampler are bit-compatible by construction).
- CONTINUOUS BATCHING: a short request admitted mid-flight finishes
  before a long earlier one (iteration-level scheduling, not run-to-
  completion).
- ONE EXECUTABLE: the whole-pool decode step compiles exactly once
  across many waves of requests (asserted through the recompile
  monitor's ``serving.step`` entry).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import generation, serving
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.observability import recompile


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    return LlamaForCausalLM(cfg), cfg


@pytest.fixture(scope="module")
def engine(tiny_model):
    model, _ = tiny_model
    return serving.ServingEngine(model, max_slots=3, max_len=64,
                                 max_queue_depth=16)


def _prompt(rng, cfg, n):
    return rng.randint(1, cfg.vocab_size, n).astype("int32")


class TestParity:
    def test_mixed_greedy_and_sampled_match_generate(self, tiny_model, engine):
        """Mixed greedy/sampled requests of different lengths share one
        step program AND each reproduces its standalone generate()."""
        model, cfg = tiny_model
        rng = np.random.RandomState(0)
        specs = [
            dict(max_new_tokens=6),
            dict(max_new_tokens=8, do_sample=True, temperature=0.8,
                 top_k=8, seed=5),
            dict(max_new_tokens=5, do_sample=True, top_p=0.9, seed=9),
            dict(max_new_tokens=7),
            dict(max_new_tokens=10, do_sample=True, temperature=1.2,
                 top_k=12, top_p=0.95, seed=3),
        ]
        prompts = [_prompt(rng, cfg, n) for n in (5, 9, 3, 17, 30)]
        reqs = [engine.submit(p, **s) for p, s in zip(prompts, specs)]
        engine.run_until_idle()
        for req, p, s in zip(reqs, prompts, specs):
            assert req.status == serving.RequestStatus.COMPLETED
            got = np.asarray(req.result(timeout=1.0))
            ref = generation.generate(model, p[None], **s).numpy()[0, len(p):]
            np.testing.assert_array_equal(got, ref)
            assert req.full_tokens()[:len(p)] == list(p)

    def test_eos_stops_request_and_matches_generate(self, tiny_model, engine):
        model, cfg = tiny_model
        rng = np.random.RandomState(7)
        p = _prompt(rng, cfg, 6)
        full = generation.generate(model, p[None], max_new_tokens=12).numpy()[0, 6:]
        eos = int(full[4])  # pretend the 5th generated token is EOS
        req = engine.submit(p, max_new_tokens=12, eos_token_id=eos)
        engine.run_until_idle()
        got = np.asarray(req.result(timeout=1.0))
        ref = generation.generate(model, p[None], max_new_tokens=12,
                                  eos_token_id=eos).numpy()[0, 6:]
        # engine stops AT the first eos; generate pads the tail with eos
        assert got[-1] == eos and len(got) <= 12
        np.testing.assert_array_equal(got, ref[:len(got)])
        assert (ref[len(got):] == eos).all()

    def test_gpt_engine_parity(self):
        """Per-row position offsets through LEARNED position embeddings
        (the GPT cached forward) — not just RoPE."""
        paddle.seed(1)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        eng = serving.ServingEngine(model, max_slots=2, max_len=48)
        rng = np.random.RandomState(3)
        prompts = [_prompt(rng, cfg, n) for n in (4, 11)]
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run_until_idle()
        for req, p in zip(reqs, prompts):
            got = np.asarray(req.result(timeout=1.0))
            ref = generation.generate(model, p[None],
                                      max_new_tokens=5).numpy()[0, len(p):]
            np.testing.assert_array_equal(got, ref)


class TestContinuousBatching:
    def test_short_request_overtakes_long(self, tiny_model):
        """The continuous-batching property: a short request ADMITTED
        MID-FLIGHT (the long one already decoding) completes first."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        rng = np.random.RandomState(11)
        long_req = eng.submit(_prompt(rng, cfg, 5), max_new_tokens=30)
        for _ in range(3):  # long request is decoding...
            eng.step()
        tokens_before = len(long_req.output_tokens)
        assert tokens_before >= 3 and not long_req.done
        short_req = eng.submit(_prompt(rng, cfg, 4), max_new_tokens=3)
        eng.run_until_idle()
        assert short_req.status == serving.RequestStatus.COMPLETED
        assert long_req.status == serving.RequestStatus.COMPLETED
        assert short_req.finish_ts < long_req.finish_ts
        # and the slot the short request used was refilled-from-queue
        # machinery, not a fresh compile (covered by TestOneCompile)

    def test_slot_refill_keeps_throughput(self, tiny_model):
        """More requests than slots: freed slots are refilled and every
        request completes (waves drain through the fixed pool)."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64,
                                    max_queue_depth=32)
        rng = np.random.RandomState(13)
        reqs = [eng.submit(_prompt(rng, cfg, 3 + i % 5),
                           max_new_tokens=3 + i % 4) for i in range(9)]
        eng.run_until_idle()
        assert all(r.status == serving.RequestStatus.COMPLETED for r in reqs)
        assert eng.mean_occupancy > 0.5  # pool actually ran batched


class TestSchedulerPolicies:
    def test_backpressure_rejects_beyond_queue_depth(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64,
                                    max_queue_depth=2)
        rng = np.random.RandomState(17)
        # admission happens inside step(); both submits sit in the queue
        keep = [eng.submit(_prompt(rng, cfg, 4), max_new_tokens=4)
                for _ in range(2)]
        with pytest.raises(serving.QueueFullError, match="queue is full"):
            eng.submit(_prompt(rng, cfg, 4), max_new_tokens=4)
        eng.run_until_idle()
        assert all(r.status == serving.RequestStatus.COMPLETED for r in keep)

    def test_oversized_request_is_a_clear_error(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=32)
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(np.arange(1, 20, dtype="int32"), max_new_tokens=20)

    def test_cancellation_frees_the_slot(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64)
        rng = np.random.RandomState(19)
        victim = eng.submit(_prompt(rng, cfg, 5), max_new_tokens=40)
        for _ in range(4):
            eng.step()
        assert eng.busy_slots() == 1 and not victim.done
        partial = len(victim.output_tokens)
        victim.cancel()
        eng.step()
        assert victim.status == serving.RequestStatus.CANCELLED
        assert eng.busy_slots() == 0
        assert len(victim.output_tokens) >= partial  # partial output kept
        # the freed slot serves the next request normally
        nxt = eng.submit(_prompt(rng, cfg, 4), max_new_tokens=3)
        eng.run_until_idle()
        assert nxt.status == serving.RequestStatus.COMPLETED

    def test_queued_cancellation_never_runs(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64)
        rng = np.random.RandomState(23)
        blocker = eng.submit(_prompt(rng, cfg, 4), max_new_tokens=6)
        queued = eng.submit(_prompt(rng, cfg, 4), max_new_tokens=6)
        assert eng.cancel(queued)
        eng.run_until_idle()
        assert queued.status == serving.RequestStatus.CANCELLED
        assert queued.output_tokens == []
        assert blocker.status == serving.RequestStatus.COMPLETED

    def test_deadline_expires_queued_request(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64)
        rng = np.random.RandomState(29)
        blocker = eng.submit(_prompt(rng, cfg, 4), max_new_tokens=8)
        eng.step()  # blocker takes the lone slot; the queue drains
        # queue empty at submit -> the deadline-infeasibility admission
        # gate stays out of the way; this test pins the QUEUED-request
        # expiry path (admission-time rejection is test_supervisor's)
        doomed = eng.submit(_prompt(rng, cfg, 4), max_new_tokens=8,
                            deadline_s=0.0)
        time.sleep(0.01)
        eng.run_until_idle()
        assert blocker.status == serving.RequestStatus.COMPLETED
        assert doomed.status == serving.RequestStatus.EXPIRED
        assert doomed.error is not None


class TestOneCompile:
    def test_exactly_one_decode_step_compile_across_waves(self, tiny_model):
        """≥3 waves of requests through one engine: the recompile
        monitor must record EXACTLY one ``serving.step`` compile (the
        warmup trace) and zero retraces — the continuous-batching
        design goal (no per-request/shape recompiles)."""
        model, cfg = tiny_model
        before = recompile.entry_stats().get("serving.step",
                                             {"compiles": 0, "retraces": 0})
        eng = serving.ServingEngine(model, max_slots=2, max_len=64,
                                    max_queue_depth=32)
        rng = np.random.RandomState(31)
        for wave in range(3):
            reqs = [eng.submit(_prompt(rng, cfg, 3 + (wave + i) % 7),
                               max_new_tokens=2 + (wave + i) % 3,
                               do_sample=bool(i % 2), seed=i, top_k=5)
                    for i in range(5)]
            eng.run_until_idle()
            assert all(r.status == serving.RequestStatus.COMPLETED
                       for r in reqs)
        after = recompile.entry_stats()["serving.step"]
        assert after["compiles"] - before["compiles"] == 1
        assert after["retraces"] - before["retraces"] == 0
        # prefill compiles are attributed per bucket, never as retraces
        pf = {k: v for k, v in recompile.entry_stats().items()
              if k.startswith("serving.prefill")}
        assert pf and all(v["retraces"] == 0 for v in pf.values())


class TestStreamingAndThread:
    def test_background_thread_stream_and_callback(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        rng = np.random.RandomState(37)
        p = _prompt(rng, cfg, 5)
        cb_tokens = []
        try:
            eng.start()
            req = eng.submit(p, max_new_tokens=6,
                             on_token=lambda r, t: cb_tokens.append(t))
            streamed = list(req.stream(timeout=60.0))
            assert req.done
            ref = generation.generate(model, p[None],
                                      max_new_tokens=6).numpy()[0, 5:]
            np.testing.assert_array_equal(np.asarray(streamed), ref)
            assert cb_tokens == streamed
        finally:
            eng.stop()

    def test_result_blocks_until_done(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64)
        rng = np.random.RandomState(41)
        try:
            eng.start()
            req = eng.submit(_prompt(rng, cfg, 4), max_new_tokens=5)
            out = req.result(timeout=60.0)
            assert len(out) == 5
            assert req.status == serving.RequestStatus.COMPLETED
        finally:
            eng.stop()


class TestHTTPFrontends:
    def test_serving_http_generate_and_healthz(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64,
                                    max_queue_depth=4)
        rng = np.random.RandomState(43)
        p = _prompt(rng, cfg, 5)
        port = serving.start_serving_http_server(eng, port=0)
        try:
            body = json.dumps({"prompt": [int(t) for t in p],
                               "max_new_tokens": 6}).encode()
            resp = urllib.request.urlopen(
                urllib.request.Request(
                    f"http://127.0.0.1:{port}/generate", data=body,
                    headers={"Content-Type": "application/json"}),
                timeout=60)
            rec = json.loads(resp.read())
            assert rec["status"] == "completed"
            ref = generation.generate(model, p[None],
                                      max_new_tokens=6).numpy()[0, 5:]
            np.testing.assert_array_equal(np.asarray(rec["tokens"]), ref)
            assert rec["ttft_s"] is not None and rec["latency_s"] is not None

            health = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10).read())
            assert health["status"] == "ok"
            assert health["slots_total"] == 2

            # bad request -> 400
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    urllib.request.Request(
                        f"http://127.0.0.1:{port}/generate",
                        data=b'{"prompt": []}'),
                    timeout=10)
            assert ei.value.code == 400
        finally:
            serving.stop_serving_http_server()
            eng.stop()

    def test_traceparent_propagation_and_metrics(self, tiny_model):
        """A valid traceparent header lands the request's span tree
        under the propagated trace id (the router's merge depends on
        it); GET /metrics serves a parseable Prometheus exposition —
        the scrape target of the router's federation."""
        from paddle_tpu.observability import fleet, tracing
        from paddle_tpu.observability.exporters import parse_prometheus_text

        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        rng = np.random.RandomState(59)
        p = _prompt(rng, cfg, 5)
        srv = serving.ServingHTTPServer(eng, port=0)
        base = f"http://127.0.0.1:{srv.port}"
        try:
            tid = fleet.attempt_trace_id(4242, 1)
            body = json.dumps({"prompt": [int(t) for t in p],
                               "max_new_tokens": 4}).encode()
            rec = json.loads(urllib.request.urlopen(
                urllib.request.Request(
                    f"{base}/generate", data=body,
                    headers={"traceparent": fleet.traceparent_of(tid)}),
                timeout=60).read())
            assert rec["status"] == "completed"
            names = {e["name"] for e in tracing.events(trace=tid)}
            assert "request" in names  # replica spans joined the id

            resp = urllib.request.urlopen(f"{base}/metrics", timeout=10)
            assert resp.headers["Content-Type"].startswith("text/plain")
            fams = parse_prometheus_text(resp.read().decode())
            assert "paddle_tpu_serving_requests_total" in fams
            assert fams["paddle_tpu_serving_ttft_summary_seconds"][
                "type"] == "summary"
        finally:
            srv.stop()
            eng.stop()

    def test_hostile_traceparent_ignored_never_4xx5xx(self, tiny_model):
        """Malformed traceparent headers are ignored (fresh local
        trace): the request still completes 200 — a hostile header must
        never cost the caller their request."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        rng = np.random.RandomState(61)
        p = _prompt(rng, cfg, 4)
        srv = serving.ServingHTTPServer(eng, port=0)
        hostile = ["", " ", "garbage", "00", "00-", "00-ab-cd-01",
                   "01-" + "ab" * 16 + "-" + "cd" * 8 + "-01",
                   "00-" + "AB" * 16 + "-" + "cd" * 8 + "-01",
                   "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",
                   "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",
                   "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01-extra",
                   "\x01\x02bin", "0" * 2048]
        try:
            for header in hostile:
                body = json.dumps({"prompt": [int(t) for t in p],
                                   "max_new_tokens": 2}).encode()
                resp = urllib.request.urlopen(
                    urllib.request.Request(
                        f"http://127.0.0.1:{srv.port}/generate", data=body,
                        headers={"traceparent": header}),
                    timeout=60)
                assert resp.status == 200, header
                assert json.loads(resp.read())["status"] == "completed"
        finally:
            srv.stop()
            eng.stop()

    def test_serving_http_stream(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64)
        rng = np.random.RandomState(47)
        p = _prompt(rng, cfg, 4)
        port = serving.start_serving_http_server(eng, port=0)
        try:
            body = json.dumps({"prompt": [int(t) for t in p],
                               "max_new_tokens": 5, "stream": True}).encode()
            resp = urllib.request.urlopen(
                urllib.request.Request(
                    f"http://127.0.0.1:{port}/generate", data=body),
                timeout=60)
            lines = [json.loads(l) for l in resp.read().splitlines() if l]
            toks = [l["token"] for l in lines if "token" in l]
            assert lines[-1].get("done") is True
            ref = generation.generate(model, p[None],
                                      max_new_tokens=5).numpy()[0, 4:]
            np.testing.assert_array_equal(np.asarray(toks), ref)
        finally:
            serving.stop_serving_http_server()
            eng.stop()

    def test_observability_healthz_shows_serving_gauges(self, tiny_model):
        from paddle_tpu import observability as obs

        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        rng = np.random.RandomState(53)
        req = eng.submit(_prompt(rng, cfg, 4), max_new_tokens=3)
        eng.run_until_idle()
        assert req.status == serving.RequestStatus.COMPLETED
        port = obs.start_http_server(port=0)
        try:
            health = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10).read())
            assert health["status"] == "ok"
            # gauges registered + live without any snapshot call
            assert health["serving_queue_depth"] == 0
            assert health["serving_slots_busy"] == 0
            text = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
            assert "paddle_tpu_serving_queue_depth" in text
            assert "paddle_tpu_serving_slot_occupancy" in text
            assert "paddle_tpu_serving_ttft_seconds_bucket" in text
            fams = obs.parse_prometheus_text(text)
            done = [s for s in fams["paddle_tpu_serving_requests_total"]["samples"]
                    if s["labels"].get("outcome") == "completed"]
            assert done and done[0]["value"] >= 1
        finally:
            obs.stop_http_server()


class TestServingMetrics:
    def test_counters_and_histograms_populate(self, tiny_model):
        from paddle_tpu.serving import metrics as sm

        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        rng = np.random.RandomState(59)
        base_steps = sm.steps_total.value()
        reqs = [eng.submit(_prompt(rng, cfg, 4), max_new_tokens=4)
                for _ in range(3)]
        eng.run_until_idle()
        assert all(r.status == serving.RequestStatus.COMPLETED for r in reqs)
        assert sm.steps_total.value() > base_steps
        _, _, ttft_count = sm.ttft_seconds._d().snapshot()
        assert ttft_count >= 3
        _, _, tpot_count = sm.tpot_seconds._d().snapshot()
        assert tpot_count >= 3
        for r in reqs:
            assert r.ttft_s is not None and r.ttft_s >= 0
            assert r.tpot_s is not None and r.tpot_s >= 0


class TestWarmup:
    """engine.warmup(): AOT-compile every executable before traffic —
    first request after warmup triggers ZERO compiles (the fast-replica-
    boot contract the multi-replica router relies on)."""

    def _serving_compiles(self):
        return {k: v["compiles"] for k, v in recompile.entry_stats().items()
                if k.startswith("serving.")}

    def test_paged_warmup_zero_compiles_on_first_traffic(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        assert not eng.warmed_up
        info = eng.warmup()
        assert eng.warmed_up
        # an engine that fuses keeps one width of prefill rows, [2, C]:
        # the prefill program, and the step with the rows in its
        # program; the [1, C] forms made room for it
        assert set(info["entries"]) == {"serving.step",
                                        "serving.step+chunk[2]",
                                        "serving.prefill_chunk[2]",
                                        "serving.cow"}
        assert info["compiles"] >= 4
        before = self._serving_compiles()
        rng = np.random.RandomState(61)
        p = _prompt(rng, cfg, 5)
        req = eng.submit(p, max_new_tokens=6)
        eng.run_until_idle()
        assert req.status == serving.RequestStatus.COMPLETED
        ref = generation.generate(model, p[None],
                                  max_new_tokens=6).numpy()[0, 5:]
        np.testing.assert_array_equal(np.asarray(req.result(1.0)), ref)
        assert self._serving_compiles() == before  # zero compiles
        # /healthz surfaces warmed_up
        assert eng.health()[1]["warmed_up"] is True

    def test_warmup_requires_idle_engine(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64)
        rng = np.random.RandomState(63)
        eng.submit(_prompt(rng, cfg, 4), max_new_tokens=2)
        with pytest.raises(RuntimeError, match="idle"):
            eng.warmup()
        eng.run_until_idle()
        eng.warmup()  # idle again: fine (and idempotent)
        eng.warmup()


class TestStopDrain:
    """stop() drains by default: in-flight requests finish, new submits
    raise, nothing is silently abandoned. stop(abort=True) keeps the
    fail-fast shutdown but fails in-flight requests EXPLICITLY."""

    def test_stop_drains_inflight_to_completion(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        rng = np.random.RandomState(67)
        eng.start()
        reqs = [eng.submit(_prompt(rng, cfg, 4 + i), max_new_tokens=10)
                for i in range(4)]
        time.sleep(0.05)
        eng.stop()  # default: drain
        assert all(r.status == serving.RequestStatus.COMPLETED
                   for r in reqs), [r.status for r in reqs]
        assert eng.stopped
        with pytest.raises(serving.EngineStoppedError, match="stopped"):
            eng.submit([1, 2, 3])
        with pytest.raises(serving.EngineStoppedError):
            eng.start()

    def test_stop_abort_fails_inflight_explicitly(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64,
                                    max_queue_depth=8)
        rng = np.random.RandomState(68)
        eng.start()
        reqs = [eng.submit(_prompt(rng, cfg, 4), max_new_tokens=40)
                for _ in range(3)]
        time.sleep(0.05)
        eng.stop(abort=True)
        for r in reqs:
            r.result(timeout=5.0)  # returns — never hangs
            assert r.status in (serving.RequestStatus.FAILED,
                                serving.RequestStatus.COMPLETED)
        aborted = [r for r in reqs if r.status == serving.RequestStatus.FAILED]
        assert aborted and all("abort" in r.error for r in aborted)

    def test_sync_engine_stop_drains_inline(self, tiny_model):
        """A never-started engine drains by driving the loop inline."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        rng = np.random.RandomState(69)
        reqs = [eng.submit(_prompt(rng, cfg, 4), max_new_tokens=4)
                for _ in range(3)]
        eng.stop()
        assert all(r.status == serving.RequestStatus.COMPLETED for r in reqs)

    def test_drain_reports_and_submit_raises_while_draining(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64)
        rng = np.random.RandomState(70)
        eng.start()
        req = eng.submit(_prompt(rng, cfg, 4), max_new_tokens=20)
        t = threading.Thread(target=eng.drain, daemon=True)
        t.start()
        # while draining: 503 payload distinguishes it, submit refused
        deadline = time.monotonic() + 10
        while not eng.draining and time.monotonic() < deadline:
            time.sleep(0.002)
        if not req.done:  # drain still in progress: check the surface
            code, payload = eng.health()
            assert code == 503 and payload["status"] == "draining"
            with pytest.raises(serving.EngineDrainingError, match="draining"):
                eng.submit([1, 2, 3])
        t.join(timeout=30)
        assert req.status == serving.RequestStatus.COMPLETED
        eng.stop()

    def test_drain_timeout_fails_stragglers_explicitly(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64)
        monkey = serving.ChaosEngine(eng).hang_after_steps(1)
        rng = np.random.RandomState(71)
        eng.start()
        req = eng.submit(_prompt(rng, cfg, 4), max_new_tokens=20)
        t0 = time.monotonic()
        while monkey.injected["hang"] == 0 and time.monotonic() - t0 < 20:
            time.sleep(0.005)
        assert eng.drain(timeout_s=0.2) is False
        req.result(timeout=5.0)  # returns with the explicit error
        assert req.status == serving.RequestStatus.FAILED
        assert "drain timed out" in req.error
        monkey.release()
        eng.stop(abort=True)


class TestHealthStates:
    """/healthz 503 semantics split: crashed / draining / stopped /
    saturated / stalled are DISTINCT, and saturated carries a
    digest-derived Retry-After."""

    def test_saturated_is_distinct_and_carries_retry_after(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64,
                                    max_queue_depth=2)
        rng = np.random.RandomState(72)
        code, payload = eng.health()
        assert (code, payload["status"]) == (200, "ok")
        # sync engine (nobody admits): fill the queue to the brim
        for _ in range(2):
            eng.submit(_prompt(rng, cfg, 4), max_new_tokens=4)
        code, payload = eng.health()
        assert (code, payload["status"]) == (503, "saturated")
        assert payload["retry_after_s"] > 0
        assert payload["crashed"] is None  # ...and NOT dead
        eng.run_until_idle()
        assert eng.health()[0] == 200

    def test_crashed_is_distinct(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64)
        monkey = serving.ChaosEngine(eng).crash_after_steps(0)
        rng = np.random.RandomState(73)
        req = eng.submit(_prompt(rng, cfg, 4), max_new_tokens=4)
        eng.start()  # first loop step hits the armed crash
        req.result(timeout=20.0)
        assert req.status == serving.RequestStatus.FAILED
        code, payload = eng.health()
        assert (code, payload["status"]) == (503, "crashed")
        assert "chaos" in payload["crashed"]
        from paddle_tpu.serving import metrics as sm
        sm.engine_unhealthy.set(0)  # reset for later tests

    def test_stalled_is_distinct(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64,
                                    stall_timeout_s=0.15)
        monkey = serving.ChaosEngine(eng).hang_after_steps(1)
        rng = np.random.RandomState(74)
        eng.start()
        req = eng.submit(_prompt(rng, cfg, 4), max_new_tokens=10)
        t0 = time.monotonic()
        while eng.health()[1]["status"] != "stalled":
            time.sleep(0.02)
            assert time.monotonic() - t0 < 20, eng.health()[1]["status"]
        monkey.release()
        req.result(timeout=30.0)
        assert req.status == serving.RequestStatus.COMPLETED
        assert eng.health()[0] == 200  # recovery clears the stall
        eng.stop()

    def test_http_429_carries_retry_after(self, tiny_model):
        """Backpressure over HTTP: 429 + Retry-After header (satellite:
        saturation is no longer indistinguishable from death)."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64,
                                    max_queue_depth=1)
        monkey = serving.ChaosEngine(eng).hang_after_steps(0)  # hold queue
        port = serving.ServingHTTPServer(eng, port=0)
        rng = np.random.RandomState(75)
        try:
            srv = port
            body = lambda: json.dumps(
                {"prompt": [int(t) for t in _prompt(rng, cfg, 4)],
                 "max_new_tokens": 4, "stream": True}).encode()
            # 1 queued (the hung loop never admits) + 1 = full
            for _ in range(2):
                try:
                    urllib.request.urlopen(urllib.request.Request(
                        f"http://127.0.0.1:{srv.port}/generate",
                        data=body()), timeout=2)
                except Exception:
                    pass  # streaming responses park; queue is the point
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{srv.port}/generate", data=body()),
                    timeout=10)
            assert ei.value.code == 429
            assert int(ei.value.headers["Retry-After"]) >= 1
            # /healthz agrees: saturated, with the hint in the payload
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/healthz", timeout=10)
            assert ei.value.code == 503
            payload = json.loads(ei.value.read())
            assert payload["status"] == "saturated"
        finally:
            monkey.release()
            srv.stop()
            eng.stop(abort=True)


class TestDeadlineCancelRacesEngine:
    """The engine-level deadline/cancel races the router relies on."""

    def test_deadline_between_admission_and_first_chunk(self, tiny_model):
        """Deadline expires AFTER admission claimed blocks but BEFORE
        the next prefill chunk: the request expires with an explicit
        error and its blocks are freed (multi-chunk prompt, driven
        step-by-step)."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64,
                                    prefill_chunk=8)
        rng = np.random.RandomState(76)
        p = _prompt(rng, cfg, 30)  # 4 chunks of 8
        req = eng.submit(p, max_new_tokens=4, deadline_s=0.05)
        eng.step()  # admission + chunk 1 (deadline still alive)
        assert req.status == serving.RequestStatus.RUNNING
        time.sleep(0.1)  # the deadline passes mid-prefill
        eng.step()
        assert req.status == serving.RequestStatus.EXPIRED
        assert "prefill" in req.error
        assert eng.busy_slots() == 0
        assert eng.pool.free_blocks == eng.pool.usable_blocks  # no leak

    def test_cancel_during_preemption_recompute(self, tiny_model):
        """Cancel delivered while the request sits REQUEUED for
        preemption-recompute: it finishes CANCELLED at the next
        admission pass, its already-delivered tokens stay as-is, and
        nothing is ever re-delivered."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=128,
                                    num_blocks=9)
        rng = np.random.RandomState(77)
        ra = eng.submit(_prompt(rng, cfg, 30), max_new_tokens=40)
        rb = eng.submit(_prompt(rng, cfg, 30), max_new_tokens=40)
        # run until b is decoding, then preempt it (the pool-pressure
        # path) and cancel it while it waits for recompute
        for _ in range(200):
            eng.step()
            if rb.slot is not None and eng._decoding[rb.slot]:
                break
        assert rb.slot is not None
        eng._preempt(rb.slot)
        assert rb.status == serving.RequestStatus.QUEUED
        delivered = list(rb.output_tokens)
        rb.cancel()
        eng.run_until_idle(max_steps=5000)
        assert rb.status == serving.RequestStatus.CANCELLED
        assert list(rb.output_tokens) == delivered  # nothing re-delivered
        assert ra.status == serving.RequestStatus.COMPLETED


class TestBatchedPrefillServing:
    """One prefill program an iteration, seen from the serving surface:
    the chunks of every prefilling slot ride it as rows, and what goes
    wrong with one row stays with that row's request."""

    KW = dict(max_slots=4, max_len=64, block_size=16, prefill_chunk=8)

    def _ref(self, model, p, **s):
        return list(generation.generate(model, p[None],
                                        **s).numpy()[0, len(p):])

    @pytest.mark.parametrize("n", [2, 4, 7])
    def test_simultaneous_arrivals_through_the_loop_match_generate(
            self, tiny_model, n):
        """Through the background loop, with more arrivals than slots
        at the largest ``n``: every stream is what ``generate`` gives
        its prompt alone, greedy or sampled."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_queue_depth=16, **self.KW)
        assert eng._chunk_rows == 4
        rng = np.random.RandomState(80 + n)
        prompts = [_prompt(rng, cfg, 3 + (11 * i) % 29) for i in range(n)]
        specs = [dict(max_new_tokens=4 + i % 3) if i % 2 else
                 dict(max_new_tokens=4 + i % 3, do_sample=True, top_k=6,
                      seed=i) for i in range(n)]
        eng.start()
        try:
            reqs = [eng.submit(p, **s) for p, s in zip(prompts, specs)]
            got = [r.result(timeout=60.0) for r in reqs]
        finally:
            eng.stop()
        for g, p, s in zip(got, prompts, specs):
            assert list(g) == self._ref(model, p, **s)
        c = eng.counters()
        assert c["prefill_rows"] == sum(-(-len(p) // 8) for p in prompts)
        assert c["prefill_programs"] <= c["prefill_rows"]

    def test_what_fails_after_a_rows_last_chunk_fails_that_request_alone(
            self, tiny_model):
        """Three last chunks in one program; the prefix-cache insert of
        the second row's prompt raises: that request fails with the
        error, its neighbours get their first tokens and go on."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        rng = np.random.RandomState(90)
        prompts = [_prompt(rng, cfg, 5) for _ in range(3)]
        real = eng.prefix_cache.insert

        def insert(tokens, n, blocks):
            if np.array_equal(tokens[:n], prompts[1]):
                raise RuntimeError("cache refused the prompt")
            return real(tokens, n, blocks)

        eng.prefix_cache.insert = insert
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run_until_idle()
        assert reqs[1].status == serving.RequestStatus.FAILED
        assert "cache refused the prompt" in reqs[1].error
        for i in (0, 2):
            assert reqs[i].status == serving.RequestStatus.COMPLETED
            assert list(reqs[i].output_tokens) == self._ref(
                model, prompts[i], max_new_tokens=4)
        assert eng.busy_slots() == 0
        assert eng.pool.used_blocks == len(eng.prefix_cache)

    def test_a_program_that_fails_to_enqueue_fails_its_rows_and_no_other(
            self, tiny_model):
        """Five prefilling slots are two programs at four rows each: the
        first one's failure is its four requests', the fifth rides the
        second program and is served."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **dict(self.KW, max_slots=5))
        rng = np.random.RandomState(91)
        prompts = [_prompt(rng, cfg, 6) for _ in range(5)]
        real, calls = eng._chunk_fn, []

        def chunk_fn(*a):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("enqueue refused")
            return real(*a)

        eng._chunk_fn = chunk_fn
        reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
        eng.run_until_idle()
        assert [r.status for r in reqs[:4]] \
            == [serving.RequestStatus.FAILED] * 4
        assert all("enqueue refused" in r.error for r in reqs[:4])
        assert reqs[4].status == serving.RequestStatus.COMPLETED
        assert list(reqs[4].output_tokens) == self._ref(
            model, prompts[4], max_new_tokens=3)
        assert eng.pool.used_blocks == len(eng.prefix_cache)

    def test_warmup_compiles_the_program_at_its_rows_and_traffic_none(
            self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        # (an engine that fuses: the [4, C] rows alone, as a prefill
        # program and in the step's)
        names = ("serving.prefill_chunk[4]", "serving.step+chunk[4]",
                 "serving.step")
        assert set(names) == {"serving.step", *eng._chunk_entries,
                              *eng._fused_entries}
        stats0 = {n: dict(recompile.entry_stats().get(
            n, {"compiles": 0, "retraces": 0})) for n in names}
        eng.warmup()
        # each executable is built once
        for n in names:
            st = recompile.entry_stats()[n]
            assert st["compiles"] - stats0[n]["compiles"] == 1, n
            assert st["retraces"] == stats0[n]["retraces"], n
        before = recompile.total_compiles()
        rng = np.random.RandomState(92)
        for n in (1, 4, 3):
            reqs = [eng.submit(_prompt(rng, cfg, 4 + 9 * i),
                               max_new_tokens=2) for i in range(n)]
            eng.run_until_idle()
            assert all(r.status == serving.RequestStatus.COMPLETED
                       for r in reqs)
        assert recompile.total_compiles() == before

    def test_a_deadline_on_one_row_leaves_the_program_to_the_others(
            self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        rng = np.random.RandomState(93)
        prompts = [_prompt(rng, cfg, 30) for _ in range(3)]  # 4 chunks
        reqs = [eng.submit(p, max_new_tokens=3,
                           deadline_s=0.05 if i == 0 else None)
                for i, p in enumerate(prompts)]
        eng.step()
        assert all(r.status == serving.RequestStatus.RUNNING for r in reqs)
        time.sleep(0.1)
        eng.step()
        assert reqs[0].status == serving.RequestStatus.EXPIRED
        assert "prefill" in reqs[0].error
        eng.run_until_idle()
        for i in (1, 2):
            assert list(reqs[i].output_tokens) == self._ref(
                model, prompts[i], max_new_tokens=3)
        assert eng.pool.used_blocks == len(eng.prefix_cache)


class TestFilledProgramServing:
    """The rows the prefill program has left carry a prompt's further
    chunks, and a first token is read once the step is dispatched: both
    seen from the serving surface, where neither may show in a stream
    but as time."""

    KW = TestBatchedPrefillServing.KW
    _ref = TestBatchedPrefillServing._ref

    @pytest.mark.parametrize("spec", [
        dict(max_new_tokens=5),
        dict(max_new_tokens=5, do_sample=True, top_k=6, temperature=0.8,
             seed=3)], ids=["greedy", "sampled"])
    def test_a_lone_prompt_takes_the_whole_program_through_the_loop(
            self, tiny_model, spec):
        """Six chunks alone in the engine: four rows of ``[4, C]``,
        then two; the stream is ``generate``'s."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        assert eng._chunk_rows == 4
        p = _prompt(np.random.RandomState(94), cfg, 45)
        eng.start()
        try:
            got = eng.submit(p, **spec).result(timeout=60.0)
        finally:
            eng.stop()
        assert list(got) == self._ref(model, p, **spec)
        c = eng.counters()
        assert (c["prefill_rows"], c["prefill_programs"],
                c["prefill_fill_rows"]) == (6, 2, 4)

    def test_a_prompt_behind_a_running_batch_needs_fewer_iterations(
            self, tiny_model):
        """Two requests decode; a prompt of five chunks joins them. It
        has its first token after two iterations (four rows, then its
        last chunk), where one chunk an iteration took five, and the
        running requests got their token in each of them: the prompt's
        rows ride the decode steps' own programs. The host is a step
        ahead of the tokens it reads, so each is seen one ``step()``
        later; the prompt's slot joins the step after the one its last
        chunk rode."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        rng = np.random.RandomState(95)
        running = [eng.submit(_prompt(rng, cfg, 6), max_new_tokens=12)
                   for _ in range(2)]
        eng.step()
        assert [len(r.output_tokens) for r in running] == [0, 0]
        assert eng.in_flight    # their first tokens parked, a step ahead
        p = _prompt(rng, cfg, 38)
        late = eng.submit(p, max_new_tokens=4)
        eng.step()
        assert late.output_tokens == [] and eng._jobs[late.slot].done == 32
        assert [len(r.output_tokens) for r in running] == [2, 2]
        eng.step()
        assert late.output_tokens == [] and eng._jobs[late.slot] is None
        assert [len(r.output_tokens) for r in running] == [3, 3]
        eng.step()
        assert len(late.output_tokens) == 1     # its first; its slot has
        assert eng._slot_due[late.slot] == 1    # joined the step in flight
        assert [len(r.output_tokens) for r in running] == [4, 4]
        assert eng.counters()["steps_fused"] == 2
        eng.run_until_idle()
        assert list(late.output_tokens) == self._ref(
            model, p, max_new_tokens=4)
        for r in running:
            assert list(r.output_tokens) == self._ref(
                model, r.prompt, max_new_tokens=12)

    def test_one_token_requests_through_the_loop_return_one_token(
            self, tiny_model):
        """Requests that end on their first token, beside one that runs
        on: each is in the step dispatched before its token was read,
        and that step's row is dropped; one token each, ``generate``'s,
        every slot and block given back."""
        from paddle_tpu.serving import metrics as sm

        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_queue_depth=16, **self.KW)
        rng = np.random.RandomState(96)
        long_p = _prompt(rng, cfg, 11)
        shorts = [_prompt(rng, cfg, 3 + 5 * i) for i in range(5)]
        _, _, ttft0 = sm.ttft_seconds._d().snapshot()
        eng.start()
        try:
            runner = eng.submit(long_p, max_new_tokens=14)
            reqs = [eng.submit(p, max_new_tokens=1) for p in shorts]
            got = [r.result(timeout=60.0) for r in reqs]
            ran = runner.result(timeout=60.0)
        finally:
            eng.stop()
        for g, p, r in zip(got, shorts, reqs):
            assert list(g) == self._ref(model, p, max_new_tokens=1)
            assert r.status == serving.RequestStatus.COMPLETED
            assert r.ttft_s is not None and r.tpot_s is None
        assert list(ran) == self._ref(model, long_p, max_new_tokens=14)
        _, _, ttft1 = sm.ttft_seconds._d().snapshot()
        assert ttft1 - ttft0 == 6       # one observation a request
        assert eng.busy_slots() == 0
        assert eng.pool.used_blocks == len(eng.prefix_cache)

    def test_a_first_token_is_streamed_ahead_of_the_steps_token(
            self, tiny_model):
        """The callback sees a request's first token before any token
        of the step that was dispatched ahead of the read, its own
        second token included, and every stream in order."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        rng = np.random.RandomState(97)
        seen = []
        first = eng.submit(_prompt(rng, cfg, 5), max_new_tokens=6,
                           on_token=lambda r, t: seen.append(("a", t)))
        eng.step()
        eng.step()
        assert len(seen) == 2
        del seen[:]
        second = eng.submit(_prompt(rng, cfg, 20), max_new_tokens=3,
                            on_token=lambda r, t: seen.append(("b", t)))
        eng.step()      # b: two rows in the step of a's fourth; a's third
        eng.step()      # b's first, read before that step's a; b joins
        assert [who for who, _ in seen] == ["a", "b", "a"]
        assert second.first_token_ts <= first.last_token_ts
        eng.step()      # the step b joined: a's fifth, b's second
        assert [who for who, _ in seen[3:]] == ["a", "b"]
        eng.run_until_idle()
        assert [t for who, t in seen if who == "b"] == self._ref(
            model, second.prompt, max_new_tokens=3)
        assert list(first.output_tokens) == self._ref(
            model, first.prompt, max_new_tokens=6)

    def test_a_cache_that_refuses_a_filled_prompt_fails_that_request_alone(
            self, tiny_model):
        """A prompt whose last chunk rode a spare row; the prefix-cache
        insert raises while the host books it: that request fails, the
        one whose rows it shared a program with is served, and nothing
        stays parked."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, **self.KW)
        rng = np.random.RandomState(98)
        prompts = [_prompt(rng, cfg, 20), _prompt(rng, cfg, 6)]
        real = eng.prefix_cache.insert

        def insert(tokens, n, blocks):
            if np.array_equal(tokens[:n], prompts[0]):
                raise RuntimeError("cache refused the prompt")
            return real(tokens, n, blocks)

        eng.prefix_cache.insert = insert
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.step()
        assert eng.counters()["prefill_fill_rows"] == 2
        assert reqs[0].status == serving.RequestStatus.FAILED
        assert "cache refused the prompt" in reqs[0].error
        eng.step()
        assert eng._parked_tokens == [] and len(reqs[1].output_tokens) == 2
        eng.run_until_idle()
        assert list(reqs[1].output_tokens) == self._ref(
            model, prompts[1], max_new_tokens=4)
        assert eng.busy_slots() == 0
        assert eng.pool.used_blocks == len(eng.prefix_cache)
