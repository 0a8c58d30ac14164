"""Multi-replica serving router + chaos suite (paddle_tpu/serving/router.py).

Invariants asserted under injected faults (the reliability contract a
router exists to provide):

- NO SILENT LOSS: with a replica killed mid-decode, every affected
  request either completes via retry on a healthy replica or fails with
  an explicit deadline/cancel/routing error — ``result()`` always
  returns, no request is dropped.
- BIT-IDENTICAL FAILOVER: a request that failed over re-derives the
  tokens its dead replica already delivered (seed-deterministic PRNG
  chain) and the relay drops the replayed prefix — the final output
  equals a single-engine ``generation.generate`` run, greedy AND
  sampled.
- ZERO RETRACES ON SURVIVORS: chaos on one replica never recompiles
  another's executables (the one-compile contract holds fleet-wide);
  a replacement replica boots with ``engine.warmup()`` and serves its
  first request with zero new compiles.
- BOUNDED AMPLIFICATION: retries + hedges stay under the configured
  cap even in a failure storm.

All faults are deterministic (step/call-count triggered, seeded RNG) —
see ``paddle_tpu/serving/chaos.py``.
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import generation, serving
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import recompile

SEED = 1234


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    return LlamaForCausalLM(cfg), cfg


def _prompt(rng, cfg, n):
    return rng.randint(1, cfg.vocab_size, n).astype("int32")


def _engine(model, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 64)
    return serving.ServingEngine(model, **kw)


def _serving_compiles():
    return {k: v["compiles"] for k, v in recompile.entry_stats().items()
            if k.startswith("serving.")}


def _serving_retraces():
    return sum(v["retraces"] for k, v in recompile.entry_stats().items()
               if k.startswith("serving."))


def _drive(router, rrs, timeout=60.0, probe=True):
    """Wait out router requests while (optionally) running probe
    rounds — the deterministic stand-in for the background prober."""
    t0 = time.monotonic()
    while not all(r.done for r in rrs):
        if probe:
            router.probe_once()
        time.sleep(0.01)
        assert time.monotonic() - t0 < timeout, (
            f"requests stuck: {[r.status for r in rrs]}")


# ---------------------------------------------------------------------------
# routing basics
# ---------------------------------------------------------------------------

class TestRouting:
    def test_multi_replica_parity_and_spread(self, tiny_model):
        """Mixed greedy/sampled requests over 2 replicas: every output
        bit-identical to generate(), and the load-aware pick actually
        uses both replicas."""
        model, cfg = tiny_model
        e1, e2 = _engine(model), _engine(model)
        router = serving.Router([e1, e2])
        rng = np.random.RandomState(SEED)
        specs = [dict(max_new_tokens=30),
                 dict(max_new_tokens=28, do_sample=True, top_k=8, seed=5),
                 dict(max_new_tokens=25, do_sample=True, top_p=0.9, seed=9),
                 dict(max_new_tokens=30)]
        prompts = [_prompt(rng, cfg, n) for n in (5, 9, 3, 12)]
        try:
            rrs = []
            for p, s in zip(prompts, specs):
                rrs.append(router.submit(p, **s))
                # deterministic spread assertion: wait until THIS
                # request is visibly in flight before submitting the
                # next, so the pick always sees the inflight counts
                t0 = time.monotonic()
                while not (rrs[-1].done or rrs[-1].output_tokens):
                    time.sleep(0.005)
                    assert time.monotonic() - t0 < 60
            _drive(router, rrs)
            used = set()
            for rr, p, s in zip(rrs, prompts, specs):
                assert rr.status == serving.RequestStatus.COMPLETED
                ref = generation.generate(
                    model, p[None], **s).numpy()[0, len(p):]
                np.testing.assert_array_equal(np.asarray(rr.result(1.0)), ref)
                used.add(rr.replica)
            assert used == {"r0", "r1"}  # inflight-aware spread
            assert all(r.retries == 0 for r in rrs)
        finally:
            router.stop(drain=True, timeout_s=10)

    def test_auto_warmup_and_zero_compile_first_traffic(self, tiny_model):
        """Registration warms replicas (``auto_warmup``): the first
        ROUTED request triggers zero serving compiles on either
        replica."""
        model, cfg = tiny_model
        router = serving.Router([_engine(model), _engine(model)])
        try:
            assert all(r["state"] == "healthy" for r in router.replicas())
            before = _serving_compiles()
            rng = np.random.RandomState(SEED + 1)
            rr = router.submit(_prompt(rng, cfg, 5), max_new_tokens=4)
            _drive(router, [rr])
            assert rr.status == serving.RequestStatus.COMPLETED
            assert _serving_compiles() == before
        finally:
            router.stop(drain=True, timeout_s=10)

    def test_bad_request_fails_fast_without_retry(self, tiny_model):
        model, cfg = tiny_model
        router = serving.Router([_engine(model, max_len=32)])
        try:
            rng = np.random.RandomState(SEED + 2)
            rr = router.submit(_prompt(rng, cfg, 20), max_new_tokens=30)
            _drive(router, [rr], timeout=10)
            assert rr.status == serving.RequestStatus.FAILED
            assert "bad request" in rr.error
            assert rr.retries == 0
        finally:
            router.stop(drain=True, timeout_s=10)

    def test_submit_with_no_replicas_raises(self):
        router = serving.Router([])
        with pytest.raises(serving.NoReplicaError, match="no live replicas"):
            router.submit([1, 2, 3])


# ---------------------------------------------------------------------------
# chaos: replica crash mid-decode (the core acceptance)
# ---------------------------------------------------------------------------

class TestCrashFailover:
    def test_crash_mid_decode_bit_identical_failover(self, tiny_model):
        """Kill replica r0 mid-decode. Every request completes (retried
        on r1) with outputs bit-identical to a single-engine run, the
        dead replica is ejected, surviving replicas never retrace, and
        amplification stays under the cap."""
        model, cfg = tiny_model
        e1, e2 = _engine(model), _engine(model)
        cfgr = serving.RouterConfig(probe_failures_to_eject=2,
                                    max_retries_per_request=2,
                                    unroutable_timeout_s=10.0)
        router = serving.Router([e1, e2], cfgr)
        monkey = serving.ChaosEngine(e1).crash_after_steps(2)
        rng = np.random.RandomState(SEED + 3)
        specs = [dict(max_new_tokens=8),
                 dict(max_new_tokens=8, do_sample=True, top_k=8, seed=11),
                 dict(max_new_tokens=6), dict(max_new_tokens=7),
                 dict(max_new_tokens=8, do_sample=True, top_p=0.9, seed=4),
                 dict(max_new_tokens=6)]
        prompts = [_prompt(rng, cfg, 4 + i) for i in range(len(specs))]
        retr0 = _serving_retraces()
        try:
            rrs = [router.submit(p, **s) for p, s in zip(prompts, specs)]
            _drive(router, rrs)
            assert monkey.injected["crash"] == 1  # the fault fired
            # no silent loss + bit-identical outputs
            for rr, p, s in zip(rrs, prompts, specs):
                assert rr.status == serving.RequestStatus.COMPLETED, rr.error
                ref = generation.generate(
                    model, p[None], **s).numpy()[0, len(p):]
                np.testing.assert_array_equal(np.asarray(rr.result(1.0)), ref)
            # the crash actually displaced someone
            assert sum(rr.retries for rr in rrs) >= 1
            # health gating saw it
            states = {r["name"]: r["state"] for r in router.replicas()}
            assert states["r0"] == serving.ReplicaState.EJECTED
            assert states["r1"] == serving.ReplicaState.HEALTHY
            assert not e1.healthy and e2.healthy
            # zero retraces on the survivor (and everywhere)
            assert _serving_retraces() == retr0
            # bounded amplification
            st = router.stats()
            rc = router.config
            assert st["extra_attempts"] <= (
                rc.retry_amplification_cap * st["requests"]
                + rc.retry_amplification_floor)
        finally:
            router.stop(drain=True, timeout_s=10)

    def test_crash_failover_merged_trace(self, tiny_model):
        """The fleet-trace acceptance: kill r0 mid-decode, then ask the
        router for ONE merged catapult file of a displaced request. It
        must carry the router's own lane plus a swimlane per attempt —
        attempt 1 on the dead replica, attempt 2 on the survivor — as
        loadable JSON with attempt spans nested inside the root span."""
        model, cfg = tiny_model
        e1, e2 = _engine(model), _engine(model)
        cfgr = serving.RouterConfig(probe_failures_to_eject=2,
                                    max_retries_per_request=2,
                                    unroutable_timeout_s=10.0)
        router = serving.Router([e1, e2], cfgr)
        monkey = serving.ChaosEngine(e1).crash_after_steps(2)
        rng = np.random.RandomState(SEED + 21)
        prompts = [_prompt(rng, cfg, 4 + i) for i in range(6)]
        try:
            rrs = [router.submit(p, max_new_tokens=8) for p in prompts]
            _drive(router, rrs)
            assert monkey.injected["crash"] == 1
            assert all(rr.status == serving.RequestStatus.COMPLETED
                       for rr in rrs)
            displaced = [rr for rr in rrs if rr.retries >= 1]
            assert displaced  # the crash took someone's first attempt
            rr = displaced[0]
            merged = router.merged_trace(rr.id)
            assert merged is not None
            merged = json.loads(json.dumps(merged))  # loadable JSON
            lanes = {ev["args"]["name"]: ev["pid"]
                     for ev in merged["traceEvents"]
                     if ev.get("ph") == "M"
                     and ev["name"] == "process_name"}
            # router lane + one swimlane per attempt
            assert f"router request {rr.id}" in lanes
            attempt_lanes = [n for n in lanes if n.startswith("attempt ")]
            assert len(attempt_lanes) >= 2
            assert any("[r0]" in n for n in attempt_lanes)
            assert any("[r1]" in n for n in attempt_lanes)
            # each attempt lane carries the replica-side request span
            by_pid = {}
            for ev in merged["traceEvents"]:
                if ev.get("ph") == "X":
                    by_pid.setdefault(ev["pid"], []).append(ev)
            for name in attempt_lanes:
                spans = {e["name"] for e in by_pid.get(lanes[name], [])}
                assert "request" in spans, (name, spans)
            # monotonic nesting on the router lane: every attempt span
            # sits inside the root router.request interval
            rl = by_pid[lanes[f"router request {rr.id}"]]
            root = next(e for e in rl if e["name"] == "router.request")
            attempts = [e for e in rl if e["name"] == "router.attempt"]
            assert len(attempts) == rr.retries + 1
            for a in attempts:
                assert a["ts"] >= root["ts"]
                assert a["ts"] + a["dur"] <= root["ts"] + root["dur"]
            # attempt trace ids are distinct per retry (one swimlane
            # each, never merged into one)
            assert len(set(attempt_lanes)) == len(attempt_lanes)
        finally:
            router.stop(drain=True, timeout_s=10)

    def test_all_replicas_dead_fails_explicitly(self, tiny_model):
        """One replica, crashed: the request fails with an actionable
        routing error (bounded by unroutable_timeout_s) — it does NOT
        hang and is NOT silently dropped."""
        model, cfg = tiny_model
        e1 = _engine(model)
        router = serving.Router(
            [e1], probe_failures_to_eject=1, max_retries_per_request=1,
            unroutable_timeout_s=0.3)
        serving.ChaosEngine(e1).crash_after_steps(0)
        rng = np.random.RandomState(SEED + 4)
        try:
            rr = router.submit(_prompt(rng, cfg, 5), max_new_tokens=8)
            _drive(router, [rr], timeout=30)
            assert rr.status == serving.RequestStatus.FAILED
            assert "no admitting replica" in rr.error \
                or "retry" in rr.error
        finally:
            router.stop()

    def test_replacement_replica_boots_warm(self, tiny_model):
        """Crash + eject r0, then register a replacement: the router
        warms it at registration, and its FIRST routed request is
        served with zero new serving compiles."""
        model, cfg = tiny_model
        e1 = _engine(model)
        router = serving.Router([e1], probe_failures_to_eject=1,
                                unroutable_timeout_s=10.0)
        serving.ChaosEngine(e1).crash_after_steps(0)
        rng = np.random.RandomState(SEED + 5)
        try:
            rr = router.submit(_prompt(rng, cfg, 5), max_new_tokens=6)
            # let the crash land and the probe eject
            t0 = time.monotonic()
            while router.replicas()[0]["state"] != "ejected":
                router.probe_once()
                time.sleep(0.01)
                assert time.monotonic() - t0 < 30
            # boot the replacement (auto-warmed at registration)
            e2 = _engine(model)
            router.add_replica(e2, name="replacement")
            assert e2.warmed_up
            before = _serving_compiles()
            _drive(router, [rr])
            assert rr.status == serving.RequestStatus.COMPLETED
            assert rr.replica == "replacement"
            ref = generation.generate(
                model,
                np.asarray(rr.prompt)[None],
                max_new_tokens=6).numpy()[0, len(rr.prompt):]
            np.testing.assert_array_equal(np.asarray(rr.output_tokens), ref)
            assert _serving_compiles() == before  # warm boot: 0 compiles
        finally:
            router.stop(drain=True, timeout_s=10)

    def test_on_token_never_fires_after_failover(self, tiny_model):
        """The satellite contract: once a request fails over, the dead
        attempt's ``on_token`` relay is detached — even if the hung
        replica later resumes and keeps decoding, the caller sees each
        token EXACTLY once, in order."""
        model, cfg = tiny_model
        e1 = _engine(model, stall_timeout_s=0.2)
        e2 = _engine(model)
        router = serving.Router([e1, e2], probe_failures_to_eject=1,
                                unroutable_timeout_s=10.0)
        monkey = serving.ChaosEngine(e1).hang_after_steps(1)
        rng = np.random.RandomState(SEED + 6)
        p = _prompt(rng, cfg, 5)
        seen = []
        try:
            rr = router.submit(p, max_new_tokens=8,
                               on_token=lambda r, t: seen.append(int(t)))
            _drive(router, [rr])  # probes see "stalled", eject, fail over
            assert monkey.injected["hang"] == 1
            assert rr.status == serving.RequestStatus.COMPLETED
            assert rr.replica == "r1" and rr.retries >= 1
            # un-hang the zombie: its engine pushes more tokens into the
            # DETACHED relay — none may reach the caller
            monkey.release()
            time.sleep(0.3)
            ref = generation.generate(model, p[None],
                                      max_new_tokens=8).numpy()[0, 5:]
            np.testing.assert_array_equal(np.asarray(rr.output_tokens), ref)
            assert seen == list(rr.output_tokens)  # exactly once, in order
        finally:
            monkey.release()
            router.stop(drain=True, timeout_s=10)


# ---------------------------------------------------------------------------
# chaos: control-plane faults (probes, stats, submit storms)
# ---------------------------------------------------------------------------

class TestControlPlaneChaos:
    def test_malformed_probes_eject_then_readmit(self, tiny_model):
        """K malformed probe payloads eject; clean probes re-admit —
        but only once the warmup probe passes."""
        model, cfg = tiny_model
        e1 = _engine(model)
        chaos = serving.ChaosReplica(serving.LocalReplica(e1, "c0"))
        router = serving.Router([chaos], probe_failures_to_eject=2)
        try:
            chaos.fail_probes(2, mode="malformed")
            router.probe_once()
            assert router.replicas()[0]["state"] == "healthy"  # 1 of K
            router.probe_once()
            assert router.replicas()[0]["state"] == "ejected"
            assert chaos.injected["probe"] == 2
            # an ok-but-cold payload must NOT readmit (warmup gate)
            chaos.fail_probes(1, mode="malformed",
                              payload={"status": "ok", "warmed_up": False})
            router.probe_once()
            assert router.replicas()[0]["state"] == "ejected"
            # the real (warmed) engine payload readmits
            router.probe_once()
            assert router.replicas()[0]["state"] == "healthy"
        finally:
            router.stop(drain=True, timeout_s=10)

    def test_stats_timeout_keeps_replica_in_rotation(self, tiny_model):
        """A hung /stats endpoint is NOT a dead replica: the router
        scores it on last-known load (bounded by stats_timeout_s) and
        requests keep completing."""
        model, cfg = tiny_model
        e1 = _engine(model)
        chaos = serving.ChaosReplica(serving.LocalReplica(e1, "s0"))
        router = serving.Router(
            [chaos], stats_timeout_s=0.05, stats_refresh_s=0.0)
        chaos.fail_stats(50, mode="timeout", hang_s=1.0)
        rng = np.random.RandomState(SEED + 7)
        p = _prompt(rng, cfg, 5)
        try:
            t0 = time.monotonic()
            rr = router.submit(p, max_new_tokens=5)
            _drive(router, [rr])
            assert rr.status == serving.RequestStatus.COMPLETED
            assert chaos.injected["stats"] >= 1
            assert router.replicas()[0]["state"] == "healthy"
            assert router.replicas()[0]["load"]["stale"]
            # the hung stats call was cut loose, not waited out
            assert time.monotonic() - t0 < 10.0
        finally:
            router.stop(drain=True, timeout_s=10)

    def test_pool_exhausted_storm_routes_to_healthy_replica(self, tiny_model):
        """Submit-time PoolExhausted storms on r0: requests route to
        r1; r0 is NOT ejected (admission failure != death)."""
        model, cfg = tiny_model
        e1, e2 = _engine(model), _engine(model)
        chaos = serving.ChaosReplica(serving.LocalReplica(e1, "p0"))
        router = serving.Router([chaos, e2])
        chaos.reject_submits(50, exc="pool")
        rng = np.random.RandomState(SEED + 8)
        try:
            rrs = [router.submit(_prompt(rng, cfg, 4 + i), max_new_tokens=4)
                   for i in range(3)]
            _drive(router, rrs)
            assert all(r.status == serving.RequestStatus.COMPLETED
                       for r in rrs)
            assert all(r.replica == "r1" for r in rrs)
            assert chaos.injected["submit"] >= 1
            states = {r["name"]: r["state"] for r in router.replicas()}
            assert states["p0"] == "healthy"
        finally:
            router.stop(drain=True, timeout_s=10)

    def test_backpressure_marks_saturated_and_backs_off(self, tiny_model):
        """QueueFullError marks the replica saturated (digest-derived
        backoff) instead of ejecting it; traffic flows to the other
        replica meanwhile."""
        model, cfg = tiny_model
        e1, e2 = _engine(model), _engine(model)
        chaos = serving.ChaosReplica(serving.LocalReplica(e1, "q0"))
        router = serving.Router([chaos, e2])
        chaos.reject_submits(1, exc="queue")
        rng = np.random.RandomState(SEED + 9)
        try:
            rr = router.submit(_prompt(rng, cfg, 5), max_new_tokens=4)
            _drive(router, [rr])
            assert rr.status == serving.RequestStatus.COMPLETED
            rows = {r["name"]: r for r in router.replicas()}
            if chaos.injected["submit"]:  # the storm hit this request
                assert rr.replica == "r1"
                assert rows["q0"]["state"] == "healthy"
        finally:
            router.stop(drain=True, timeout_s=10)

    def test_amplification_cap_bounds_a_failure_storm(self, tiny_model):
        """With every replica crashing, retries stop at the global
        amplification cap and requests fail EXPLICITLY — a storm sheds
        load instead of multiplying it."""
        model, cfg = tiny_model
        e1 = _engine(model)
        router = serving.Router(
            [e1], probe_failures_to_eject=100,  # keep it routable:
            max_retries_per_request=50,         # only the cap may stop us
            retry_amplification_cap=0.5, retry_amplification_floor=2,
            retry_backoff_base_s=0.001, unroutable_timeout_s=0.5)
        serving.ChaosEngine(e1).crash_after_steps(0)
        rng = np.random.RandomState(SEED + 10)
        try:
            rrs = [router.submit(_prompt(rng, cfg, 4), max_new_tokens=4)
                   for _ in range(2)]
            _drive(router, rrs, timeout=30, probe=False)
            assert all(r.status in (serving.RequestStatus.FAILED,
                                    serving.RequestStatus.EXPIRED)
                       for r in rrs)
            st = router.stats()
            assert st["extra_attempts"] <= 0.5 * st["requests"] + 2
            assert any(r.error and ("retry" in r.error
                                    or "no admitting replica" in r.error)
                       for r in rrs)
        finally:
            router.stop()


# ---------------------------------------------------------------------------
# deadline / cancel races the router relies on
# ---------------------------------------------------------------------------

class TestDeadlineCancelRaces:
    def test_cancelled_request_is_never_retried(self, tiny_model):
        """Cancel while the attempt's replica is hung: the request ends
        CANCELLED with zero retries (cancelled requests never fail
        over)."""
        model, cfg = tiny_model
        e1 = _engine(model, stall_timeout_s=30.0)  # stall stays invisible
        router = serving.Router([e1], probe_failures_to_eject=1)
        monkey = serving.ChaosEngine(e1).hang_after_steps(1)
        rng = np.random.RandomState(SEED + 11)
        try:
            rr = router.submit(_prompt(rng, cfg, 5), max_new_tokens=10)
            t0 = time.monotonic()
            while monkey.injected["hang"] == 0:
                time.sleep(0.005)
                assert time.monotonic() - t0 < 20
            rr.cancel()
            _drive(router, [rr], probe=False)
            assert rr.status == serving.RequestStatus.CANCELLED
            assert rr.retries == 0
        finally:
            monkey.release()
            router.stop()

    def test_deadline_expiring_during_backoff_fails_expired(self, tiny_model):
        """A retry whose backoff cannot beat the deadline fails as
        EXPIRED immediately (deadline-aware retry), not after a doomed
        attempt."""
        model, cfg = tiny_model
        e1 = _engine(model)
        router = serving.Router(
            [e1], probe_failures_to_eject=100, max_retries_per_request=5,
            retry_backoff_base_s=5.0, retry_backoff_max_s=5.0,
            retry_jitter=0.0, unroutable_timeout_s=5.0)
        serving.ChaosEngine(e1).crash_after_steps(0)
        rng = np.random.RandomState(SEED + 12)
        try:
            rr = router.submit(_prompt(rng, cfg, 5), max_new_tokens=8,
                               deadline_s=1.0)
            _drive(router, [rr], timeout=30, probe=False)
            assert rr.status == serving.RequestStatus.EXPIRED
            assert "backoff" in rr.error or "deadline" in rr.error
        finally:
            router.stop()


# ---------------------------------------------------------------------------
# hedging
# ---------------------------------------------------------------------------

class TestHedging:
    def test_hedge_rescues_slow_replica(self, tiny_model):
        """A replica slowed far past the TTFT threshold gets hedged to
        the other replica; the winner's tokens are delivered exactly
        once and match generate()."""
        model, cfg = tiny_model
        e1, e2 = _engine(model), _engine(model)
        router = serving.Router(
            [e1, e2], hedge=True, hedge_min_wait_s=0.15,
            hedge_ttft_factor=1.0, w_inflight=0.0)  # keep r0 preferred
        # r0 crawls: every step +0.4 s (alive, just slow)
        monkey = serving.ChaosEngine(e1).slow_steps(0.4, after=0,
                                                    for_steps=200)
        rng = np.random.RandomState(SEED + 13)
        p = _prompt(rng, cfg, 5)
        try:
            # pin the first pick to r0 deterministically: r1 briefly
            # saturated at submit time
            router._replicas["r1"].saturated_until = \
                time.perf_counter() + 0.1
            rr = router.submit(p, max_new_tokens=6)
            _drive(router, [rr], probe=False)
            assert rr.status == serving.RequestStatus.COMPLETED
            ref = generation.generate(model, p[None],
                                      max_new_tokens=6).numpy()[0, 5:]
            np.testing.assert_array_equal(np.asarray(rr.result(1.0)), ref)
            if monkey.injected["slow"]:  # r0 really was the first pick
                assert rr.hedged
                assert rr.replica == "r1"
        finally:
            monkey.restore()
            router.stop(drain=True, timeout_s=10)


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------

class TestDrain:
    def test_drain_finishes_inflight_and_routes_new_elsewhere(
            self, tiny_model):
        """router.drain(r0) on a loaded replica: its in-flight requests
        complete within their deadlines, new traffic lands on r1, and
        r0 ends stopped with /healthz distinguishing the drain."""
        model, cfg = tiny_model
        e1, e2 = _engine(model), _engine(model)
        router = serving.Router([e1, e2])
        rng = np.random.RandomState(SEED + 14)
        try:
            inflight = [router.submit(_prompt(rng, cfg, 4 + i),
                                      max_new_tokens=12, deadline_s=30.0)
                        for i in range(4)]
            time.sleep(0.1)  # let them land on both replicas
            router.drain("r0", wait=True)
            assert e1.stopped
            assert {r["name"]: r["state"] for r in router.replicas()}[
                "r0"] == "stopped"
            rr = router.submit(_prompt(rng, cfg, 5), max_new_tokens=4)
            _drive(router, inflight + [rr], probe=False)
            assert all(r.status == serving.RequestStatus.COMPLETED
                       for r in inflight + [rr])
            assert rr.replica == "r1"
            with pytest.raises(serving.EngineStoppedError):
                e1.submit([1, 2, 3])
        finally:
            router.stop(drain=True, timeout_s=10)

    def test_sigterm_drains_the_fleet(self, tiny_model):
        """The SIGTERM path (driven via the fault-tolerance preemption
        listener, no real signal needed): every replica drains, nothing
        in flight is lost."""
        from paddle_tpu.fault_tolerance.preemption import (
            clear_preemption, request_preemption)

        model, cfg = tiny_model
        e1, e2 = _engine(model), _engine(model)
        router = serving.Router([e1, e2])
        serving.install_sigterm_drain(router, timeout_s=30.0)
        rng = np.random.RandomState(SEED + 15)
        try:
            rrs = [router.submit(_prompt(rng, cfg, 4 + i),
                                 max_new_tokens=10) for i in range(3)]
            time.sleep(0.05)
            request_preemption()  # the SIGTERM stand-in
            _drive(router, rrs, probe=False)
            assert all(r.status == serving.RequestStatus.COMPLETED
                       for r in rrs)
            t0 = time.monotonic()
            while not (e1.stopped and e2.stopped):
                time.sleep(0.01)
                assert time.monotonic() - t0 < 30
        finally:
            serving.uninstall_sigterm_drain(router)
            clear_preemption()
            router.stop()


# ---------------------------------------------------------------------------
# spec-decode engines ride the same router (warmup covers draft+verify)
# ---------------------------------------------------------------------------

class TestSpecEngineWarmup:
    @pytest.mark.slow
    def test_spec_engine_warmup_covers_draft_and_verify(self, tiny_model):
        model, cfg = tiny_model
        draft = generation.truncated_draft(model, 1)
        eng = serving.ServingEngine(model, draft_model=draft, spec_k=2,
                                    max_slots=2, max_len=64)
        info = eng.warmup()
        assert set(info["entries"]) == {"serving.prefill_chunk",
                                        "serving.prefill_chunk[2]",
                                        "serving.cow", "serving.spec_draft",
                                        "serving.spec_verify"}
        before = _serving_compiles()
        rng = np.random.RandomState(SEED + 16)
        p = _prompt(rng, cfg, 5)
        req = eng.submit(p, max_new_tokens=6)
        eng.run_until_idle()
        assert req.status == serving.RequestStatus.COMPLETED
        ref = generation.generate(model, p[None],
                                  max_new_tokens=6).numpy()[0, 5:]
        np.testing.assert_array_equal(np.asarray(req.result(1.0)), ref)
        assert _serving_compiles() == before


# ---------------------------------------------------------------------------
# router over HTTP (router_http.py) + the HTTPReplica client
# ---------------------------------------------------------------------------

class TestRouterHTTP:
    def test_generate_healthz_replicas_drain(self, tiny_model):
        model, cfg = tiny_model
        e1, e2 = _engine(model), _engine(model)
        router = serving.Router([e1, e2])
        srv = serving.RouterHTTPServer(router, port=0)
        rng = np.random.RandomState(SEED + 17)
        p = _prompt(rng, cfg, 5)
        try:
            body = json.dumps({"prompt": [int(t) for t in p],
                               "max_new_tokens": 6}).encode()
            rec = json.loads(urllib.request.urlopen(
                urllib.request.Request(
                    f"http://127.0.0.1:{srv.port}/generate", data=body),
                timeout=60).read())
            assert rec["status"] == "completed"
            ref = generation.generate(model, p[None],
                                      max_new_tokens=6).numpy()[0, 5:]
            np.testing.assert_array_equal(np.asarray(rec["tokens"]), ref)
            assert rec["replica"] in ("r0", "r1")

            health = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=10).read())
            assert health["status"] == "ok"
            assert health["healthy_replicas"] == 2

            # drain one replica over HTTP; fleet stays ok
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/drain",
                data=json.dumps({"replica": "r0",
                                 "timeout_s": 30}).encode()), timeout=10)
            t0 = time.monotonic()
            while True:
                rows = json.loads(urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/replicas",
                    timeout=10).read())["replicas"]
                if {r["name"]: r["state"] for r in rows}["r0"] == "stopped":
                    break
                time.sleep(0.02)
                assert time.monotonic() - t0 < 30
            health = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=10).read())
            assert health["healthy_replicas"] == 1
        finally:
            srv.stop()
            router.stop(drain=True, timeout_s=10)

    def test_http_replica_client_roundtrip(self, tiny_model):
        """A Router over an HTTPReplica (an engine behind serving.http):
        probes read the 503-capable /healthz, generation streams through
        POST /generate, outputs match generate()."""
        model, cfg = tiny_model
        eng = _engine(model)
        esrv = serving.ServingHTTPServer(eng, port=0)
        hr = serving.HTTPReplica(f"http://127.0.0.1:{esrv.port}",
                                 name="remote0")
        router = serving.Router([hr])
        rng = np.random.RandomState(SEED + 18)
        p = _prompt(rng, cfg, 5)
        try:
            assert hr.healthz()["status"] == "ok"
            rr = router.submit(p, max_new_tokens=6)
            _drive(router, [rr])
            assert rr.status == serving.RequestStatus.COMPLETED
            ref = generation.generate(model, p[None],
                                      max_new_tokens=6).numpy()[0, 5:]
            np.testing.assert_array_equal(np.asarray(rr.result(1.0)), ref)
            assert rr.replica == "remote0"
        finally:
            esrv.stop()
            eng.stop()
            router.stop()

    def test_fleet_endpoints(self, tiny_model):
        """Router GET /metrics federates every replica's series under
        replica=<name> labels plus replica="fleet" roll-ups; GET /slo
        reports the burn-rate verdict; GET /trace?request= returns the
        merged catapult file (404 for unknown ids, 400 without one)."""
        from paddle_tpu.observability.exporters import parse_prometheus_text

        model, cfg = tiny_model
        e1, e2 = _engine(model), _engine(model)
        router = serving.Router([e1, e2], stats_refresh_s=0.05)
        srv = serving.RouterHTTPServer(router, port=0)
        base = f"http://127.0.0.1:{srv.port}"
        rng = np.random.RandomState(SEED + 22)
        p = _prompt(rng, cfg, 5)
        try:
            body = json.dumps({"prompt": [int(t) for t in p],
                               "max_new_tokens": 6}).encode()
            rec = json.loads(urllib.request.urlopen(
                urllib.request.Request(f"{base}/generate", data=body),
                timeout=60).read())
            assert rec["status"] == "completed"
            time.sleep(0.1)  # let the staleness window lapse

            resp = urllib.request.urlopen(f"{base}/metrics", timeout=10)
            assert resp.headers["Content-Type"].startswith("text/plain")
            fams = parse_prometheus_text(resp.read().decode())
            reqs = fams["paddle_tpu_serving_requests_total"]["samples"]
            reps = {s["labels"].get("replica") for s in reqs}
            assert {"r0", "r1", "fleet"} <= reps
            assert "paddle_tpu_fleet_scrape_age_seconds" in fams

            slo = json.loads(urllib.request.urlopen(
                f"{base}/slo", timeout=10).read())
            assert slo["ok"] is True and slo["observed"] >= 1
            assert set(slo["objectives"]) == {"availability", "goodput",
                                              "ttft_p95"}

            merged = json.loads(urllib.request.urlopen(
                f"{base}/trace?request={rec['request_id']}",
                timeout=10).read())
            lanes = [ev["args"]["name"] for ev in merged["traceEvents"]
                     if ev.get("ph") == "M"
                     and ev["name"] == "process_name"]
            assert f"router request {rec['request_id']}" in lanes
            assert any(n.startswith("attempt 1 ") for n in lanes)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/trace?request=999999",
                                       timeout=10)
            assert ei.value.code == 404
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/trace", timeout=10)
            assert ei.value.code == 400
        finally:
            srv.stop()
            router.stop(drain=True, timeout_s=10)

    def test_hostile_traceparent_never_errors(self, tiny_model):
        """Malformed traceparent headers on the routed /generate path
        cost nothing: the request completes 200 with a fresh local
        trace — never a 400/500."""
        model, cfg = tiny_model
        eng = _engine(model)
        esrv = serving.ServingHTTPServer(eng, port=0)
        hr = serving.HTTPReplica(f"http://127.0.0.1:{esrv.port}",
                                 name="remote0")
        router = serving.Router([hr])
        srv = serving.RouterHTTPServer(router, port=0)
        rng = np.random.RandomState(SEED + 23)
        p = _prompt(rng, cfg, 4)
        hostile = ["", "garbage", "00-zz-11-01", "00-" + "0" * 32 + "-"
                   + "0" * 16 + "-01", "01-" + "ab" * 16 + "-" + "cd" * 8
                   + "-01", "x" * 512]
        try:
            for header in hostile:
                body = json.dumps({"prompt": [int(t) for t in p],
                                   "max_new_tokens": 2}).encode()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{srv.port}/generate", data=body,
                    headers={"traceparent": header})
                resp = urllib.request.urlopen(req, timeout=60)
                assert resp.status == 200
                assert json.loads(resp.read())["status"] == "completed"
        finally:
            srv.stop()
            esrv.stop()
            eng.stop()
            router.stop()

    def test_router_metrics_scrape(self, tiny_model):
        """The router instrument family lands in the shared registry
        exposition."""
        from paddle_tpu import observability as obs
        from paddle_tpu.serving import metrics as sm

        # labeled instruments expose once a child exists; make sure the
        # scrape doesn't depend on suite ordering
        sm.router_requests_total.labels("completed")
        sm.router_probe_failures_total.labels("error")
        text = obs.prometheus_text()
        for name in ("paddle_tpu_router_requests_total",
                     "paddle_tpu_router_attempts_total",
                     "paddle_tpu_router_ejections_total",
                     "paddle_tpu_router_probe_failures_total"):
            assert name in text


# ---------------------------------------------------------------------------
# supervisor-aware placement
# ---------------------------------------------------------------------------

class TestSupervisorAwareScoring:
    def test_restart_pressure_sheds_load(self, tiny_model):
        """A replica whose supervisor block shows a nearly-spent restart
        budget scores worse than an equally-loaded clean replica, so the
        fleet sheds load off it BEFORE the crash-loop breaker trips —
        and ``/replicas`` surfaces the pressure for operators."""
        model, cfg = tiny_model
        e1, e2 = _engine(model), _engine(model)
        router = serving.Router([e1, e2], w_ttft=0.0)
        try:
            flappy = router._replicas["r0"]
            clean = router._replicas["r1"]
            real_stats = flappy.client.stats

            def flapping_stats():
                st = real_stats()
                st["supervisor"] = {"max_restarts": 3,
                                    "restarts_in_window": 2,
                                    "quarantined": ["deadbeef01"]}
                return st

            flappy.client.stats = flapping_stats
            now = time.perf_counter()
            flappy.load.ts = clean.load.ts = 0.0
            router._refresh_load(flappy, now)
            router._refresh_load(clean, now)
            assert flappy.load.restart_pressure == pytest.approx(2 / 3)
            assert flappy.load.quarantined_count == 1
            assert clean.load.restart_pressure == 0.0
            # strictly worse at equal load; weight off -> term gone
            assert router._score(flappy, 0.0) > router._score(clean, 0.0)
            assert (router._score(flappy, 0.0) - router._score(clean, 0.0)
                    == pytest.approx(router.config.w_restart * 2 / 3))
            # the same block still gossips quarantines fleet-wide
            assert "deadbeef01" in router._quarantined
            rows = {r["name"]: r for r in router.replicas()}
            assert rows["r0"]["load"]["restart_pressure"] == pytest.approx(
                2 / 3, abs=1e-4)
            assert rows["r0"]["load"]["quarantined_count"] == 1
            assert rows["r1"]["load"]["restart_pressure"] == 0.0
            # end-to-end: sequential picks on an idle pool all avoid the
            # flapping replica
            rng = np.random.RandomState(SEED + 70)
            for _ in range(3):
                rr = router.submit(_prompt(rng, cfg, 4), max_new_tokens=3)
                _drive(router, [rr], probe=False)
                assert rr.status == serving.RequestStatus.COMPLETED
                assert rr.replica == "r1"
        finally:
            router.stop(drain=True, timeout_s=10)

    def test_w_restart_validation_and_off_switch(self, tiny_model):
        model, _ = tiny_model
        with pytest.raises(ValueError, match="w_restart"):
            serving.RouterConfig(w_restart=-0.1)
        eng = _engine(model)
        router = serving.Router([eng], w_restart=0.0, auto_warmup=False)
        try:
            rep = router._replicas["r0"]
            rep.load.restart_pressure = 1.0  # even a breaker-edge replica
            base = serving.Router([_engine(model)], w_restart=0.0,
                                  auto_warmup=False)
            try:
                other = base._replicas["r0"]
                assert router._score(rep, 0.0) == base._score(other, 0.0)
            finally:
                base.stop(drain=False)
        finally:
            router.stop(drain=False)
