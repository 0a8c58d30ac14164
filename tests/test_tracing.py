"""Request-lifecycle tracing, flight recorder, and latency digests.

Oracles:
- SPAN SEMANTICS: spans/instants carry monotonic perf_counter_ns
  timestamps, thread-local trace context propagates, cross-call-site
  begin/end works, and disable reduces recording to nothing.
- SINGLE TRACE PER REQUEST: a request that is preempted and resumed
  yields ONE trace (filtered by its id) containing every lifecycle
  phase — queued/admitted/prefill-chunk/preemption/requeue/resume/
  decode/complete — with nesting-consistent timestamps, exportable as
  valid Chrome-trace JSON via ``GET /trace``.
- FLIGHT RECORDER: an injected decode-loop crash writes a dump with
  the last-N events AND the engine/pool state.
- DIGEST ACCURACY: streaming p50/p95/p99 match ``numpy.percentile``
  exactly within the window.
- ZERO RETRACES: the one-step-compile invariant holds over 3 request
  waves WITH tracing enabled (host-side instrumentation only).
- THE LANE ``proc``: the beat records nothing while it wakes on time and
  one ``proc.pause`` for a late wake, with the kernel's counters as
  differences of two reads; a process stopped and continued comes back
  with one pause of that length; a pass of the collector is a
  ``proc.gc`` where it was long or of the oldest generation; a flight
  dump taken after a stall holds the stall and what overlapped it; with
  tracing disabled no thread, no ``gc.callbacks`` entry and no thread
  clock.
"""

import gc
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import profiler, serving
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import recompile, tracing

SEED = 4242


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(max_position_embeddings=256)
    return LlamaForCausalLM(cfg), cfg


def _prompt(rng, cfg, n):
    return rng.randint(1, cfg.vocab_size, n).astype("int32")


def _spans(evs, name=None):
    out = [e for e in evs if e["ph"] == "X"]
    return [e for e in out if e["name"] == name] if name else out


def _instants(evs, name=None):
    out = [e for e in evs if e["ph"] == "i"]
    return [e for e in out if e["name"] == name] if name else out


# ---------------------------------------------------------------------------
# span / instant / context API
# ---------------------------------------------------------------------------


class TestSpanAPI:
    def test_span_instant_and_context(self):
        with tracing.trace_context("t_api"):
            assert tracing.current_trace() == "t_api"
            with tracing.span("outer", cat="test"):
                tracing.instant("mark", args={"k": 1})
            with tracing.trace_context("t_inner"):
                assert tracing.current_trace() == "t_inner"
            assert tracing.current_trace() == "t_api"
        evs = tracing.events(trace="t_api")
        (sp,) = _spans(evs, "outer")
        (inst,) = _instants(evs, "mark")
        assert sp["dur_ns"] >= 0 and inst["dur_ns"] == 0
        assert inst["args"] == {"k": 1}
        # the instant happened inside the span
        assert sp["ts_ns"] <= inst["ts_ns"] <= sp["ts_ns"] + sp["dur_ns"]

    def test_begin_end_across_threads(self):
        sp = tracing.begin_span("crossing", trace="t_cross")
        t = threading.Thread(target=lambda: tracing.end_span(sp))
        t.start()
        t.join()
        (got,) = _spans(tracing.events(trace="t_cross"), "crossing")
        assert got["dur_ns"] >= 0

    def test_end_is_idempotent(self):
        sp = tracing.begin_span("once", trace="t_idem")
        tracing.end_span(sp)
        tracing.end_span(sp)
        assert len(_spans(tracing.events(trace="t_idem"), "once")) == 1

    def test_disable_records_nothing(self):
        tracing.disable_tracing()
        try:
            assert tracing.begin_span("gone", trace="t_off") is None
            tracing.end_span(None)  # no-op, no guard needed at call sites
            with tracing.span("gone", trace="t_off"):
                tracing.instant("gone_i", trace="t_off")
        finally:
            tracing.enable_tracing()
        assert tracing.events(trace="t_off") == []

    def test_monotonic_ordering_and_counts(self):
        for i in range(5):
            tracing.instant("tick", trace="t_mono", args={"i": i})
        evs = tracing.events(trace="t_mono", name="tick")
        ts = [e["ts_ns"] for e in evs]
        assert ts == sorted(ts)
        assert [e["args"]["i"] for e in evs] == list(range(5))
        assert tracing.span_counts()["tick"] >= 5

    def test_chrome_trace_structure(self):
        with tracing.span("lane_span", trace="t_chrome"):
            tracing.instant("lane_mark", trace="t_chrome")
        ct = tracing.chrome_trace("t_chrome")
        ct = json.loads(json.dumps(ct))  # JSON-clean
        evs = ct["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        assert any(e["name"] == "process_name" for e in meta)
        assert any(e["name"] == "thread_name"
                   and e["args"]["name"] == "t_chrome" for e in meta)
        xs = [e for e in evs if e["ph"] == "X"]
        assert xs and all("dur" in e and "ts" in e for e in xs)
        assert all(e["ph"] in ("M", "X", "i") for e in evs)

    def test_profiler_record_event_interop(self):
        tracing.attach_profiler_spans()
        try:
            with tracing.trace_context("t_prof"):
                with profiler.RecordEvent("interop_span"):
                    time.sleep(0.001)
        finally:
            tracing.detach_profiler_spans()
        (sp,) = _spans(tracing.events(trace="t_prof"), "interop_span")
        assert sp["cat"] == "profiler" and sp["dur_ns"] > 0
        # detached again: RecordEvent no longer feeds the trace
        with profiler.RecordEvent("interop_span2"):
            pass
        assert not _spans(tracing.events(), "interop_span2")


# ---------------------------------------------------------------------------
# digests + summary metrics
# ---------------------------------------------------------------------------


class TestDigests:
    def test_digest_matches_numpy_percentiles(self):
        rng = np.random.RandomState(7)
        xs = rng.gamma(2.0, 0.05, size=1000)
        d = tracing.Digest(window=4096)
        for v in xs:
            d.observe(float(v))
        for q, p in ((0.5, 50), (0.95, 95), (0.99, 99)):
            assert d.quantile(q) == pytest.approx(
                np.percentile(xs, p), rel=1e-12)
        pct = d.percentiles()
        assert pct["count"] == 1000
        assert pct["p95"] == pytest.approx(np.percentile(xs, 95), rel=1e-12)
        assert pct["mean"] == pytest.approx(xs.mean(), rel=1e-9)

    def test_digest_window_slides(self):
        d = tracing.Digest(window=100)
        for v in range(1000):
            d.observe(float(v))
        # only the last 100 samples (900..999) remain
        assert d.quantile(0.0) == 900.0
        assert d.quantile(1.0) == 999.0
        assert d.count == 1000  # lifetime count keeps counting

    def test_summary_metric_quantiles_and_exposition(self):
        s = obs.summary("t_tr_lat_seconds", "test summary")
        xs = np.linspace(0.01, 1.0, 200)
        for v in xs:
            s.observe(float(v))
        assert s.quantile(0.5) == pytest.approx(np.percentile(xs, 50))
        text = obs.prometheus_text()
        parsed = obs.parse_prometheus_text(text)
        fam = parsed["t_tr_lat_seconds"]
        assert fam["type"] == "summary"
        series = {(x["series"], x["labels"].get("quantile")): x["value"]
                  for x in fam["samples"]}
        assert series[("t_tr_lat_seconds", "0.5")] == pytest.approx(
            np.percentile(xs, 50))
        assert series[("t_tr_lat_seconds_count", None)] == 200
        assert series[("t_tr_lat_seconds_sum", None)] == pytest.approx(
            xs.sum())


# ---------------------------------------------------------------------------
# the serving engine's request-lifecycle trace
# ---------------------------------------------------------------------------


class TestEngineLifecycleTrace:
    def test_preempted_resumed_request_single_trace(self, tiny_model):
        """THE acceptance criterion: an oversubscribed pool forces
        preemption; the preempted+resumed request's trace (one trace id)
        contains every lifecycle phase with monotonic, nesting-consistent
        timestamps and exports as valid Chrome-trace JSON."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=3, max_len=128,
                                    num_blocks=13)  # 12 usable << 3*8
        rng = np.random.RandomState(SEED)
        prompts = [_prompt(rng, cfg, n) for n in (40, 55, 33)]
        reqs = [eng.submit(p, max_new_tokens=30) for p in prompts]
        eng.run_until_idle(max_steps=5000)
        assert eng._preempt_count >= 1
        assert all(r.status == serving.RequestStatus.COMPLETED for r in reqs)
        pre = [r for r in reqs if r.preempt_count > 0]
        assert pre, "no request was preempted"
        req = pre[0]

        evs = tracing.events(trace=req.id)
        # every lifecycle phase present
        assert len(_spans(evs, "request")) == 1
        assert len(_spans(evs, "queued")) == 2      # initial + post-preempt
        assert len(_spans(evs, "prefill")) == 2     # initial + recompute
        assert len(_spans(evs, "decode")) == 2      # around the preemption
        assert _spans(evs, "prefill_chunk")
        assert _instants(evs, "admitted") and _instants(evs, "preempted")
        assert _instants(evs, "requeued") and _instants(evs, "resume")
        assert _instants(evs, "first_token")
        assert _instants(evs, "completed")

        # monotonic + nesting-consistent: every event inside the root
        # request span; each decode span after its prefill span
        (root,) = _spans(evs, "request")
        for e in evs:
            assert e["ts_ns"] >= root["ts_ns"]
            assert e["ts_ns"] + e["dur_ns"] <= root["ts_ns"] + root["dur_ns"]
        pf = sorted(_spans(evs, "prefill"), key=lambda e: e["ts_ns"])
        dc = sorted(_spans(evs, "decode"), key=lambda e: e["ts_ns"])
        for p, d in zip(pf, dc):
            assert p["ts_ns"] + p["dur_ns"] <= d["ts_ns"]
        # the preemption instant falls between the two decode windows
        (prem,) = _instants(evs, "preempted")
        assert dc[0]["ts_ns"] <= prem["ts_ns"] <= dc[1]["ts_ns"]

        # chunk latency fed the digest; queue wait covers both waits
        st = eng.stats()
        assert st["latency_digests"]["prefill_chunk_s"]["count"] >= 1
        assert st["latency_digests"]["queue_wait_s"]["count"] >= len(reqs)
        assert req.queue_wait_total_s >= 0.0
        assert st["goodput_tokens_per_s"] > 0

        # valid, loadable catapult JSON
        ct = json.loads(json.dumps(tracing.chrome_trace(req.id)))
        xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
        assert {"request", "queued", "prefill", "decode"} <= \
            {e["name"] for e in xs}

    def test_compile_events_attributed_into_trace(self, tiny_model):
        """A fresh engine's first chunk compile lands in the active
        request's trace (cat=compile), not in limbo."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64,
                                    prefill_chunk=16)
        rng = np.random.RandomState(SEED + 1)
        req = eng.submit(_prompt(rng, cfg, 8), max_new_tokens=4)
        eng.run_until_idle()
        assert req.status == serving.RequestStatus.COMPLETED
        compiles = [e for e in tracing.events(trace=req.id)
                    if e["cat"] == "compile"]
        # (the lone chunk rides the engine's one width of rows, [2, C])
        assert any(e["name"] == "xla_compile:serving.prefill_chunk[2]"
                   and e["dur_ns"] > 0 for e in compiles)

    def test_zero_retraces_with_tracing_on_3_waves(self, tiny_model):
        """Tracing is host-side only: with it ENABLED (default), the
        pool decode step still compiles exactly once across >=3 mixed
        request waves — zero retraces."""
        assert tracing.tracing_enabled()
        model, cfg = tiny_model
        before = recompile.entry_stats().get("serving.step",
                                             {"compiles": 0, "retraces": 0})
        eng = serving.ServingEngine(model, max_slots=2, max_len=128,
                                    max_queue_depth=32, prefill_chunk=32)
        rng = np.random.RandomState(SEED + 2)
        for wave in range(3):
            reqs = [eng.submit(_prompt(rng, cfg, 3 + 9 * ((wave + i) % 5)),
                               max_new_tokens=2 + (wave + i) % 3,
                               do_sample=bool(i % 2), seed=i, top_k=5)
                    for i in range(4)]
            eng.run_until_idle()
            assert all(r.status == serving.RequestStatus.COMPLETED
                       for r in reqs)
        after = recompile.entry_stats()["serving.step"]
        assert after["compiles"] - before["compiles"] == 1
        assert after["retraces"] - before["retraces"] == 0
        # and the engine lane recorded its step spans without clocking
        # anything extra
        assert tracing.span_counts().get("serving.step", 0) >= 3

    def test_http_trace_debug_and_stats_endpoints(self, tiny_model):
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        rng = np.random.RandomState(SEED + 3)
        port = serving.start_serving_http_server(eng, port=0)
        try:
            body = json.dumps({
                "prompt": _prompt(rng, cfg, 6).tolist(),
                "max_new_tokens": 4}).encode()
            resp = json.loads(urllib.request.urlopen(
                urllib.request.Request(
                    f"http://127.0.0.1:{port}/generate", data=body,
                    headers={"Content-Type": "application/json"}),
                timeout=30).read())
            assert resp["status"] == "completed" and len(resp["tokens"]) == 4
            rid = resp["request_id"]

            trace = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/trace?trace={rid}",
                timeout=10).read())
            names = {e["name"] for e in trace["traceEvents"]
                     if e["ph"] == "X"}
            assert {"request", "queued", "prefill", "decode"} <= names

            dbg = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/requests", timeout=10).read())
            assert {"queued", "running", "recent"} <= set(dbg)
            assert any(r["request_id"] == rid for r in dbg["recent"])
            row = next(r for r in dbg["recent"] if r["request_id"] == rid)
            assert row["generated"] == 4 and row["ttft_s"] is not None

            stats = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=10).read())
            dig = stats["latency_digests"]
            assert dig["ttft_s"]["count"] >= 1
            assert dig["ttft_s"]["p50"] is not None
            assert dig["ttft_s"]["p99"] >= dig["ttft_s"]["p50"]
            assert "goodput_tokens_per_s" in stats
        finally:
            serving.stop_serving_http_server()
            eng.stop()

    def test_snapshot_captures_serving_state(self, tiny_model):
        """satellite: one observability.snapshot() call carries the
        serving gauges AND the live engine's block-pool stats."""
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        rng = np.random.RandomState(SEED + 4)
        eng.submit(_prompt(rng, cfg, 6), max_new_tokens=3)
        eng.run_until_idle()
        snap = obs.snapshot()
        assert "paddle_tpu_kv_blocks_in_use" in snap["serving"]["gauges"]
        assert "paddle_tpu_serving_queue_depth" in snap["serving"]["gauges"]
        engine_state = snap["serving"]["serving_engine"]
        assert engine_state["kv_mode"] == "paged"
        assert engine_state["kv_blocks"]["usable"] >= 1
        assert engine_state["latency_digests"]["ttft_s"]["count"] >= 1
        assert snap["tracing"]["span_counts"].get("serving.step", 0) >= 1
        json.dumps(snap)  # JSON-clean end to end


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_dump_contains_events_and_provider_state(self, tmp_path,
                                                     monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SINK_DIR", str(tmp_path))
        tracing.instant("fr_mark", trace="t_fr")
        tracing.register_state_provider("t_fr_state",
                                        lambda: {"answer": 42})
        tracing.register_state_provider("t_fr_broken",
                                        lambda: 1 / 0)
        try:
            path = tracing.flight_dump("unit_test")
        finally:
            tracing.unregister_state_provider("t_fr_state")
            tracing.unregister_state_provider("t_fr_broken")
        assert path is not None and path.startswith(str(tmp_path))
        dump = json.loads(open(path).read())
        assert dump["reason"] == "unit_test"
        assert any(e["name"] == "fr_mark" for e in dump["events"])
        assert dump["state"]["t_fr_state"] == {"answer": 42}
        # a broken provider contributes its error, not a dump failure
        assert "error" in dump["state"]["t_fr_broken"]
        assert tracing.last_flight_dump() == path

    def test_the_dump_reads_the_rings_tail_not_the_whole_ring(
            self, tmp_path, monkeypatch):
        """``last_n`` newest events, oldest first, without a pass over
        a ring that may hold a quarter of a million."""
        monkeypatch.setenv("PADDLE_TPU_SINK_DIR", str(tmp_path))
        for k in range(40):
            tracing.instant("fr_tail", trace="t_fr_tail", args={"k": k})
        # (another test's engine may idle on a thread of its own
        # meanwhile: its events may sit among the newest)
        got = [e["args"]["k"] for e in tracing.events(
            trace="t_fr_tail", last=5)]
        assert 3 <= len(got) <= 5 and got == list(range(40 - len(got), 40))
        assert len(tracing.events(trace="t_fr_tail")) == 40
        dump = json.loads(open(tracing.flight_dump(
            "unit_test_tail", last_n=8)).read())
        assert len(dump["events"]) == 8
        got = [e["args"]["k"] for e in dump["events"]
               if e["name"] == "fr_tail"]
        assert len(got) >= 6 and got == list(range(40 - len(got), 40))

    def test_dump_on_injected_decode_loop_crash(self, tiny_model, tmp_path,
                                                monkeypatch):
        """Acceptance: an injected engine crash writes a flight dump
        holding the last-N events + engine/pool state, and the engine
        fails every request instead of hanging."""
        monkeypatch.setenv("PADDLE_TPU_SINK_DIR", str(tmp_path))
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        rng = np.random.RandomState(SEED + 5)

        def _boom(*a, **k):
            raise RuntimeError("injected decode-loop crash")

        eng._step_fn = _boom
        req = eng.submit(_prompt(rng, cfg, 6), max_new_tokens=4)
        eng.start()
        try:
            req.result(timeout=30)
        finally:
            eng.stop()
        assert req.status == serving.RequestStatus.FAILED
        assert "injected decode-loop crash" in req.error
        assert eng.crashed is not None

        path = tracing.last_flight_dump()
        assert path is not None and path.startswith(str(tmp_path))
        dump = json.loads(open(path).read())
        assert dump["reason"] == "engine_crash"
        assert "injected decode-loop crash" in dump["extra"]["error"]
        # last-N events include this request's lifecycle
        traces = {e["trace"] for e in dump["events"]}
        assert req.id in traces
        # engine/pool state captured BEFORE the requests were failed
        state = dump["state"]["serving_engine"]
        assert state["kv_blocks"]["in_use"] >= 1
        assert state["slots_busy"] >= 1

    def test_a_dump_after_a_stall_holds_it_and_what_overlapped_it(
            self, tiny_model, tmp_path, monkeypatch, proc_watch):
        """What an operator asks a crash dump first: did the loop stand
        still, in which phase, and what did the process do meanwhile.
        A dispatch that sleeps 0.3 s behind a full collection leaves one
        ``engine.stall`` and the ``proc.gc`` inside it, both among the
        dump's events."""
        monkeypatch.setenv("PADDLE_TPU_SINK_DIR", str(tmp_path))
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=2, max_len=64)
        eng.warmup()
        tracing.watch_process()
        eng.submit(_prompt(np.random.RandomState(SEED + 9), cfg, 6),
                   max_new_tokens=8)
        for _ in range(3):
            assert eng.step()
        real, t0 = eng._enqueue_step, tracing._now()

        def slow(*a, **k):
            gc.collect()
            time.sleep(0.3)
            return real(*a, **k)

        eng._enqueue_step = slow
        assert eng.step()
        eng._enqueue_step = real
        eng.run_until_idle()
        dump = json.loads(open(tracing.flight_dump("unit_test_stall")).read())
        (stall,) = [e for e in dump["events"]
                    if e["name"] == "engine.stall" and e["ts_ns"] >= t0]
        assert stall["args"]["phase"] == "engine.dispatch"
        lo = stall["ts_ns"]
        hi = lo + stall["args"]["ms"] * 1e6
        inside = [e for e in dump["events"] if e["name"] == "proc.gc"
                  and lo <= e["ts_ns"] and e["ts_ns"] + e["dur_ns"] <= hi]
        assert inside and inside[0]["args"]["gen"] == 2
        assert dump["state"]["serving_engine"]["counters"]["stalls"] == 1

    def test_pool_exhausted_escape_dumps(self, tiny_model, tmp_path,
                                         monkeypatch):
        """Every in-engine PoolExhaustedError is absorbed by
        eviction/preemption today, so an ESCAPE from step() can only be
        a reclaim-logic regression — injected here — and must snapshot
        the flight recorder before propagating."""
        monkeypatch.setenv("PADDLE_TPU_SINK_DIR", str(tmp_path))
        model, cfg = tiny_model
        eng = serving.ServingEngine(model, max_slots=1, max_len=64)

        def _wedged():
            raise serving.PoolExhaustedError("injected reclaim wedge")

        eng._step_impl = _wedged
        before = tracing.last_flight_dump()
        with pytest.raises(serving.PoolExhaustedError):
            eng.step()
        path = tracing.last_flight_dump()
        assert path is not None and path != before
        dump = json.loads(open(path).read())
        assert dump["reason"] == "pool_exhausted"
        assert "injected reclaim wedge" in dump["extra"]["error"]
        # the state provider captured this engine's pool accounting
        assert dump["state"]["serving_engine"]["kv_blocks"]["usable"] >= 1


# ---------------------------------------------------------------------------
# the lane ``proc``
# ---------------------------------------------------------------------------

MS = 1_000_000


@pytest.fixture()
def proc_watch():
    """No watch running before the test (an engine that an earlier test
    started has turned one on, and its lines may have used up the
    process's eight of a kind) and none after it."""
    tracing._unwatch_process()
    tracing._warned.clear()
    yield
    tracing._unwatch_process()


def _proc_lane(since=0):
    return [e for e in tracing.events(trace="proc") if e["ts_ns"] >= since]


class _Script:
    """A clock and a kernel that a test moves by hand."""

    def __init__(self):
        self.now, self.reads = 0, 0
        self.cpu_ms, self.majflt = 10.0, 3

    def clock(self):
        return self.now

    def reading(self):
        self.reads += 1
        return int(self.cpu_ms * MS), self.majflt


class _GuardedLock:
    """Stands in for the ring's lock: a second ``with`` on it fails the
    test instead of hanging the suite."""

    def __init__(self):
        self._lock = threading.Lock()

    def __enter__(self):
        assert self._lock.acquire(timeout=2), "would wait for ever"

    def __exit__(self, *exc):
        self._lock.release()


class TestProcLane:
    def test_a_wake_on_time_records_nothing_and_a_late_one_is_a_pause(self):
        sc = _Script()
        sc.now = 5_000 * MS
        t0 = tracing._now()
        beat = tracing._Beat(sc.clock, sc.reading)
        assert beat.due == sc.now + tracing._BEAT_NS
        # on time, and late by less than the rule's 100 ms
        for late in (0, 1 * MS, 100 * MS):
            sc.now = beat.due + late
            sc.cpu_ms += 1.0
            beat.woke()
            assert beat.due == sc.now + tracing._BEAT_NS
        assert _proc_lane(t0) == []
        # 500 ms late: a span from the time it was due to the time it
        # came; the kernel's totals less what they read a beat before
        due = beat.due
        sc.now = due + 500 * MS
        sc.cpu_ms, sc.majflt = sc.cpu_ms + 2.5, sc.majflt + 4
        beat.woke()
        (e,) = [e for e in tracing.events(trace="proc")
                if e["ts_ns"] == due]
        assert (e["name"], e["ph"], e["cat"], e["dur_ns"]) \
            == ("proc.pause", "X", "proc", 500 * MS)
        assert e["args"] == {"cpu_ms": 2.5, "majflt": 4}
        assert e["tid"] == threading.get_ident()
        # one reading a beat, and one when the beat began
        assert sc.reads == 1 + 4
        cpu_ns, majflt = tracing._kernel_reading()
        assert cpu_ns > 0 and majflt >= 0

    def test_a_late_wake_that_the_collector_explains_is_no_pause(
            self, proc_watch, caplog):
        """The collector holds the interpreter, so the beat wakes late
        by every long pass: the pass is told once, as ``proc.gc``. A
        pause is what the passes leave of the lateness, where that is
        over the rule's 100 ms; it is recorded whole (the reader takes
        the overlap off). The long passes are logged here, by the beat's
        thread, not by the collector's callback."""
        sc = _Script()
        sc.now = 9_000 * MS
        beat = tracing._Beat(sc.clock, sc.reading)
        caplog.set_level("WARNING", logger="paddle_tpu.observability")

        def late(by_ms, *passes):
            due = beat.due
            for start_ms, dur_ms in passes:
                tracing._gc_passes.append(
                    (due + start_ms * MS, due + (start_ms + dur_ms) * MS,
                     2, 0))
            sc.now = due + by_ms * MS
            beat.woke()
            assert not tracing._gc_passes
            return [e for e in tracing.events(trace="proc")
                    if e["name"] == "proc.pause" and e["ts_ns"] == due]

        # 130 ms late behind a pass of 125 ms (what a quiet run of the
        # benchmark's served cells does two or three times): nothing
        assert late(130, (2, 125)) == []
        # the pass began before the wake was due: only its part inside
        # counts, and 150 - 40 is still a pause
        (e,) = late(150, (-300, 340))
        assert e["dur_ns"] == 150 * MS
        # two passes and a stretch nobody explains
        assert late(400, (0, 150), (160, 150)) == []
        (e,) = late(700, (0, 150), (160, 150))
        assert e["dur_ns"] == 700 * MS
        # a pass that is still open as the beat wakes (a callback of the
        # collector's that stands before ours let go of the interpreter)
        tracing._gc_open[0] = beat.due - 10 * MS
        assert late(200) == []
        tracing._gc_open[0] = 0
        (e,) = late(200)
        said = [r.getMessage() for r in caplog.records]
        # a line for the one pass over the stall threshold, and for the
        # one pause of which more than that was nobody's
        assert [m for m in said if m.startswith("collector ran")] \
            == ["collector ran 0.34 s (generation 2, 0 collected)"]
        assert [m for m in said if m.startswith("process paused")] \
            == ["process paused 0.70 s: cpu 0.00 s, 0 major faults"]

    def test_a_pass_that_fires_while_the_rings_lock_is_held_takes_no_lock(
            self, proc_watch, monkeypatch):
        """The collector runs on whichever thread trips it, at any
        bytecode, ``_flush_locked``'s first lines among them: with the
        caller's buffer one short of compaction and the ring's lock
        held, a full pass must leave its event without asking for the
        lock (it once recorded through ``_record``, which flushes at
        ``_COMPACT_AT`` and would have waited for its own thread for
        ever)."""
        monkeypatch.setattr(tracing, "_lock", _GuardedLock())
        tracing.watch_process()
        done = []

        def holder():
            t0 = tracing._now()
            for i in range(tracing._COMPACT_AT - 1):
                tracing.instant("t_lock.fill", "test", "t_lock")
            buf = tracing._buf()
            assert len(buf) == tracing._COMPACT_AT - 1
            with tracing._lock:
                tracing._on_gc("start", {"generation": 2})
                tracing._on_gc("stop", {"generation": 2, "collected": 5})
                gc.collect()         # and a real one, callbacks and all
                assert len(buf) == tracing._COMPACT_AT - 1
            mine = [e for e in _proc_lane(t0) if e["name"] == "proc.gc"
                    and e["tid"] == threading.get_ident()]
            assert len(mine) == 2 and mine[0]["args"] \
                == {"gen": 2, "collected": 5}
            done.append(True)

        t = threading.Thread(target=holder, daemon=True)
        t.start()
        t.join(30)
        assert done == [True]

    def test_a_process_stopped_and_continued_comes_back_with_one_pause(
            self, tmp_path):
        """A real ``SIGSTOP`` / ``SIGCONT`` of a child that watches
        itself: one ``proc.pause`` of the time it stood still, and the
        kernel's counters say nobody computed and nobody waited for a
        CPU meanwhile."""
        code = (
            "import json, sys\n"
            "from paddle_tpu.observability import tracing\n"
            "tracing.watch_process()\n"
            "print('ready', flush=True)\n"
            "sys.stdin.readline()\n"
            "print(json.dumps(tracing.events(trace='proc')), flush=True)\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PADDLE_TPU_TRACING="1")
        child = subprocess.Popen(
            [sys.executable, "-c", code], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        try:
            assert child.stdout.readline().strip() == "ready"
            time.sleep(0.3)          # some beats on time first
            os.kill(child.pid, signal.SIGSTOP)
            time.sleep(0.5)
            os.kill(child.pid, signal.SIGCONT)
            time.sleep(0.2)
            out, err = child.communicate("go\n", timeout=60)
        finally:
            child.kill()
        events = json.loads(out.strip().splitlines()[-1])
        assert events[0]["name"] == "proc.watch"
        pauses = [e for e in events if e["name"] == "proc.pause"
                  and e["dur_ns"] > 300 * MS]
        assert len(pauses) == 1, events
        assert 400 * MS <= pauses[0]["dur_ns"] <= 700 * MS
        # the operator's line, on standard error, with when it began
        # (some 0.3 s into the watch)
        (line,) = [ln for ln in err.splitlines()
                   if ln.startswith("process paused ")
                   and float(ln.split()[2]) >= 0.4]
        # nobody computed meanwhile: the process was stopped whole
        assert ": cpu 0.0" in line and line.endswith(" s)")
        assert pauses[0]["args"]["cpu_ms"] < 100
        assert 0.2 <= float(line.rsplit("(at +", 1)[1][:-3]) <= 1.5

    def test_a_full_collection_is_recorded_and_a_short_young_one_is_not(
            self, proc_watch):
        tracing.watch_process()
        t0 = tracing._now()
        gc.collect(0)
        gc.collect(1)
        young = [e for e in _proc_lane(t0) if e["name"] == "proc.gc"]
        assert all(e["dur_ns"] > tracing._GC_NS for e in young)
        t1 = tracing._now()
        gc.collect()
        full = [e for e in _proc_lane(t1) if e["name"] == "proc.gc"
                and e["tid"] == threading.get_ident()]
        assert full and full[-1]["args"]["gen"] == 2
        assert set(full[-1]["args"]) == {"gen", "collected"}
        assert (full[-1]["cat"], full[-1]["trace"]) == ("proc", "proc")

    def test_the_watch_is_one_thread_one_entry_and_one_mark(self, proc_watch):
        before = list(gc.callbacks)
        t0 = tracing._now()
        tracing.watch_process()
        tracing.watch_process()
        names = [t.name for t in threading.enumerate()]
        assert names.count(tracing._WATCH_THREAD) == 1
        assert gc.callbacks == before + [tracing._on_gc]
        assert [e["name"] for e in _proc_lane(t0)].count("proc.watch") == 1
        tracing._unwatch_process()
        assert gc.callbacks == before
        assert tracing._WATCH_THREAD not in [
            t.name for t in threading.enumerate()]

    def test_with_tracing_off_nothing_is_started_or_installed(
            self, proc_watch, monkeypatch):
        before = list(gc.callbacks)
        reads = []
        tracing.disable_tracing()
        try:
            monkeypatch.setattr(tracing.time, "thread_time_ns",
                                lambda: reads.append(1) or 0)
            tracing.watch_process()
            assert gc.callbacks == before
            assert tracing._WATCH_THREAD not in [
                t.name for t in threading.enumerate()]
            # and the loop's phases read no thread clock, stall or not
            ph = tracing.Phases("t_off.iter", "test", "t_off")
            ph.open("t_off.a")
            ph.close(True)
            ph.follows = True
            ph.open("t_off.a")
            ph.close(True)
        finally:
            monkeypatch.undo()
            tracing.enable_tracing()
        assert reads == [] and ph.stalls == 0
        assert tracing.events(trace="t_off") == []


# ---------------------------------------------------------------------------
# generation hook points
# ---------------------------------------------------------------------------


class TestGenerationSpans:
    def test_generate_phases_traced(self, tiny_model):
        from paddle_tpu import generation

        model, cfg = tiny_model
        rng = np.random.RandomState(SEED + 7)
        prompt = _prompt(rng, cfg, 5)
        with tracing.trace_context("t_gen_scan"):
            generation.generate(model, prompt[None], max_new_tokens=4)
        assert _spans(tracing.events(trace="t_gen_scan"),
                      "generation.generate")
        with tracing.trace_context("t_gen_py"):
            generation.generate(model, prompt[None], max_new_tokens=4,
                                loop_mode="python", eos_token_id=None)
        evs = tracing.events(trace="t_gen_py")
        (pf,) = _spans(evs, "generation.prefill")
        (dc,) = _spans(evs, "generation.decode")
        assert pf["ts_ns"] + pf["dur_ns"] <= dc["ts_ns"] + dc["dur_ns"]
