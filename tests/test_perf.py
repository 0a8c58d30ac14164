"""Performance observability (paddle_tpu/observability/perf.py):
per-executable cost/roofline attribution captured at compile time, the
HBM ledger, OOM forensics dumps, and the perf-regression gate.

Oracles:
- CAPTURE: a jitted entry's ledger row carries the SAME flops/bytes XLA
  reports through the AOT ``lower().compile().cost_analysis()`` path —
  captured for free off the live dispatch, no second compile (the
  one-step-compile invariant is re-asserted with capture ON).
- HONESTY: CPU has no published peaks, so MFU is None and the roofline
  class is "unknown" unless the PADDLE_TPU_PEAK_* env overrides supply
  peaks; memory_stats-free transports read "unsupported", never 0.
- FORENSICS: an injected allocation failure produces a flight-recorder
  dump that NAMES the top temp-byte executable.
- GATE: a synthetic 20% tok/s regression against the committed
  ``benchmarks/perf_baseline.json`` fails loudly.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.core import memory as core_memory
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import perf, recompile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.join(os.path.dirname(HERE), "benchmarks")

LEDGER_FIELDS = ("flops", "bytes_accessed", "arithmetic_intensity",
                 "roofline")

# On the chip lane the peak table resolves from the real device_kind:
# rooflines classify instead of reading "unknown".
ON_TPU = os.environ.get("PADDLE_TPU_TEST_PLATFORM", "cpu") == "tpu"
EXPECTED_ROOFLINES = (("compute-bound", "bandwidth-bound", "unknown")
                      if ON_TPU else ("unknown",))


@pytest.fixture(autouse=True)
def _no_peak_env(monkeypatch):
    """Peaks come only from the table/explicit env set inside a test."""
    monkeypatch.delenv(perf.PEAK_FLOPS_ENV, raising=False)
    monkeypatch.delenv(perf.PEAK_HBM_ENV, raising=False)


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------


class TestCapture:
    def test_jit_entry_captured_matches_aot_analysis(self):
        """The wrapper-captured flops/bytes equal what the explicit AOT
        compile reports — one cost-extraction path, no drift."""
        def f(x):
            return x @ x + x.sum()

        jf = jax.jit(f)
        x = jnp.ones((48, 48), jnp.float32)
        with recompile.entrypoint("t_perf.capture"):
            jf(x).block_until_ready()
        row = perf.ledger()["t_perf.capture"]
        ref = perf.extract_cost_analysis(jf.lower(x).compile())
        assert row["flops"] == ref["flops"] > 0
        assert row["bytes_accessed"] == ref["bytes_accessed"] > 0
        assert row["arithmetic_intensity"] == pytest.approx(
            ref["flops"] / ref["bytes_accessed"])
        assert row["compiles_captured"] >= 1

    def test_dominant_executable_wins(self):
        """Two programs under one entry: the ledger keeps the big one's
        analysis (the tiny helper compile must not shadow the step)."""
        big = jax.jit(lambda x: x @ x @ x)
        small = jax.jit(lambda x: x + 1)
        x = jnp.ones((64, 64), jnp.float32)
        with recompile.entrypoint("t_perf.dominant"):
            small(x[0]).block_until_ready()
            big(x).block_until_ready()
        row = perf.ledger()["t_perf.dominant"]
        ref = perf.extract_cost_analysis(big.lower(x).compile())
        assert row["flops"] == ref["flops"]
        assert row["compiles_captured"] >= 2

    def test_warmup_call_excluded_from_timing_window(self):
        """The call that paid the compile is warmup: the time its owner
        hands over (compile included) must not enter the achieved-rate
        window. The owner times a synced interval; the entrypoint scope
        itself is not timed (it closes before the device has run)."""
        jf = jax.jit(lambda x: x * 2.0)
        x = jnp.ones((32,), jnp.float32)
        with recompile.entrypoint("t_perf.warmup"):
            jf(x).block_until_ready()  # compiles -> excluded
        perf.note_entry_time("t_perf.warmup", 1.0)
        assert perf.ledger()["t_perf.warmup"]["calls"] == 0
        for _ in range(3):
            with recompile.entrypoint("t_perf.warmup"):
                jf(x).block_until_ready()
            perf.note_entry_time("t_perf.warmup", 0.002)
        row = perf.ledger()["t_perf.warmup"]
        assert row["calls"] == 3
        assert row["mean_time_s"] == pytest.approx(0.002)
        assert row["achieved_flops_per_s"] is None or \
            row["achieved_flops_per_s"] > 0

    def test_an_entry_nobody_timed_has_null_rates(self):
        """An ``entrypoint`` scope alone (an asynchronous dispatch)
        gives the ledger static cost and no time: achieved rate, MFU
        and bandwidth are null instead of wrong."""
        jf = jax.jit(lambda x: x @ x)
        x = jnp.ones((64, 64), jnp.float32)
        for _ in range(3):
            with recompile.entrypoint("t_perf.untimed"):
                jf(x)
        row = perf.ledger()["t_perf.untimed"]
        # the HBM ledger's tests count on the few executables they add
        perf._entries.pop("t_perf.untimed")
        assert row["flops"] > 0 and row["calls"] == 0
        for k in ("mean_time_s", "achieved_flops_per_s", "achieved_gbps",
                  "mfu", "hbm_bw_util", "items_per_s", "bytes_per_item"):
            assert row[k] is None, k

    def test_disable_stops_capture_and_timing(self):
        jf = jax.jit(lambda x: x - 1)
        x = jnp.ones((16,), jnp.float32)
        perf.disable()
        try:
            with recompile.entrypoint("t_perf.disabled"):
                jf(x).block_until_ready()
        finally:
            perf.enable()
        assert "t_perf.disabled" not in perf.ledger()

    def test_items_accounting(self):
        perf.note_entry_items("t_perf.items", 128)
        perf.note_entry_time("t_perf.items", 0.5)  # one timed call
        row = perf.ledger()["t_perf.items"]
        assert row["items"] == 128
        assert row["items_per_s"] is not None


# ---------------------------------------------------------------------------
# peaks + roofline honesty
# ---------------------------------------------------------------------------


class TestPeaks:
    @pytest.mark.skipif(ON_TPU, reason="chip lane resolves real peaks")
    def test_cpu_is_honest_unknown(self):
        peaks = perf.peak_specs()
        assert peaks["peak_flops_per_s"] is None
        assert peaks["peak_hbm_gbps"] is None
        assert peaks["source"] == "unknown"
        assert perf.roofline_class(3.0, peaks) == "unknown"

    def test_table_lookup_by_device_kind(self):
        peaks = perf.peak_specs(device_kind="TPU v4")
        assert peaks["peak_flops_per_s"] == 275e12
        assert peaks["peak_hbm_gbps"] == 1228.0
        assert peaks["source"] == "table"
        balance = peaks["machine_balance_flops_per_byte"]
        assert perf.roofline_class(balance * 2, peaks) == "compute-bound"
        assert perf.roofline_class(balance / 2, peaks) == "bandwidth-bound"

    def test_env_override_enables_mfu(self, monkeypatch):
        monkeypatch.setenv(perf.PEAK_FLOPS_ENV, "1e12")
        monkeypatch.setenv(perf.PEAK_HBM_ENV, "100")
        jf = jax.jit(lambda x: x @ x)
        x = jnp.ones((64, 64), jnp.float32)
        for _ in range(2):
            t0 = time.perf_counter()
            with recompile.entrypoint("t_perf.env"):
                jf(x).block_until_ready()
            perf.note_entry_time("t_perf.env", time.perf_counter() - t0)
        peaks = perf.peak_specs()
        assert peaks["source"] == "env"
        assert peaks["machine_balance_flops_per_byte"] == pytest.approx(10.0)
        row = perf.ledger()["t_perf.env"]
        assert row["mfu"] is not None and 0 < row["mfu"] < 1
        assert row["hbm_bw_util"] is not None and row["hbm_bw_util"] > 0
        assert row["roofline"] in ("compute-bound", "bandwidth-bound")
        # the gauges publish on ledger reads
        fam = obs.get_registry().get("paddle_tpu_mfu")
        labels = [s["labels"]["entry"] for s in fam.collect()]
        assert "t_perf.env" in labels

    def test_bad_env_value_ignored(self, monkeypatch):
        monkeypatch.setenv(perf.PEAK_FLOPS_ENV, "fast")
        peaks = perf.peak_specs(device_kind="TPU v3")
        assert peaks["peak_flops_per_s"] == 123e12  # table survives


# ---------------------------------------------------------------------------
# extraction helpers (the deduped distributed-engine path)
# ---------------------------------------------------------------------------


class FakeMemStats:
    argument_size_in_bytes = 100
    output_size_in_bytes = 200
    temp_size_in_bytes = 4096
    generated_code_size_in_bytes = 8


class FakeCompiled:
    """Duck-types BOTH analysis surfaces the helpers accept."""

    def __init__(self, flops=1e6, nbytes=1e5, temp=4096):
        self._flops, self._nbytes = flops, nbytes
        self._stats = FakeMemStats()
        self._stats.temp_size_in_bytes = temp

    def cost_analysis(self):
        return {"flops": self._flops, "bytes accessed": self._nbytes}

    def get_compiled_memory_stats(self):
        return self._stats


class TestExtractionHelpers:
    def test_aot_compiled_roundtrip(self):
        jf = jax.jit(lambda x: jnp.tanh(x) @ x)
        x = jnp.ones((32, 32), jnp.float32)
        compiled = jf.lower(x).compile()
        cost = perf.extract_cost_analysis(compiled)
        mem = perf.extract_memory_analysis(compiled)
        assert cost["flops"] > 0 and cost["bytes_accessed"] > 0
        assert mem["argument_bytes"] == x.nbytes
        assert mem["output_bytes"] == x.nbytes

    def test_helpers_survive_garbage(self):
        class Broken:
            def cost_analysis(self):
                raise RuntimeError("no")

        assert perf.extract_cost_analysis(Broken()) is None
        assert perf.extract_cost_analysis(object()) is None
        assert perf.extract_memory_analysis(object()) is None

    def test_raw_executable_shapes(self):
        fake = FakeCompiled()
        assert perf.extract_cost_analysis(fake)["flops"] == 1e6
        assert perf.extract_memory_analysis(fake)["temp_bytes"] == 4096


# ---------------------------------------------------------------------------
# core/memory device-stat accessors (CPU contracts)
# ---------------------------------------------------------------------------


class _NoStatsDevice:
    def memory_stats(self):
        raise AttributeError("memory_stats is unsupported")


class _SparseStatsDevice:
    def memory_stats(self):
        return {"bytes_in_use": 1234}  # no peak, no limit


class TestCoreMemoryAccessors:
    def test_unsupported_device_empty_stats(self):
        assert core_memory.device_memory_stats(_NoStatsDevice()) == {}
        assert core_memory.memory_allocated(_NoStatsDevice()) == 0
        assert core_memory.max_memory_allocated(_NoStatsDevice()) == 0
        assert core_memory.memory_reserved(_NoStatsDevice()) == 0
        assert core_memory.memory_headroom(_NoStatsDevice()) is None

    def test_missing_keys_zero_or_none(self):
        dev = _SparseStatsDevice()
        assert core_memory.memory_allocated(dev) == 1234
        assert core_memory.max_memory_allocated(dev) == 0
        assert core_memory.memory_headroom(dev) is None  # limit absent

    def test_cpu_default_device_contract(self):
        # the build container's CPU PJRT reports nothing: every accessor
        # must hold its 0/None contract rather than raise
        stats = core_memory.device_memory_stats()
        assert isinstance(stats, dict)
        assert core_memory.memory_allocated() >= 0
        assert core_memory.memory_headroom() is None or \
            isinstance(core_memory.memory_headroom(), int)


# ---------------------------------------------------------------------------
# StepTelemetry memory-watermark handling (unsupported transports)
# ---------------------------------------------------------------------------


class TestStepTelemetryMemory:
    def test_unsupported_marks_instead_of_nulls(self, monkeypatch,
                                                tmp_path):
        from paddle_tpu.observability import telemetry as tmod

        monkeypatch.setattr(tmod, "memory_watermarks", lambda: (None, None))
        live_g = obs.get_registry().get("paddle_tpu_device_live_bytes")
        live_g.set(-1.0)  # sentinel: the step must NOT overwrite it
        path = tmp_path / "steps.jsonl"
        st = obs.StepTelemetry(entry="t_perf_mem", jsonl_path=str(path))
        rec = st.step(num_samples=4)
        st.close()
        assert rec["memory"] == obs.MEMORY_STATS_UNSUPPORTED
        assert "live_bytes" not in rec and "peak_bytes" not in rec
        assert live_g.value() == -1.0  # no 0-valued gauge write
        line = json.loads(path.read_text().splitlines()[0])
        assert line["memory"] == "unsupported"
        assert "live_bytes" not in line

    def test_supported_keeps_byte_fields(self, monkeypatch):
        from paddle_tpu.observability import telemetry as tmod

        monkeypatch.setattr(tmod, "memory_watermarks",
                            lambda: (1024, 2048))
        st = obs.StepTelemetry(entry="t_perf_mem2")
        rec = st.step(num_samples=4)
        st.close()
        assert rec["live_bytes"] == 1024 and rec["peak_bytes"] == 2048
        assert "memory" not in rec
        assert obs.get_registry().get(
            "paddle_tpu_device_live_bytes").value() == 1024


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------


class TestHbmLedger:
    def test_component_registration_and_errors(self):
        perf.register_memory_component("t_comp", lambda: {"bytes": 4096})
        perf.register_memory_component(
            "t_broken", lambda: (_ for _ in ()).throw(RuntimeError("x")))
        try:
            led = perf.hbm_ledger()
            assert led["components"]["t_comp"]["bytes"] == 4096
            assert "error" in led["components"]["t_broken"]
            assert led["component_bytes_total"] >= 4096
        finally:
            perf.unregister_memory_component("t_comp")
            perf.unregister_memory_component("t_broken")
        assert "t_comp" not in perf.hbm_ledger()["components"]

    def test_cpu_device_section_unsupported_not_zero(self):
        dev = perf.hbm_ledger()["device"]
        for k in ("live_bytes", "bytes_limit", "headroom_bytes"):
            assert dev[k] == "unsupported" or isinstance(dev[k], int)
        # the container's CPU PJRT reports nothing — the ledger must say
        # so, not claim an empty device
        if not core_memory.device_memory_stats():
            assert dev["live_bytes"] == "unsupported"

    def test_executable_rows_sorted_by_temp(self):
        perf.capture_compiled("t_hbm.small", FakeCompiled(temp=10))
        perf.capture_compiled("t_hbm.big", FakeCompiled(temp=1 << 20))
        # (every entry this process has captured: the ledger's default
        # top eight need not reach down to ten bytes of temporaries)
        rows = perf.hbm_ledger(top_k=1 << 20)["executables"]
        names = [r["entry"] for r in rows]
        assert names.index("t_hbm.big") < names.index("t_hbm.small")


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------


class TestOomForensics:
    def test_is_oom_error(self):
        assert perf.is_oom_error(RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 2147483648 "
            "bytes"))
        assert perf.is_oom_error(MemoryError("failed to allocate 1GB"))
        from paddle_tpu.serving.block_pool import PoolExhaustedError

        assert perf.is_oom_error(PoolExhaustedError("need 3 blocks"))
        assert not perf.is_oom_error(ValueError("shape mismatch"))

    def test_dump_names_top_temp_executable(self, monkeypatch, tmp_path):
        monkeypatch.setenv("PADDLE_TPU_SINK_DIR", str(tmp_path))
        perf.capture_compiled("t_oom.culprit", FakeCompiled(temp=1 << 30))
        path = perf.dump_oom(RuntimeError("RESOURCE_EXHAUSTED: boom"))
        assert path is not None and os.path.exists(path)
        with open(path) as fh:
            dump = json.load(fh)
        extra = dump["extra"]
        assert extra["suspect"] == "t_oom.culprit"
        assert extra["top_temp_executables"][0]["entry"] == "t_oom.culprit"
        assert "RESOURCE_EXHAUSTED" in extra["error"]
        # the perf state provider rides every dump too
        assert "perf" in dump["state"]
        assert "hbm" in dump["state"]["perf"]

    def test_engine_allocation_failure_forensics(self, monkeypatch,
                                                 tmp_path):
        """Injected allocation-failure acceptance: the engine loop dying
        with an OOM-shaped error writes the forensics dump naming the
        top temp-byte executable, and fails the in-flight requests."""
        monkeypatch.setenv("PADDLE_TPU_SINK_DIR", str(tmp_path))
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        eng = serving.ServingEngine(model, max_slots=2, max_len=32)
        perf.capture_compiled("t_oom.engine_culprit",
                              FakeCompiled(temp=1 << 31))

        def _boom():
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory while trying to "
                "allocate 8589934592 bytes")

        monkeypatch.setattr(eng, "_step_impl", _boom)
        from paddle_tpu.observability import tracing as tracing_mod

        before = tracing_mod.last_flight_dump()
        req = eng.submit(np.array([1, 2, 3], np.int32), max_new_tokens=4)
        eng.start()
        req.result(timeout=10.0)  # returns once the crash fails it
        eng.stop()
        assert req.status == "failed"
        assert "RESOURCE_EXHAUSTED" in req.error
        assert eng.crashed is not None
        path = tracing_mod.last_flight_dump()
        assert path is not None and path != before
        with open(path) as fh:
            dump = json.load(fh)
        assert dump["reason"] == "oom"
        tops = dump["extra"]["top_temp_executables"]
        assert tops[0]["entry"] == "t_oom.engine_culprit"
        assert dump["extra"]["suspect"] == "t_oom.engine_culprit"


# ---------------------------------------------------------------------------
# serving + hapi acceptance: populated ledger, zero-retrace with capture ON
# ---------------------------------------------------------------------------


class TestServingLedgerAcceptance:
    @pytest.fixture(scope="class")
    def engines(self):
        paddle.seed(0)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        from paddle_tpu.generation import truncated_draft

        plain = serving.ServingEngine(model, max_slots=3, max_len=64)
        spec = serving.ServingEngine(
            model, draft_model=truncated_draft(model, 1),
            max_slots=3, max_len=64, spec_k=2)
        return cfg, plain, spec

    def _waves(self, eng, cfg, waves=3, sampled=False):
        rng = np.random.RandomState(7)
        shared = rng.randint(1, cfg.vocab_size, 8).astype(np.int32)
        for w in range(waves):
            reqs = []
            for i in range(3):
                # shared prefix across requests/waves -> prefix-cache
                # hits -> the first divergent decode write COW-forks
                prompt = np.concatenate(
                    [shared, rng.randint(1, cfg.vocab_size, 2 + i)
                     .astype(np.int32)])
                kw = dict(max_new_tokens=4)
                if sampled:
                    kw.update(do_sample=True, temperature=0.9, top_k=8,
                              seed=w * 10 + i)
                reqs.append(eng.submit(prompt, **kw))
            eng.run_until_idle()
            assert all(r.status == "completed" for r in reqs)

    def test_every_serving_executable_has_ledger_entry(self, engines):
        """Acceptance: step, prefill_chunk, cow, spec_draft, spec_verify
        all show populated ledger rows (flops, bytes, intensity,
        roofline class) in snapshot() and engine /stats."""
        cfg, plain, spec = engines
        self._waves(plain, cfg)
        # a second engine in the process shares entry names with the
        # first: its first compiles are warm-up, not retraces, and must
        # not be left behind as such for whatever file this worker runs
        # next (test_paged_kv and test_spec_decode hold the process-wide
        # count of serving.prefill_chunk retraces at zero)
        with recompile.warmup_scope():
            self._waves(spec, cfg, sampled=True)
        led = obs.snapshot()["perf"]["ledger"]
        for entry in ("serving.step", "serving.prefill_chunk",
                      "serving.cow", "serving.spec_draft",
                      "serving.spec_verify"):
            assert entry in led, f"{entry} missing from ledger"
            row = led[entry]
            for f in LEDGER_FIELDS:
                assert row[f] is not None, f"{entry}.{f} not populated"
            assert row["flops"] > 0 and row["bytes_accessed"] > 0
            assert row["roofline"] in EXPECTED_ROOFLINES
        stats_led = plain.stats()["perf"]["ledger"]
        assert "serving.step" in stats_led
        assert stats_led["serving.step"]["flops"] > 0
        spec_led = spec.stats()["perf"]["ledger"]
        assert spec_led["serving.spec_verify"]["flops"] > 0

    def test_one_compile_zero_retrace_with_perf_on(self, engines):
        """Satellite: the one-step-compile/zero-retrace invariant holds
        with perf capture ON across 3 request waves (capture is
        compile-time + host-side only)."""
        cfg, plain, _ = engines
        assert perf.perf_enabled()
        self._waves(plain, cfg)  # engines fixture already warmed it
        before = recompile.entry_stats()["serving.step"]
        self._waves(plain, cfg, waves=3)
        after = recompile.entry_stats()["serving.step"]
        assert after["compiles"] - before["compiles"] == 0
        assert after["retraces"] - before["retraces"] == 0
        # and the ledger kept joining timings the whole way
        assert perf.ledger()["serving.step"]["calls"] > 0

    def test_http_stats_and_debug_memory(self, engines):
        import urllib.request

        cfg, plain, _ = engines
        from paddle_tpu.serving.http import (start_serving_http_server,
                                             stop_serving_http_server)

        port = start_serving_http_server(plain, port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/stats", timeout=10) as r:
                stats = json.loads(r.read())
            assert "serving.step" in stats["perf"]["ledger"]
            assert stats["perf"]["peaks"]["device_kind"] is not None
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/memory",
                    timeout=10) as r:
                mem = json.loads(r.read())
            assert "serving_kv_pool" in mem["hbm"]["components"]
            assert mem["hbm"]["components"]["serving_kv_pool"]["bytes"] > 0
            assert "serving_model_weights" in mem["hbm"]["components"]
            assert "device" in mem["hbm"] and "ledger" in mem
        finally:
            stop_serving_http_server()
            plain.stop()


class TestHapiTrainLedger:
    def test_train_batch_ledger_populated(self):
        """Acceptance: the hapi train step shows a populated ledger
        entry after a short fit."""
        import paddle_tpu.nn as nn

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        model = paddle.Model(net)
        model.prepare(
            optimizer=paddle.optimizer.SGD(learning_rate=0.01,
                                           parameters=net.parameters()),
            loss=nn.CrossEntropyLoss())
        rng = np.random.RandomState(0)
        X = rng.rand(8, 8).astype(np.float32)
        Y = rng.randint(0, 4, (8, 1)).astype(np.int64)
        model.fit([(X[i], Y[i]) for i in range(8)], batch_size=4,
                  epochs=1, verbose=0)
        row = obs.snapshot()["perf"]["ledger"].get("hapi.Model.train_batch")
        assert row is not None
        assert row["flops"] and row["flops"] > 0
        assert row["bytes_accessed"] and row["bytes_accessed"] > 0
        assert row["arithmetic_intensity"] > 0
        assert row["roofline"] in EXPECTED_ROOFLINES


# ---------------------------------------------------------------------------
# xprof_top roofline columns (pure summarize — no xprof install needed)
# ---------------------------------------------------------------------------


class TestXprofTopRoofline:
    def _load(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "xprof_top", os.path.join(BENCH_DIR, "xprof_top.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_summarize_carries_peaks_and_roofline(self, monkeypatch):
        monkeypatch.setenv(perf.PEAK_FLOPS_ENV, "1e12")
        monkeypatch.setenv(perf.PEAK_HBM_ENV, "100")
        mod = self._load()
        rows = [
            {"total_self_time": 900.0, "occurrences": 3, "category": "fusion",
             "hlo_op_expression": "fusion.1", "model_flops": 4e9,
             "bytes_accessed": 1e6},   # intensity 4000 >> balance 10
            {"total_self_time": 100.0, "occurrences": 1, "category": "copy",
             "hlo_op_expression": "copy.1"},  # no flop columns -> no roofline
        ]
        s = mod.summarize(rows, 5)
        assert s["peaks"]["source"] == "env"
        top = s["top_ops"]
        assert top[0]["roofline"] == "compute-bound"
        assert top[0]["arithmetic_intensity"] == 4000.0
        assert top[0]["mfu"] is not None
        assert "roofline" not in top[1]  # honest absence

    def test_summarize_without_peaks_omits_classes(self, monkeypatch):
        mod = self._load()
        rows = [{"total_self_time": 10.0, "occurrences": 1,
                 "category": "fusion", "hlo_op_expression": "f",
                 "model_flops": 1e6, "bytes_accessed": 1e6}]
        s = mod.summarize(rows, 1)
        op = s["top_ops"][0]
        assert op["arithmetic_intensity"] == 1.0
        if s["peaks"]["machine_balance_flops_per_byte"] is None:
            assert "roofline" not in op and "mfu" not in op


# ---------------------------------------------------------------------------
# the perf-regression gate
# ---------------------------------------------------------------------------


class TestRegressionGate:
    def test_collect_reads_committed_artifacts(self):
        fresh = perf.collect_bench_metrics(BENCH_DIR)
        assert fresh["serving.tok_s"] > 0
        assert fresh["paged.capacity_ratio"] > 1.0
        assert fresh["spec.best_speedup"] > 1.0

    def test_committed_artifacts_pass_committed_baseline(self):
        baseline = perf.load_baseline(
            os.path.join(BENCH_DIR, "perf_baseline.json"))
        assert baseline is not None
        verdict = perf.compare_to_baseline(
            perf.collect_bench_metrics(BENCH_DIR), baseline)
        assert verdict["ok"], verdict["failures"]
        assert verdict["checked"] >= 5

    def test_synthetic_20pct_regression_fails(self):
        """The headline acceptance: -20% tok/s against the committed
        baseline + its pinned tolerances MUST fail."""
        baseline = perf.load_baseline(
            os.path.join(BENCH_DIR, "perf_baseline.json"))
        fresh = perf.collect_bench_metrics(BENCH_DIR)
        fresh["serving.tok_s"] *= 0.8
        verdict = perf.compare_to_baseline(fresh, baseline)
        assert not verdict["ok"]
        failed = [f["metric"] for f in verdict["failures"]]
        assert failed == ["serving.tok_s"]
        f = verdict["failures"][0]
        assert f["fresh"] < f["bound"] <= f["baseline"]

    def test_missing_metrics_skip_never_fail(self):
        baseline = {"metrics": {"ghost.tok_s": {"value": 100.0,
                                                "rel_tol": 0.1}}}
        verdict = perf.compare_to_baseline({}, baseline)
        assert verdict["ok"] and verdict["skipped"] == ["ghost.tok_s"]

    def test_no_baseline_is_skip(self):
        verdict = perf.compare_to_baseline({"x": 1.0}, None)
        assert verdict["ok"] and "gate skipped" in verdict["note"]

    def test_lower_is_better_direction(self):
        baseline = {"metrics": {"lat.p99": {
            "value": 10.0, "rel_tol": 0.1, "direction": "lower"}}}
        assert perf.compare_to_baseline({"lat.p99": 10.5}, baseline)["ok"]
        assert not perf.compare_to_baseline({"lat.p99": 12.0},
                                            baseline)["ok"]

    def test_run_shards_perf_ledger_block(self, tmp_path):
        """run_shards' block builder: green on the committed artifacts,
        rc=1 on a synthetically regressed bench_serving.json."""
        import run_shards

        block, rc = run_shards.build_perf_ledger_block(BENCH_DIR, {})
        assert rc == 0
        assert block["baseline_gate"]["ok"]
        assert "serving.tok_s" in block["bench_metrics"]

        # synthetic regression lane: copy artifacts, cut serving tok/s
        import shutil

        for f in ("bench_serving.json", "bench_paged_kv.json",
                  "bench_spec_decode.json", "perf_baseline.json"):
            shutil.copy(os.path.join(BENCH_DIR, f), tmp_path / f)
        with open(tmp_path / "bench_serving.json") as fh:
            art = json.load(fh)
        art["serving"]["tok_s"] = round(art["serving"]["tok_s"] * 0.8, 1)
        with open(tmp_path / "bench_serving.json", "w") as fh:
            json.dump(art, fh)
        block, rc = run_shards.build_perf_ledger_block(str(tmp_path), {})
        assert rc == 1
        assert [f["metric"] for f in block["baseline_gate"]["failures"]] \
            == ["serving.tok_s"]
