"""The readers of the program's spans on synthetic event lists with
known answers, the stretch they read (the window outside the profiler's
session), the rules that make them give nothing (an evicted ring, too
few iterations, a program that records no phases), ``gap_phases`` on
synthetic traces, and the manifest with the four-chip cell."""

import json
import os

import pytest

import perfbench_tiny as tiny

from perfbench import gap_phases, manifest, run
from perfbench.programs import observe, spans

MS = 1_000_000
T0 = 100.0   # the measured window on the host clock, seconds
T1 = 151.0
S0 = 146.0   # the profiler's session: the window's last five seconds
READ_S = S0 - spans.SESSION_MARGIN_S - T0   # what a span reader reads


def ev(name, start_ms, dur_ms, trace="engine", cat="engine", ph="X", **args):
    """An event ``start_ms`` after T0, as the program's ring gives it."""
    out = {"ph": ph, "name": name, "cat": cat, "trace": trace, "tid": 1,
           "ts_ns": int(T0 * 1e9 + start_ms * MS), "dur_ns": int(dur_ms * MS)}
    if args:
        out["args"] = args
    return out


def iteration(i, start_ms, admit=1.0, prefill=0.0, reserve=0.5, dispatch=2.0,
              wait=6.0, emit=0.5, **iter_args):
    """One ``engine.iter`` and its six children, back to back; a
    ``prefill`` phase is one chunk of some request, enqueued as the
    phase ends."""
    out, t = [], start_ms
    for name, d in (("admit", admit), ("prefill", prefill),
                    ("reserve", reserve), ("dispatch", dispatch),
                    ("wait", wait), ("emit", emit)):
        out.append(ev("engine." + name, t, d, iter=i, **(
            dict(prompt_tokens=100, prefix_hit_tokens=10)
            if name == "admit" else {})))
        if name == "prefill" and d:
            out.append(ev("prefill_chunk", t, d, trace=1000 + i,
                          cat="request", iter=i))
        t += d
    out.append(ev("engine.iter", start_ms, t - start_ms, iter=i,
                  preempted=0, **iter_args))
    return out


def steady(n=40, period=10.0, **kw):
    """``n`` iterations of 10 ms, one after the other: 4 ms of host
    work and 6 ms of waiting each."""
    return [e for i in range(n) for e in iteration(i, i * period, **kw)]


def facts_for(events, evicted=False, window=(T0, T1), session=(S0, T1)):
    return {"trace": {"host_window": session} if session else None,
            "window": window,
            "_events": sorted(events, key=lambda e: e["ts_ns"]),
            "_evicted": evicted}


@pytest.fixture()
def ring(monkeypatch):
    """Put a synthetic ring under the readers."""
    def put(facts):
        monkeypatch.setattr(spans, "_ring", lambda: (facts["_events"],
                                                     not facts["_evicted"]))
        return facts
    return put


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(tiny.REPO)


def reading(man, name, facts):
    mf = man.metric_file(name)
    return man.reader(mf["reader"])(facts, **mf.get("args", {}))


SPAN_METRICS = ["engine_host_ms.chat", "engine_host_ms.doc",
                "engine_host_share.chat", "engine_host_share.doc",
                "engine_dispatch_ms.chat", "engine_dispatch_ms.doc",
                "engine_emit_p99_ms.chat", "engine_emit_p99_ms.doc",
                "decode_stall_p99_ms.chat"]
WINDOW_METRICS = ["admit_to_first_token_p50_ms.chat",
                  "prefix_hit_share.chat", "preemptions.chat",
                  "preemptions.doc"]
TRAIN_METRICS = ["train_dispatch_ms", "train_dispatch_ms.dp2mp2"]


def requests(n=30, prefill_ms=80.0):
    out = []
    for k in range(n):
        out.append(ev("admitted", k * 100.0, 0, trace=k, cat="request",
                      ph="i", slot=0))
        out.append(ev("first_token", k * 100.0 + prefill_ms + k, 0, trace=k,
                      cat="request", ph="i"))
    return out


def test_host_time_of_an_iteration_is_its_length_less_the_wait(man, ring):
    facts = ring(facts_for(steady()))
    assert reading(man, "engine_host_ms.chat", facts) == pytest.approx(4.0)
    assert reading(man, "engine_dispatch_ms.doc", facts) == pytest.approx(2.0)
    assert reading(man, "engine_emit_p99_ms.chat", facts) == pytest.approx(0.5)


def test_one_slow_emit_is_the_99th_percentile_not_the_median(man, ring):
    events = steady(n=200, period=10.0)
    events += iteration(200, 2000.0, emit=30.0)
    events += iteration(201, 2040.0, emit=30.0)
    events += iteration(202, 2080.0, emit=30.0)
    facts = ring(facts_for(events))
    assert reading(man, "engine_emit_p99_ms.doc", facts) > 25.0
    assert reading(man, "engine_dispatch_ms.doc", facts) \
        == pytest.approx(2.0)
    # the mean host time carries them by their weight
    assert reading(man, "engine_host_ms.doc", facts) \
        == pytest.approx(4.0 + 3 * 29.5 / 203)


def test_host_time_is_a_mean_over_both_kinds_of_iteration(man, ring):
    # one iteration in four runs a 12 ms chunk first: a median would
    # read 4 ms whatever the chunks cost
    events, t = [], 0.0
    for i in range(400):
        one = iteration(i, t, prefill=12.0 if i % 4 == 0 else 0.0)
        t += one[-1]["dur_ns"] / MS
        events += one
    facts = ring(facts_for(events))
    assert reading(man, "engine_host_ms.chat", facts) \
        == pytest.approx(4.0 + 12.0 / 4)


def test_span_times_are_read_outside_the_profilers_session(man, ring):
    # under the profiler every host phase is four times as long: the
    # readers do not see it, nor the second before the session
    quiet = steady(n=200, period=10.0)
    loud = [e for i in range(100) for e in iteration(
        1000 + i, (S0 - T0 - 1.0) * 1e3 + i * 70.0, admit=4.0, reserve=2.0,
        dispatch=8.0, emit=2.0)]
    facts = ring(facts_for(quiet + loud))
    assert reading(man, "engine_host_ms.doc", facts) == pytest.approx(4.0)
    assert reading(man, "engine_dispatch_ms.chat", facts) \
        == pytest.approx(2.0)
    assert reading(man, "engine_emit_p99_ms.chat", facts) \
        == pytest.approx(0.5)
    assert reading(man, "decode_stall_p99_ms.chat", facts) \
        == pytest.approx(2.0)
    # a run that took no trace reads its whole window
    whole = ring(facts_for(quiet + loud, session=None))
    assert reading(man, "engine_emit_p99_ms.chat", whole) \
        == pytest.approx(2.0)
    assert spans.stretches(whole) == [(T0, T1)]
    assert spans.stretches(facts) == [(T0, S0 - spans.SESSION_MARGIN_S)]


def test_a_training_cell_reads_both_sides_of_its_session(man, ring):
    events = [ev("train.dispatch", 160.0 * k, 9.0 if 6 <= k < 32 else 3.0,
                 trace="train", cat="train", step=k) for k in range(300)]
    facts = ring(facts_for(events, session=(T0 + 1.0, T0 + 4.0)))
    assert spans.stretches(facts) == [(T0 + 5.0, T1)]
    assert reading(man, "train_dispatch_ms", facts) == pytest.approx(3.0)


def test_host_share_is_the_time_the_device_has_nothing_queued(man, ring):
    # back to back: after a step's tokens are on the host the device
    # waits through emit, admit, reserve and dispatch (4 ms of 10)
    n = 400
    facts = ring(facts_for(steady(n=n)))
    assert reading(man, "engine_host_share.chat", facts) \
        == pytest.approx(100.0 * (n - 1) * 0.004 / READ_S)
    # with a chunk first the device is fed from the chunk's enqueue on:
    # emit, admit and the chunk's own 3 ms count, reserve and dispatch
    # run while the device works
    events, t = [], 0.0
    for i in range(n):
        one = iteration(i, t, prefill=3.0)
        t += one[-1]["dur_ns"] / MS
        events += one
    fed = ring(facts_for(events))
    assert reading(man, "engine_host_share.doc", fed) \
        == pytest.approx(100.0 * (n - 1) * (0.5 + 1.0 + 3.0) / 1e3 / READ_S)


def test_a_first_token_is_no_sync_and_an_idle_engine_starves_nobody(
        man, ring):
    events = steady(n=40)
    # iteration 40: a request's last chunk (enqueued at 402 ms), its
    # token read at 410 ms with work still queued behind it (since PR 30
    # the step is enqueued before the token is read), then another chunk
    events += [ev("engine.admit", 400.0, 0.0, iter=40),
               ev("prefill_chunk", 400.0, 2.0, trace=7, cat="request",
                  iter=40),
               ev("first_token", 410.0, 0.0, trace=7, cat="request", ph="i"),
               ev("prefill_chunk", 410.0, 1.0, trace=8, cat="request",
                  iter=40),
               ev("engine.prefill", 400.0, 11.0, iter=40),
               ev("engine.dispatch", 411.0, 2.0, iter=40),
               ev("engine.wait", 413.0, 6.0, iter=40),
               ev("engine.emit", 419.0, 0.5, iter=40),
               ev("engine.iter", 400.0, 19.5, iter=40, preempted=0)]
    # the engine then idles for want of requests: only the emit counts
    events += [ev("engine.idle", 419.5 + 50.0 * k, 50.0) for k in range(20)]
    facts = ring(facts_for(events))
    # 39 gaps of 4 ms; emit of 39 + admit and the first chunk (2.5 ms);
    # nothing from the first token on; the last emit (0.5 ms)
    want = 39 * 4.0 + 2.5 + 0.5
    assert reading(man, "engine_host_share.chat", facts) \
        == pytest.approx(100.0 * want / 1e3 / READ_S)


def test_a_known_stall_between_two_decode_steps(man, ring):
    # back to back, a decoding row waits emit + admit + reserve = 2 ms
    # between two steps; three of a hundred iterations run 40 ms of
    # prefill chunks first
    events, t = [], 0.0
    for i in range(100):
        one = iteration(i, t, prefill=40.0 if i in (50, 60, 70) else 0.0)
        t += one[-1]["dur_ns"] / MS
        events += one
    facts = ring(facts_for(events))
    assert reading(man, "decode_stall_p99_ms.chat", facts) \
        == pytest.approx(42.0)
    plain = ring(facts_for(steady(n=60)))
    assert reading(man, "decode_stall_p99_ms.chat", plain) \
        == pytest.approx(2.0)


def test_a_stall_is_only_between_consecutive_iterations(man, ring):
    events = steady(n=30) + [e for i in range(31, 61)
                             for e in iteration(i, 3000.0 + i * 10.0)]
    facts = ring(facts_for(events))   # iteration 30 never dispatched
    assert reading(man, "decode_stall_p99_ms.chat", facts) \
        == pytest.approx(2.0)


def test_nobody_waits_through_an_idle_engine(man, ring):
    # the last row finished in iteration 29; the engine idled 2.7 s
    # before iteration 30 admitted the next request
    events = steady(n=30) + [e for i in range(30, 60)
                             for e in iteration(i, 2700.0 + i * 10.0)]
    events += [ev("engine.idle", 300.0 + 50.0 * k, 50.0) for k in range(54)]
    facts = ring(facts_for(events))
    assert reading(man, "decode_stall_p99_ms.chat", facts) \
        == pytest.approx(2.0)


def test_a_known_prefix_share(man, ring):
    events = steady()
    for e in events:
        if e["name"] == "engine.admit":
            e["args"].update(prompt_tokens=200, prefix_hit_tokens=50)
    # an admission before the window's start does not count
    events.append(ev("engine.admit", -50_000.0, 1.0, iter=-1,
                     prompt_tokens=10_000, prefix_hit_tokens=0))
    facts = ring(facts_for(events))
    assert reading(man, "prefix_hit_share.chat", facts) == pytest.approx(25.0)


def test_preemptions_are_summed_over_the_window(man, ring):
    events = steady()
    for e in events:
        if e["name"] == "engine.iter" and e["args"]["iter"] in (3, 7, 9):
            e["args"]["preempted"] = 1
    facts = ring(facts_for(events))
    assert reading(man, "preemptions.chat", facts) == 3
    assert reading(man, "preemptions.doc", facts) == 3


def test_admission_to_first_token_by_request_lane(man, ring):
    facts = ring(facts_for(requests(n=31), window=(T0, T1)))
    assert reading(man, "admit_to_first_token_p50_ms.chat", facts) \
        == pytest.approx(80.0 + 15.0)
    # a preempted request admitted twice counts from its first admission
    events = requests(n=31) + [ev("admitted", 15 * 100.0 + 50.0, 0, trace=15,
                                  cat="request", ph="i")]
    again = ring(facts_for(events, window=(T0, T1)))
    assert reading(man, "admit_to_first_token_p50_ms.chat", again) \
        == pytest.approx(95.0)


def test_the_trainers_dispatch_span(man, ring):
    events = [ev("train.dispatch", 160.0 * k, 3.0 + (k % 2), trace="train",
                 cat="train", step=k) for k in range(19)]
    facts = ring(facts_for(events))
    assert reading(man, "train_dispatch_ms", facts) == pytest.approx(3.0)
    assert reading(man, "train_dispatch_ms.dp2mp2", facts) \
        == pytest.approx(3.0)


@pytest.mark.parametrize("name", SPAN_METRICS + WINDOW_METRICS)
def test_an_evicted_ring_gives_nothing(man, ring, name):
    # the oldest event the ring still has starts inside the interval
    events = [e for e in steady(n=200, period=20.0) + requests()
              if e["ts_ns"] > T0 * 1e9 + 30 * MS]
    facts = ring(facts_for(events, evicted=True, window=(T0 - 1.0, T1)))
    assert reading(man, name, facts) is None
    whole = ring(facts_for(events, evicted=False, window=(T0 - 1.0, T1)))
    assert reading(man, name, whole) is not None


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_fewer_than_twenty_iterations_give_nothing(man, ring, name):
    facts = ring(facts_for(steady(n=19)))
    assert reading(man, name, facts) is None
    assert reading(man, name, ring(facts_for(steady(n=21)))) is not None


@pytest.mark.parametrize("name", SPAN_METRICS + TRAIN_METRICS + [
    "prefix_hit_share.chat", "preemptions.chat", "preemptions.doc"])
def test_a_program_that_records_no_phases_gives_nothing(man, ring, name):
    """The parent of the PR that added the spans: request lanes and
    ``serving.step`` only."""
    events = requests() + [ev("serving.step", 10.0 * k, 8.0, active=2, step=k)
                           for k in range(400)]
    facts = ring(facts_for(events))
    assert reading(man, name, facts) is None


@pytest.mark.parametrize("name", SPAN_METRICS + TRAIN_METRICS)
def test_an_untraced_run_reads_its_whole_window(man, ring, name):
    events = steady() + [ev("train.dispatch", 160.0 * k, 3.0, trace="train",
                            cat="train", step=k) for k in range(19)]
    facts = ring(facts_for(events, session=None))
    assert reading(man, name, facts) is not None
    del facts["window"]    # a flow that measured no window
    assert reading(man, name, facts) is None


def test_the_ring_is_read_once_for_each_of_its_states(monkeypatch):
    from paddle_tpu.observability import tracing

    tracing.instant("t_spans.mark", cat="test", trace="t_spans")
    calls = []
    real = tracing.events
    monkeypatch.setattr(tracing, "events",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    a, _ = spans.lane(0.0, 1e12, trace="t_spans")
    b, complete = spans.lane(0.0, 1e12, trace="t_spans")
    assert a == b and len(a) == 1 and len(calls) == 1
    tracing.instant("t_spans.mark", cat="test", trace="t_spans")
    c, _ = spans.lane(0.0, 1e12, trace="t_spans")
    assert len(c) == 2 and len(calls) == 2


# -- gap_phases ------------------------------------------------------------


def _dev(busy):
    return {"XLA Ops": [(f"%fusion.{i} = f32[] fusion()", float(s), float(d))
                        for i, (s, d) in enumerate(busy)],
            "XLA Modules": []}


def test_gaps_are_put_down_to_the_phase_that_covers_them():
    # busy 0-1, 3-4, 9-10, 20-20.5 us: gaps 1-3, 4-9, 10-20
    planes = {
        "/device:TPU:0": _dev([(0, 1000), (3000, 1000), (9000, 1000),
                               (20000, 500)]),
        "/host:CPU": {"host": [
            ("engine.iter", 500.0, 9000.0), ("engine.dispatch", 500.0, 1500.0),
            ("engine.wait", 2000.0, 2500.0), ("engine.emit", 4500.0, 4000.0),
            ("engine.idle", 10000.0, 9000.0),
            ("$engine.py:2400 _iterate", 5000.0, 100.0)]}}
    out = gap_phases.attribute(planes)
    assert out["n_devices"] == 1
    assert out["idle_s"] == pytest.approx(17e-6)
    assert out["by_phase"] == pytest.approx({
        "engine.idle": 9e-6, "engine.emit": 4e-6, "engine.wait": 1.5e-6,
        "engine.dispatch": 1e-6, "engine.iter": 0.5e-6,
        "outside_any_phase": 1e-6})
    assert out["named_share"] == pytest.approx(16 / 17)
    assert out["longest"] == [["engine.idle", pytest.approx(10e-6)],
                              ["engine.emit", pytest.approx(5e-6)],
                              ["engine.dispatch", pytest.approx(2e-6)]]


def test_gaps_of_several_devices_are_averaged():
    host = {"host": [("train.dispatch", 0.0, 4000.0)]}
    planes = {"/device:TPU:0": _dev([(0, 1000), (3000, 1000)]),
              "/device:TPU:1": _dev([(0, 2000), (3000, 1000)]),
              "/host:CPU": host}
    out = gap_phases.attribute(planes)
    assert out["n_devices"] == 2
    assert out["idle_s"] == pytest.approx(1.5e-6)
    assert out["by_phase"]["train.dispatch"] == pytest.approx(1.5e-6)
    assert out["named_share"] == pytest.approx(1.0)


def test_a_trace_of_a_program_without_phases_names_nothing(capsys):
    trace = os.path.join(os.path.dirname(__file__), "data",
                         "small_v5e.xplane.pb")
    assert gap_phases.main([trace, "--top", "3"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["named_share"] == 0 and len(out["longest"]) == 3
    assert set(out["by_phase"]) == {"outside_any_phase"}
    assert all(name == "outside_any_phase" for name, _ in out["longest"])


def test_a_trace_with_no_device_has_nothing_idle():
    out = gap_phases.attribute({"/host:CPU": {"host": [("x", 0.0, 1.0)]}})
    assert out["idle_s"] == 0 and out["named_share"] is None


# -- the manifest and the four-chip cell -----------------------------------

NEW_CELL = "mistral-7b-cut.pretrain-2k-dp2mp2"


def test_the_manifest_with_the_new_entries_meets_the_static_rules():
    assert manifest.problems(tiny.REPO) == []


def test_the_four_chip_cell_is_the_traffic_file_that_was_there(man):
    cell = man.cell(NEW_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("mistral-7b-cut", "pretrain-2k-dp2mp2", 4)
    tr = man.traffic(cell["traffic"])
    assert (tr["batch"], tr["seq"], tr["mesh"], tr["flash_attention"]) \
        == (8, 2048, {"dp": 2, "mp": 2}, False)
    assert {m["name"] for m in man.metrics_of(NEW_CELL, "end_to_end")} \
        == {"train_tok_s", "setup_s"}
    per_layer = {m["name"] for m in man.metrics_of(NEW_CELL, "per_layer")}
    assert per_layer == {
        "collective_exposed_share", "train_step_ms.dp2mp2", "mfu.dp2mp2",
        "device_idle_share.dp2mp2", "peak_hbm_gb.dp2mp2",
        "compiles_in_window.dp2mp2", "train_dispatch_ms.dp2mp2",
        "backend_compiles_setup"}
    assert set(man.limits(NEW_CELL)) == set(
        man.limits("mistral-7b-cut.pretrain-4k"))


def test_each_twin_of_the_new_cell_names_its_one_chip_twins_reader(man):
    for twin, one_chip in (("train_step_ms.dp2mp2", "train_step_ms"),
                           ("mfu.dp2mp2", "mfu"),
                           ("device_idle_share.dp2mp2",
                            "device_idle_share.train"),
                           ("peak_hbm_gb.dp2mp2", "peak_hbm_gb.train"),
                           ("compiles_in_window.dp2mp2",
                            "compiles_in_window.train")):
        a, b = man.metric_file(twin), man.metric_file(one_chip)
        assert (a["reader"], a["args"], a["unit"], a["layer"], a["moves"]) \
            == (b["reader"], b["args"], b["unit"], b["layer"], b["moves"])


def test_only_the_training_metrics_list_the_four_chip_cell(man):
    """Only ``train_tok_s`` gained the new cell; of the cells the
    manifest holds a quarter, rounded down, may take four chips, and
    one always may."""
    listing = [m["name"] for m in man.data["end_to_end"] + man.data["per_layer"]
               if NEW_CELL in m.get("workloads", [])
               and not m["name"].endswith(".dp2mp2")]
    assert listing == ["train_tok_s", "collective_exposed_share"]
    four = sum(w["chips"] == 4 for w in man.data["workloads"])
    assert 1 <= four <= max(1, len(man.data["workloads"]) // 4)


# -- the cells at tiny sizes, spans read end to end --------------------------


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    mp = pytest.MonkeyPatch()
    mp.setattr(observe, "enable_compile_cache", lambda: "off (tests)")
    yield
    mp.undo()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("checkout")))


def test_the_tiny_checkout_holds_every_cell_once(root, man):
    assert manifest.problems(root) == []
    names = [w["name"] for w in manifest.Manifest(root).data["workloads"]]
    assert names.count(tiny.TRAIN4) == 1
    assert len(names) == len(set(names)) == len(man.data["workloads"])


def test_the_new_cell_on_four_virtual_devices_prints_what_it_reports(
        root, capsys):
    run.main(["--workload", tiny.TRAIN4, "--seed", "2147483777",
              "--seconds", "8", "--trace", "1"], root=root, on_chip=False)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True and res["device"]["count"] >= 4
    # everything but what needs a TPU trace, the peaks or PJRT's peak
    assert {"train_step_ms.dp2mp2", "compiles_in_window.dp2mp2",
            "train_dispatch_ms.dp2mp2", "backend_compiles_setup",
            "train_step_ms", "train_dispatch_ms"} <= set(res["metrics"])
    got = res["metrics"]["train_dispatch_ms.dp2mp2"]
    assert got["unit"] == "ms"
    assert 0 < got["value"] < res["metrics"]["train_step_ms.dp2mp2"]["value"]
    run.main(["--workload", tiny.TRAIN4, "--seed", "5", "--seconds", "2",
              "--trace", "0"], root=root, on_chip=False)
    res0 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(res0["metrics"]) == {"train_tok_s", "setup_s"}


def test_a_served_tiny_cell_reads_every_span_metric(root, capsys):
    run.main(["--workload", "tiny-gpt.tiny-chat", "--seed", "31",
              "--seconds", "7", "--trace", "1"], root=root, on_chip=False)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    got = res["metrics"]
    want = {n for n in SPAN_METRICS + WINDOW_METRICS if n.endswith(".chat")}
    assert want <= set(got), want - set(got)
    assert 0 < got["engine_host_share.chat"]["value"] <= 100
    assert got["engine_host_ms.chat"]["value"] > 0
    assert got["engine_dispatch_ms.chat"]["value"] \
        < got["engine_host_ms.chat"]["value"]
    assert 0 < got["prefix_hit_share.chat"]["value"] < 100
    assert got["preemptions.chat"]["value"] == 0
    assert got["admit_to_first_token_p50_ms.chat"]["value"] > 0
