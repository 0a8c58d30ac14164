"""``stall_s.*`` and ``stall_blocked_s.*`` (reader ``stall_seconds``)
and ``proc_pause_s.*`` and ``proc_gc_s.*`` (reader ``span_sum_s``): the
seconds a run lost, and to whom, from the program's ``engine.stall``
instants and its lane ``proc``, over the measured window outside the
profiler's session. On synthetic rings with known answers: a span
counts with its part inside the window, what overlaps a stall is
subtracted once, a program that watched itself and lost nothing reads
0.0, and one that did not (the parent of the PR that added the lane),
or whose ring no longer reaches back, gives nothing. Then the tiny
cells reading them end to end, and what two accepted tests that the
twenty entries push out of place (``tests/conftest.py`` marks them)
said of ``per_layer``, held by the entries' order."""

import json
import os

import pytest

import perfbench_tiny as tiny
import perfbench_tiny_ouro as tiny_ouro
import test_perfbench_spans as base
import test_perfbench_steps_fused as fused_base

from perfbench import manifest, run
from perfbench.programs import observe
from test_perfbench_spans import man, ring   # noqa: F401  (fixtures)

CELLS = {"chat": ("gpt3-1p3b.chat-open", "ttft_p75_ms"),
         "doc": ("gpt3-1p3b.doc-closed", "serve_tok_s"),
         "eva": ("evabyte-6p5b-cut.doc-bytes-closed", "serve_tok_s"),
         "ouro": ("ouro-2p6b.reason-closed", "serve_tok_s"),
         "train": ("mistral-7b-cut.pretrain-4k", "train_tok_s"),
         "dp2mp2": ("mistral-7b-cut.pretrain-2k-dp2mp2", "train_tok_s")}
SERVED = ["chat", "doc", "eva", "ouro"]
SERVING = "serving entry (serving/engine.py scheduler, block pool)"
PROCESS = "process (interpreter, host threads, machine)"
# name -> (its cells, layer, reader, the reader's arguments)
KINDS = {
    "stall_s": (SERVED, SERVING, "stall_seconds", {"part": "all"}),
    "stall_blocked_s": (SERVED, SERVING, "stall_seconds",
                        {"part": "blocked"}),
    "proc_pause_s": (list(CELLS), PROCESS, "span_sum_s",
                     {"trace": "proc", "span": "proc.pause",
                      "less": ["proc.gc", "xla_compile:"]}),
    "proc_gc_s": (list(CELLS), PROCESS, "span_sum_s",
                  {"trace": "proc", "span": "proc.gc"})}
NAMES = [f"{kind}.{suf}" for kind, (sufs, *_) in KINDS.items()
         for suf in sufs]
STALLS = [n for n in NAMES if n.startswith("stall_s.")]
BLOCKED = [n for n in NAMES if n.startswith("stall_blocked_s.")]
PAUSES = [n for n in NAMES if n.startswith("proc_pause_s.")]
GCS = [n for n in NAMES if n.startswith("proc_gc_s.")]


# -- the entries -------------------------------------------------------------


@pytest.mark.parametrize("name", NAMES)
def test_each_metric_is_data_beside_the_accepted_ones(name):
    """A file of arguments for one of the two new readers, and an entry
    of ``per_layer`` that lists its one cell, behind every entry that
    was there, in the order of ``NAMES``."""
    real = manifest.Manifest(tiny.REPO)
    kind, suf = name.rsplit(".", 1)
    _, layer, reader, args = KINDS[kind]
    cell, moves = CELLS[suf]
    mf = real.metric_file(name)
    assert (mf["name"], mf["reader"], mf["args"]) == (name, reader, args)
    assert os.path.isfile(os.path.join(
        tiny.REPO, "perfbench", "readers", reader + ".py"))
    names = [m["name"] for m in real.data["per_layer"]]
    entry = real.data["per_layer"][names.index(name)]
    assert entry["workloads"] == [cell] and entry["moves"] == moves
    assert entry["layer"] == mf["layer"] == layer
    assert (entry["unit"], entry["better"], entry["source"]) \
        == (mf["unit"], "lower", "program_span") and mf["unit"] == "s"
    assert len(NAMES) == 20 and names[-20:] == NAMES
    assert real.cell(cell)
    # the cell reports the end-to-end metric this one moves
    (e2e,) = [m for m in real.data["end_to_end"] if m["name"] == moves]
    assert cell in e2e["workloads"]


def test_the_manifest_with_the_twenty_entries_meets_the_static_rules():
    assert manifest.problems(tiny.REPO) == []
    real = manifest.Manifest(tiny.REPO)
    # the serving layer is one the benchmark had; the process is new,
    # and every metric of it names it letter for letter
    before = {m["layer"] for m in real.data["per_layer"]
              if m["name"] not in NAMES}
    assert SERVING in before and PROCESS not in before
    assert len({m["layer"] for m in real.data["per_layer"]}) \
        == len(before) + 1


def test_the_ouro_entries_stay_together_where_pr_37_put_them():
    """What ``test_perfbench_steps_fused.py`` says of the entries that
    list the Ouro cell alone: PR 37's sixteen are consecutive, PR 38's
    two stand right behind them, this PR's twenty behind those; each of
    the sixteen moves ``serve_tok_s`` and has a metric file whose
    reader exists."""
    real = manifest.Manifest(tiny.REPO)
    per_layer = real.data["per_layer"]
    names = [m["name"] for m in per_layer]
    mine = [i for i, m in enumerate(per_layer)
            if m.get("workloads") == [tiny_ouro.CELL]]
    at, new = mine[:16], mine[16:]
    assert at == list(range(at[0], at[0] + 16))
    assert names[at[-1] + 1:at[-1] + 3] == fused_base.NAMES
    assert names[at[-1] + 3:] == NAMES
    assert [names[i] for i in new] == [n for n in NAMES
                                       if n.endswith(".ouro")]
    assert {"loop_passes_per_step.ouro", "loop_attn_roofline.ouro",
            "loop_step_roofline.ouro"} <= {names[i] for i in at}
    for i in at + new:
        m = per_layer[i]
        assert m["moves"] == "serve_tok_s"
        mf = real.metric_file(m["name"])
        assert mf["name"] == m["name"] and mf["unit"] == m["unit"]
        assert os.path.isfile(os.path.join(
            tiny.REPO, "perfbench", "readers", mf["reader"] + ".py"))
    serve_cells = next(m["workloads"] for m in real.data["end_to_end"]
                       if m["name"] == "serve_tok_s")
    assert serve_cells[-1] == tiny_ouro.CELL and len(serve_cells) == 3


def test_the_four_chip_cell_is_the_traffic_file_that_was_there(man):
    """What ``test_perfbench_spans.py`` says of the four-chip cell, with
    the two metrics of the process that it reports now."""
    cell = man.cell(base.NEW_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("mistral-7b-cut", "pretrain-2k-dp2mp2", 4)
    tr = man.traffic(cell["traffic"])
    assert (tr["batch"], tr["seq"], tr["mesh"], tr["flash_attention"]) \
        == (8, 2048, {"dp": 2, "mp": 2}, False)
    assert {m["name"] for m in man.metrics_of(base.NEW_CELL, "end_to_end")} \
        == {"train_tok_s", "setup_s"}
    per_layer = {m["name"] for m in man.metrics_of(base.NEW_CELL,
                                                   "per_layer")}
    assert per_layer == {
        "collective_exposed_share", "train_step_ms.dp2mp2", "mfu.dp2mp2",
        "device_idle_share.dp2mp2", "peak_hbm_gb.dp2mp2",
        "compiles_in_window.dp2mp2", "train_dispatch_ms.dp2mp2",
        "backend_compiles_setup", "proc_pause_s.dp2mp2", "proc_gc_s.dp2mp2"}
    assert set(man.limits(base.NEW_CELL)) == set(
        man.limits("mistral-7b-cut.pretrain-4k"))
    # the twins read what their one-chip twins read
    for kind in ("proc_pause_s", "proc_gc_s"):
        a = man.metric_file(f"{kind}.dp2mp2")
        b = man.metric_file(f"{kind}.train")
        assert (a["reader"], a["args"], a["unit"], a["layer"], a["moves"]) \
            == (b["reader"], b["args"], b["unit"], b["layer"], b["moves"])


# -- the readers on synthetic rings ------------------------------------------

# the mark the program leaves when it starts to watch itself, before the
# window opens
WATCH = base.ev("proc.watch", -5_000.0, 0, trace="proc", cat="proc", ph="i")


def proc(name, start_ms, dur_ms, **args):
    return base.ev(name, start_ms, dur_ms, trace="proc", cat="proc", **args)


def stall(start_ms, ms, cpu_ms=0.0, phase="engine.wait", **args):
    if cpu_ms is not None:
        args["cpu_ms"] = cpu_ms
    return base.ev("engine.stall", start_ms, 0, ph="i", iter=7, ms=ms,
                   phase=phase, phase_ms=ms, **args)


def compiling(start_ms, dur_ms):
    return base.ev("xla_compile:serving.step", start_ms, dur_ms,
                   cat="compile", entry="serving.step")


@pytest.mark.parametrize("name", NAMES)
def test_a_quiet_program_that_watched_itself_reads_zero_not_nothing(
        man, ring, name):
    facts = ring(base.facts_for(base.steady() + [WATCH]))
    got = base.reading(man, name, facts)
    assert got == 0.0 and got is not None


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_ring_gives_nothing(man, ring, name):
    """The parent records its phases and never the mark: no lane, no
    number, no error, whatever else the ring holds."""
    events = base.steady() + base.requests()
    assert base.reading(man, name, ring(base.facts_for(events))) is None
    assert base.reading(man, name, ring(base.facts_for([]))) is None


@pytest.mark.parametrize("name", NAMES)
def test_an_evicted_ring_gives_nothing(man, ring, name):
    """A ring evicts its oldest events first: once it has lost any it
    has lost the mark, and it gives nothing, whether or not it still
    reaches back to the window's start (a sum over a lane that may have
    begun later would read low)."""
    lost = [stall(2_000.0, 500.0), proc("proc.pause", 3_000.0, 400.0),
            proc("proc.gc", 3_500.0, 50.0, gen=2, collected=0)]
    facts = ring(base.facts_for(base.steady() + lost, evicted=True))
    assert base.reading(man, name, facts) is None
    early = [e for i in range(-40, 40)
             for e in base.iteration(i, i * 10.0)]
    facts = ring(base.facts_for(early + lost, evicted=True))
    assert base.reading(man, "engine_host_ms.doc", facts) is not None
    assert base.reading(man, name, facts) is None
    # while the ring holds everything since the mark, it is read
    facts = ring(base.facts_for(early + lost + [WATCH], evicted=True))
    assert base.reading(man, name, facts) is not None


@pytest.mark.parametrize("name", NAMES)
def test_a_watch_that_began_inside_the_window_gives_nothing(man, ring, name):
    inside = base.ev("proc.watch", 4_000.0, 0, trace="proc", cat="proc",
                     ph="i")
    facts = ring(base.facts_for(base.steady() + [inside]))
    assert base.reading(man, name, facts) is None


@pytest.mark.parametrize("name", PAUSES + GCS)
def test_a_span_counts_with_its_part_inside_the_stretch(man, ring, name):
    span = "proc.pause" if name in PAUSES else "proc.gc"
    other = "proc.gc" if name in PAUSES else "proc.pause"
    events = [WATCH,
              proc(span, -300.0, 500.0),        # 200 ms inside the window
              proc(span, 10_000.0, 2_000.0),    # whole
              proc(other, 20_000.0, 700.0),     # the other span: not mine
              base.ev(span, 21_000.0, 900.0),   # another lane's: not mine
              # the session (and its margin) opens at 45 s: 400 ms before
              proc(span, 44_600.0, 1_000.0),
              proc(span, 47_000.0, 3_000.0)]    # inside the session
    facts = ring(base.facts_for(events))
    assert base.reading(man, name, facts) == pytest.approx(0.2 + 2.0 + 0.4)
    # an untraced run reads its whole window: up to 51 s
    facts = ring(base.facts_for(events, session=None))
    assert base.reading(man, name, facts) == pytest.approx(
        0.2 + 2.0 + 1.0 + 3.0)


@pytest.mark.parametrize("name", PAUSES)
def test_a_pause_that_a_pass_or_a_compile_explains_is_told_once(
        man, ring, name):
    """The collector holds the interpreter, so the beat wakes late by
    every long pass, and so does a compile's tracing: what of a pause a
    ``proc.gc`` or an ``xla_compile:*`` (on whatever lane) covers is
    theirs, taken off once where the two overlap. ``proc_gc_s`` is not
    touched by a pause."""
    events = [WATCH,
              proc("proc.pause", 10_000.0, 130.0),     # all of it a pass
              proc("proc.gc", 9_990.0, 150.0, gen=2, collected=0),
              proc("proc.pause", 20_000.0, 2_000.0),   # 1.3 s unexplained
              proc("proc.gc", 20_100.0, 400.0, gen=2, collected=0),
              compiling(20_300.0, 500.0),
              proc("proc.pause", 30_000.0, 700.0)]     # nobody's
    facts = ring(base.facts_for(events))
    assert base.reading(man, name, facts) == pytest.approx(0.0 + 1.3 + 0.7)
    gc_s = name.replace("proc_pause_s", "proc_gc_s")
    assert base.reading(man, gc_s, facts) == pytest.approx(0.15 + 0.4)


@pytest.mark.parametrize("name", ["proc_pause_s.train", "proc_pause_s.dp2mp2"])
def test_a_training_cell_reads_both_sides_of_its_session(man, ring, name):
    """A trainer's session lies inside its window (1 s to 6 s here, a
    margin of 1 s on either side): the lane is read before and behind
    it. The trainer has no ``engine`` lane at all."""
    events = [WATCH, proc("proc.pause", -500.0, 800.0),
              proc("proc.pause", 2_000.0, 1_000.0),    # in the session
              proc("proc.pause", 6_500.0, 1_000.0),    # 500 ms past its margin
              proc("proc.pause", 30_000.0, 250.0)] + [
        base.ev("train.dispatch", 100.0 * k, 3.0, trace="train",
                cat="train", step=k) for k in range(500)]
    facts = ring(base.facts_for(events, session=(base.T0 + 1.0,
                                                 base.T0 + 6.0)))
    # (the first stretch is the window's start up to the session's
    # margin, which is the window's start itself: empty)
    assert base.reading(man, name, facts) == pytest.approx(0.5 + 0.25)


@pytest.mark.parametrize("name", STALLS)
def test_stall_seconds_are_the_sum_of_the_stalls_lengths(man, ring, name):
    events = base.steady() + [
        WATCH, stall(-400.0, 1_000.0),         # began before the window
        stall(5_000.0, 3_410.0, phase="engine.wait"),
        stall(20_000.0, 300.0, cpu_ms=290.0, phase="engine.emit"),
        stall(30_000.0, 700.0, cpu_ms=None, phase="between"),
        stall(44_000.0, 2_000.0),              # 1 s of it before the session
        stall(48_000.0, 1_000.0)]              # inside the session
    facts = ring(base.facts_for(events))
    assert base.reading(man, name, facts) == pytest.approx(
        0.6 + 3.41 + 0.3 + 0.7 + 1.0)


@pytest.mark.parametrize("name", BLOCKED)
def test_what_overlaps_a_stall_is_subtracted_once(man, ring, name):
    """3 s in ``engine.wait``, of which the thread computed 0.2 s, the
    process stood still for 1 s, the collector ran 1 s (half of it
    inside the pause) and a program compiled for 0.2 s: 1.1 s are left
    in which the engine alone waited."""
    events = base.steady() + [
        WATCH, stall(10_000.0, 3_000.0, cpu_ms=200.0),
        proc("proc.pause", 10_500.0, 1_000.0, cpu_ms=0.0),
        proc("proc.gc", 11_000.0, 1_000.0, gen=2, collected=0),
        compiling(12_500.0, 200.0),
        # nothing of this is inside the stall
        proc("proc.pause", 14_000.0, 800.0), compiling(9_000.0, 900.0)]
    facts = ring(base.facts_for(events))
    assert base.reading(man, name, facts) == pytest.approx(1.1)
    all_s = name.replace("stall_blocked_s", "stall_s")
    assert base.reading(man, all_s, facts) == pytest.approx(3.0)


@pytest.mark.parametrize("name", BLOCKED)
def test_blocked_seconds_by_cause(man, ring, name):
    """The three causes one at a time: a device that gave no answer for
    2 s (all of it blocked), a process stopped for 2 s (none of it), a
    thread that computed for 2 s (none of it); then a stall recorded
    without the thread's clock (busy, for all anyone knows), a pause
    that covers more than the stall, and a stall cut by the session."""
    def one(*events):
        return base.reading(man, name, ring(base.facts_for(
            base.steady() + [WATCH, *events])))

    assert one(stall(10_000.0, 2_000.0, cpu_ms=1.0)) \
        == pytest.approx(1.999)
    assert one(stall(10_000.0, 2_010.0, cpu_ms=2.0),
               proc("proc.pause", 10_005.0, 2_000.0)) \
        == pytest.approx(0.008)
    assert one(stall(10_000.0, 2_000.0, cpu_ms=1_990.0)) \
        == pytest.approx(0.010)
    assert one(stall(10_000.0, 2_000.0, cpu_ms=None)) == 0.0
    assert one(stall(10_000.0, 2_000.0, cpu_ms=300.0),
               proc("proc.pause", 9_000.0, 5_000.0)) == 0.0
    # half of it lies before the session's margin: half its idle time,
    # less the part of the pause inside that half
    assert one(stall(44_000.0, 2_000.0, cpu_ms=200.0),
               proc("proc.pause", 44_800.0, 1_000.0)) \
        == pytest.approx(0.9 - 0.2)
    # and one that began 1.5 s before the window: a quarter of it, and
    # of its idle time, inside, less the pause's part inside
    assert one(stall(-1_500.0, 2_000.0, cpu_ms=400.0),
               proc("proc.pause", -200.0, 300.0)) \
        == pytest.approx(0.4 - 0.1)


# -- the cells at tiny sizes, read end to end --------------------------------


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    mp = pytest.MonkeyPatch()
    mp.setattr(observe, "enable_compile_cache", lambda: "off (tests)")
    yield
    mp.undo()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("checkout")))


def _run(root, capsys, *argv):
    run.main(list(argv), root=root, on_chip=False)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_served_tiny_cell_prints_the_four_it_lists(root, capsys):
    res = _run(root, capsys, "--workload", "tiny-gpt.tiny-doc", "--seed",
               "2147484101", "--seconds", "7", "--trace", "1")
    assert res["correct"] is True
    got = res["metrics"]
    mine = {n for n in NAMES if n.endswith(".doc")}
    assert mine <= set(got), mine - set(got)
    assert all(got[n]["unit"] == "s" and got[n]["value"] >= 0 for n in mine)
    # whatever this machine did to the run: the blocked part is a part
    assert got["stall_blocked_s.doc"]["value"] \
        <= got["stall_s.doc"]["value"] + 1e-9
    assert got["stall_s.doc"]["value"] < 7
    # and an untraced run prints none of them, as it prints no other
    # per-layer metric
    res0 = _run(root, capsys, "--workload", "tiny-gpt.tiny-doc", "--seed",
                "2147484102", "--seconds", "3", "--trace", "0")
    assert set(res0["metrics"]) == {"serve_tok_s", "setup_s"}


def test_a_training_tiny_cell_prints_its_two(root, capsys):
    res = _run(root, capsys, "--workload", tiny.TRAIN4, "--seed",
               "2147483801", "--seconds", "8", "--trace", "1")
    assert res["correct"] is True
    for n in ("proc_pause_s.dp2mp2", "proc_gc_s.dp2mp2",
              "proc_pause_s.train", "proc_gc_s.train"):
        assert res["metrics"][n]["unit"] == "s"
        assert 0 <= res["metrics"][n]["value"] < 8
    assert not [n for n in res["metrics"] if n.startswith("stall_")]


def test_a_program_that_does_not_watch_itself_prints_none_and_fails_nothing(
        root, capsys, monkeypatch):
    """The parent under this PR's benchmark files: its engine records
    its phases and starts no watch, so the lane has no mark. The run
    is correct, the accepted span metrics are read, and the line leaves
    the new ones out."""
    from paddle_tpu.observability import tracing

    tracing._unwatch_process()
    tracing.clear()     # an earlier test's mark is not this program's
    monkeypatch.setattr(tracing, "watch_process", lambda: None)
    res = _run(root, capsys, "--workload", "tiny-gpt.tiny-doc", "--seed",
               "2147484103", "--seconds", "7", "--trace", "1")
    assert res["correct"] is True and res["failed"] == 0
    assert "engine_host_ms.doc" in res["metrics"]
    assert not set(res["metrics"]) & set(NAMES)
