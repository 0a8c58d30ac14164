"""The engine iteration's phase spans, the counters bumped where the
work happens, and the instrumentation taken off the per-block path.

Oracles:
- PHASES: every iteration that worked has ONE ``engine.iter`` whose
  children (admit, prefill, reserve, dispatch, wait, emit) are disjoint,
  lie inside it and cover it; each carries ``iter``; a request's
  ``prefill_chunk`` carries the ``iter`` of the iteration that ran it.
  The host is one step ahead: ``engine.wait`` and ``engine.emit`` of an
  iteration belong to the step the iteration before dispatched, so the
  iteration that fills the pipeline has neither and the one that drains
  it has no ``engine.dispatch``.
- COUNTERS: ``counters()`` is lock-free and agrees with ``stats()`` and
  with the sums of the span args; a span carries no arg, and
  ``counters()`` no key, that nothing reads.
- GAUGES: the ``kv_blocks_*`` gauges are set once an iteration and equal
  ``BlockPool.stats()``; no pool operation reduces over the pool.
- FIRST TOKEN AFTER THE DISPATCH: a prompt's last chunk leaves its first
  token on the device; the slot joins the step at once, the token is
  read in the next iteration's ``engine.wait``, behind that iteration's
  enqueues and before the step's own tokens, and a failed iteration
  leaves none unread.
- HOST ARGUMENTS: a prefill program (one row or several) and a step hand
  their host arrays to the executable as they are; nothing is made with
  ``jnp.asarray`` a chunk or a step.
- ONE CLOCK: the phases also reach an active profiler session.
- A STALL NAMES ITS CAUSE: an iteration that worked and lasted longer
  than ``tracing.STALL_NS`` leaves ONE ``engine.stall`` with its longest
  phase and the thread's CPU time over it (near 0 where the thread was
  blocked, near the length where it was busy), a gap between two
  iterations that the loop did not idle in leaves one with ``phase``
  ``"between"``, both count in ``counters()``, and no ordinary iteration
  of a warm tiny engine is one.
- OFF MEANS OFF: with tracing disabled nothing of this is recorded, no
  annotation is made, no clock is read beyond the step's own, and the
  tokens are the same.
"""

import glob
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import tracing
from paddle_tpu.serving import metrics as sm
from paddle_tpu.serving.block_pool import BlockPool

CHILDREN = ["engine.admit", "engine.prefill", "engine.reserve",
            "engine.dispatch", "engine.wait", "engine.emit"]
BLOCK = 16


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(max_position_embeddings=256)
    return LlamaForCausalLM(cfg), cfg


def _prompt(rng, cfg, n):
    return rng.randint(1, cfg.vocab_size, n).astype("int32")


def _engine(model, **kw):
    kw = {"max_slots": 3, "max_len": 128, "prefill_chunk": 16,
          "block_size": BLOCK, **kw}
    return serving.ServingEngine(model, **kw)


def _engine_lane(since=0):
    """What this thread's (synchronously driven) engine recorded; an
    engine another test left serving idles on a thread of its own."""
    return [e for e in tracing.events(trace="engine")
            if e["ts_ns"] >= since and e["tid"] == threading.get_ident()]


def _by_iter(events):
    out = {}
    for e in events:
        if e["name"].startswith("engine.") and e["name"] != "engine.idle":
            out.setdefault(e["args"]["iter"], []).append(e)
    return out


@pytest.fixture(scope="module")
def served(tiny_model):
    """One engine driven through shared prefixes, preemption-free
    decode and completion; returns (engine, requests, its events)."""
    model, cfg = tiny_model
    tracing.clear()
    eng = _engine(model)
    rng = np.random.RandomState(11)
    base = _prompt(rng, cfg, 2 * BLOCK)
    first = eng.submit(base, max_new_tokens=6)
    eng.run_until_idle()
    # two follow-ups that extend the first prompt by less than a block:
    # each adopts its two whole blocks from the prefix cache
    more = [eng.submit(np.concatenate([base, _prompt(rng, cfg, 5 + i)]),
                       max_new_tokens=4 + i) for i in range(2)]
    lone = eng.submit(_prompt(rng, cfg, 21), max_new_tokens=3)
    eng.run_until_idle()
    reqs = [first, *more, lone]
    assert all(r.status == serving.RequestStatus.COMPLETED for r in reqs)
    return eng, reqs, tracing.events()


class TestPhases:
    def test_one_iter_per_worked_iteration_with_children_that_cover_it(
            self, served):
        eng, _, events = served
        groups = _by_iter([e for e in events if e["trace"] == "engine"])
        assert sorted(groups) == list(range(eng._phases.seq))
        for it, evs in groups.items():
            (parent,) = [e for e in evs if e["name"] == "engine.iter"]
            kids = sorted((e for e in evs if e["name"] in CHILDREN),
                          key=lambda e: e["ts_ns"])
            names = [k["name"] for k in kids]
            # nothing decoding; the pipeline fills; steady; it drains
            assert names in (CHILDREN[:3], CHILDREN[:4], CHILDREN,
                             CHILDREN[:3] + CHILDREN[4:]), it
            lo, hi = parent["ts_ns"], parent["ts_ns"] + parent["dur_ns"]
            for a, b in zip(kids, kids[1:]):
                assert a["ts_ns"] + a["dur_ns"] <= b["ts_ns"]   # disjoint
            assert all(lo <= k["ts_ns"] and k["ts_ns"] + k["dur_ns"] <= hi
                       for k in kids)
            assert sum(k["dur_ns"] for k in kids) >= 0.95 * parent["dur_ns"]

    def test_every_engine_span_is_on_the_engine_lane(self, served):
        _, _, events = served
        mine = [e for e in events if e["name"].startswith("engine.")]
        assert mine and all(
            e["cat"] == "engine" and e["trace"] == "engine"
            and e["ph"] == ("i" if e["name"] == "engine.stall" else "X")
            for e in mine)

    def test_a_span_carries_only_the_args_a_metric_reads(self, served):
        # iter ties the lanes together; preempted is preemptions.*;
        # the two token counts are prefix_hit_share.chat; kv_blocks is
        # decode_live_blocks_per_step.*; ahead is
        # steps_ahead_per_step.*; fused is steps_fused_per_step.* and
        # prefill_rows the rows such a step carried; rows, programs and
        # fill are
        # prefill_rows_per_iter.*, prefill_programs_per_iter.* and
        # prefill_fill_rows_per_iter.*, on the iterations that enqueued
        # a prefill program and no other
        # ms, phase, phase_ms and cpu_ms of the instant engine.stall
        # (the fixture's engine is not warmed up: its first iterations
        # compile, and stall) are stall_s.* and stall_blocked_s.* and
        # the line the engine logs
        _, _, events = served
        want = {"engine.iter": {"iter", "preempted"},
                "engine.admit": {"iter", "prefix_hit_tokens",
                                 "prompt_tokens"},
                "engine.dispatch": {"iter", "kv_blocks", "ahead", "fused",
                                    "prefill_rows"},
                "engine.stall": {"iter", "ms", "phase", "phase_ms",
                                 "cpu_ms"}}
        # (another test's engine may idle on a thread of its own meanwhile)
        mine = [e for e in events if e["name"].startswith("engine.")
                and e["name"] != "engine.idle"]
        assert {e["name"] for e in mine} - {"engine.stall"} \
            == {"engine.iter", *CHILDREN}
        assert all(e["ph"] == "i" for e in mine
                   if e["name"] == "engine.stall")
        chunk_iters = {e["args"]["iter"] for e in events
                       if e["name"] == "prefill_chunk"}
        for e in mine:
            if e["name"] == "engine.prefill":
                ran = e["args"]["iter"] in chunk_iters
                assert set(e["args"]) == (
                    {"iter", "rows", "programs", "fill"} if ran
                    else {"iter"}), e
                continue
            if e["name"] == "engine.stall":
                # (the engine's very first iteration has no close before
                # it to hold the thread's clock against)
                assert set(e["args"]) | {"cpu_ms"} == want[e["name"]], e
                continue
            assert set(e["args"]) == want.get(e["name"], {"iter"}), e
        assert chunk_iters

    def test_a_windowed_engine_adds_its_summary_blocks_and_no_other_arg(
            self):
        """An EVA model's slot reads the exact keys of its window and
        the summaries behind it: ``engine.dispatch`` splits the count
        (``decode_live_blocks_per_step.eva``,
        ``eva_summary_blocks_per_step.eva``), and no other span gains an
        arg."""
        from paddle_tpu.models import EvaByteConfig, EvaByteForCausalLM

        paddle.seed(0)
        cfg = EvaByteConfig.tiny()     # window 16 in chunks of 4
        eng = serving.ServingEngine(
            EvaByteForCausalLM(cfg), max_slots=2, max_len=128, block_size=4,
            prefill_chunk=8, prefix_caching=False)
        t0 = tracing.events()[-1]["ts_ns"] + 1 if tracing.events() else 0
        eng.submit(_prompt(np.random.RandomState(3), cfg, 37),
                   max_new_tokens=7)
        eng.run_until_idle()
        # (engine.stall: the iterations that compile, on this cold engine)
        lane = [e for e in _engine_lane(t0)
                if e["name"].startswith("engine.")
                and e["name"] not in ("engine.idle", "engine.stall")]
        want = {"engine.iter": {"iter", "preempted"},
                "engine.admit": {"iter", "prefix_hit_tokens",
                                 "prompt_tokens"},
                "engine.dispatch": {"iter", "kv_blocks", "summary_blocks",
                                    "ahead", "fused", "prefill_rows"}}
        for e in lane:
            # (engine.prefill: rows, programs and fill, as on any paged
            # engine; a windowed slot takes no spare row, so fill is 0)
            assert e["args"].get("fill", 0) == 0
            assert set(e["args"]) - {"rows", "programs", "fill"} \
                == want.get(e["name"], {"iter"}), e
        # six steps, the queries at positions 37..42: two windows behind
        # (a block of four summaries each), the window's 6..11 keys
        reads = [(e["args"]["kv_blocks"], e["args"]["summary_blocks"])
                 for e in lane if e["name"] == "engine.dispatch"]
        assert reads == [(-(-(p % 16 + 1) // 4), 2) for p in range(37, 43)]
        c = eng.counters()
        assert (c["window_rolls"], c["window_blocks_released"],
                c["summary_entries_written"]) == (2, 8, 43 // 4)

    def test_prefill_chunk_carries_the_iter_that_ran_it(self, served):
        _, reqs, events = served
        prefill = {e["args"]["iter"]: e for e in events
                   if e["name"] == "engine.prefill"}
        chunks = [e for e in events if e["name"] == "prefill_chunk"]
        assert len(chunks) >= len(reqs)
        dispatch = {e["args"]["iter"]: e for e in events
                    if e["name"] == "engine.dispatch"}
        rode = 0
        for ch in chunks:
            it = ch["args"]["iter"]
            # inside its iteration's engine.prefill, or, where its rows
            # rode the step's program, inside that engine.dispatch
            ph = prefill[it]                   # its iteration recorded one
            if ch["ts_ns"] >= ph["ts_ns"] + ph["dur_ns"]:
                ph = dispatch[it]
                assert ph["args"]["fused"] == 1
                rode += 1
            assert ph["ts_ns"] <= ch["ts_ns"]
            assert ch["ts_ns"] + ch["dur_ns"] <= ph["ts_ns"] + ph["dur_ns"]
            assert ch["trace"] != "engine"     # stays on the request's lane
        # the three requests admitted together: the second program's two
        # rows rode the step that the first program's two slots joined
        assert rode == 2 == sum(e["args"]["prefill_rows"]
                                for e in dispatch.values())

    def test_one_prefill_chunk_span_a_live_row_and_the_sums_are_the_counters(
            self, served):
        """``engine.prefill`` carries ``rows`` (live rows enqueued),
        ``programs`` and ``fill`` (the rows that were a slot's second
        or later of the iteration: the last program's spare rows) on
        the iterations that enqueued any; each request keeps one
        ``prefill_chunk`` span a chunk, with the args it always had,
        and the rows of one program share its clock reads."""
        eng, reqs, events = served
        prefill = {e["args"]["iter"]: e["args"] for e in events
                   if e["name"] == "engine.prefill" and "rows" in e["args"]}
        chunks = {}
        for e in events:
            if e["name"] == "prefill_chunk":
                assert set(e["args"]) == {"slot", "start", "end", "last",
                                          "iter"}
                chunks.setdefault(e["args"]["iter"], []).append(e)
        assert set(prefill) == set(chunks)
        P = eng._chunk_rows
        assert P == 2       # (16, float32, 3 slots): a power of two
        for it, args in prefill.items():
            assert args["rows"] == len(chunks[it])
            assert args["programs"] == -(-args["rows"] // P)
            # every prefilling slot has one row, and a spare row is a
            # further chunk of one of them, starting where its last ended
            assert len({e["args"]["slot"] for e in chunks[it]}) \
                == args["rows"] - args["fill"]
            for slot in {e["args"]["slot"] for e in chunks[it]}:
                mine = [e["args"] for e in chunks[it]
                        if e["args"]["slot"] == slot]
                assert all(a["end"] == b["start"]
                           for a, b in zip(mine, mine[1:]))
                assert [a["last"] for a in mine[:-1]] \
                    == [False] * (len(mine) - 1)
        # the first prompt, alone, rode both of its chunks in one program;
        # the three requests admitted together rode two programs, the
        # second one's spare row the third request's last chunk, and
        # the rows of one program share its two clock reads
        assert [prefill[i] for i in sorted(prefill)] == [
            {"iter": min(prefill), "rows": 2, "programs": 1, "fill": 1},
            {"iter": max(prefill), "rows": 4, "programs": 2, "fill": 1}]
        assert len({(e["ts_ns"], e["dur_ns"])
                    for e in chunks[max(prefill)]}) == 2
        c = eng.counters()
        assert c["prefill_rows"] == sum(a["rows"] for a in prefill.values())
        assert c["prefill_programs"] \
            == sum(a["programs"] for a in prefill.values())
        assert c["prefill_fill_rows"] \
            == sum(a["fill"] for a in prefill.values())
        # every chunk of every request is a row: 32 | 5+i, 21 -> 16s
        assert c["prefill_rows"] == 2 + 1 + 1 + 2

    def test_prefix_hit_tokens_sum_to_the_cache_hits_times_block_size(
            self, served):
        eng, _, events = served
        admits = [e for e in events if e["name"] == "engine.admit"]
        hit = sum(e["args"]["prefix_hit_tokens"] for e in admits)
        assert hit == eng.prefix_cache.stats()["hits"] * BLOCK == 4 * BLOCK
        c = eng.counters()
        assert hit == c["prefix_hit_tokens"]
        assert sum(e["args"]["prompt_tokens"] for e in admits) \
            == c["prompt_tokens"] == 32 + 37 + 38 + 21

    def test_every_decode_step_has_one_emit_and_one_wait(self, served):
        """... in the iteration after its dispatch; a wait with no step
        behind it read a parked first token alone."""
        eng, reqs, events = served
        emits = [e["args"]["iter"] for e in events
                 if e["name"] == "engine.emit"]
        waits = [e["args"]["iter"] for e in events
                 if e["name"] == "engine.wait"]
        disp = [e["args"]["iter"] for e in events
                if e["name"] == "engine.dispatch"]
        assert emits == waits and len(disp) == eng.counters()["steps"] > 0
        assert {it + 1 for it in disp} <= set(waits)
        assert eng.counters()["ahead_flushes"] == 0
        # every request's first token comes out of its last prefill
        # chunk; the rest one a row a step, which slot_steps integrates
        assert eng.counters()["slot_steps"] \
            == sum(len(r.output_tokens) - 1 for r in reqs)

    def test_serving_step_runs_from_sync_to_sync(self, served):
        """The one interval a pipelined step has: it ends where the
        next iteration's ``engine.wait`` does (its tokens on the host),
        and starts where the step before it ended, or at its own
        dispatch where the device had drained."""
        _, _, events = served
        steps = sorted((e for e in events if e["name"] == "serving.step"),
                       key=lambda e: e["args"]["step"])
        disp = {e["args"]["iter"]: e for e in events
                if e["name"] == "engine.dispatch"}
        wait = {e["args"]["iter"]: e for e in events
                if e["name"] == "engine.wait"}
        assert len(steps) == len(disp) > 0
        ahead, synced = 0, 0
        for st, it in zip(steps, sorted(disp)):
            d, w = disp[it], wait[it + 1]
            assert st["ts_ns"] + st["dur_ns"] == w["ts_ns"] + w["dur_ns"]
            assert st["ts_ns"] == max(d["ts_ns"], synced)
            assert d["args"]["ahead"] == (d["ts_ns"] < synced)
            ahead += d["args"]["ahead"]
            synced = st["ts_ns"] + st["dur_ns"]
        assert 0 < ahead < len(steps)

    def test_an_iteration_that_did_nothing_records_nothing(self, tiny_model):
        model, _ = tiny_model
        eng = _engine(model)
        t0 = tracing.events()[-1]["ts_ns"] + 1 if tracing.events() else 0
        assert eng.step() is False and eng.step() is False
        assert _engine_lane(t0) == [] and eng._phases.seq == 0

    def test_preemptions_in_the_spans_sum_to_the_counter(self, tiny_model):
        model, cfg = tiny_model
        eng = _engine(model, num_blocks=13)   # 12 usable blocks, 3 slots
        rng = np.random.RandomState(4242)
        t0 = tracing.events()[-1]["ts_ns"] + 1
        for n in (40, 55, 33):
            eng.submit(_prompt(rng, cfg, n), max_new_tokens=30)
        eng.run_until_idle(max_steps=5000)
        lane = _engine_lane(t0)
        c = eng.counters()
        assert c["preemptions"] >= 1
        assert sum(e["args"]["preempted"] for e in lane
                   if e["name"] == "engine.iter") == c["preemptions"]
        assert eng.stats()["preemptions"] == c["preemptions"]

    def test_the_serving_loop_records_its_idle_wait(self, tiny_model):
        model, cfg = tiny_model
        eng = _engine(model)
        eng.start()
        tid = eng._thread.ident   # other engines' loops may idle beside it

        def mine(name):
            return [e for e in tracing.events(trace="engine", name=name)
                    if e["tid"] == tid]

        try:
            req = eng.submit(_prompt(np.random.RandomState(5), cfg, 8),
                             max_new_tokens=3)
            req.result(timeout=120)
            for _ in range(200):
                if mine("engine.idle"):
                    break
                threading.Event().wait(0.02)
        finally:
            eng.stop()
        idle, iters = mine("engine.idle"), mine("engine.iter")
        assert idle and iters and all(e["dur_ns"] > 0 for e in idle)
        for e in idle:   # idle lies between iterations, never inside one
            assert not any(i["ts_ns"] < e["ts_ns"] < i["ts_ns"] + i["dur_ns"]
                           for i in iters)

    def test_disabled_tracing_records_nothing_and_serves_the_same_tokens(
            self, tiny_model):
        model, cfg = tiny_model
        prompts = [_prompt(np.random.RandomState(6), cfg, n)
                   for n in (9, 30)]

        def run():
            eng = _engine(model)
            reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
            eng.run_until_idle()
            return eng, [list(r.output_tokens) for r in reqs]

        _, want = run()
        before = tracing.span_counts()
        tracing.disable_tracing()
        try:
            eng, got = run()
        finally:
            tracing.enable_tracing()
        assert got == want
        after = tracing.span_counts()
        assert {k: v for k, v in after.items() if k.startswith("engine.")} \
            == {k: v for k, v in before.items() if k.startswith("engine.")}
        assert eng._phases.seq > 0 and eng.counters()["steps"] > 0
        # ... and the loop paid no annotation, no list append and no
        # clock read beyond open's for them
        assert not eng._phases.on and eng._phases._done == []


class TestStalls:
    def _warm(self, tiny_model, n_new=24):
        model, cfg = tiny_model
        eng = _engine(model)
        eng.warmup()
        req = eng.submit(_prompt(np.random.RandomState(71), cfg, 20),
                         max_new_tokens=n_new)
        for _ in range(4):
            assert eng.step()
        return eng, req

    def _slowed(self, eng, how):
        """One dispatch that first spends 0.3 s in ``how``; returns the
        engine lane's stalls since."""
        real, t0 = eng._enqueue_step, tracing._now()

        def slow(*a, **k):
            how()
            return real(*a, **k)

        eng._enqueue_step = slow
        assert eng.step()
        eng._enqueue_step = real
        eng.run_until_idle()
        return [e for e in _engine_lane(t0) if e["name"] == "engine.stall"]

    def test_a_sleeping_dispatch_is_one_stall_that_used_no_cpu(
            self, tiny_model):
        eng, req = self._warm(tiny_model)
        assert eng.counters()["stalls"] == 0
        it, since = eng._phases.seq, tracing._now()
        (e,) = self._slowed(eng, lambda: threading.Event().wait(0.3))
        a = e["args"]
        assert (e["ph"], e["cat"], e["trace"]) == ("i", "engine", "engine")
        assert a["phase"] == "engine.dispatch" and a["iter"] == it
        assert 300 <= a["phase_ms"] <= a["ms"] < 600
        assert 0 <= a["cpu_ms"] < 50
        # the instant stands at the start of the iteration it names
        (parent,) = [p for p in _engine_lane(since)
                     if p["name"] == "engine.iter" and p["args"]["iter"] == it]
        assert e["ts_ns"] == parent["ts_ns"]
        assert a["ms"] == pytest.approx(parent["dur_ns"] / 1e6)
        c = eng.counters()
        assert c["stalls"] == 1
        assert c["stall_ns"] == pytest.approx(a["ms"] * 1e6)
        assert eng.stats()["counters"]["stalls"] == 1
        assert len(req.output_tokens) == 24

    def test_a_busy_dispatch_is_a_stall_that_used_its_time(self, tiny_model):
        eng, _ = self._warm(tiny_model)

        def spin():
            # 0.3 s of the thread's OWN clock: on a machine that shares
            # its cores the wall clock runs ahead of it
            end = time.thread_time_ns() + 300_000_000
            while time.thread_time_ns() < end:
                pass

        (e,) = self._slowed(eng, spin)
        a = e["args"]
        assert a["phase"] == "engine.dispatch"
        assert 300 <= a["cpu_ms"] <= a["ms"] + 1

    def test_no_ordinary_iteration_of_a_warm_tiny_engine_stalls(
            self, tiny_model, models):
        model, cfg = tiny_model
        engines = [_engine(model)] + [
            serving.ServingEngine(m, **kw) for m, kw in models.values()]
        for eng in engines:
            eng.warmup()
            vocab = eng.model.config.vocab_size
            # (a loaded machine may hold any one wave up for a quarter
            # of a second: what the lane is for. Not three in a row.)
            for _ in range(3):
                t0, before = tracing._now(), eng.counters()
                for n in (9, 40):
                    eng.submit(np.random.RandomState(n).randint(
                        1, vocab, n).astype("int32"), max_new_tokens=6)
                eng.run_until_idle()
                c = eng.counters()
                new = [e for e in _engine_lane(t0)
                       if e["name"] == "engine.stall"]
                assert c["steps"] > before["steps"]
                assert c["stalls"] - before["stalls"] == len(new)
                if not new:
                    break
            assert not new, new

    def test_a_gap_the_loop_did_not_idle_in_is_a_stall_between(self):
        ph = tracing.Phases("t_st.iter", "test", "t_st")
        ph.open("t_st.a")
        t_close = ph.close(True)
        # the caller idled, or drives by hand: a late open is no stall
        threading.Event().wait(0.3)
        ph.open("t_st.a")
        t_close = ph.close(True)
        assert ph.stalls == 0
        # the loop says it comes straight from close(): the gap is one
        ph.follows = True
        threading.Event().wait(0.3)
        t_open = ph.open("t_st.a")
        assert not ph.follows
        ph.close(True)
        (e,) = tracing.events(trace="t_st", name="t_st.stall")
        assert e["ts_ns"] == t_close and e["ph"] == "i"
        a = e["args"]
        assert (a["phase"], a["iter"]) == ("between", 2)
        assert a["ms"] == a["phase_ms"] == pytest.approx(
            (t_open - t_close) / 1e6)
        assert 0 <= a["cpu_ms"] < 50
        assert (ph.stalls, ph.stall_ns) == (1, t_open - t_close)
        # and the iteration behind the gap is not charged the gap's CPU
        ph.follows = True
        ph.open("t_st.a")
        ph.close(True)
        assert ph.stalls == 1

    def test_an_iteration_another_thread_closed_before_has_no_cpu_reading(
            self):
        """The thread's CPU clock is held against the reading of the
        ``close`` before: where another thread took that, the two are
        different clocks and the arg is left out."""
        ph = tracing.Phases("t_st2.iter", "test", "t_st2")
        t = threading.Thread(target=lambda: (ph.open("t_st2.a"),
                                             ph.close(True)))
        t.start()
        t.join()
        ph.open("t_st2.a")
        threading.Event().wait(0.3)
        ph.close(True)
        (e,) = tracing.events(trace="t_st2", name="t_st2.stall")
        assert set(e["args"]) == {"iter", "ms", "phase", "phase_ms"}
        assert e["args"]["phase"] == "t_st2.a"

    def test_the_serving_loop_says_when_it_goes_straight_on(self, tiny_model):
        """``_serve_loop`` sets ``follows`` after an iteration that
        worked and not after its idle wait; a request served from start
        to end by a warm engine leaves no stall, and ``start()`` has
        turned the lane ``proc`` on."""
        eng, _ = self._warm(tiny_model, n_new=6)
        eng.run_until_idle()
        seen, real = [], eng.step

        def step():
            follows = eng._phases.follows
            seen.append((follows, real()))
            return seen[-1][1]

        eng.step = step
        eng.start()
        try:
            assert tracing._WATCH_THREAD in [
                t.name for t in threading.enumerate()]
            req = eng.submit(_prompt(np.random.RandomState(72),
                                     tiny_model[1], 12), max_new_tokens=8)
            req.result(timeout=120)
            threading.Event().wait(0.12)     # two idle waits
        finally:
            eng.stop()
        assert (True, True) in seen and (False, False) in seen
        for (_, worked), (follows, _) in zip(seen, seen[1:]):
            assert follows == worked
        assert eng.counters()["stalls"] == 0
        assert any(e["name"] == "proc.watch"
                   for e in tracing.events(trace="proc"))


class TestPhasesObject:
    def test_marks_are_shared_edges_and_close_records_in_order(self):
        ph = tracing.Phases("t_ph.iter", "test", "t_ph")
        t0 = ph.open("t_ph.a")
        t1 = ph.mark("t_ph.b", {"n": 1})
        t2 = ph.close(True, {"m": 2}, {"p": 3})
        evs = tracing.events(trace="t_ph")
        assert [(e["name"], e["ts_ns"], e["dur_ns"]) for e in evs] == [
            ("t_ph.a", t0, t1 - t0), ("t_ph.iter", t0, t2 - t0),
            ("t_ph.b", t1, t2 - t1)]
        args = {e["name"]: e["args"] for e in evs}
        assert args == {"t_ph.a": {"n": 1, "iter": 0},
                        "t_ph.b": {"m": 2, "iter": 0},
                        "t_ph.iter": {"p": 3, "iter": 0}}
        assert ph.seq == 1

    def test_an_iteration_that_did_not_work_leaves_no_event_and_no_number(
            self):
        ph = tracing.Phases("t_ph2.iter", "test", "t_ph2")
        ph.open("t_ph2.a")
        ph.mark("t_ph2.b")
        ph.close(False)
        assert tracing.events(trace="t_ph2") == [] and ph.seq == 0
        ph.open("t_ph2.a")      # an exception skipped close(): reopened
        ph.open("t_ph2.a")
        ph.close(True)
        assert [e["args"] for e in tracing.events(trace="t_ph2")] \
            == [{"iter": 0}, {"iter": 0}]

    def test_disabled_phases_read_one_clock_and_annotate_nothing(
            self, monkeypatch):
        made, reads = [], []
        real = tracing.time.perf_counter_ns
        monkeypatch.setattr(tracing, "_Annotation",
                            lambda name: made.append(name))
        ph = tracing.Phases("t_ph3.iter", "test", "t_ph3")
        tracing.disable_tracing()
        try:
            monkeypatch.setattr(tracing.time, "perf_counter_ns",
                                lambda: reads.append(1) or real())
            assert ph.open("t_ph3.a") > 0 and not ph.on
            assert ph.mark("t_ph3.b", {"n": 1}) == 0
            assert ph.close(True) == 0
            with tracing.profiled_span("t_ph3.x", "test", "t_ph3"):
                pass
        finally:
            monkeypatch.undo()
            tracing.enable_tracing()
        assert made == [] and len(reads) == 1 and ph.seq == 1
        assert tracing.events(trace="t_ph3") == []

    def test_profiled_span_records_like_a_span(self):
        with tracing.profiled_span("t_ps.x", "test", "t_ps", {"k": 1}):
            pass
        (e,) = tracing.events(trace="t_ps")
        assert (e["name"], e["ph"], e["args"]) == ("t_ps.x", "X", {"k": 1})


class TestCounters:
    def test_counters_never_takes_the_step_lock(self, served):
        eng, _, _ = served
        got = []
        with eng._step_lock:   # a step is held
            t = threading.Thread(target=lambda: got.append(eng.counters()))
            t.start()
            t.join(timeout=10)
            assert not t.is_alive() and got

    def test_counters_agree_with_stats(self, served):
        eng, reqs, _ = served
        c, st = eng.counters(), eng.stats()
        assert st["counters"] == c
        assert (st["steps"], st["slots"], st["queue_depth"],
                st["preemptions"]) == (c["steps"], c["slots"],
                                       c["queue_depth"], c["preemptions"])
        assert st["mean_occupancy"] == pytest.approx(
            c["slot_steps"] / (c["steps"] * c["slots"]))
        # nothing here that the pool or the registry counts already
        assert set(c) == {"steps", "slots", "slot_steps", "queue_depth",
                          "prompt_tokens", "prefix_hit_tokens",
                          "preemptions", "prefill_rows",
                          "prefill_programs", "prefill_fill_rows",
                          "steps_ahead", "ahead_flushes", "dead_rows",
                          "steps_fused", "stalls", "stall_ns"}


class TestFirstTokenAfterDispatch:
    def test_the_step_is_enqueued_before_the_first_token_is_read(
            self, served):
        """A prompt's last chunk parks its first token; the step of that
        iteration is enqueued behind it (or is the program the chunk
        rode), and the token is read in the NEXT iteration's
        ``engine.wait``: after that iteration's own enqueues, and
        before the tokens of that step."""
        _, reqs, events = served
        by_name = {n: {e["args"]["iter"]: e for e in events
                       if e["name"] == n}
                   for n in ("engine.dispatch", "engine.wait",
                             "engine.emit")}
        seen = 0
        for req in reqs:
            mine = [e for e in events if e["trace"] == req.trace]
            (first,) = [e for e in mine if e["name"] == "first_token"]
            (last,) = [e for e in mine if e["name"] == "prefill_chunk"
                       and e["args"]["last"]]
            it = last["args"]["iter"]
            disp, wait = by_name["engine.dispatch"][it], \
                by_name["engine.wait"][it + 1]
            # (the lone request's rows rode the step's own program)
            rode = last["ts_ns"] >= disp["ts_ns"]
            assert rode == (req is reqs[3]) <= disp["args"]["fused"]
            assert last["ts_ns"] + last["dur_ns"] \
                <= disp["ts_ns"] + (disp["dur_ns"] if rode else 0)
            assert disp["ts_ns"] + disp["dur_ns"] <= wait["ts_ns"] \
                <= first["ts_ns"] <= wait["ts_ns"] + wait["dur_ns"]
            ahead = by_name["engine.dispatch"].get(it + 1)
            assert ahead is None or ahead["args"]["ahead"] == 1
            seen += 1
        assert seen == 4

    def test_the_cache_holds_the_prompt_before_the_step_reserves(
            self, tiny_model):
        """insert, then the decode write's reservation (a COW fork of
        the half block the cache now shares), then the step; the next
        iteration reserves and enqueues its own step, and only then is
        the token read: the host's side of a finished prefill is booked
        at once, only the read waits."""
        model, cfg = tiny_model
        eng = _engine(model)
        order = []

        def spy(obj, name, tag, when=lambda *a, **k: True):
            real = getattr(obj, name)

            def wrapped(*a, **k):
                if when(*a, **k):
                    order.append(tag)
                return real(*a, **k)
            setattr(obj, name, wrapped)

        spy(eng.prefix_cache, "insert", "insert")
        spy(eng, "_reserve_write", "reserve",
            lambda slot, start, end, **k: end - start == 1)
        spy(eng, "_step_fn", "step")
        spy(eng, "_first_token", "token")
        forks = eng.pool.stats()["cow_forks"]
        req = eng.submit(_prompt(np.random.RandomState(21), cfg, BLOCK + 5),
                         max_new_tokens=3)
        assert eng.step()
        assert order == ["insert", "reserve", "step"]
        assert eng.pool.stats()["cow_forks"] == forks + 1
        assert list(req.output_tokens) == [] and eng.in_flight
        assert eng.step()
        assert order[3:] == ["reserve", "step", "token"]
        assert len(req.output_tokens) == 2 and eng._parked_tokens == []
        assert eng.step() and not eng.in_flight
        assert len(req.output_tokens) == 3 and eng.step() is False

    @pytest.mark.parametrize("how", ["max_new_tokens", "eos"])
    def test_a_request_that_ends_on_its_first_token(self, tiny_model, how):
        """By count it is given no row at all: the one token it may have
        is the one parked. By value (end of sequence) the host cannot
        know before it reads: the rows it was given meanwhile are dead,
        counted, and deliver nothing. Either way exactly one token goes
        out and the neighbour's tokens are undisturbed."""
        model, cfg = tiny_model
        rng = np.random.RandomState(22)
        long_, short = _prompt(rng, cfg, 9), _prompt(rng, cfg, 7)
        eng = _engine(model)
        probe = eng.submit(short, max_new_tokens=1)
        alone = eng.submit(long_, max_new_tokens=6)
        eng.run_until_idle()
        (tok0,) = probe.output_tokens
        params = {"max_new_tokens": 1} if how == "max_new_tokens" \
            else {"max_new_tokens": 8, "eos_token_id": tok0}
        eng = _engine(model, prefix_caching=False)
        other = eng.submit(long_, max_new_tokens=6)
        assert eng.step()       # other: first token parked, a step ahead
        req = eng.submit(short, **params)
        eng.run_until_idle()
        c = eng.counters()
        assert req.status == serving.RequestStatus.COMPLETED
        assert list(req.output_tokens) == [tok0]
        assert req.slot is not None and eng._slot_req[req.slot] is None
        assert list(other.output_tokens) == list(alone.output_tokens)
        # the steps carried other's five rows; req's are not counted.
        # Its chunk rode a step, so it joined the one after, and had one
        # row out when its first token was read
        assert c["slot_steps"] == 5 and c["steps_fused"] == 1
        assert c["dead_rows"] == (0 if how == "max_new_tokens" else 1)
        assert eng.pool.used_blocks == 0 and not eng.in_flight

    def test_a_failed_dispatch_still_delivers_the_first_token(
            self, tiny_model):
        model, cfg = tiny_model
        eng = _engine(model)

        def refuse(*a):
            raise RuntimeError("dispatch refused")

        eng._step_fn = refuse
        req = eng.submit(_prompt(np.random.RandomState(23), cfg, 20),
                         max_new_tokens=3)
        with pytest.raises(RuntimeError, match="dispatch refused"):
            eng.step()
        assert len(req.output_tokens) == 1 and eng._parked_tokens == []
        assert req.first_token_ts is not None

    def test_a_slot_cancelled_before_the_read_gets_what_was_selected(
            self, tiny_model):
        """Cancelled between its last chunk's booking and the step: the
        cancel is seen with a first token in flight, which is read
        first (none is lost before a hand-over); the request then ends
        as cancelled, no step is paid for it and nothing stays parked."""
        model, cfg = tiny_model
        eng = _engine(model)
        real = eng._finish_prefill

        def finish(slot, job):
            real(slot, job)
            job.req.cancel()

        eng._finish_prefill = finish
        req = eng.submit(_prompt(np.random.RandomState(24), cfg, 20),
                         max_new_tokens=3)
        before = eng.counters()["steps"]
        assert eng.step()
        assert req.status == serving.RequestStatus.CANCELLED
        assert len(req.output_tokens) == 1 and not eng.in_flight
        assert eng.counters()["steps"] == before and eng.busy_slots() == 0
        assert eng.counters()["ahead_flushes"] == 1

    def test_a_slot_preempted_before_the_read_keeps_its_first_token(
            self, tiny_model):
        """The decode write's reservation preempts the slot whose first
        token is parked: the token is read first, the request goes back
        to the queue's front as one that has produced it, and its tokens
        are those of an undisturbed run, each once."""
        model, cfg = tiny_model
        prompt = _prompt(np.random.RandomState(25), cfg, 20)
        eng = _engine(model)
        want = eng.submit(prompt, max_new_tokens=4)
        eng.run_until_idle()
        eng = _engine(model)
        real, fired = eng._reserve_write, []

        def reserve(slot, start, end, **kw):
            if end - start == 1 and not fired:
                fired.append(slot)
                raise serving.block_pool.PoolExhaustedError("no block")
            return real(slot, start, end, **kw)

        eng._reserve_write = reserve
        req = eng.submit(prompt, max_new_tokens=4)
        assert eng.step()
        assert fired and req.preempt_count == 1 and req.slot is None
        assert list(req.output_tokens) == list(want.output_tokens)[:1]
        assert not eng.in_flight
        eng.run_until_idle()
        assert list(req.output_tokens) == list(want.output_tokens)

    def test_the_speculative_lane_reads_before_it_dispatches(
            self, tiny_model):
        """Its bundle widths come from what each request has been given
        (``_row_spec_len``), so a speculative engine reads a first token
        where the parent did: before the round is dispatched."""
        from paddle_tpu import generation

        model, cfg = tiny_model
        eng = _engine(model, draft_model=generation.truncated_draft(model, 1),
                      spec_k=2)
        t0 = tracing.events()[-1]["ts_ns"] + 1
        req = eng.submit(_prompt(np.random.RandomState(26), cfg, 20),
                         max_new_tokens=5)
        eng.run_until_idle()
        assert len(req.output_tokens) == 5
        evs = [e for e in tracing.events() if e["ts_ns"] >= t0]
        (first,) = [e for e in evs if e["trace"] == req.trace
                    and e["name"] == "first_token"]
        disp = min((e for e in evs if e["name"] == "engine.dispatch"
                    and e["tid"] == threading.get_ident()),
                   key=lambda e: e["ts_ns"])
        assert first["ts_ns"] <= disp["ts_ns"]
        plain = _engine(model)   # the plain engine's tokens
        ref = plain.submit(_prompt(np.random.RandomState(26), cfg, 20),
                           max_new_tokens=5)
        plain.run_until_idle()
        assert list(req.output_tokens) == list(ref.output_tokens)


class TestHostArguments:
    def test_chunks_and_steps_construct_no_device_array(self, tiny_model,
                                                        monkeypatch):
        """The arguments of a prefill program (one row or several) and
        of a step are host arrays handed to the executable as they are:
        what the engine builds with ``jnp.asarray`` it builds once a
        request, however many chunks and steps the request takes and
        however many requests share a program."""
        import sys

        import jax.numpy as jnp

        model, cfg = tiny_model
        eng = _engine(model)
        eng.warmup()
        real, calls = jnp.asarray, []

        def counting(*a, **kw):
            if sys._getframe(1).f_code.co_filename.endswith(
                    "serving/engine.py"):
                calls.append(sys._getframe(1).f_code.co_name)
            return real(*a, **kw)

        monkeypatch.setattr(jnp, "asarray", counting)
        rng = np.random.RandomState(5)
        made = []
        for n_prompt, n_new in ((BLOCK + 3, 2), (6 * BLOCK + 3, 20)):
            del calls[:]
            req = eng.submit(_prompt(rng, cfg, n_prompt),
                             max_new_tokens=n_new)
            eng.run_until_idle()
            assert len(req.output_tokens) == n_new
            made.append(sorted(calls))
        assert made[0] == made[1]
        hot = {"_claim_chunk", "_claim_spare_rows", "_chunk_args",
               "_enqueue_claimed", "_book_chunks", "_finish_prefill",
               "_deliver_first_tokens", "_first_token", "_step_impl"}
        assert not hot & set(made[1])
        # the batched call: three prompts whose chunks share programs
        del calls[:]
        before = eng.counters()
        reqs = [eng.submit(_prompt(rng, cfg, n), max_new_tokens=3)
                for n in (2 * BLOCK + 3, 3 * BLOCK, 5)]
        eng.run_until_idle()
        assert all(len(r.output_tokens) == 3 for r in reqs)
        after = eng.counters()
        assert after["prefill_rows"] - before["prefill_rows"] == 3 + 3 + 1
        assert after["prefill_programs"] - before["prefill_programs"] \
            == 2 + 1 + 1
        assert not hot & set(calls)
        # and the rows reach the program as one host array
        assert all(type(eng._chunk_args([], w)) is np.ndarray
                   for w in (1, eng._chunk_rows))


class TestPoolGauges:
    def test_gauges_after_an_iteration_equal_the_pool_statistics(
            self, tiny_model):
        model, cfg = tiny_model
        eng = _engine(model)
        rng = np.random.RandomState(8)
        base = _prompt(rng, cfg, 2 * BLOCK)
        eng.submit(base, max_new_tokens=2)
        eng.run_until_idle()
        eng.submit(np.concatenate([base, _prompt(rng, cfg, 3)]),
                   max_new_tokens=8)
        for _ in range(3):
            assert eng.step()
            st = eng.pool.stats()
            assert (sm.kv_blocks_total.value(), sm.kv_blocks_in_use.value(),
                    sm.kv_blocks_shared.value()) \
                == (st["usable"], st["in_use"], st["shared"])
            assert st["shared"] >= 2

    def test_freeing_200_blocks_reduces_over_nothing(self, monkeypatch):
        pool = BlockPool(256, BLOCK)
        calls = []
        real = BlockPool._shared_unlocked
        monkeypatch.setattr(
            BlockPool, "_shared_unlocked",
            lambda self: calls.append(1) or real(self))
        ids = pool.alloc(200)
        for b in ids[:50]:
            pool.incref(b)
        for b in ids:
            pool.decref(b)
        assert calls == [] and pool.used_blocks == 50
        pool.set_gauges()
        assert len(calls) == 1 and sm.kv_blocks_shared.value() == 0

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_the_running_shared_count_is_the_reduction(self, seed):
        rng = np.random.RandomState(seed)
        pool = BlockPool(64, BLOCK)
        live = []
        for _ in range(600):
            op = rng.randint(3)
            if op == 0 and pool.free_blocks:
                live.extend(pool.alloc(1))
            elif op == 1 and live:
                b = live[rng.randint(len(live))]
                pool.incref(b)
                live.append(b)
            elif live:
                pool.decref(live.pop(rng.randint(len(live))))
            assert pool.shared_blocks == int((pool._ref[1:] > 1).sum())
        assert pool.stats()["shared"] == pool.shared_blocks


class TestOneClock:
    def test_phases_reach_an_active_profiler_session(self, tiny_model,
                                                     tmp_path):
        import jax

        from perfbench import trace_reduce

        model, cfg = tiny_model
        eng = _engine(model)
        eng.submit(_prompt(np.random.RandomState(9), cfg, 20),
                   max_new_tokens=3)
        eng.run_until_idle()    # compile outside the session
        eng.submit(_prompt(np.random.RandomState(10), cfg, 20),
                   max_new_tokens=3)
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.run_until_idle()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        host = [ev for plane, lines in trace_reduce.load(path).items()
                if plane.startswith("/host:") for ev in lines["host"]]
        names = [n for n, _, _ in host if n.startswith("engine.")]
        assert {"engine.iter", *CHILDREN} <= set(names)
        # as many as the ring holds of the same run, no-work spins aside
        assert names.count("engine.wait") == 2

    def test_train_dispatch_is_recorded_for_each_step(self):
        import paddle_tpu.distributed as dist
        from paddle_tpu import nn
        from paddle_tpu.distributed.engine import ShardedTrainStep

        paddle.seed(0)
        m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        lossfn = nn.CrossEntropyLoss()
        opt = paddle.optimizer.SGD(0.1, parameters=m.parameters())
        mesh = dist.ProcessMesh(np.arange(2).reshape(2), ["dp"])
        step = ShardedTrainStep(m, lambda o, lab: lossfn(o, lab), opt, mesh)
        rng = np.random.RandomState(1)
        x = paddle.to_tensor(rng.randn(16, 8).astype(np.float32))
        y = paddle.to_tensor(rng.randint(0, 4, 16).astype(np.int64))
        before = len(tracing.events(trace="train"))
        for _ in range(3):
            float(step.step(x, y))
        evs = tracing.events(trace="train")[before:]
        assert [e["name"] for e in evs] == ["train.dispatch"] * 3
        assert [e["args"]["step"] for e in evs] == [0, 1, 2]
        assert all(e["cat"] == "train" and e["dur_ns"] > 0 for e in evs)


# ---------------------------------------------------------------------------
# the host one step ahead of the tokens it reads
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def models():
    from paddle_tpu.models import (EvaByteConfig, EvaByteForCausalLM,
                                   GPTConfig, GPTForCausalLM)

    paddle.seed(0)
    return {
        "gpt": (GPTForCausalLM(GPTConfig.tiny()), dict(
            max_slots=3, max_len=64, block_size=8, prefill_chunk=16)),
        "llama_gqa": (LlamaForCausalLM(LlamaConfig.tiny(
            num_key_value_heads=2, max_position_embeddings=256)), dict(
            max_slots=3, max_len=128, block_size=BLOCK, prefill_chunk=16)),
        # window 16 in chunks of 4: a prompt of 37 and 30 more roll thrice
        "evabyte": (EvaByteForCausalLM(EvaByteConfig.tiny()), dict(
            max_slots=2, max_len=128, block_size=4, prefill_chunk=8,
            prefix_caching=False)),
    }


def _traffic(eng, vocab, sampled, n=5, seed=31, longest=40, most=30):
    rng = np.random.RandomState(seed)
    reqs = []
    for k in range(n):
        kw = dict(do_sample=True, temperature=0.8, top_k=20, top_p=0.9,
                  seed=100 + k) if sampled else {}
        reqs.append(eng.submit(
            rng.randint(1, vocab, rng.randint(3, longest)).astype("int32"),
            max_new_tokens=int(rng.randint(1, most)), **kw))
    return reqs


def _leaked(eng):
    """Blocks still held once the prefix cache has let go of its own."""
    if eng.prefix_cache is not None:
        eng.prefix_cache.evict(eng.pool.num_blocks)
    return eng.pool.used_blocks


def _drive(eng, flush):
    """``step()`` by hand to the end; ``flush``: read what is in flight
    after every iteration, which is the engine with no step ahead."""
    n = 0
    while eng.has_work():
        assert eng.step()
        if flush:
            with eng._step_lock:
                eng._flush_ahead()
        n += 1
        assert n < 5000
    assert eng.step() is False and not eng.in_flight
    return n


class TestStepAhead:
    @pytest.mark.parametrize("kind,fmt,sampled", [
        ("gpt", "bf16", False), ("gpt", "bf16", True),
        ("gpt", "int8", True), ("llama_gqa", "bf16", True),
        ("llama_gqa", "int8", False), ("llama_gqa", "int8", True),
        ("evabyte", "bf16", False), ("evabyte", "bf16", True)])
    def test_tokens_are_those_of_the_engine_that_flushes_every_iteration(
            self, models, kind, fmt, sampled):
        model, kw = models[kind]
        vocab = model.config.vocab_size
        got = {}
        for flush in (True, False):
            eng = serving.ServingEngine(model, kv_format=fmt, **kw)
            reqs = _traffic(eng, vocab, sampled)
            _drive(eng, flush)
            assert all(r.status == serving.RequestStatus.COMPLETED
                       and len(r.output_tokens) == r.params.max_new_tokens
                       for r in reqs)
            got[flush] = [list(r.output_tokens) for r in reqs]
            c = eng.counters()
            assert c["dead_rows"] == 0 and _leaked(eng) == 0
            if flush:
                assert c["steps_ahead"] == 0 and c["ahead_flushes"] > 0
            else:
                # only the step that fills the pipeline finds it empty
                assert c["ahead_flushes"] == 0
                assert c["steps"] - 3 <= c["steps_ahead"] < c["steps"]
        assert got[True] == got[False]

    def test_a_window_rolls_on_the_step_in_flight(self, models):
        """The roll depends on the length alone, which moved when the
        step was enqueued: the blocks the window gives back are free
        for the next program while the step that read them is unread."""
        model, kw = models["evabyte"]
        prompt = _prompt(np.random.RandomState(41), model.config, 13)
        outs = []
        for flush in (True, False):
            eng = serving.ServingEngine(model, **kw)
            req = eng.submit(prompt, max_new_tokens=24)
            rolled_ahead = 0
            while eng.has_work():
                rolls = eng.counters()["window_rolls"]
                ahead = eng._ahead is not None
                assert eng.step()
                rolled_ahead += ahead and \
                    eng.counters()["window_rolls"] > rolls
                if flush:
                    eng._flush_ahead()
            outs.append(list(req.output_tokens))
            # positions 16 and 32 are crossed by decode steps
            assert eng.counters()["window_rolls"] == 2
            assert rolled_ahead == (0 if flush else 2)
        assert outs[0] == outs[1] and len(outs[0]) == 24

    def test_end_of_sequence_mid_stream_leaves_one_dead_row(self, models):
        """The host learns of it a step late: the row it gave the
        request meanwhile is dead, its token never delivered, and the
        prompt's blocks stay as the prefix cache holds them."""
        model, kw = models["llama_gqa"]
        prompt = _prompt(np.random.RandomState(42), model.config,
                         2 * BLOCK + 3)
        eng = serving.ServingEngine(model, **kw)
        probe = eng.submit(prompt, max_new_tokens=12)
        eng.run_until_idle()
        want = list(probe.output_tokens)
        cut = next(j for j in range(3, 12) if want[j] not in want[:j])
        seen = []
        eng = serving.ServingEngine(model, **kw)
        req = eng.submit(prompt, max_new_tokens=12, eos_token_id=want[cut],
                         on_token=lambda r, t: seen.append(t))
        _drive(eng, flush=False)
        assert req.status == serving.RequestStatus.COMPLETED
        assert list(req.output_tokens) == seen == want[:cut + 1]
        c = eng.counters()
        assert (c["dead_rows"], c["ahead_flushes"]) == (1, 0)
        assert c["slot_steps"] == cut and c["steps"] == cut + 1
        # the same prompt again adopts its whole blocks and says the same
        again = eng.submit(prompt, max_new_tokens=12)
        eng.run_until_idle()
        assert list(again.output_tokens) == want
        assert eng.counters()["prefix_hit_tokens"] == 2 * BLOCK
        assert _leaked(eng) == 0

    @pytest.mark.parametrize("how", ["cancel", "deadline"])
    def test_a_request_ended_from_outside_with_a_step_in_flight(
            self, models, how):
        """A cancel is seen between steps and flushes; a deadline is
        seen at emit and leaves a dead row. Either way the tokens
        delivered are a prefix of the undisturbed run's, none arrives
        after the request has ended, and the neighbour is untouched."""
        model, kw = models["gpt"]
        rng = np.random.RandomState(43)
        p0, p1 = (_prompt(rng, model.config, n) for n in (9, 12))
        eng = serving.ServingEngine(model, **kw)
        alone = [eng.submit(p, max_new_tokens=20) for p in (p0, p1)]
        eng.run_until_idle()
        eng = serving.ServingEngine(model, **kw)
        seen = []
        req = eng.submit(p0, max_new_tokens=20,
                         on_token=lambda r, t: seen.append(t))
        other = eng.submit(p1, max_new_tokens=20)
        for _ in range(6):
            assert eng.step()
        assert eng._ahead is not None and 0 < len(seen) < 20
        if how == "cancel":
            req.cancel()
        else:
            req.deadline_ts = 0.0
        assert eng.step()
        status = serving.RequestStatus.CANCELLED if how == "cancel" \
            else serving.RequestStatus.EXPIRED
        assert req.status == status
        n = len(seen)
        assert seen == list(alone[0].output_tokens)[:n] \
            == list(req.output_tokens)
        eng.run_until_idle()
        assert len(seen) == n
        assert list(other.output_tokens) == list(alone[1].output_tokens)
        c = eng.counters()
        assert (c["ahead_flushes"], c["dead_rows"]) \
            == ((1, 0) if how == "cancel" else (0, 1))

    def test_pool_pressure_preempts_only_after_the_step_in_flight_is_read(
            self, models):
        model, kw = models["llama_gqa"]
        vocab = model.config.vocab_size
        outs = []
        for blocks in (None, 13):   # roomy; 12 usable blocks for 3 slots
            eng = serving.ServingEngine(model, num_blocks=blocks, **kw)
            rng = np.random.RandomState(4242)
            reqs = [eng.submit(_prompt(rng, model.config, n),
                               max_new_tokens=30, do_sample=True,
                               temperature=0.9, seed=n)
                    for n in (40, 55, 33)]
            eng.run_until_idle(max_steps=5000)
            outs.append([list(r.output_tokens) for r in reqs])
            c = eng.counters()
            if blocks:
                assert c["preemptions"] >= 1
                assert c["ahead_flushes"] >= c["preemptions"] > 0
                assert sum(r.preempt_count for r in reqs) \
                    == c["preemptions"]
            assert not eng.in_flight and _leaked(eng) == 0
        assert outs[0] == outs[1]
        assert vocab and all(len(o) == 30 for o in outs[0])

    @pytest.mark.parametrize("how", ["abort", "drain", "export", "crash"])
    def test_a_hand_over_takes_what_is_in_flight_first(self, models, how):
        """None lost before it, none after it: what the device had
        selected when the engine was stopped, drained, exported or
        crashed reaches its request first, and nothing reaches a
        request afterwards. An exported request resumes on a fresh
        engine to the undisturbed run's tokens."""
        model, kw = models["gpt"]
        rng = np.random.RandomState(44)
        prompts = [_prompt(rng, model.config, n) for n in (7, 18, 11)]
        eng = serving.ServingEngine(model, **kw)
        alone = [eng.submit(p, max_new_tokens=16, do_sample=True, seed=k)
                 for k, p in enumerate(prompts)]
        eng.run_until_idle()
        want = [list(r.output_tokens) for r in alone]
        eng = serving.ServingEngine(model, **kw)
        seen = [[] for _ in prompts]
        reqs = [eng.submit(p, max_new_tokens=16, do_sample=True, seed=k,
                           on_token=lambda r, t, k=k: seen[k].append(t))
                for k, p in enumerate(prompts)]
        for _ in range(7):
            assert eng.step()
        assert eng._ahead is not None
        given = [len(s) for s in seen]
        due = [eng._slot_due[r.slot] for r in reqs]
        assert sum(due) == 3
        if how == "abort":
            eng.stop(abort=True)
        elif how == "drain":
            assert eng.drain() is True
        elif how == "export":
            with eng._step_lock:
                running, queued = eng._export_inflight()
            assert running == reqs and queued == []
        else:
            def refuse(*a):
                raise RuntimeError("device lost")

            eng._step_fn = refuse
            with pytest.raises(RuntimeError, match="device lost"):
                eng.step()
            assert not eng.in_flight
            eng._on_loop_crash(RuntimeError("device lost"))
        assert not eng.in_flight and eng.counters()["ahead_flushes"] \
            == (0 if how == "drain" else 1)
        after = [len(s) for s in seen]
        if how == "drain":
            assert [list(r.output_tokens) for r in reqs] == want == seen
            return
        assert after == [g + d for g, d in zip(given, due)]
        assert all(s == w[:len(s)] == list(r.output_tokens)
                   for s, w, r in zip(seen, want, reqs))
        if how != "export":
            assert all(r.status == serving.RequestStatus.FAILED
                       for r in reqs)
            assert eng.step() is False
            assert [len(s) for s in seen] == after
            return
        fresh = serving.ServingEngine(model, **kw)
        for r in reversed(running):
            fresh.scheduler.requeue(r)
        fresh.run_until_idle()
        assert eng.step() is False       # the old engine holds nothing
        assert [list(r.output_tokens) for r in reqs] == want == seen

    def test_a_device_that_gives_no_tokens_back_drops_what_was_in_flight(
            self, models):
        """The crash path's flush where the read itself fails: nothing
        is delivered, nothing stays in flight, the requests are failed
        with what they had."""
        model, kw = models["gpt"]
        eng = serving.ServingEngine(model, **kw)
        req = eng.submit(_prompt(np.random.RandomState(45), model.config,
                                 9), max_new_tokens=10)
        for _ in range(4):
            assert eng.step()
        had = len(req.output_tokens)

        class Lost:
            def __array__(self, *a, **k):
                raise RuntimeError("buffer lost")

        eng._ahead = (Lost(),) + eng._ahead[1:]
        with pytest.raises(RuntimeError, match="buffer lost"):
            eng.step()
        assert not eng.in_flight and len(req.output_tokens) == had
        eng._on_loop_crash(RuntimeError("buffer lost"))
        assert req.status == serving.RequestStatus.FAILED
        assert len(req.output_tokens) == had and eng.busy_slots() == 0

    def test_by_hand_and_run_until_idle_leave_nothing_in_flight(
            self, models):
        model, kw = models["llama_gqa"]
        eng = serving.ServingEngine(model, **kw)
        reqs = _traffic(eng, model.config.vocab_size, False, n=4)
        # a token of a step is seen no later than the next step() returns
        total = 0
        while eng.step():
            now = sum(len(r.output_tokens) for r in reqs)
            due = sum(eng._slot_due)
            assert due <= 2 * eng.config.max_slots
            assert now >= total
            total = now + 0
        assert not eng.in_flight and not eng.has_work()
        assert all(len(r.output_tokens) == r.params.max_new_tokens
                   for r in reqs)
        more = _traffic(eng, model.config.vocab_size, False, n=4, seed=32)
        assert eng.run_until_idle(max_steps=4) == 4
        assert not eng.in_flight and eng.has_work()   # max_steps: flushed
        assert sum(eng._slot_due) == 0
        eng.run_until_idle()
        assert all(r.status == serving.RequestStatus.COMPLETED
                   for r in more) and not eng.has_work()

    def test_a_request_between_the_queue_and_its_slot_counts_as_work(
            self, models):
        """``drain()`` asks ``has_work()`` without the step lock: a
        request ``_admit`` has popped and not yet seated is in neither
        the queue nor a slot, and must not read as an idle engine."""
        model, kw = models["gpt"]
        eng = serving.ServingEngine(model, **kw)
        real, seen = eng._begin_prefill, []

        def seat(req, slot):
            seen.append((eng.scheduler.depth, eng.busy_slots(),
                         eng.has_work()))
            return real(req, slot)

        eng._begin_prefill = seat
        req = eng.submit(_prompt(np.random.RandomState(47), model.config,
                                 9), max_new_tokens=2)
        eng.run_until_idle()
        assert seen == [(0, 0, True)] and len(req.output_tokens) == 2
        assert eng._unseated == 0 and not eng.has_work()

    def test_a_speculative_engine_is_never_ahead(self, tiny_model):
        from paddle_tpu import generation

        model, cfg = tiny_model
        eng = _engine(model, draft_model=generation.truncated_draft(model, 1),
                      spec_k=2)
        t0 = tracing.events()[-1]["ts_ns"] + 1
        reqs = _traffic(eng, cfg.vocab_size, False, n=3)
        while eng.step():
            assert not eng.in_flight
        c = eng.counters()
        assert all(r.status == serving.RequestStatus.COMPLETED for r in reqs)
        assert (c["steps_ahead"], c["ahead_flushes"], c["dead_rows"]) \
            == (0, 0, 0) and c["steps"] > 0
        disp = [e for e in _engine_lane(t0) if e["name"] == "engine.dispatch"]
        assert disp and all(e["args"]["ahead"] == 0 for e in disp)

    def test_the_dispatch_arg_sums_to_the_counter(self, models):
        model, kw = models["gpt"]
        eng = serving.ServingEngine(model, **kw)
        t0 = tracing.events()[-1]["ts_ns"] + 1
        _traffic(eng, model.config.vocab_size, True)
        eng.run_until_idle()
        first = eng.counters()
        _traffic(eng, model.config.vocab_size, True, seed=33)
        eng.run_until_idle()
        c = eng.counters()
        disp = [e["args"]["ahead"] for e in _engine_lane(t0)
                if e["name"] == "engine.dispatch"]
        assert len(disp) == c["steps"] and sum(disp) == c["steps_ahead"]
        # the engine had idled between the two batches: one more step
        # found the device drained
        assert c["steps"] - c["steps_ahead"] \
            == 2 * (first["steps"] - first["steps_ahead"])

    def test_disabled_tracing_pays_one_flag_check_and_the_steps_two_edges(
            self, models, monkeypatch):
        """With ``PADDLE_TPU_TRACING=0`` a steady iteration's phases
        read the tracing flag once (``Phases.open``; ``serving.step``'s
        own span reads it too, as it always did) and the clock three
        times: open, the step's dispatch, the sync."""
        import paddle_tpu.serving.engine as engine_mod

        model, kw = models["gpt"]
        eng = serving.ServingEngine(model, **kw)
        req = eng.submit(_prompt(np.random.RandomState(46), model.config, 9),
                         max_new_tokens=12)
        for _ in range(4):
            assert eng.step()
        reads = []
        real = engine_mod.time.perf_counter_ns

        class Flag(list):
            def __getitem__(self, i):
                reads.append("flag")
                return False

        tracing.disable_tracing()
        try:
            monkeypatch.setattr(tracing, "_TRACING", Flag([False]))
            monkeypatch.setattr(
                engine_mod.time, "perf_counter_ns",
                lambda: reads.append("clock") or real())
            assert eng.step()
        finally:
            monkeypatch.undo()
            tracing.enable_tracing()
        assert reads == ["flag", "clock", "clock", "clock", "flag"]
        assert eng._ahead is not None and not eng._phases.on
        eng.run_until_idle()
        assert len(req.output_tokens) == 12


# ---------------------------------------------------------------------------
# who fuses: decided by what the engine can see
# ---------------------------------------------------------------------------

# sha256 (first 16 hex digits) of each program's lowered text, locations
# stripped, as the tree BEFORE the step learned to carry prefill rows
# lowers it (PR 37's, commit b879033): an engine that does not fuse
# keeps its pair of programs to the byte. After a change that is MEANT
# to alter one of these programs, read the new values off the assertion.
PARENT_PROGRAMS = {
    "windowed": {"step": "32d37b4bd1bca4b7", "chunk1": "1a1f5869a1a51027",
                 "chunk2": "1cf6f1fe09e6aecf"},
    "looped": {"step": "59b11bd31512f08c", "chunk1": "fa00005191cd7c80",
               "chunk2": "8de0a59a918496e3"},
    "speculative": {"draft": "a617c0279bb0f853", "verify": "e07f95900d4241c4",
                    "chunk1": "3c2146d857f51616",
                    "chunk4": "3a5c737a77241795"},
}


def _unfused_engine(kind):
    if kind == "windowed":
        import test_evabyte as te
        return te.engine_for(te.build()[0], slots=2), te.SIZES["vocab_size"]
    if kind == "looped":
        import test_ouro as to
        return (to.engine_for(to.build()[0], slots=3, max_len=96,
                              num_blocks=30), to.SIZES["vocab_size"])
    from paddle_tpu import generation

    paddle.seed(0)
    llama = LlamaForCausalLM(LlamaConfig.tiny(num_key_value_heads=2))
    return serving.ServingEngine(
        llama, draft_model=generation.truncated_draft(llama, 1), spec_k=3,
        max_slots=4, max_len=64, block_size=8), llama.config.vocab_size


def _lowered_programs(eng):
    """{name: digest} of the engine's decode and prefill programs."""
    import hashlib
    import re

    import jax.numpy as jnp

    loc = re.compile(r"\s*loc\([^\n]*\)|^#loc[^\n]*\n", re.M)
    B, nb = eng.config.max_slots, eng._bt.shape[1]
    bt, off = np.zeros((B, nb), np.int32), np.zeros(B, bool)
    no = np.asarray(False, bool)
    low = {}
    if eng.spec:
        sv0 = jnp.zeros(B, jnp.int32)
        low["draft"] = eng._draft_fn.lower(
            eng._dpb, eng._dpools, eng._state, bt, sv0, jnp.asarray(False))
        low["verify"] = eng._verify_fn.lower(
            eng._pb, eng._pools, eng._state, bt, eng._zero_drafts, sv0,
            jnp.asarray(False), off)
    else:
        low["step"] = eng._step_fn.lower(eng._pb, eng._pools, eng._state,
                                         bt, no, off)
    for w in sorted({1, eng._chunk_rows}):
        rows = eng._chunk_args((), w)
        low[f"chunk{w}"] = eng._chunk_spec_fn.lower(
            eng._pb, eng._dpb, eng._pools, eng._dpools, eng._state, rows) \
            if eng.spec else eng._chunk_fn.lower(eng._pb, eng._pools,
                                                 eng._state, rows)
    return {k: hashlib.sha256(loc.sub("", v.as_text()).encode())
            .hexdigest()[:16] for k, v in low.items()}


class TestWhoFuses:
    @pytest.mark.parametrize("kind", list(PARENT_PROGRAMS))
    def test_an_engine_that_does_not_fuse_lowers_the_parents_programs(
            self, kind):
        """A windowed layout (a row rolls between the two halves), a
        looped stack (the passes' loop) and the speculative lane
        (synchronous) keep today's pair of programs, byte for byte, go
        on in the parent's order (nothing is ever held for the step)
        and say ``fused`` 0 of every step."""
        eng, vocab = _unfused_engine(kind)
        assert not eng._fuses and eng._fused_entries == []
        assert _lowered_programs(eng) == PARENT_PROGRAMS[kind]
        t0 = tracing.events()[-1]["ts_ns"] + 1 if tracing.events() else 0
        rng = np.random.RandomState(61)
        first = eng.submit(rng.randint(1, vocab, 9).astype("int32"),
                           max_new_tokens=8)
        eng.step()
        eng.step()
        # prefill rows while a slot decodes: what a fusing engine carries
        # in its step
        late = eng.submit(rng.randint(1, vocab, 21).astype("int32"),
                          max_new_tokens=3)
        eng.run_until_idle()
        assert (len(first.output_tokens), len(late.output_tokens)) == (8, 3)
        disp = [e["args"] for e in _engine_lane(t0)
                if e["name"] == "engine.dispatch"]
        assert disp and all((a["fused"], a["prefill_rows"]) == (0, 0)
                            for a in disp)
        assert eng.counters()["steps_fused"] == 0
        assert not [e for e in eng.warmup()["entries"]
                    if "+" in e]

    def test_a_plain_paged_engine_fuses_and_a_sharded_one_keeps_the_pair(
            self, tiny_model):
        """Every plain GPT or Llama engine carries its prefill rows in
        the step; under ``tp > 1`` the executables' shardings are one
        fixed tuple and the pair stays."""
        model, cfg = tiny_model
        assert _engine(model)._fuses
        assert _engine(model, kv_format="int8")._fuses
        assert not _engine(model, tp=2)._fuses
