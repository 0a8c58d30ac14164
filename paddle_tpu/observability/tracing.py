"""Request-lifecycle tracing: low-overhead spans/instants, a bounded
flight-recorder ring, and streaming latency digests.

The third leg of the observability stack: the profiler answers "where
did this STEP's time go" (host/device spans around one training step),
the metrics registry answers "what is the runtime doing over time"
(counters/gauges), and this module answers "what happened to THIS
request" — the per-iteration timeline Orca/vLLM-class serving systems
treat as the primary operational tool. The serving engine threads
spans through the whole request lifecycle (queued → admitted → prefill
chunks → decode windows → terminal), the recompile monitor attributes
XLA compiles into the active trace, and ``generation.generate`` marks
its prefill/decode phases.

Hot-path contract (the metrics registry's discipline, applied to
events): recording a span or instant NEVER takes a lock — it is one
``perf_counter_ns`` read (or zero, when the caller already holds the
timestamps) plus a ``deque.append`` into a per-thread buffer.
Per-thread buffers self-compact into the global bounded ring every
``_COMPACT_AT`` events (one amortized lock), and readers (exporters,
the flight recorder) drain them under the same lock. Tracing is
DEFAULT-ON and host-side only — no traced value ever sees an event, so
the one-step-compile invariant holds with tracing enabled. What it
costs is measured on the chip by the benchmark's cells, spans on in
every run (PERF.md, section 6, PR 25); no CPU timing stands for it.
``PADDLE_TPU_TRACING=0`` (or ``disable_tracing()``) reduces every site
to a single list-index check (``Phases``: one an iteration, in
``open``), the profiler annotations of ``Phases`` and ``profiled_span``
included.

Clocks. The ring's ``ts_ns`` is ``time.perf_counter_ns`` (Linux:
``CLOCK_MONOTONIC``), the clock the ``Request`` timestamps and the
benchmark's host clock use. A ``jax.profiler`` session keeps its own
clock: an ``.xplane.pb`` stores every host and device event relative
to the start of its session, and exports no offset to
``perf_counter``. So the spans that have to be read against device
operations (``Phases``, ``profiled_span``: the serving engine's
``engine.*`` phases and the trainer's ``train.dispatch``) are written
twice at the same boundaries: into the ring from the timestamps the
caller took, and, through ``jax.profiler.TraceAnnotation``, onto the
host plane of whatever profiler session is active, under the same
name. A tool that needs both clocks in one file reads the annotation
(``perfbench/gap_phases.py``); one that has the session's start on the
host clock adds it to the trace's times. With no session active an
annotation is a flag check in the runtime (0.3 us each, measured here).

Lost time. A second that a loop loses names its cause on the same
ring. ``Phases`` records the instant ``<lane>.stall`` where an iteration
that worked, or the gap between two that the loop did not idle in,
lasts longer than ``STALL_NS``, with its longest phase and the thread's
own CPU time (one ``thread_time_ns`` read an iteration). The lane
``proc`` (``watch_process()``, started by whoever starts a loop, never
at import) says what the whole process did meanwhile: a ``gc.callbacks``
entry records the collector's long or full passes (``proc.gc``), and a
daemon thread sleeps 25 ms at a time and records ONLY late wakes that
those passes do not explain (``proc.pause``: the process stood still,
with the CPU it used and its major page faults over that stretch, two
calls a beat and no file). The callback runs wherever the collector
trips, under the ring's lock as likely as anywhere, and takes no lock.
Each kind is also one ``logger.warning`` line, the first eight times a
process. What it costs while on was measured on the chip (PERF.md
section 6, PR 40; three untraced pairs a cell on one lease, parent /
change on the same seeds, with a beat that still opened three files of
``/proc`` four times a second where this one makes its two calls):
nothing that an end-to-end metric resolves. Chat-open's ``itl_p99_ms``,
the metric a thread that asks for the interpreter's lock forty times a
second could move, read 9.52 / 9.55, 9.64 / 9.50 and 9.59 / 9.51 ms
(9.66 with tracing off), doc-closed 19,134 / 19,240, 19,161 / 19,272
and 19,092 / 19,239 tokens/s (19,332 off), ``setup_s`` the same on both
sides. With tracing disabled: no thread, no ``gc.callbacks`` entry, no
thread clock, no stall.

Event schema (what ``events()`` returns and the JSONL export writes,
one JSON object per line):

- ``ph``:     ``"X"`` (complete span) or ``"i"`` (instant event)
- ``name``:   span/event name (``queued``, ``prefill_chunk``, ...)
- ``cat``:    category (``request``, ``engine``, ``proc``,
              ``generation``, ``compile``, ``profiler``)
- ``trace``:  trace id — the serving request id for request-lifecycle
              events, ``"engine"`` for pool-wide engine events,
              ``"proc"`` for what the whole process did, or null for
              unattributed events
- ``tid``:    OS thread ident of the recording thread
- ``ts_ns``:  monotonic start time (``time.perf_counter_ns`` — the
              same clock the Request timestamps use)
- ``dur_ns``: span duration (0 for instants)
- ``args``:   optional dict of small JSON-ready values

``chrome_trace()`` renders the same events as Chrome-trace (catapult)
JSON — one synthetic thread lane per trace id, so loading ``/trace``
in chrome://tracing or Perfetto shows each request as its own swimlane
with nested spans.

The **flight recorder** is the ring itself: ``flight_dump(reason)``
writes the last-N events plus every registered state provider's
snapshot (the serving engine registers ``engine.stats()``, which
carries the block-pool accounting) to one JSON file. It is wired to
the engine crash path, ``PoolExhaustedError`` escaping the step loop,
and the fault-tolerance SIGTERM/SIGINT handler — the post-mortem for
"what was the engine doing when it died".
"""

from __future__ import annotations

import atexit
import gc
import itertools
import json
import logging
import os
import resource
import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, List, Optional

# the span on the host plane of an active profiler session (Clocks, above)
from jax.profiler import TraceAnnotation as _Annotation

from . import metrics as _m

__all__ = [
    "tracing_enabled", "enable_tracing", "disable_tracing",
    "span", "begin_span", "end_span", "instant", "complete",
    "profiled_span", "Phases", "watch_process",
    "trace_context", "current_trace",
    "events", "clear", "chrome_trace", "export_chrome_trace",
    "export_jsonl", "span_counts", "summary",
    "Digest",
    "flight_dump", "last_flight_dump", "register_state_provider",
    "unregister_state_provider", "state_snapshot",
    "attach_profiler_spans", "detach_profiler_spans",
]

logger = logging.getLogger("paddle_tpu.observability")

# Kill switch (single list-index check per site, like metrics._ENABLED;
# observability.disable() gates this too — both flags must be up).
_TRACING = [os.environ.get("PADDLE_TPU_TRACING", "1") != "0"]

# Per-thread buffers self-compact into the ring at this length.
_COMPACT_AT = 512

# The bounded flight-recorder ring: most recent events, process-wide.
# Sized for the serving engine's iteration phases: eight events an
# iteration and ten or so a request. With the host a step ahead of the
# tokens it reads an iteration is as short as the decode step, 5 ms on
# a chip, so some 1,800 events a second: 262,144 reach back over two
# minutes (the benchmark reads a 51 s window after its drain; 65,536
# stopped short of its start). About 400 bytes an event.
_RING_CAPACITY = int(os.environ.get("PADDLE_TPU_TRACE_RING", "262144"))

_lock = threading.Lock()
_ring: deque = deque(maxlen=_RING_CAPACITY)
_tls = threading.local()
# [(weakref-to-thread, buffer)] — registered once per thread (under
# _lock); pruned when the thread is gone and its buffer drained.
_buffers: List[tuple] = []
# total events ever recorded per (ph, name) — survives ring eviction,
# feeds the CI trace summary (span counts per phase)
_counts: Dict[str, int] = {}

_events_total = _m.counter(
    "paddle_tpu_trace_events_total",
    "trace events recorded (spans + instants), by category", ("cat",))
_flight_dumps = _m.counter(
    "paddle_tpu_flight_dumps_total",
    "flight-recorder dumps written, by trigger reason", ("reason",))

_last_dump_path: List[Optional[str]] = [None]


def tracing_enabled() -> bool:
    return _TRACING[0] and _m._ENABLED[0]


def enable_tracing():
    _TRACING[0] = True


def disable_tracing():
    """Reduce every tracing site to one list-index check."""
    _TRACING[0] = False


# ---------------------------------------------------------------------------
# recording (the lock-free hot path)
# ---------------------------------------------------------------------------


def _buf() -> deque:
    b = getattr(_tls, "buf", None)
    if b is None:
        b = _tls.buf = deque()
        t = threading.current_thread()
        with _lock:
            _buffers.append((weakref.ref(t), b))
    return b


def _record(ph: str, name: str, cat: str, trace, tid: int, ts_ns: int,
            dur_ns: int, args):
    b = _buf()
    b.append((ph, name, cat, trace, tid, ts_ns, dur_ns, args))
    if len(b) >= _COMPACT_AT:
        _flush_locked()


def _flush_locked():
    """Drain every thread's buffer into the bounded ring (and the
    per-name totals); prune buffers whose threads are gone."""
    with _lock:
        dead = []
        by_cat: Dict[str, int] = {}
        for i, (tref, b) in enumerate(_buffers):
            while True:
                try:
                    ev = b.popleft()
                except IndexError:
                    break
                _ring.append(ev)
                key = ev[1]
                _counts[key] = _counts.get(key, 0) + 1
                by_cat[ev[2]] = by_cat.get(ev[2], 0) + 1
            if tref() is None:
                dead.append(i)
        for i in reversed(dead):
            del _buffers[i]
        for cat, n in by_cat.items():
            _events_total.labels(cat).inc(n)


# ---------------------------------------------------------------------------
# trace-context propagation (thread-local)
# ---------------------------------------------------------------------------


def current_trace():
    """The active trace id on this thread (set by ``trace_context``),
    or None. Compile events and nested spans attribute to it."""
    stack = getattr(_tls, "trace", None)
    return stack[-1] if stack else None


class trace_context:
    """Mark ``trace_id`` as the active trace on this thread for the
    duration of the ``with`` block (re-entrant; innermost wins)."""

    __slots__ = ("trace_id",)

    def __init__(self, trace_id):
        self.trace_id = trace_id

    def __enter__(self):
        stack = getattr(_tls, "trace", None)
        if stack is None:
            stack = _tls.trace = []
        stack.append(self.trace_id)
        return self

    def __exit__(self, *exc):
        _tls.trace.pop()
        return False


# ---------------------------------------------------------------------------
# spans + instants
# ---------------------------------------------------------------------------


class _Span:
    """An open span handle: begun on one call site (possibly one
    thread), ended on another — how the cross-iteration lifecycle spans
    (``queued``, ``decode``) are recorded."""

    __slots__ = ("name", "cat", "trace", "tid", "t0", "args", "_open")

    def __init__(self, name, cat, trace, tid, t0, args):
        self.name = name
        self.cat = cat
        self.trace = trace
        self.tid = tid
        self.t0 = t0
        self.args = args
        self._open = True


def begin_span(name: str, cat: str = "", trace=None, args=None,
               ts_ns: Optional[int] = None) -> Optional[_Span]:
    """Open a span; returns a handle for ``end_span`` (None when
    tracing is off — ``end_span(None)`` is a no-op, so call sites need
    no guards)."""
    if not tracing_enabled():
        return None
    if trace is None:
        trace = current_trace()
    return _Span(name, cat, trace, threading.get_ident(),
                 ts_ns if ts_ns is not None else time.perf_counter_ns(),
                 args)


def end_span(sp: Optional[_Span], ts_ns: Optional[int] = None, args=None):
    """Close an open span and record it as one complete event (idempotent
    — a span already ended, e.g. by ``Request.finish``, is skipped)."""
    if sp is None or not sp._open:
        return
    sp._open = False
    if not tracing_enabled():
        return
    t1 = ts_ns if ts_ns is not None else time.perf_counter_ns()
    a = sp.args
    if args:
        a = {**(a or {}), **args}
    _record("X", sp.name, sp.cat, sp.trace, sp.tid, sp.t0,
            max(t1 - sp.t0, 0), a)


class span:
    """Lexical span context manager::

        with tracing.span("generation.prefill", cat="generation"):
            ...
    """

    __slots__ = ("_sp", "name", "cat", "trace", "args")

    def __init__(self, name: str, cat: str = "", trace=None, args=None):
        self.name = name
        self.cat = cat
        self.trace = trace
        self.args = args
        self._sp = None

    def __enter__(self):
        self._sp = begin_span(self.name, self.cat, self.trace, self.args)
        return self._sp

    def __exit__(self, *exc):
        end_span(self._sp)
        return False


def instant(name: str, cat: str = "", trace=None, args=None,
            ts_ns: Optional[int] = None):
    """Record a zero-duration event (prefix-cache hit, COW fork,
    preemption, completion...)."""
    if not tracing_enabled():
        return
    if trace is None:
        trace = current_trace()
    _record("i", name, cat, trace, threading.get_ident(),
            ts_ns if ts_ns is not None else time.perf_counter_ns(), 0, args)


def complete(name: str, cat: str, trace, ts_ns: int, dur_ns: int, args=None):
    """Record an already-measured span from existing timestamps — zero
    extra clock reads (the engine's step loop already timed itself)."""
    if not tracing_enabled():
        return
    _record("X", name, cat, trace, threading.get_ident(), ts_ns,
            max(dur_ns, 0), args)


class profiled_span:
    """``span`` that is also written to an active profiler session
    under the same name::

        with tracing.profiled_span("train.dispatch", "train", "train",
                                   {"step": n}):
            ...

    With tracing disabled it is the one flag check and nothing else:
    no annotation, no clock read."""

    __slots__ = ("name", "cat", "trace", "args", "_t0", "_ann")

    def __init__(self, name: str, cat: str = "", trace=None, args=None):
        self.name, self.cat, self.trace, self.args = name, cat, trace, args
        self._ann = None

    def __enter__(self):
        if _TRACING[0]:
            self._ann = _Annotation(self.name)
            self._ann.__enter__()
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            t1 = time.perf_counter_ns()
            self._ann.__exit__(*exc)
            self._ann = None
            complete(self.name, self.cat, self.trace, self._t0,
                     t1 - self._t0, self.args)
        return False


# An iteration that worked, or the gap between two that the loop did not
# idle in, is a stall once it lasts longer than this. The longest
# ordinary iteration of the benchmark's cells is 86 ms (Ouro's step
# behind a prefill program, 38.5 + 47.8 ms on a v5e, PERF.md section 6,
# PR 37): three times that, and a quarter of the shortest loss anyone
# asked about (1 s). ``health()``'s ``stalled`` is the same clock held
# against ``stall_timeout_s``, forty times this.
STALL_NS = 250_000_000
# the lines a process logs of each kind of lost time (a stall, a pause
# or a pass of the collector longer than ``STALL_NS``); the ring holds
# every one of them regardless, and the shorter pauses and passes
_WARNINGS = 8
_warned: Dict[str, int] = {}
# the process's one watch (``watch_process``): (stop event, thread, when
# it began), or None
_watch: list = [None]


def _warn(kind: str, at_ns: int, fmt: str, *args):
    """One line an operator can grep, the first ``_WARNINGS`` times,
    with when the lost stretch began, in seconds since the process
    began to watch itself (a bare log has no clock of its own)."""
    n = _warned.get(kind, 0)
    if n < _WARNINGS:
        _warned[kind] = n + 1
        watch = _watch[0]
        at = f" (at +{(at_ns - watch[2]) / 1e9:.1f} s)" if watch else ""
        logger.warning(fmt + "%s", *args, at)


class Phases:
    """The consecutive phases of one loop iteration on one thread, under
    a parent span: ``open`` starts the parent and its first phase,
    every ``mark`` is ONE clock read that ends the open phase and
    starts the next, ``close`` ends both. No clock is read that is not
    a span's edge. The phases are kept until ``close`` says whether the
    iteration did any work: an iteration that did none records nothing
    in the ring. All of them go to an active profiler session, where a
    spin that found nothing to do is worth seeing. Each recorded event
    carries ``iter``, the number of the iteration, which ties a span on
    another lane to the iteration that ran it.

    A stall. Where an iteration that worked lasted longer than
    ``STALL_NS``, ``close`` records one instant ``<lane>.stall`` at the
    iteration's start: ``ms`` (its length), ``phase`` and ``phase_ms``
    (its longest child) and ``cpu_ms``, the thread's own CPU time since
    the ``close`` before (ONE ``thread_time_ns`` read an iteration,
    taken beside ``close``'s clock; left out where another thread closed
    the iteration before). ``cpu_ms`` near ``ms``: the thread was busy;
    near 0: it was blocked or not scheduled. A loop that goes straight
    from one iteration into the next says so (``follows``); where the
    next ``open`` then comes later than the ``close`` by more than
    ``STALL_NS`` the thread lost that time between the two, which is a
    stall with ``phase`` ``"between"``. ``stalls`` and ``stall_ns``
    count both kinds, and the first few are logged.

    ``open`` reads the tracing flag once for the iteration (``on``).
    With tracing disabled ``open`` is its one clock read (its caller
    uses the time), ``mark`` returns 0 and ``close`` only counts: no
    annotation, no list append, no further clock read, no stall. A
    caller builds a phase's args only where ``on`` is true."""

    __slots__ = ("parent", "cat", "trace", "seq", "on", "t_open", "t_mark",
                 "_name", "_done", "_ann", "_ann_parent",
                 "follows", "stalls", "stall_ns", "_t_close", "_cpu", "_tid")

    def __init__(self, parent: str, cat: str, trace):
        self.parent, self.cat, self.trace = parent, cat, trace
        self.seq = 0          # number of the open (or next) iteration
        self.on = False       # tracing was enabled when this one opened
        self.t_open = self.t_mark = 0
        self._name = None
        self._done: list = []
        self._ann = self._ann_parent = None
        self.follows = False  # the loop came here straight from close()
        self.stalls = self.stall_ns = 0
        # the last close(): its clock (0: tracing was off), the thread's
        # CPU clock there, and the thread
        self._t_close = self._cpu = self._tid = 0

    def open(self, first: str) -> int:
        """Start an iteration in phase ``first``; returns the time."""
        if self._name is not None:   # an exception skipped close()
            self.close(False)
        self.on = _TRACING[0]
        if self.on:
            self._done.clear()
            self._ann_parent = _Annotation(self.parent)
            self._ann_parent.__enter__()
            self._ann = _Annotation(first)
            self._ann.__enter__()
            self._name = first
        self.t_open = self.t_mark = time.perf_counter_ns()
        if self.follows:
            self.follows = False
            if self.on and self._t_close \
                    and self.t_open - self._t_close > STALL_NS:
                # rare: the one place a second CPU clock is read
                cpu = time.thread_time_ns()
                self._stall(self._t_close, self.t_open, "between",
                            self.t_open - self._t_close, cpu,
                            threading.get_ident())
                self._cpu = cpu
        return self.t_open

    def mark(self, name: str, args: Optional[dict] = None) -> int:
        """End the open phase, with its ``args``, and start ``name``;
        returns the time of the boundary (0 with tracing disabled)."""
        if not self.on:
            return 0
        now = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        self._done.append((self._name, self.t_mark, now, args))
        self._ann = _Annotation(name)
        self._ann.__enter__()
        self._name, self.t_mark = name, now
        return now

    def close(self, worked: bool, args: Optional[dict] = None,
              parent_args: Optional[dict] = None) -> int:
        """End the open phase (``args``) and the parent
        (``parent_args``); record them if the iteration ``worked``.
        Returns the time, as ``mark`` does."""
        now = 0
        if self.on:
            now = time.perf_counter_ns()
            cpu = time.thread_time_ns()
            tid = threading.get_ident()
            self._ann.__exit__(None, None, None)
            self._ann_parent.__exit__(None, None, None)
            self._done.append((self._name, self.t_mark, now, args))
            self._name = None
            if worked:
                seq = {"iter": self.seq}
                for name, t0, t1, a in self._done:
                    _record("X", name, self.cat, self.trace, tid, t0,
                            t1 - t0, {**a, **seq} if a else seq)
                _record("X", self.parent, self.cat, self.trace, tid,
                        self.t_open, now - self.t_open,
                        {**parent_args, **seq} if parent_args else seq)
                if now - self.t_open > STALL_NS:
                    name, t0, t1, _ = max(self._done,
                                          key=lambda d: d[2] - d[1])
                    self._stall(self.t_open, now, name, t1 - t0, cpu, tid)
            self._cpu, self._tid = cpu, tid
        self._t_close = now
        if worked:
            self.seq += 1
        return now

    def _stall(self, t0: int, t1: int, phase: str, phase_ns: int,
               cpu: int, tid: int):
        """Record, count and (the first few times) log the stall
        ``[t0, t1)`` whose longest part was ``phase``."""
        args = {"iter": self.seq, "ms": (t1 - t0) / 1e6, "phase": phase,
                "phase_ms": phase_ns / 1e6}
        said = "not read"
        if tid == self._tid:
            args["cpu_ms"] = (cpu - self._cpu) / 1e6
            said = f"{args['cpu_ms'] / 1e3:.2f} s"
        self.stalls += 1
        self.stall_ns += t1 - t0
        _record("i", f"{self.trace}.stall", self.cat, self.trace, tid, t0, 0,
                args)
        _warn("stall", t0,
              "%s stalled %.2f s in %s (iter %d): thread cpu %s",
              self.trace, (t1 - t0) / 1e9, phase, self.seq, said)


# ---------------------------------------------------------------------------
# the lane ``proc``: what the whole process did meanwhile
# ---------------------------------------------------------------------------

# The beat thread sleeps this long at a time and records NOTHING while
# it wakes on time; a wake later than ``_LATE_NS`` is a ``proc.pause``
# from the time it was due to the time it came, unless the collector's
# passes (``proc.gc``, which hold the interpreter) cover all but
# ``_LATE_NS`` of it: the same seconds are not told twice.
_BEAT_NS = 25_000_000
_LATE_NS = 100_000_000
# a pass of the collector is recorded where it took longer than this, or
# was of the oldest generation
_GC_NS = 10_000_000
_WATCH_THREAD = "paddle-tpu-proc-watch"

# bound here, once: a test that patches ``time.perf_counter_ns`` to count
# a loop's clock reads must not count the beat's or the collector's
_now = time.perf_counter_ns

# What the collector's callback leaves behind. It runs on whichever
# thread trips the collector, at any bytecode: inside ``with _lock:``
# as likely as anywhere (``_flush_locked`` allocates under it). So it
# takes NO lock, not the ring's (``_record`` does, on a thread's first
# event and at every compaction) and not the logger's: a bare append to
# each of two deques that others drain. ``_gc_buf`` holds the finished
# ring events and stands among the threads' buffers for good, so every
# flush carries them over; ``_gc_passes`` holds (start, end, generation,
# collected) of the same passes for the beat, which logs the long ones
# and takes them off a late wake.
_gc_buf: deque = deque()
_buffers.append((lambda: _gc_buf, _gc_buf))
_gc_passes: deque = deque(maxlen=256)
# the pass that is open: its start (0: none is), its annotation
_gc_open: list = [0, None]


def _kernel_reading() -> tuple:
    """Running totals of what the kernel says of the process: (ns of CPU
    on all its threads, major page faults). Two calls and no file, 0.8 us
    here: cheap enough to be taken at every beat, so a pause's args are
    differences over the pause and the beat before it, no more."""
    return (time.process_time_ns(),
            resource.getrusage(resource.RUSAGE_SELF).ru_majflt)


class _Beat:
    """The beat's rule, apart from its thread (a test drives ``woke``
    with a clock and a reading of its own)."""

    __slots__ = ("_clock", "_reading", "due", "_kept")

    def __init__(self, clock=_now, reading=_kernel_reading):
        self._clock, self._reading = clock, reading
        self._kept = reading()
        self.due = clock() + _BEAT_NS

    def woke(self):
        """Called as the thread comes back from each sleep."""
        now, new = self._clock(), self._reading()
        # the passes that ended since the last wake: the only ones that
        # can lie in [due, now) (this thread alone pops)
        in_gc = 0
        while _gc_passes:
            t0, t1, gen, collected = _gc_passes.popleft()
            in_gc += max(0, min(t1, now) - max(t0, self.due))
            if t1 - t0 > STALL_NS:
                _warn("gc", t0,
                      "collector ran %.2f s (generation %d, %d collected)",
                      (t1 - t0) / 1e9, gen, collected)
        # and the pass that is still open: a callback that stands before
        # ours (jax's frees device buffers at every "stop", and lets go
        # of the interpreter for it) lets this thread in before ours has
        # closed the pass
        if _gc_open[0]:
            in_gc += max(0, now - max(_gc_open[0], self.due))
        unexplained = now - self.due - in_gc
        if unexplained > _LATE_NS and tracing_enabled():
            args = {"cpu_ms": (new[0] - self._kept[0]) / 1e6,
                    "majflt": new[1] - self._kept[1]}
            _record("X", "proc.pause", "proc", "proc", threading.get_ident(),
                    self.due, now - self.due, args)
            if unexplained > STALL_NS:
                _warn("pause", self.due,
                      "process paused %.2f s: cpu %.2f s, %d major faults",
                      (now - self.due) / 1e9, args["cpu_ms"] / 1e3,
                      args["majflt"])
        self._kept = new
        self.due = now + _BEAT_NS


def _on_gc(phase: str, info: dict):
    """``gc.callbacks`` entry: times every pass (two clock reads), and
    puts those of an older generation onto the host plane of an active
    profiler session under the name the ring gives them, so that a gap
    of the device can be named ``proc.gc`` as it is named ``engine.*``.
    The collector never runs inside itself: one slot holds the open
    pass. Takes no lock (``_gc_buf``, above)."""
    if phase == "start":
        if info["generation"]:
            _gc_open[1] = _Annotation("proc.gc")
            _gc_open[1].__enter__()
        _gc_open[0] = _now()
        return
    t1 = _now()
    ann, _gc_open[1] = _gc_open[1], None
    if ann is not None:
        ann.__exit__(None, None, None)
    t0, gen, _gc_open[0] = _gc_open[0], info["generation"], 0
    if (t1 - t0 > _GC_NS or gen == 2) and t0 and tracing_enabled():
        _gc_buf.append(("X", "proc.gc", "proc", "proc",
                        threading.get_ident(), t0, t1 - t0,
                        {"gen": gen, "collected": info["collected"]}))
        _gc_passes.append((t0, t1, gen, info["collected"]))


def watch_process():
    """Start watching the process, once: a daemon thread that records a
    ``proc.pause`` whenever it wakes late (``_Beat``) and a
    ``gc.callbacks`` entry that records ``proc.gc`` (``_on_gc``), both
    on the lane ``proc``, whose first event is the instant
    ``proc.watch``: a reader tells by it an empty lane (nothing was
    lost) from a missing one. Idempotent. Called by whoever starts a
    loop worth watching (``ServingEngine.start``, the first
    ``ShardedTrainStep.step``), never at import: a process pays for the
    thread only once it serves or trains. With tracing disabled nothing
    is started and nothing installed."""
    if _watch[0] is not None or not _TRACING[0]:
        return
    with _lock:
        if _watch[0] is not None:
            return
        stop = threading.Event()

        def run():
            beat = _Beat()
            while not stop.wait(_BEAT_NS / 1e9):
                beat.woke()

        thread = threading.Thread(target=run, name=_WATCH_THREAD, daemon=True)
        _watch[0] = (stop, thread, _now())
    gc.callbacks.append(_on_gc)
    # the interpreter's last collections are nobody's lost time
    atexit.register(_unwatch_process)
    instant("proc.watch", "proc", "proc")
    thread.start()


def _unwatch_process():
    """Stop the watch and take the collector's entry out (tests)."""
    with _lock:
        watch, _watch[0] = _watch[0], None
    if watch is None:
        return
    watch[0].set()
    watch[1].join()
    gc.callbacks.remove(_on_gc)
    _gc_open[:] = [0, None]
    _gc_passes.clear()


# ---------------------------------------------------------------------------
# reading + export
# ---------------------------------------------------------------------------


def _to_dict(ev: tuple) -> dict:
    ph, name, cat, trace, tid, ts, dur, args = ev
    out = {"ph": ph, "name": name, "cat": cat, "trace": trace, "tid": tid,
           "ts_ns": ts, "dur_ns": dur}
    if args:
        out["args"] = args
    return out


def events(trace=None, name: Optional[str] = None,
           last: Optional[int] = None) -> List[dict]:
    """All buffered events (ring + live thread buffers), oldest first;
    optionally filtered to one trace id and/or one event name.
    ``last``: only the newest ``last`` events by their place in the
    ring, at a cost that does not grow with the ring (the flight
    recorder's read, under the crashed engine's step lock)."""
    _flush_locked()
    with _lock:
        evs = list(_ring) if last is None else \
            list(itertools.islice(reversed(_ring), int(last)))
    if trace is not None:
        evs = [e for e in evs if e[3] == trace]
    if name is not None:
        evs = [e for e in evs if e[1] == name]
    evs.sort(key=lambda e: e[5])
    return [_to_dict(e) for e in evs]


def clear():
    """Drop every buffered event + the per-name totals (tests)."""
    _flush_locked()
    with _lock:
        _ring.clear()
        _counts.clear()


def span_counts() -> Dict[str, int]:
    """Total events ever recorded per name — NOT bounded by the ring,
    so CI span-count summaries survive long runs."""
    _flush_locked()
    with _lock:
        return dict(_counts)


def summary() -> dict:
    """JSON-ready tracing summary for ``observability.snapshot()`` and
    the run_shards telemetry lane."""
    counts = span_counts()
    with _lock:
        buffered = len(_ring)
    return {
        "enabled": tracing_enabled(),
        "ring_capacity": _RING_CAPACITY,
        "events_buffered": buffered,
        "events_recorded": sum(counts.values()),
        "span_counts": counts,
        "last_flight_dump": _last_dump_path[0],
    }


def chrome_trace(trace=None) -> dict:
    """Render buffered events as Chrome-trace (catapult) JSON: one
    synthetic thread lane per trace id (``request <id>`` /
    ``engine`` / ``untraced``), spans as ``"X"`` complete events in
    microseconds, instants as thread-scoped ``"i"`` events. Loadable in
    chrome://tracing and Perfetto; merge-compatible with the profiler's
    ``export_chrome_tracing`` output (same ``traceEvents`` shape)."""
    evs = events(trace)
    pid = os.getpid()
    lanes: Dict[Any, int] = {}
    out = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": "paddle_tpu trace"}}]

    def lane(tr) -> int:
        if tr not in lanes:
            lanes[tr] = len(lanes)
            if tr is None:
                lname = "untraced"
            elif isinstance(tr, int):
                lname = f"request {tr}"
            else:
                lname = str(tr)
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": lanes[tr], "args": {"name": lname}})
        return lanes[tr]

    for e in evs:
        rec = {"name": e["name"], "cat": e["cat"] or "event", "ph": e["ph"],
               "pid": pid, "tid": lane(e["trace"]),
               "ts": e["ts_ns"] / 1000.0}
        if e["ph"] == "X":
            rec["dur"] = e["dur_ns"] / 1000.0
        else:
            rec["s"] = "t"
        if "args" in e:
            rec["args"] = e["args"]
        out.append(rec)
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def export_chrome_trace(path: str, trace=None) -> str:
    """Write ``chrome_trace()`` JSON to ``path`` (relative paths land
    in the ``PADDLE_TPU_SINK_DIR`` override, like every other sink)."""
    from .exporters import resolve_sink_path

    path = resolve_sink_path(path)
    with open(path, "w") as fh:
        json.dump(chrome_trace(trace), fh)
    return path


def export_jsonl(path: str, trace=None, max_bytes: int = 64 << 20) -> str:
    """Append every buffered event as one JSON line each, through the
    size-rotating sink (``max_bytes``, keep-1)."""
    from .exporters import RotatingJsonlSink

    sink = RotatingJsonlSink(path, max_bytes=max_bytes)
    try:
        for e in events(trace):
            sink.write(e)
    finally:
        sink.close()
    return sink.path


# ---------------------------------------------------------------------------
# streaming percentile digests
# ---------------------------------------------------------------------------


class Digest:
    """Streaming p50/p95/p99: a bounded ring of the most recent
    ``window`` samples (``deque.append`` — the lock-free writer path)
    with exact percentiles computed over the window at read time.
    Within the window this is EXACTLY ``numpy.percentile`` (method
    'linear'); beyond it, a sliding-window quantile — the operational
    behavior a latency dashboard wants anyway (old traffic ages out)."""

    __slots__ = ("_q", "count", "sum")

    def __init__(self, window: int = 4096):
        self._q: deque = deque(maxlen=int(window))
        self.count = 0
        self.sum = 0.0

    def observe(self, value: float):
        self._q.append(value)
        self.count += 1
        self.sum += value

    def quantile(self, q: float) -> Optional[float]:
        xs = sorted(self._q)
        if not xs:
            return None
        # numpy's default 'linear' interpolation
        pos = q * (len(xs) - 1)
        lo = int(pos)
        hi = min(lo + 1, len(xs) - 1)
        return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)

    def percentiles(self) -> dict:
        xs = sorted(self._q)

        def at(q):
            if not xs:
                return None
            pos = q * (len(xs) - 1)
            lo = int(pos)
            hi = min(lo + 1, len(xs) - 1)
            return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)

        return {"p50": at(0.50), "p95": at(0.95), "p99": at(0.99),
                "count": self.count,
                "mean": (self.sum / self.count) if self.count else None}


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

_providers: Dict[str, Any] = {}
_providers_lock = threading.Lock()


def register_state_provider(name: str, fn):
    """Register a zero-arg callable whose return value (a JSON-ready
    dict, or None to be skipped) is captured in every flight dump and
    in ``state_snapshot()``. The serving engine registers a weakref'd
    ``engine.stats`` here, so dumps carry pool/slot/queue state."""
    with _providers_lock:
        _providers[name] = fn


def unregister_state_provider(name: str):
    with _providers_lock:
        _providers.pop(name, None)


def state_snapshot() -> dict:
    """Every registered provider's current state ({} when none). A
    provider that raises contributes its error instead of killing the
    dump — the flight recorder must never be the second crash."""
    with _providers_lock:
        items = list(_providers.items())
    out = {}
    for name, fn in items:
        try:
            state = fn()
        except Exception as e:  # noqa: BLE001 — dump must survive
            state = {"error": repr(e)}
        if state is not None:
            out[name] = state
    return out


def last_flight_dump() -> Optional[str]:
    return _last_dump_path[0]


def flight_dump(reason: str, extra: Optional[dict] = None,
                path: Optional[str] = None, last_n: int = 4096) -> Optional[str]:
    """Write the flight-recorder dump: the last ``last_n`` buffered
    events + every state provider's snapshot + the tracing summary, as
    one JSON file. Returns the path, or None when the write failed
    (logged — a dump failure must never mask the original crash).

    Triggers wired in-tree: serving-engine loop crash,
    ``PoolExhaustedError`` escaping ``ServingEngine.step()``, and the
    fault-tolerance preemption handler's SIGTERM/SIGINT."""
    try:
        from .exporters import SINK_DIR_ENV, resolve_sink_path

        if path is None:
            name = (f"flight_{reason}_{os.getpid()}_"
                    f"{int(time.time() * 1000)}.json")
            if os.environ.get(SINK_DIR_ENV):
                path = resolve_sink_path(name)
            else:
                # never litter the cwd: unconfigured dumps go to tmp
                # (the warning log below carries the path)
                import tempfile

                path = os.path.join(tempfile.gettempdir(), name)
        else:
            path = resolve_sink_path(path)
        rec = {
            "reason": reason,
            "ts": time.time(),
            "pid": os.getpid(),
            "tracing": summary(),
            "events": events(last=last_n),
            "state": state_snapshot(),
        }
        if extra:
            rec["extra"] = extra
        with open(path, "w") as fh:
            json.dump(rec, fh)
        _flight_dumps.labels(reason).inc()
        _last_dump_path[0] = path
        logger.warning("flight recorder dump (%s) -> %s", reason, path)
        return path
    except Exception:  # noqa: BLE001
        logger.exception("flight recorder dump failed (reason=%s)", reason)
        return None


# ---------------------------------------------------------------------------
# interop: profiler RecordEvent spans -> trace events
# ---------------------------------------------------------------------------


def _profiler_sink(name: str, t0_ns: int, t1_ns: int, event_type: int):
    _record("X", name, "profiler", current_trace(), threading.get_ident(),
            t0_ns, max(t1_ns - t0_ns, 0), None)


def attach_profiler_spans():
    """Forward every completed ``profiler.RecordEvent`` span into the
    trace buffer (cat=``profiler``), so one ``/trace`` export carries
    request lifecycle AND step-internal spans on a shared clock.
    Zero-cost when detached (the profiler checks one list index)."""
    from .. import profiler as _prof

    _prof._trace_sink[0] = _profiler_sink


def detach_profiler_spans():
    from .. import profiler as _prof

    _prof._trace_sink[0] = None


# recompile-monitor attribution: compile events land in the active trace
def _on_compile(entry: str, duration_s: float):
    if not tracing_enabled():
        return
    now = time.perf_counter_ns()
    dur = int(duration_s * 1e9)
    _record("X", f"xla_compile:{entry}", "compile", current_trace(),
            threading.get_ident(), now - dur, dur, {"entry": entry})
