"""The reduction from a profiler trace to numbers, on a small trace
recorded on a TPU v5e (three decode steps of a toy engine and three
toy training steps with the flash kernels) and on synthetic planes."""

import os

import pytest

from perfbench import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data", "small_v5e.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    return tr.load(TRACE)


def test_the_recorded_trace_has_one_tpu_plane_and_host_spans(planes):
    assert [p for p in planes if p.startswith("/device:TPU:")] \
        == ["/device:TPU:0"]
    assert len(planes["/device:TPU:0"]["XLA Ops"]) == 93
    assert len(planes["/device:TPU:0"]["XLA Modules"]) == 9
    assert len(planes["/host:CPU"]["host"]) > 4000


def test_reduction_of_the_recorded_trace(planes):
    red = tr.reduce(planes)
    assert red["n_devices"] == 1
    assert red["module_calls"] == {"jit__step": 3, "jit_loss": 3,
                                   "jit__reduce_sum": 3}
    assert red["busy_s"] == pytest.approx(178.114e-6, rel=1e-3)
    # operations on one line do not overlap: they add up to busy time
    assert sum(red["op_s"].values()) == pytest.approx(red["busy_s"], rel=1e-6)
    assert red["op_s"]["_step_custom-call"] == pytest.approx(75.76e-6, rel=1e-3)
    assert red["device_ops"][0][0] == "_step_custom-call"
    assert red["collective_s"] == 0 and red["collective_exposed_s"] == 0
    assert len(red["idle_gaps"]) <= 10
    assert red["idle_gaps"][0][1] == pytest.approx(0.0989, rel=1e-2)
    assert red["idle_gaps"][0][0].startswith("XLA::TPU")  # a compiler pass


def test_module_time_is_at_least_its_operations(planes):
    red = tr.reduce(planes)
    assert sum(red["module_s"].values()) >= red["busy_s"]


@pytest.mark.parametrize("name,key", [
    ("%multiply_reduce_fusion.12 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop",
     "multiply_reduce_fusion"),
    ("%_step.1 = (f32[4,16]) custom-call(s32[4] %a), custom_call_target="
     "\"tpu_custom_call\"", "_step_custom-call"),
    ("%all-reduce.3 = bf16[8]{0} all-reduce(bf16[8]{0} %x)", "all-reduce"),
    ("%copy = bf16[4]{0} copy(bf16[4]{0} %b)", "copy"),
    ("%fusion = f32[2]{0} fusion(f32[2]{0} %c)", "fusion"),
])
def test_operation_names(name, key):
    assert tr.op_key(name) == key


def test_program_names_lose_their_hash():
    assert tr.module_key("jit__step(6509165654319569684)") == "jit__step"
    assert tr.module_key("jit_step") == "jit_step"


def test_union_and_subtract():
    merged = tr.union([(0, 4), (2, 6), (10, 12)])
    assert merged == [[0, 6], [10, 12]] and tr.length(merged) == 8
    assert tr.subtract([[0, 10]], [[2, 3], [5, 7]]) == 7
    assert tr.subtract([[0, 10], [20, 30]], [[8, 25]]) == 13
    assert tr.subtract([[0, 10]], []) == 10


def test_collective_time_with_no_compute_running():
    """Two devices; on each an all-reduce of 4 us of which 1 us is
    covered by a fusion on the same device."""
    def dev():
        return {"XLA Ops": [("%fusion.1 = f32[] fusion()", 0.0, 5000.0),
                            ("%all-reduce.2 = f32[] all-reduce()", 4000.0,
                             4000.0),
                            ("%fusion.3 = f32[] fusion()", 10000.0, 2000.0)],
                "XLA Modules": [("jit_step(1)", 0.0, 12000.0)]}

    red = tr.reduce({"/device:TPU:0": dev(), "/device:TPU:1": dev(),
                     "/host:CPU": {"host": [("loop", 0.0, 20000.0),
                                            ("np.asarray", 8100.0, 1800.0)]}})
    assert red["n_devices"] == 2
    assert red["busy_s"] == pytest.approx(10e-6)     # averaged over devices
    assert red["collective_s"] == pytest.approx(4e-6)
    assert red["collective_exposed_s"] == pytest.approx(3e-6)
    assert red["module_calls"] == {"jit_step": 1}
    assert red["idle_gaps"][0] == ["np.asarray", pytest.approx(2e-6)]


def test_a_trace_with_no_device_plane_reduces_to_nothing_busy():
    red = tr.reduce({"/host:CPU": {"host": [("x", 0.0, 1.0)]}})
    assert red["n_devices"] == 0 and red["busy_s"] == 0
    assert red["device_ops"] == [] and red["idle_gaps"] == []


def test_a_gap_no_span_covers_is_named_by_the_call_it_led_up_to():
    dev = {"XLA Ops": [("%fusion.1 = f32[] fusion()", 0.0, 1000.0),
                       ("%fusion.2 = f32[] fusion()", 9000.0, 1000.0)],
           "XLA Modules": []}
    host = {"host": [("PjitFunction(_step)", 100.0, 300.0),
                     ("D2H Dispatch", 8000.0, 500.0)]}
    red = tr.reduce({"/device:TPU:0": dev, "/host:CPU": host})
    assert red["idle_gaps"] == [["python_before_D2H_Dispatch",
                                 pytest.approx(8e-6)]]


# -- busy time and the window it is divided by: one stretch, one clock -------


def _marks(lo, hi):
    return [(tr.WINDOW_OPEN, lo, 50.0), (tr.WINDOW_SHUT, hi, 50.0)]


def test_a_device_that_never_idles_is_busy_for_the_window_and_no_more():
    """The trace reaches further than the window on both sides (it
    holds what ran while the profiler started and stopped); operations
    back to back, one overhanging each end: busy time is the window's
    length, never above, and the idle share is 0."""
    from perfbench.readers import device_idle_share

    ops = [(f"%fusion.{k} = f32[] fusion()", 1000.0 * k, 1000.0)
           for k in range(-3, 14)]               # -3 us .. 14 us
    ops.append(("%_step.1 = f32[] custom-call()", 9500.0, 1000.0))
    dev = {"XLA Ops": ops,
           "XLA Modules": [("jit__step(1)", -3000.0, 3500.0),   # overhangs
                           ("jit__step(1)", 500.0, 4000.0),
                           ("jit__step(1)", 4500.0, 5000.0),
                           ("jit__step(1)", 9500.0, 4500.0)]}   # overhangs
    host = {"host": [("loop", -5000.0, 25000.0)] + _marks(500.0, 10500.0)}
    red = tr.reduce({"/device:TPU:0": dev, "/host:CPU": host})
    assert red["window_marked"] is True
    assert red["window_s"] == pytest.approx(10e-6)
    assert red["busy_s"] == red["window_s"]
    assert device_idle_share.read({"trace": red}) == 0.0
    # what PR 34 was refused on: the whole trace's busy time is longer
    assert red["busy_whole_trace_s"] == pytest.approx(17e-6)
    # an operation that overhangs counts with its part inside, so the
    # operations still add up to busy time (the kernel ran beside one)
    assert sum(red["op_s"].values()) == pytest.approx(11e-6)
    assert red["op_s"]["_step_custom-call"] == pytest.approx(1e-6)
    # a program counts only where it ran whole: two of four, 4.5 us each
    assert red["module_calls"] == {"jit__step": 2}
    assert red["module_s"]["jit__step"] == pytest.approx(9e-6)
    assert red["idle_gaps"] == []


def test_idle_time_is_read_inside_the_marks_alone():
    """Busy 2 of the 8 us between the marks; what ran before the window
    opened and after it shut is in the trace and counts for nothing."""
    dev = {"XLA Ops": [("%fusion.1 = f32[] fusion()", 0.0, 4000.0),
                       ("%fusion.2 = f32[] fusion()", 6000.0, 1000.0),
                       ("%fusion.3 = f32[] fusion()", 11000.0, 9000.0)],
           "XLA Modules": []}
    host = {"host": _marks(3000.0, 11000.0)}
    red = tr.reduce({"/device:TPU:0": dev, "/host:CPU": host})
    assert red["window_s"] == pytest.approx(8e-6)
    assert red["busy_s"] == pytest.approx(2e-6)
    # the one gap between two operations, from 4 us to 6 us
    assert [g for _, g in red["idle_gaps"]] == [pytest.approx(2e-6)]


def test_a_trace_without_marks_is_taken_from_first_event_to_last(planes):
    red = tr.reduce(planes)
    assert red["window_marked"] is False
    lo, hi, _ = tr.window(planes)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    every = [(s, s + d) for lines in planes.values()
             for line in lines.values() for _, s, d in line]
    assert lo == min(s for s, _ in every) and hi == max(e for _, e in every)


def test_marks_out_of_order_or_alone_are_no_marks():
    dev = {"XLA Ops": [("%fusion.1 = f32[] fusion()", 0.0, 4000.0)],
           "XLA Modules": []}
    for host in ([(tr.WINDOW_OPEN, 1000.0, 10.0)],
                 _marks(3000.0, 1000.0)):
        red = tr.reduce({"/device:TPU:0": dev, "/host:CPU": {"host": host}})
        assert red["window_marked"] is False
        assert red["busy_s"] == pytest.approx(4e-6)
        assert red["busy_s"] <= red["window_s"]


def test_the_tracer_reads_its_clock_inside_the_marks(tmp_path, monkeypatch):
    """``run.Tracer`` on the CPU: the marks are on the trace's host
    plane, and the stretch between them is the host clock's to a tenth
    of a millisecond."""
    import time

    from perfbench import run

    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    tracer = run.Tracer(True, 0.0, 0.2)
    tracer.between_steps(0.0)
    time.sleep(0.25)
    tracer.between_steps(0.25)
    red = tracer.finish()
    assert red["window_marked"] is True
    t0, t1 = red["host_window"]
    assert red["window_s"] == pytest.approx(t1 - t0, abs=1e-4)
    assert red["busy_s"] <= red["window_s"]
