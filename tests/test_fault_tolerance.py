"""Fault tolerance: atomic commit protocol, kill-mid-save matrix, async
checkpointer, kill-and-restart bit-identical resume, preemption handler,
loss-spike sentinel, retention GC, dataloader retry, serving crash
handling.

The acceptance tests of ISSUE 4:
- kill-and-restart determinism: a fit run preempted mid-training and
  resumed via ``resume_from`` produces bit-identical final weights to an
  uninterrupted run (``TestKillRestartDeterminism``);
- the injected-failure matrix: a save killed at ANY stage of the commit
  protocol leaves either a committed-and-verifiable checkpoint or an
  ignorable orphan — never a committed-but-corrupt dir
  (``TestKillMidSaveMatrix``).
"""

import json
import os
import pickle
import shutil
import signal

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.checkpoint import (CheckpointCorruptError,
                                               latest_checkpoint,
                                               load_state_dict,
                                               read_state_dict,
                                               save_state_dict,
                                               verify_checkpoint)
from paddle_tpu.distributed.checkpoint.atomic import (COMMITTED_MARKER,
                                                      commit_dir,
                                                      is_committed)
from paddle_tpu.fault_tolerance import (AsyncCheckpointer,
                                        FaultTolerantCheckpoint,
                                        LossSpikeSentinel, clear_preemption,
                                        preemption_requested,
                                        request_preemption)
from paddle_tpu.hapi import Model
from paddle_tpu.io.dataset import Dataset
from paddle_tpu.nn import CrossEntropyLoss


# ---------------------------------------------------------------------------
# shared toys
# ---------------------------------------------------------------------------

class ToyClassification(Dataset):
    def __init__(self, n=64, seed=0):
        rs = np.random.RandomState(seed)
        self.x = rs.randn(n, 8).astype(np.float32)
        w = rs.randn(8)
        self.y = (self.x @ w > 0).astype(np.int64)

    def __getitem__(self, i):
        return self.x[i], self.y[i]

    def __len__(self):
        return len(self.x)


def _prepared_model(opt_cls=None, lr=0.05):
    paddle.seed(42)
    np.random.seed(1234)
    net = paddle.nn.Sequential(
        paddle.nn.Linear(8, 16), paddle.nn.ReLU(), paddle.nn.Linear(16, 2))
    model = Model(net)
    opt_cls = opt_cls or paddle.optimizer.Adam
    opt = opt_cls(learning_rate=lr, parameters=net.parameters())
    model.prepare(opt, CrossEntropyLoss())
    return model


def _weights(model):
    return {k: np.asarray(v._data)
            for k, v in model.network.state_dict().items()}


class KillAtStep(paddle.hapi.callbacks.Callback):
    """Requests preemption after N train steps (programmatic or via a
    real SIGTERM to our own pid)."""

    def __init__(self, at, use_signal=False):
        self.at, self.n, self.use_signal = at, 0, use_signal

    def on_train_batch_end(self, step, logs=None):
        self.n += 1
        if self.n == self.at:
            if self.use_signal:
                os.kill(os.getpid(), signal.SIGTERM)
            else:
                request_preemption()


@pytest.fixture(autouse=True)
def _clear_preemption_flag():
    clear_preemption()
    yield
    clear_preemption()


# ---------------------------------------------------------------------------
# atomic commit protocol
# ---------------------------------------------------------------------------

class TestAtomicProtocol:
    def test_save_commits_with_digests(self, tmp_path):
        path = str(tmp_path / "ck")
        save_state_dict({"w": paddle.to_tensor(np.arange(6., dtype=np.float32))},
                        path)
        assert is_committed(path)
        marker = verify_checkpoint(path, deep=True)
        assert marker["files"] and all(
            len(d) == 64 for d in marker["files"].values())  # sha256 hex
        # nothing but the committed dir remains (no tmp orphans)
        assert sorted(os.listdir(tmp_path)) == ["ck"]

    def test_uncommitted_dir_refused(self, tmp_path):
        path = str(tmp_path / "ck")
        save_state_dict({"w": paddle.to_tensor(np.ones(3, np.float32))}, path)
        os.remove(os.path.join(path, COMMITTED_MARKER))
        t = paddle.to_tensor(np.zeros(3, np.float32))
        with pytest.raises(CheckpointCorruptError, match="never committed"):
            load_state_dict({"w": t}, path)

    def test_truncated_distcp_names_file_and_hint(self, tmp_path):
        path = str(tmp_path / "ck")
        save_state_dict({"w": paddle.to_tensor(np.ones(128, np.float32))}, path)
        distcp = os.path.join(path, "0_0.distcp")
        with open(distcp, "r+b") as f:
            f.truncate(8)  # simulated kill mid-write after a fake commit
        with pytest.raises(CheckpointCorruptError) as ei:
            load_state_dict({"w": paddle.to_tensor(np.zeros(128, np.float32))},
                            path)
        assert "0_0.distcp" in str(ei.value)
        assert "latest_checkpoint" in str(ei.value)

    def test_manifest_process_count_mismatch_hard_errors(self, tmp_path):
        # build a committed dir whose manifest claims 2 ranks but only
        # rank 0's shards exist -> must refuse, not silently merge
        tmp = str(tmp_path / "scratch")
        final = str(tmp_path / "ck")
        os.makedirs(tmp)
        from paddle_tpu.distributed.checkpoint import write_state_dict_files

        write_state_dict_files(
            {"w": paddle.to_tensor(np.ones(4, np.float32))}, tmp)
        with open(os.path.join(tmp, "manifest.pkl"), "wb") as f:
            pickle.dump({"process_count": 2}, f, protocol=4)
        commit_dir(tmp, final)
        with pytest.raises(CheckpointCorruptError, match="process_count=2"):
            read_state_dict(final)

    def test_stale_extra_metadata_hard_errors(self, tmp_path):
        tmp = str(tmp_path / "scratch")
        final = str(tmp_path / "ck")
        os.makedirs(tmp)
        from paddle_tpu.distributed.checkpoint import write_state_dict_files

        write_state_dict_files(
            {"w": paddle.to_tensor(np.ones(4, np.float32))}, tmp)
        with open(os.path.join(tmp, "7.metadata"), "wb") as f:
            f.write(open(os.path.join(tmp, "0.metadata"), "rb").read())
        commit_dir(tmp, final)
        with pytest.raises(CheckpointCorruptError, match="stale"):
            read_state_dict(final)


class TestKillMidSaveMatrix:
    """Inject a failure at every stage of the commit protocol; assert
    latest_checkpoint always resolves the previous good step and no dir
    is ever committed-but-corrupt."""

    def _save_steps(self, root, steps):
        for s in steps:
            save_state_dict(
                {"w": paddle.to_tensor(np.full(8, float(s), np.float32)),
                 "step": s},
                os.path.join(root, f"step_{s:08d}"), extra_marker={"step": s})

    def _assert_no_committed_corrupt(self, root):
        """THE invariant: every dir that claims committed must verify."""
        for name in os.listdir(root):
            p = os.path.join(root, name)
            if os.path.isdir(p) and ".tmp-" not in name \
                    and os.path.exists(os.path.join(p, COMMITTED_MARKER)):
                try:
                    verify_checkpoint(p, deep=True)
                except CheckpointCorruptError:
                    continue  # detected as corrupt == NOT trusted; fine
        # and everything latest_checkpoint returns verifies deeply
        best = latest_checkpoint(root)
        if best is not None:
            verify_checkpoint(best, deep=True)

    def test_pre_rename_tmp_dir_ignored(self, tmp_path):
        root = str(tmp_path)
        self._save_steps(root, [1, 2])
        # kill BEFORE the rename: a half-written tmp dir is all that's left
        tmp = os.path.join(root, "step_00000003.tmp-dead0")
        os.makedirs(tmp)
        with open(os.path.join(tmp, "0_0.distcp"), "wb") as f:
            f.write(b"half a pickle")
        assert latest_checkpoint(root).endswith("step_00000002")
        self._assert_no_committed_corrupt(root)

    def test_missing_committed_marker_skipped(self, tmp_path):
        root = str(tmp_path)
        self._save_steps(root, [1, 2, 3])
        os.remove(os.path.join(root, "step_00000003", COMMITTED_MARKER))
        assert latest_checkpoint(root).endswith("step_00000002")
        self._assert_no_committed_corrupt(root)

    def test_bad_digest_skipped(self, tmp_path):
        root = str(tmp_path)
        self._save_steps(root, [1, 2, 3])
        with open(os.path.join(root, "step_00000003", "0_0.distcp"),
                  "r+b") as f:
            f.truncate(4)
        assert latest_checkpoint(root).endswith("step_00000002")
        self._assert_no_committed_corrupt(root)

    def test_missing_committed_file_skipped(self, tmp_path):
        root = str(tmp_path)
        self._save_steps(root, [1, 2, 3])
        os.remove(os.path.join(root, "step_00000003", "0_0.distcp"))
        assert latest_checkpoint(root).endswith("step_00000002")
        self._assert_no_committed_corrupt(root)

    def test_every_save_corrupt_returns_none(self, tmp_path):
        root = str(tmp_path)
        self._save_steps(root, [1])
        os.remove(os.path.join(root, "step_00000001", COMMITTED_MARKER))
        assert latest_checkpoint(root) is None

    def test_resume_data_from_previous_good_step(self, tmp_path):
        root = str(tmp_path)
        self._save_steps(root, [1, 2, 3])
        with open(os.path.join(root, "step_00000003", "0_0.distcp"),
                  "r+b") as f:
            f.truncate(4)
        best = latest_checkpoint(root)
        sd = read_state_dict(best)
        assert sd["step"] == 2
        np.testing.assert_array_equal(np.asarray(sd["w"]),
                                      np.full(8, 2.0, np.float32))


# ---------------------------------------------------------------------------
# async checkpointer
# ---------------------------------------------------------------------------

class TestAsyncCheckpointer:
    def test_background_commit_and_restore(self, tmp_path):
        ck = AsyncCheckpointer(str(tmp_path))
        state = {"w": paddle.to_tensor(np.arange(12, dtype=np.float32))}
        ck.save(5, state, meta={"global_step": 5})
        ck.wait_until_finished()
        assert is_committed(ck.step_path(5))
        sd, meta = ck.restore()
        assert meta["global_step"] == 5
        np.testing.assert_array_equal(
            np.asarray(sd["w"]), np.arange(12, dtype=np.float32))
        ck.close()

    def test_snapshot_is_immune_to_later_updates(self, tmp_path):
        """The device->host snapshot decouples the save from the live
        training state: mutating the tensor after save() must not leak
        into the checkpoint (CheckFreq's correctness requirement)."""
        ck = AsyncCheckpointer(str(tmp_path))
        t = paddle.to_tensor(np.zeros(64, np.float32))
        ck.save(1, {"w": t}, sync=False)
        t._data = t._data + 999.0  # "the next optimizer step"
        ck.wait_until_finished()
        sd, _ = ck.restore(1)
        np.testing.assert_array_equal(np.asarray(sd["w"]),
                                      np.zeros(64, np.float32))
        ck.close()

    def test_retention_gc(self, tmp_path):
        ck = AsyncCheckpointer(str(tmp_path), max_to_keep=2,
                               keep_every_n_steps=4)
        for s in (1, 2, 3, 4, 5, 6):
            ck.save(s, {"w": paddle.to_tensor(np.full(4, float(s)))},
                    sync=True)
        kept = sorted(n for n in os.listdir(str(tmp_path))
                      if n.startswith("step_"))
        # newest two (5, 6) plus the keep-every-4 step 4
        assert kept == ["step_00000004", "step_00000005", "step_00000006"]
        ck.close()

    def test_background_error_surfaces(self, tmp_path):
        ck = AsyncCheckpointer(str(tmp_path))
        ck.save(1, {"w": object()})  # unpicklable-as-tensor object rides as
        ck.wait_until_finished()     # a python object: fine. Now poison:
        ck._err = RuntimeError("disk on fire")
        with pytest.raises(RuntimeError, match="background checkpoint"):
            ck.save(2, {"w": paddle.to_tensor(np.ones(2))})
        ck.close()


# ---------------------------------------------------------------------------
# kill-and-restart determinism (ISSUE acceptance)
# ---------------------------------------------------------------------------

class TestKillRestartDeterminism:
    def _run_uninterrupted(self, ds):
        m = _prepared_model()
        m.fit(ds, batch_size=16, epochs=3, verbose=0, shuffle=True)
        return _weights(m)

    def test_bit_identical_resume_mid_epoch(self, tmp_path):
        ds = ToyClassification()
        w_ref = self._run_uninterrupted(ds)

        root = str(tmp_path / "ft")
        m1 = _prepared_model()
        ft = FaultTolerantCheckpoint(root, save_freq_steps=3,
                                     install_signal_handlers=False)
        m1.fit(ds, batch_size=16, epochs=3, verbose=0, shuffle=True,
               callbacks=[ft, KillAtStep(6)])
        assert ft.preempted
        assert latest_checkpoint(root) is not None
        # killed run stopped early (3 epochs x 4 steps = 12 total)
        assert ft.global_step < 12

        clear_preemption()
        m2 = _prepared_model()  # fresh init, different param values
        m2.fit(ds, batch_size=16, epochs=3, verbose=0, shuffle=True,
               callbacks=[FaultTolerantCheckpoint(
                   root, save_freq_steps=3, install_signal_handlers=False)],
               resume_from=root)
        w_res = _weights(m2)
        for k in w_ref:
            np.testing.assert_array_equal(w_ref[k], w_res[k]), k

    def test_resume_skips_corrupt_newest(self, tmp_path):
        ds = ToyClassification()
        root = str(tmp_path / "ft")
        m1 = _prepared_model()
        m1.fit(ds, batch_size=16, epochs=2, verbose=0, shuffle=True,
               callbacks=[FaultTolerantCheckpoint(
                   root, save_freq_steps=2, install_signal_handlers=False)])
        saves = sorted(n for n in os.listdir(root) if n.startswith("step_"))
        assert len(saves) >= 2
        # corrupt the newest committed save; resume must fall back
        with open(os.path.join(root, saves[-1], "0_0.distcp"), "r+b") as f:
            f.truncate(4)
        m2 = _prepared_model()
        m2.fit(ds, batch_size=16, epochs=2, verbose=0, shuffle=True,
               resume_from=root)
        assert all(np.isfinite(v).all() for v in _weights(m2).values())

    def test_sigterm_preempts_and_saves(self, tmp_path):
        ds = ToyClassification()
        root = str(tmp_path / "ft")
        m = _prepared_model()
        ft = FaultTolerantCheckpoint(root, save_freq_steps=None,
                                     save_on_train_end=False)
        m.fit(ds, batch_size=16, epochs=4, verbose=0, shuffle=False,
              callbacks=[ft, KillAtStep(3, use_signal=True)])
        assert ft.preempted
        best = latest_checkpoint(root)
        assert best is not None
        from paddle_tpu.fault_tolerance import load_train_state

        _, meta = load_train_state(best)
        assert meta["global_step"] == 4  # signal lands at 3, seen at 4


# ---------------------------------------------------------------------------
# loss-spike sentinel
# ---------------------------------------------------------------------------

class TestLossSpikeSentinel:
    def _warm(self, s, n=20, level=1.0):
        for _ in range(n):
            assert s._update_filter([level + np.random.uniform(-0.01, 0.01)])

    def test_nan_inf_and_spike_detection(self):
        np.random.seed(0)
        s = LossSpikeSentinel(k=6.0, warmup_steps=8, verbose=0)
        self._warm(s)
        assert not s._update_filter([float("nan")])   # skip
        assert not s._update_filter([float("inf")])   # skip
        assert not s._update_filter([1e6])            # k-sigma spike: skip
        assert s._update_filter([1.0])                # recovery: apply
        assert s.skipped == 3

    def test_skip_budget_exhausts(self):
        np.random.seed(0)
        s = LossSpikeSentinel(k=6.0, warmup_steps=8, max_skips=2,
                              rollback_after=99, verbose=0)
        self._warm(s)
        assert not s._update_filter([1e6])
        assert not s._update_filter([1e6])
        assert s._update_filter([1e6])  # budget spent, no rollback target

    def test_model_integration_skips_poisoned_update(self):
        """A poisoned batch (Inf activations -> non-finite loss) must
        leave the weights untouched."""
        ds = ToyClassification()
        m = _prepared_model()
        sent = LossSpikeSentinel(warmup_steps=4, verbose=0)
        m.fit(ds, batch_size=16, epochs=1, verbose=0, shuffle=False,
              callbacks=[sent])  # fit wires sentinel via set_model
        w_before = _weights(m)
        bad_x = np.full((16, 8), np.inf, np.float32)
        m.train_batch([bad_x], [ds.y[:16]])
        w_after = _weights(m)
        for k in w_before:
            np.testing.assert_array_equal(w_before[k], w_after[k])
        assert sent.skipped >= 1

    def test_rollback_restores_checkpoint(self, tmp_path):
        ds = ToyClassification()
        root = str(tmp_path / "ft")
        m = _prepared_model()
        ft = FaultTolerantCheckpoint(root, save_freq_steps=2,
                                     install_signal_handlers=False)
        m.fit(ds, batch_size=16, epochs=2, verbose=0, shuffle=False,
              callbacks=[ft])
        best = latest_checkpoint(root)
        w_ckpt = {k: np.asarray(v) for k, v in
                  read_state_dict(best)["model"].items()}

        sent = LossSpikeSentinel(warmup_steps=4, max_skips=1,
                                 rollback_after=2, checkpoint_dir=root,
                                 verbose=0)
        sent.set_model(m)
        sent.on_train_begin()
        for _ in range(8):
            sent._update_filter([0.5])
        # wreck the weights, then two consecutive bad steps -> rollback
        for p in m.network.parameters():
            p._data = p._data * 0 + 123.0
        assert not sent._update_filter([float("nan")])
        assert not sent._update_filter([float("nan")])
        assert sent.rollbacks == 1
        w_now = _weights(m)
        for k in w_ckpt:
            np.testing.assert_array_equal(w_ckpt[k], w_now[k])


# ---------------------------------------------------------------------------
# hapi ModelCheckpoint retention
# ---------------------------------------------------------------------------

def test_model_checkpoint_max_to_keep(tmp_path):
    from paddle_tpu.hapi import ModelCheckpoint

    ds = ToyClassification()
    m = _prepared_model()
    m.fit(ds, batch_size=16, epochs=5, verbose=0, shuffle=False,
          callbacks=[ModelCheckpoint(save_freq=1, save_dir=str(tmp_path),
                                     max_to_keep=2)])
    saved = sorted(f for f in os.listdir(tmp_path) if f.endswith(".pdparams"))
    assert saved == ["3.pdparams", "4.pdparams", "final.pdparams"]


# ---------------------------------------------------------------------------
# dataloader retry
# ---------------------------------------------------------------------------

class TestDataloaderRetry:
    class Flaky(Dataset):
        def __init__(self, fail):
            self.fail = dict(fail)

        def __getitem__(self, i):
            if self.fail.get(i, 0) > 0:
                self.fail[i] -= 1
                raise IOError(f"transient read error idx {i}")
            return np.float32(i)

        def __len__(self):
            return 8

    def test_transient_failures_retried_and_counted(self):
        from paddle_tpu.io.dataloader import DataLoader, retries_total

        base = retries_total.value()
        loader = DataLoader(self.Flaky({2: 2, 5: 1}), batch_size=4,
                            retry_backoff_s=0.001)
        batches = [np.asarray(b.numpy()) for b in loader]
        np.testing.assert_array_equal(np.concatenate(batches),
                                      np.arange(8, dtype=np.float32))
        assert retries_total.value() - base == 3

    def test_exhaustion_reraises_original(self):
        from paddle_tpu.io.dataloader import DataLoader

        loader = DataLoader(self.Flaky({1: 99}), batch_size=4,
                            retry_attempts=3, retry_backoff_s=0.001)
        with pytest.raises(IOError, match="idx 1"):
            list(loader)


# ---------------------------------------------------------------------------
# serving engine loop crash handling
# ---------------------------------------------------------------------------

class TestServingEngineCrash:
    def _bare_engine(self):
        """An engine skeleton (no model, no jit): exactly the state
        _on_loop_crash touches."""
        from paddle_tpu.serving.engine import ServingConfig, ServingEngine
        from paddle_tpu.serving.scheduler import Scheduler
        import threading

        eng = object.__new__(ServingEngine)
        eng.config = ServingConfig(max_slots=2, max_len=32)
        eng.scheduler = Scheduler(8)
        # an empty pool and tables: what freeing a slot gives back
        from paddle_tpu.serving.block_pool import BlockPool

        eng.pool = BlockPool(3, 16)
        eng._jobs = [None, None]
        eng._slot_blocks = [[], []]
        eng._bt = np.zeros((2, 2), np.int32)
        eng._slot_len = [0, 0]
        eng._slot_win = [0, 0]
        # nothing in flight: no step ahead, no first token parked
        eng._slot_due = [0, 0]
        eng._ahead = None
        eng._parked_tokens = []
        eng._slot_req = [None, None]
        eng._slot_sampling = [False, False]
        eng._decoding = [False, False]
        eng._outcomes = {}
        eng._step_lock = threading.RLock()
        eng._wake = threading.Condition()
        eng._running = True
        eng._thread = None
        eng._crashed = None
        eng._crash_hook = None  # unsupervised: crash fails everything
        eng._steps = 0
        eng._occupancy_integral = 0
        # round-8 observability state: the /debug/requests recent ring +
        # goodput window (_free_slot touches both on the crash path)
        from collections import deque

        eng._recent = deque(maxlen=256)
        eng._goodput_window = deque()
        eng._goodput_span_s = 30.0
        return eng

    def test_crash_fails_running_and_queued(self):
        from paddle_tpu.serving.request import (Request, RequestStatus,
                                                SamplingParams)
        from paddle_tpu.serving import metrics as sm

        eng = self._bare_engine()
        running = Request(np.array([1, 2], np.int32), SamplingParams())
        running.status = RequestStatus.RUNNING
        eng._slot_req[0] = running
        queued = eng.scheduler
        q1 = Request(np.array([3], np.int32), SamplingParams())
        q2 = Request(np.array([4], np.int32), SamplingParams())
        queued.submit(q1)
        queued.submit(q2)

        base = sm.engine_crashes_total.value()
        try:
            eng._on_loop_crash(RuntimeError("pool program corrupted"))

            # result() returns instead of hanging; status FAILED + error
            for r in (running, q1, q2):
                r.result(timeout=1.0)
                assert r.status == RequestStatus.FAILED
                assert "pool program corrupted" in r.error
            assert not eng.healthy and "pool program corrupted" in eng.crashed
            assert not eng._running
            assert sm.engine_crashes_total.value() - base == 1
            assert sm.engine_unhealthy.value() == 1  # healthz 503 driver
            with pytest.raises(RuntimeError, match="crashed"):
                eng.submit([1, 2, 3])
        finally:
            # a fresh ServingEngine.__init__ does this in real life
            sm.engine_unhealthy.set(0)

    def test_serve_loop_routes_crash(self):
        from paddle_tpu.serving import metrics as sm

        eng = self._bare_engine()

        def boom():
            raise RuntimeError("decode step exploded")

        eng.step = boom
        try:
            eng._serve_loop()  # must return (not raise), flipping health
            assert not eng.healthy
            assert "decode step exploded" in eng.crashed
        finally:
            sm.engine_unhealthy.set(0)


# ---------------------------------------------------------------------------
# optimizer state restore into a fresh instance (any accumulator names)
# ---------------------------------------------------------------------------

def test_optimizer_restore_infers_accumulator_names():
    paddle.seed(7)
    net = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.RMSProp(learning_rate=0.01, momentum=0.9,
                                   parameters=net.parameters())
    x = paddle.to_tensor(np.random.RandomState(0).randn(8, 4).astype("float32"))
    loss = net(x).square().mean()
    loss.backward()
    opt.step()
    state = opt.state_dict()
    assert any("mean_square" in k for k in state)

    opt2 = paddle.optimizer.RMSProp(learning_rate=0.01, momentum=0.9,
                                    parameters=net.parameters())
    opt2.set_state_dict(state)  # fresh instance: no accumulators created yet
    assert opt2._step_count == 1
    for name in ("mean_square", "mean_grad", "velocity"):
        assert opt2._accumulators.get(name), name
        for key, v in opt._accumulators[name].items():
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(opt2._accumulators[name][key]))


def test_preemption_request_roundtrip():
    assert not preemption_requested()
    request_preemption()
    assert preemption_requested()
    clear_preemption()
    assert not preemption_requested()
