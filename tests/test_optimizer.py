"""Optimizer tests (reference: test/legacy_test/test_sgd_op.py,
test_adam_op.py, test_adamw_op.py — update-rule parity vs numpy)."""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.optimizer import SGD, Adam, AdamW, Lamb, Momentum, RMSProp, lr


def make_param(val):
    p = paddle.Parameter(np.asarray(val, np.float32))
    return p


def set_grad(p, g):
    p.grad = paddle.to_tensor(np.asarray(g, np.float32))


def test_sgd_update_rule():
    p = make_param([1.0, 2.0])
    opt = SGD(learning_rate=0.1, parameters=[p])
    set_grad(p, [0.5, 1.0])
    opt.step()
    np.testing.assert_allclose(p.numpy(), [0.95, 1.9], rtol=1e-6)


def test_momentum_update_rule():
    p = make_param([1.0])
    opt = Momentum(learning_rate=0.1, momentum=0.9, parameters=[p])
    set_grad(p, [1.0])
    opt.step()
    np.testing.assert_allclose(p.numpy(), [0.9], rtol=1e-6)
    set_grad(p, [1.0])
    opt.step()
    # v = 0.9*1 + 1 = 1.9; p = 0.9 - 0.19
    np.testing.assert_allclose(p.numpy(), [0.71], rtol=1e-6)


def test_adam_update_rule():
    p = make_param([1.0])
    opt = Adam(learning_rate=0.1, beta1=0.9, beta2=0.999, epsilon=1e-8, parameters=[p])
    g = 0.5
    m = v = 0.0
    ref = 1.0
    for t in range(1, 4):
        set_grad(p, [g])
        opt.step()
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1 - 0.9**t)
        vh = v / (1 - 0.999**t)
        ref -= 0.1 * mh / (np.sqrt(vh) + 1e-8)
        np.testing.assert_allclose(p.numpy(), [ref], rtol=1e-5)


def test_adamw_decoupled_decay():
    p = make_param([1.0])
    opt = AdamW(learning_rate=0.1, weight_decay=0.1, parameters=[p])
    set_grad(p, [0.0])
    opt.step()
    # zero grad: m=v=0 → no adam term; only decay 1*(1-0.1*0.1)
    np.testing.assert_allclose(p.numpy(), [0.99], rtol=1e-6)


def test_adamw_decay_filter():
    p1 = make_param([1.0])
    p1.name = "w"
    p2 = make_param([1.0])
    p2.name = "bn_scale"
    opt = AdamW(learning_rate=0.1, weight_decay=0.1, parameters=[p1, p2],
                apply_decay_param_fun=lambda n: n == "w")
    set_grad(p1, [0.0])
    set_grad(p2, [0.0])
    opt.step()
    np.testing.assert_allclose(p1.numpy(), [0.99], rtol=1e-6)
    np.testing.assert_allclose(p2.numpy(), [1.0], rtol=1e-6)


def test_weight_decay_coupled_sgd():
    p = make_param([1.0])
    opt = SGD(learning_rate=0.1, weight_decay=0.1, parameters=[p])
    set_grad(p, [0.0])
    opt.step()
    np.testing.assert_allclose(p.numpy(), [0.99], rtol=1e-6)  # g + wd*p = 0.1


def test_state_dict_roundtrip():
    p = make_param([1.0, 2.0])
    p.name = "p0"
    opt = Adam(learning_rate=0.1, parameters=[p])
    set_grad(p, [0.1, 0.2])
    opt.step()
    state = opt.state_dict()
    p2 = make_param([1.0, 2.0])
    p2.name = "p0"
    opt2 = Adam(learning_rate=0.1, parameters=[p2])
    opt2.set_state_dict(state)
    assert opt2._step_count == 1
    set_grad(p, [0.1, 0.2])
    set_grad(p2, [0.1, 0.2])
    opt.step()
    opt2.step()
    # same moments → same next update from the same start? p differs (one step ahead)
    np.testing.assert_allclose(
        np.asarray(opt._accumulators["moment1"][id(p)]),
        np.asarray(opt2._accumulators["moment1"][id(p2)]), rtol=1e-6)


def test_grad_clip_integration():
    p = make_param([1.0])
    opt = SGD(learning_rate=1.0, parameters=[p], grad_clip=nn.ClipGradByGlobalNorm(0.5))
    set_grad(p, [2.0])
    opt.step()
    np.testing.assert_allclose(p.numpy(), [0.5], rtol=1e-5)  # clipped grad 0.5


def test_lr_scheduler_basic():
    sched = lr.StepDecay(learning_rate=0.1, step_size=2, gamma=0.5)
    p = make_param([1.0])
    opt = SGD(learning_rate=sched, parameters=[p])
    lrs = []
    for _ in range(5):
        lrs.append(opt.get_lr())
        sched.step()
    np.testing.assert_allclose(lrs, [0.1, 0.1, 0.05, 0.05, 0.025], rtol=1e-6)


def test_warmup_cosine():
    base = lr.CosineAnnealingDecay(learning_rate=1.0, T_max=10)
    sched = lr.LinearWarmup(base, warmup_steps=5, start_lr=0.0, end_lr=1.0)
    vals = []
    for _ in range(8):
        vals.append(sched())
        sched.step()
    assert vals[0] == 0.0
    np.testing.assert_allclose(vals[1], 0.2, rtol=1e-6)
    assert vals[5] <= 1.0 and vals[7] < vals[5]  # decaying after warmup


def test_set_lr():
    p = make_param([1.0])
    opt = SGD(learning_rate=0.1, parameters=[p])
    opt.set_lr(0.5)
    assert opt.get_lr() == 0.5


def test_minimize():
    p = make_param([2.0])
    p.stop_gradient = False
    opt = SGD(learning_rate=0.1, parameters=[p])
    loss = (p * p).sum()
    opt.minimize(loss)
    np.testing.assert_allclose(p.numpy(), [1.6], rtol=1e-6)  # 2 - 0.1*4


def test_bf16_param_fp32_state():
    p = paddle.Parameter(np.asarray([1.0], np.float32))
    p._data = p._data.astype(paddle.bfloat16)
    opt = Adam(learning_rate=0.01, parameters=[p])
    set_grad(p, [0.5])
    opt.step()
    assert str(p.dtype) == "bfloat16"
    m = opt._accumulators["moment1"][id(p)]
    assert str(m.dtype) == "float32"


class TestNewOptimizers:
    """Rprop/ASGD/NAdam/RAdam/Lars/LBFGS: descent oracle on a quadratic
    (pattern: reference per-optimizer op tests + convergence checks)."""

    def _quadratic_steps(self, opt_factory, steps=30, closure_based=False):
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu import nn

        paddle.seed(0)
        lin = nn.Linear(4, 1)
        opt = opt_factory(lin.parameters())
        rng = np.random.RandomState(0)
        X = paddle.to_tensor(rng.randn(32, 4).astype("float32"))
        yt = paddle.to_tensor((rng.randn(32, 1) * 0.1 + 1.0).astype("float32"))
        losses = []

        def closure():
            opt.clear_grad()
            loss = ((lin(X) - yt) ** 2).mean()
            loss.backward()
            return loss

        for _ in range(steps):
            if closure_based:
                loss = opt.step(closure)
            else:
                loss = closure()
                opt.step()
            losses.append(float(loss.numpy()))
        return losses

    def test_rprop_descends(self):
        import paddle_tpu as paddle

        losses = self._quadratic_steps(
            lambda ps: paddle.optimizer.Rprop(learning_rate=0.01, parameters=ps))
        assert losses[-1] < losses[0] * 0.5

    def test_asgd_descends_and_averages(self):
        import paddle_tpu as paddle

        losses = self._quadratic_steps(
            lambda ps: paddle.optimizer.ASGD(learning_rate=0.05, batch_num=5, parameters=ps))
        assert losses[-1] < losses[0] * 0.3

    def test_nadam_descends(self):
        import paddle_tpu as paddle

        losses = self._quadratic_steps(
            lambda ps: paddle.optimizer.NAdam(learning_rate=0.05, parameters=ps))
        assert losses[-1] < losses[0] * 0.3

    def test_radam_descends(self):
        import paddle_tpu as paddle

        losses = self._quadratic_steps(
            lambda ps: paddle.optimizer.RAdam(learning_rate=0.05, parameters=ps))
        assert losses[-1] < losses[0] * 0.3

    def test_lars_descends(self):
        import paddle_tpu as paddle

        # LARS's trust ratio (coeff * |p|/|g|) makes steps tiny on toy
        # problems; assert steady descent rather than a large drop
        losses = self._quadratic_steps(
            lambda ps: paddle.optimizer.Lars(learning_rate=0.1, parameters=ps))
        assert losses[-1] < losses[0] * 0.95

    def test_lbfgs_converges_fast(self):
        import paddle_tpu as paddle

        losses = self._quadratic_steps(
            lambda ps: paddle.optimizer.LBFGS(learning_rate=0.5, history_size=10,
                                              line_search_fn="strong_wolfe", parameters=ps),
            steps=15, closure_based=True)
        assert losses[-1] < losses[0] * 0.05  # quadratic: LBFGS should crush it
