"""A linear's weight gradient made from factors written once (PR 41):
``nn.functional._matmul_factors_once`` against plain ``matmul``, the
train step that routes its linears through it against the step that
does not, the rule that decides which of the two a step is, and
``linear`` outside a tracing step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn
from paddle_tpu.core.autograd import no_grad
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.engine import ShardedTrainStep
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                               llama_pretrain_loss)
from paddle_tpu.nn import functional as F
from paddle_tpu.observability import tracing

DENSE_FACTS = {"fused_leaves": 0, "fused_param_share": 0.0}


# ---------------------------------------------------------------------------
# the matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("lead", [(), (24,), (2, 12), (2, 3, 4)])
def test_its_gradients_are_matmuls(lead, dtype):
    rng = np.random.RandomState(len(lead))
    a = jnp.asarray(rng.randn(*lead, 16), dtype)
    w = jnp.asarray(rng.randn(16, 8), dtype)
    c = jnp.asarray(rng.randn(*lead, 8), dtype)

    def loss(mm):
        return lambda a, w: (mm(a, w) * c).astype(jnp.float32).sum()

    want_out = jnp.matmul(a, w)
    got_out = F._matmul_factors_once(a, w)
    assert got_out.dtype == want_out.dtype
    np.testing.assert_array_equal(got_out, want_out)
    want = jax.grad(loss(jnp.matmul), argnums=(0, 1))(a, w)
    got = jax.grad(loss(F._matmul_factors_once), argnums=(0, 1))(a, w)
    for g, x in zip(got, want):
        assert g.shape == x.shape and g.dtype == x.dtype
    # the input's gradient is the same contraction; the weight's runs
    # over one flattened row axis where autodiff's runs over ``lead``
    np.testing.assert_array_equal(got[0], want[0])
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16" else dict(
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1].astype(jnp.float32),
                               want[1].astype(jnp.float32), **tol)


def test_the_barrier_is_on_the_weight_gradients_operands_alone():
    a, w = jnp.ones((2, 12, 16)), jnp.ones((16, 8))

    def loss(a, w):
        return F._matmul_factors_once(a, w).sum()

    fwd = str(jax.make_jaxpr(F._matmul_factors_once)(a, w))
    assert "optimization_barrier" not in fwd
    dw = str(jax.make_jaxpr(jax.grad(loss, argnums=1))(a, w))
    assert dw.count("optimization_barrier") == 1
    # the barrier holds the [24, 16] rows and the [24, 8] cotangents
    assert "f32[24,16]" in dw and "f32[24,8]" in dw


# ---------------------------------------------------------------------------
# the train step
# ---------------------------------------------------------------------------

ONE_CHIP = ([1, 1], ["dp", "mp"])


def llama_step(dtype="float32", mesh=ONE_CHIP, optimizer="AdamW", tie=False,
               shard=None, **kw):
    paddle.seed(0)
    cfg = LlamaConfig.tiny(num_key_value_heads=2, tie_word_embeddings=tie)
    model = LlamaForCausalLM(cfg)
    for p in model.parameters():
        p._data = p._data.astype(dtype)
    shape, names = mesh
    mesh = dist.ProcessMesh(np.arange(int(np.prod(shape))).reshape(shape),
                            names)
    if shard:
        from paddle_tpu.models import llama_shard_fn

        dist.shard_layer(model, mesh, llama_shard_fn(mesh, mp_axis=shard))
    if optimizer is None:
        opt = None
    else:
        opt = getattr(paddle.optimizer, optimizer)(
            learning_rate=1e-3, parameters=model.parameters(),
            **({"weight_decay": 0.1} if optimizer == "AdamW" else {}))
    kw.setdefault("dp_axis", None)      # as the benchmark's one-chip trainer
    return cfg, ShardedTrainStep(model, llama_pretrain_loss, opt, mesh, **kw)


def batch(cfg, rng):
    return paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (2, 64)).astype(np.int32))


def run(cfg, step, n):
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(n):
        ids = batch(cfg, rng)
        losses.append(float(step.step(ids, ids)))
    return losses


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_routed_step_is_the_plain_step(dtype):
    cfg, fact = llama_step(dtype)
    # a norm that never clips: the plain step, by what the rule observes
    _, dense = llama_step(dtype, grad_clip_norm=1e9)
    got, want = run(cfg, fact, 5), run(cfg, dense, 5)
    # every matrix but the embedding table: 7 a layer and lm_head
    assert fact._fused["fused_leaves"] == 15
    assert 0.8 < fact._fused["fused_param_share"] < 0.9
    assert dense._fused == DENSE_FACTS

    def tol(want):      # bfloat16: a few of its ulps at the leaf's scale
        if dtype == "float32":
            return dict(rtol=1e-5, atol=1e-7)
        return dict(rtol=0, atol=0.05 * float(jnp.abs(want).max()))

    # in bfloat16 a gradient may round to the neighbouring value (the
    # contraction runs over one flattened token axis, autodiff's over two)
    np.testing.assert_allclose(got, want,
                               rtol=2e-4 if dtype == "bfloat16" else 1e-5)
    assert jax.tree.structure(fact.params) == jax.tree.structure(dense.params)
    assert jax.tree.structure(fact.opt_state) == jax.tree.structure(
        dense.opt_state)
    assert float(fact.opt_state["t"]) == 5.0
    for k, p in dense.params.items():
        assert fact.params[k].dtype == p.dtype
        assert fact.params[k].sharding == p.sharding
        p32 = p.astype(jnp.float32)
        np.testing.assert_allclose(fact.params[k].astype(jnp.float32), p32,
                                   err_msg=k, **tol(p32))
        for s in ("m", "v"):
            want_s = dense.opt_state[s][k]
            np.testing.assert_allclose(fact.opt_state[s][k], want_s,
                                       err_msg=k, **tol(want_s))
    # ... and it did move
    assert float(jnp.abs(fact.opt_state["m"]["lm_head.weight"]).max()) > 0


def test_a_barrier_a_linear_in_the_routed_step_and_none_in_the_plain_one():
    cfg, fact = llama_step()
    _, dense = llama_step(grad_clip_norm=1e9)
    ids = batch(cfg, np.random.RandomState(0))
    assert fact.lowered_text(ids, ids).count("optimization_barrier") == 15
    assert "optimization_barrier" not in dense.lowered_text(ids, ids)
    # tracing leaves the slot as it found it
    assert F._factors_once.routed is None


def test_the_leaves_are_in_the_dispatch_span():
    cfg, step = llama_step()
    before = len(tracing.events(trace="train"))
    run(cfg, step, 2)
    evs = tracing.events(trace="train")[before:]
    assert [e["name"] for e in evs] == ["train.dispatch"] * 2
    assert [e["args"]["step"] for e in evs] == [0, 1]
    assert [e["args"]["fused_leaves"] for e in evs] == [15, 15]
    assert all(0.8 < e["args"]["fused_param_share"] < 0.9 for e in evs)


PLAIN = {
    "dp axis": dict(mesh=([2, 1], ["dp", "mp"]), dp_axis="dp"),
    "dp axis of one": dict(dp_axis="dp"),
    "placements": dict(mesh=([1, 2], ["dp", "mp"]), shard="mp"),
    "clip": dict(grad_clip_norm=1.0),
    "remat": dict(remat=True),
    "remat policy": dict(remat="dots_saveable"),
    "batch spec": dict(mesh=([2, 1], ["dp", "mp"]),
                       batch_spec=jax.sharding.PartitionSpec("dp")),
    "momentum": dict(optimizer="Momentum"),
    "adam": dict(optimizer="Adam"),
    "sgd": dict(optimizer="SGD"),
}


@pytest.mark.parametrize("case", sorted(PLAIN))
def test_the_rule_lands_on_the_plain_step(case):
    cfg, step = llama_step(**PLAIN[case])
    if "remat" not in case:     # ``jax.checkpoint`` brings barriers of its own
        ids = batch(cfg, np.random.RandomState(0))
        assert "optimization_barrier" not in step.lowered_text(ids, ids)
    run(cfg, step, 1)
    assert step._fused == DENSE_FACTS
    assert tracing.events(trace="train")[-1]["args"]["fused_leaves"] == 0


def test_no_optimizer_keeps_todays_forward_and_backward():
    """``HostOffloadTrainStep`` hands ``optimizer=None`` and builds its
    programs from ``_make_forward_loss``: nothing sets the slot."""
    _, step = llama_step(optimizer=None)
    assert step._fopt is None and step.opt_state is None
    assert not step._one_program_one_device()


def test_a_tied_head_is_no_linear():
    """The tied head reads the embedding table through a transposed
    matmul, not ``linear``: the table's gradient is autodiff's."""
    cfg, fact = llama_step(tie=True)
    _, dense = llama_step(tie=True, grad_clip_norm=1e9)
    np.testing.assert_allclose(run(cfg, fact, 2), run(cfg, dense, 2),
                               rtol=1e-5)
    assert fact._fused["fused_leaves"] == 14
    np.testing.assert_allclose(fact.params["llama.embed_tokens.weight"],
                               dense.params["llama.embed_tokens.weight"],
                               rtol=1e-5, atol=1e-7)


class _Twice(nn.Layer):
    """One weight in two linears, one in a linear and in the loss, one
    in a linear alone, and a weight that is no matrix."""

    def __init__(self):
        super().__init__()
        self.shared = nn.Linear(16, 16, bias_attr=False)
        self.seen = nn.Linear(16, 16, bias_attr=False)
        self.once = nn.Linear(16, 8)
        self.scale = self.create_parameter((16,))

    def forward(self, x):
        h = self.shared(self.shared(x)) * self.scale
        return self.once(self.seen(h)) + self.seen.weight.sum()


def test_a_weight_used_twice_adds_its_gradients_up():
    def build(**kw):
        paddle.seed(0)
        model = _Twice()
        opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters())
        mesh = dist.ProcessMesh(np.arange(1).reshape(1), ["x"])
        return ShardedTrainStep(model, lambda o, y: ((o - y) ** 2).mean(),
                                opt, mesh, **kw)

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(4, 16).astype(np.float32))
    y = paddle.to_tensor(rng.randn(4, 8).astype(np.float32))
    fact, dense = build(), build(grad_clip_norm=1e9)
    for _ in range(3):
        np.testing.assert_allclose(float(fact.step(x, y)),
                                   float(dense.step(x, y)), rtol=1e-6)
    # three matrices, the shared one counted once
    assert fact._fused["fused_leaves"] == 3
    for k, p in dense.params.items():
        np.testing.assert_allclose(fact.params[k], p, rtol=1e-5, atol=1e-7,
                                   err_msg=k)


# ---------------------------------------------------------------------------
# ``linear`` outside a tracing step
# ---------------------------------------------------------------------------


def _ours(bias):
    def f(a, w, b):
        with no_grad():
            return F.linear(Tensor(a), Tensor(w),
                            Tensor(b) if bias else None)._data
    return f


def _plain(bias):
    return lambda a, w, b: jnp.matmul(a, w) + b if bias else jnp.matmul(a, w)


@pytest.mark.parametrize("bias", [False, True])
def test_linear_outside_a_step_is_the_plain_matmul(bias):
    assert F._factors_once.routed is None
    args = (jnp.ones((2, 3, 8)), jnp.ones((8, 4)), jnp.ones((4,)))
    assert str(jax.make_jaxpr(_ours(bias))(*args)) == str(
        jax.make_jaxpr(_plain(bias))(*args))


@pytest.mark.parametrize("case", ["batched weight", "mixed dtypes"])
def test_what_is_no_matrix_of_the_inputs_dtype_is_passed_by(case, monkeypatch):
    monkeypatch.setattr(F._factors_once, "routed", [])
    a = jnp.ones((2, 3, 8))
    w = jnp.ones((2, 8, 4)) if case == "batched weight" else jnp.ones(
        (8, 4), jnp.bfloat16)
    args = (a, w, jnp.ones((4,)))
    assert str(jax.make_jaxpr(_ours(False))(*args)) == str(
        jax.make_jaxpr(_plain(False))(*args))
    assert F._factors_once.routed == []
    # a matrix of the input's dtype is routed, and the list says so
    args = (a, jnp.ones((8, 4)), jnp.ones((4,)))
    assert "custom_vjp" in str(jax.make_jaxpr(_ours(True))(*args))
    assert len(F._factors_once.routed) == 1
