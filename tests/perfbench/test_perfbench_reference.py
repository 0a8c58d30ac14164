"""The plain references against the program at tiny sizes on the CPU,
both in float32. Tolerances: both sides compute in float32 with the
same weights; they differ by the order of float32 sums (a fused kernel
against einsum, a fused cross entropy against logsumexp), so logits of
order 1 agree to about 1e-5 and a loss of order 6 to 1e-6 relative.
The limits below are some ten times that, far under what a change of
precision moves them by (bfloat16 moves logits by 1e-2)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from perfbench import lowp, weights
from perfbench.references import gpt as ref_gpt
from perfbench.references import llama as ref_llama

GPT = dict(hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
           intermediate_size=256, vocab_size=384, max_position_embeddings=64,
           layer_norm_eps=1e-5)
MISTRAL = dict(hidden_size=128, intermediate_size=320, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=384,
               max_position_embeddings=64, rms_norm_eps=1e-5,
               rope_theta=10000.0)
HYPER = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
         "weight_decay": 0.01}


def fill(model, spec, seed):
    leaves = weights.make(spec, seed, jnp.float32)
    params = model.named_parameters_dict()
    assert set(params) == set(spec)
    for k, p in params.items():
        assert tuple(p.shape) == tuple(spec[k][0]), k
        p._data = leaves[k]
    return leaves


def test_weights_are_a_function_of_the_seed_alone():
    spec = ref_gpt.param_spec(GPT)
    a = weights.make(spec, 2147483659, jnp.float32)
    b = weights.make(spec, 2147483659, jnp.float32)
    c = weights.make(spec, 2147483660, jnp.float32)
    k = "gpt.h.1.fc_in.weight"
    assert np.array_equal(a[k], b[k]) and not np.array_equal(a[k], c[k])
    assert np.asarray(a[k]).std() == pytest.approx(0.02, rel=0.05)
    assert np.asarray(a["gpt.ln_f.weight"]).mean() == pytest.approx(1, abs=0.02)
    one = weights.leaf(spec[k][0], jnp.uint32(weights.salts(
        2147483659, len(spec))[list(spec).index(k)]), 0.0, 0.02, jnp.float32)
    assert np.array_equal(one, a[k])  # a leaf can be made again alone


def test_bf16_weights_upcast_are_the_served_values():
    spec = {"w": ((64, 64), 0.0, 0.02)}
    served = weights.make(spec, 3, jnp.bfloat16)["w"]
    ref = weights.make(spec, 3, jnp.bfloat16, upcast=jnp.float32)["w"]
    assert ref.dtype == jnp.float32
    assert np.array_equal(np.asarray(served, np.float32), ref)


def test_gpt_logits_agree_with_the_program():
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    model = GPTForCausalLM(GPTConfig(**GPT))
    params = fill(model, ref_gpt.param_spec(GPT), 11)
    ids = np.random.default_rng(0).integers(1, GPT["vocab_size"], 48)
    got = np.asarray(model(paddle.to_tensor(ids[None].astype(np.int32)))._data)[0]
    want = np.asarray(ref_gpt.logit_rows(params, jnp.asarray(ids, jnp.int32),
                                         0, 48, GPT))
    assert got.shape == want.shape == (48, GPT["vocab_size"])
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < 1e-4
    # padding the end of a sequence changes no earlier row
    padded = np.concatenate([ids, np.zeros(16, ids.dtype)])
    again = np.asarray(ref_gpt.logit_rows(
        params, jnp.asarray(padded, jnp.int32), 8, 40, GPT))
    assert np.abs(again - want[8:]).max() < 1e-5


def test_gpt_int8_control_moves_the_logits_more_than_the_tolerance():
    params = weights.make(ref_gpt.param_spec(GPT), 11, jnp.float32)
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 384, 48), jnp.int32)
    a = np.asarray(ref_gpt.logit_rows(params, ids, 0, 48, GPT))
    b = np.asarray(ref_gpt.logit_rows(params, ids, 0, 48, GPT,
                                      mm=lowp.int8_matmul))
    assert 1e-3 < np.abs(a - b).max() < 0.5


def test_int8_rounding_is_by_token_and_by_output_channel():
    x = jnp.asarray([[1.0, -127.0], [0.5, 0.25]])
    w = jnp.asarray([[1.0, 100.0], [0.01, -50.0]])
    got = lowp.int8_matmul(x, w)
    # each row of x and each column of w keeps its own largest value
    xq = np.array([[1.0, -127.0], [0.5, 0.25196850393700787]])
    wq = np.array([[1.0, 100.0], [0.007874015748031496, -50.39370078740158]])
    assert np.allclose(got, xq @ wq, rtol=1e-6)


def program_first_steps(seed, batches):
    """Losses, first gradient norms and change norms of the program's
    trainer on the tiny Mistral, through the benchmark's adapter."""
    from perfbench.drivers import train
    from perfbench.programs import llama_trainer

    spec = ref_llama.param_spec(MISTRAL)
    cfg = dict(MISTRAL, dtype="float32", training=HYPER)
    tr = {"mesh": {"dp": 1, "mp": 1}, "flash_attention": False, "batch": 2,
          "seq": 32, "follow_steps": 3, "reference_rows_per_block": 1}
    trainer = llama_trainer.Trainer(
        cfg, tr, spec, weights.make(spec, seed, jnp.float32), 1)
    got = train.first_steps(trainer, spec, tr, seed, MISTRAL["vocab_size"],
                            jnp.float32, HYPER["beta1"])
    want = train.follow(ref_llama, MISTRAL, HYPER, spec, tr, seed,
                        MISTRAL["vocab_size"], jnp.float32)
    return got, want, train


def test_llama_loss_gradients_and_updates_agree_with_the_trainer():
    got, want, train = program_first_steps(5, None)
    vals = {k: v for k, (v, _) in train.compare(got, want).items()}
    assert all(vals[f"loss_rel_gap_step{i}"] < 1e-5 for i in (1, 2, 3)), vals
    assert vals["first_grad_norm_gap"] < 1e-4, vals
    # Adam's first updates are lr * sign-like: float32 rounding of a
    # gradient near zero can flip one, so this norm is looser
    assert vals["param_change_norm_gap"] < 1e-3, vals
    assert set(got["grad_norms"]) == set(ref_llama.param_spec(MISTRAL))


def test_llama_reference_gradient_is_the_autodiff_gradient():
    """The layer-by-layer walk in blocks of rows against jax.grad of
    the same mathematics written in one piece."""
    spec = ref_llama.param_spec(MISTRAL)
    params = weights.make(spec, 9, jnp.float32)
    ids = np.random.default_rng(1).integers(0, 384, (4, 24)).astype(np.int32)
    fol = ref_llama.Follower(MISTRAL, dict(params), HYPER, rows_per_block=2)
    loss, grads = fol.loss_and_grads(ids)

    def whole(p):
        x = p["llama.embed_tokens.weight"][ids]
        for i in range(MISTRAL["num_hidden_layers"]):
            lp = {k: p[f"llama.layers.{i}.{k}"] for k in ref_llama.LAYER_KEYS}
            x = ref_llama.layer(lp, x, MISTRAL, jnp.matmul)
        return ref_llama.head_loss(p, x, jnp.asarray(ids),
                                   1.0 / (4 * 23), MISTRAL, jnp.matmul)

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(whole)(params)
    assert loss == pytest.approx(float(want_loss), rel=1e-6)
    assert 5.5 < loss < 6.5  # near ln(384) at seeded weights
    for k in spec:
        scale = float(jnp.abs(want[k]).max())
        assert float(jnp.abs(grads[k] - want[k]).max()) < 1e-5 * scale + 1e-9, k
    only_loss, none = fol.loss_and_grads(ids, want_grads=False)
    assert only_loss == pytest.approx(loss, rel=1e-6) and none == {}


def test_adamw_from_the_gradient_history_is_adamw_with_moments():
    p0 = {"w": jnp.asarray(np.random.default_rng(2).normal(size=(8, 8)),
                           jnp.float32)}
    g1 = {"w": jnp.asarray(np.random.default_rng(3).normal(size=(8, 8)),
                           jnp.float32)}
    g2 = {"w": g1["w"] * -0.5 + 0.1}
    fol = ref_llama.Follower(MISTRAL, dict(p0), HYPER)
    fol.adamw([g1])
    fol.adamw([g1, g2])
    p, m, v = np.asarray(p0["w"], np.float64), 0.0, 0.0
    for t, g in enumerate((g1, g2), 1):
        g = np.asarray(g["w"], np.float64)
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        p = p * (1 - 3e-4 * 0.01) - 3e-4 * (m / (1 - 0.9 ** t)) / (
            np.sqrt(v / (1 - 0.999 ** t)) + 1e-8)
    assert np.abs(np.asarray(fol.params["w"]) - p).max() < 1e-6


def test_fp8_control_moves_the_first_gradient():
    spec = ref_llama.param_spec(MISTRAL)
    ids = np.random.default_rng(1).integers(0, 384, (2, 24)).astype(np.int32)
    norms = []
    for mm in (None, lowp.fp8_matmul):
        fol = ref_llama.Follower(MISTRAL, weights.make(spec, 9, jnp.float32),
                                 HYPER, mm=mm)
        _, g = fol.loss_and_grads(ids)
        norms.append({k: float(jnp.linalg.norm(v)) for k, v in g.items()})
    worst = max(abs(norms[1][k] - norms[0][k]) / norms[0][k] for k in spec)
    assert worst > 5e-3
