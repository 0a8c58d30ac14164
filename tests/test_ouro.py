"""Ouro (a looped stack: L layers run T times over one set of weights)
against its plain reference, ``perfbench/references/ouro.py``: every
pass of the uncached forward, the gate, the exit distribution and the
exit rule; chunked prefill and decode through the paged pools (a K/V
plane for every pass and layer); the loop folded into the program
against the loop unrolled (the model through ``ServingEngine``:
``test_ouro_engine.py``). Float32 at tiny sizes on seeded weights, compared on logits, with T = 3
so that nothing passes by the symmetry of two.

Tolerance: 1e-4 absolute on logits of order 1. Program and reference
are both float32 and differ in the order of their sums alone (attention
over a gathered table or through the kernel against a dense softmax),
which reads 7e-7 here after 3 x 2 layers whose branch outputs are
normalised again; a pass left out, a plane shared between passes, a
dropped post-norm or a final norm outside the loop moves a logit by
0.64-1.16 (the controls hold that at 100 x the tolerance).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, serving
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.generation import (generate_uncached, kv_cache_bytes_per_token,
                                   kv_cache_planes, make_kv_caches,
                                   make_paged_kv_pools)
from paddle_tpu.models import (EvaByteConfig, GPTConfig, LlamaConfig,
                               OuroConfig, OuroForCausalLM)
from perfbench import weights
from perfbench.programs import install_weights
from perfbench.references import ouro as ref

TOL = 1e-4
T, L = 3, 2
SIZES = dict(vocab_size=160, hidden_size=128, intermediate_size=192,
             num_hidden_layers=L, num_attention_heads=4,
             num_key_value_heads=4, max_position_embeddings=256,
             rms_norm_eps=1e-6, rope_theta=1000000.0, total_ut_steps=T,
             early_exit_threshold=1.0)


def build(seed=7, **over):
    """(model, reference parameters) on the same seeded leaves."""
    spec = ref.param_spec(SIZES)
    model = OuroForCausalLM(OuroConfig(dtype="float32", **{**SIZES, **over}))
    install_weights(model, spec, weights.make(spec, seed, jnp.float32))
    return model, weights.make(spec, seed, jnp.float32)


@pytest.fixture(scope="module")
def pair():
    return build()


def tokens(n, seed=3):
    return np.random.default_rng(seed).integers(
        1, SIZES["vocab_size"], size=n).astype(np.int32)


# -- the uncached forward ------------------------------------------------------

def test_every_pass_the_gate_and_the_exit_distribution_match(pair):
    model, params = pair
    ids = tokens(37)
    got = model(paddle.to_tensor(ids[None]), return_passes=True)
    want = ref.all_passes(params, jnp.asarray(ids), SIZES)
    for key in ("logits_per_pass", "gate", "exit_pdf"):
        g, w = np.asarray(got[key]._data)[:, 0], np.asarray(want[key])
        assert g.shape == w.shape and g.shape[0] == T
        assert float(np.abs(g - w).max()) < TOL, key
    # at the published threshold every token leaves after pass T
    assert np.asarray(got["exit_pass"]._data).tolist() == [[T - 1] * 37]
    assert float(np.abs(np.asarray(got["logits"]._data)[0]
                        - np.asarray(want["logits_per_pass"][-1])).max()) < TOL
    pdf = np.asarray(want["exit_pdf"])
    assert np.allclose(pdf.sum(0), 1.0, atol=1e-5) and (pdf >= 0).all()
    # the passes differ: a model that ran one pass T times would not
    per_pass = np.asarray(want["logits_per_pass"])
    assert float(np.abs(per_pass[0] - per_pass[-1]).max()) > 1000 * TOL


def test_the_exit_rule_picks_the_references_pass_per_token():
    # a seed on which the tokens leave after each of the three passes
    model, params = build(seed=8, early_exit_threshold=0.5)
    ids = tokens(41, seed=5)
    got = model(paddle.to_tensor(ids[None]), return_passes=True)
    want = ref.all_passes(params, jnp.asarray(ids), SIZES, q=0.5)
    picked = np.asarray(want["exit_pass"])
    assert np.asarray(got["exit_pass"]._data)[0].tolist() == picked.tolist()
    assert set(picked.tolist()) == {0, 1, 2}
    plain = model(paddle.to_tensor(ids[None]))
    for out in (got["logits"], plain):
        assert float(np.abs(np.asarray(out._data)[0]
                            - np.asarray(want["logits"])).max()) < TOL


# -- the cached forward on paged pools ----------------------------------------

def engine_for(model, slots=1, max_len=96, num_blocks=None, chunk=8,
               prefix_caching=False):
    return serving.ServingEngine(model, serving.ServingConfig(
        max_slots=slots, max_len=max_len, block_size=4, prefill_chunk=chunk,
        prefix_caching=prefix_caching, num_blocks=num_blocks))


def served_logits(model, ids, prefill_len, chunk=8):
    """Pass T's logits at every position of ``ids``: the first
    ``prefill_len`` through prefill chunks, the rest one decode step at a
    time, on the engine's own pools, table and block bookkeeping.
    Returns them with the pools as they were left."""
    eng = engine_for(model, chunk=chunk)
    run, pb = eng._run, eng._pb

    @jax.jit
    def chunk_fn(pools, bt, toks, pos0, valid):
        return run(pb, toks, [dict(c, bt=bt, valid=valid[None])
                              for c in pools], pos0)

    @jax.jit
    def step_fn(pools, bt, tok, pos):
        return run(pb, tok, [dict(c, bt=bt) for c in pools], pos)

    pools, out = eng._pools, []
    for a in range(0, prefill_len, chunk):
        b = min(a + chunk, prefill_len)
        eng._reserve_write(0, a, b)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :b - a] = ids[a:b]
        lg, pools = chunk_fn(pools, jnp.asarray(eng._bt), jnp.asarray(toks),
                             jnp.asarray(a, jnp.int32),
                             jnp.asarray(b - a, jnp.int32))
        out.append(np.asarray(lg[0, :b - a]))
    for p in range(prefill_len, len(ids)):
        eng._reserve_write(0, p, p + 1)
        lg, pools = step_fn(pools, jnp.asarray(eng._bt),
                            jnp.asarray(ids[p:p + 1])[None],
                            jnp.asarray([p], jnp.int32))
        out.append(np.asarray(lg[:, 0]))
    return np.concatenate(out), pools


def cached_gap(model, params, n=44, prefill_len=21):
    ids = tokens(n, seed=prefill_len)
    got, _ = served_logits(model, ids, prefill_len)
    want = np.asarray(ref.logit_rows(params, jnp.asarray(ids), 0, 256, SIZES))
    assert got.shape == want.shape == (n, SIZES["vocab_size"])
    return float(np.abs(got - want).max())


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_prefill_in_chunks_then_decode_matches_the_reference(
        pair, monkeypatch, kernel):
    """T * L planes behind one block table, pass t reading and writing
    through ``table + t * num_blocks``, against the reference's forward
    over the whole sequence; with the paged kernel (interpreted here)
    and with the XLA gather path."""
    monkeypatch.setenv("PADDLE_TPU_FLASH_DECODE",
                       "1" if kernel == "pallas" else "0")
    assert cached_gap(*pair) < TOL


def _a_pass_left_out(model):
    model.config.total_ut_steps = T - 1


def _passes_share_pass_0s_planes(model):
    inner = model.ouro.one_pass

    def one_pass(h, attn_mask=None, caches=None, position_offset=0):
        n = caches[0]["k"].shape[0] // T
        caches = [dict(c, bt=Tensor(c["bt"]._data % n)) for c in caches]
        return inner(h, attn_mask, caches, position_offset)

    model.ouro.one_pass = one_pass


def _post_norms_dropped(model):
    for layer in model.ouro.layers:
        layer.input_layernorm_2 = nn.Identity()
        layer.post_attention_layernorm_2 = nn.Identity()


class _OnTheLastPassAlone(nn.Layer):
    """The final norm once after the loop: nothing on passes 1 .. T-1."""

    def __init__(self, norm):
        super().__init__()
        self.norm, self.calls = norm, 0

    def forward(self, x):
        self.calls += 1
        return self.norm(x) if self.calls % T == 0 else x


def _final_norm_once_after_the_loop(model):
    model.config.fold_loop = False     # a trace walks the T passes in turn
    model.ouro.norm = _OnTheLastPassAlone(model.ouro.norm)


@pytest.mark.parametrize("break_it", [
    _a_pass_left_out, _passes_share_pass_0s_planes, _post_norms_dropped,
    _final_norm_once_after_the_loop])
def test_a_part_of_the_loop_left_out_fails_the_same_comparison(break_it):
    model, params = build()
    break_it(model)
    assert cached_gap(model, params) > 100 * TOL


def test_the_folded_program_equals_the_unrolled_one_to_rounding(pair):
    """The passes as one ``fori_loop`` over L layer bodies against T * L
    layer bodies in a row: the same arithmetic in the same order, in the
    logits and in every plane. Not bit for bit: XLA:CPU fuses the ops of
    a loop's body otherwise than the same ops in a straight line, and
    already layer 0's keys of pass 0 (embedding, norm, projection,
    rotation) differ in the last bit, 2e-7 on values of order 1; a few
    ulps is what is held, fifty times under ``TOL`` and five orders under
    what any of the controls above moves."""
    folded, _ = pair
    unrolled, _ = build(fold_loop=False)
    ids = tokens(30, seed=9)
    a, pools_a = served_logits(folded, ids, 19)
    b, pools_b = served_logits(unrolled, ids, 19)
    assert float(np.abs(a - b).max()) < 2e-6
    assert a.argmax(-1).tolist() == b.argmax(-1).tolist()
    for ca, cb in zip(pools_a, pools_b):
        for key in ("k", "v"):
            assert float(np.abs(np.asarray(ca[key])
                                - np.asarray(cb[key])).max()) < 2e-6
    # every pass wrote its own plane: a layer's T planes all differ
    k = np.asarray(pools_a[0]["k"])
    planes = k.reshape(T, k.shape[0] // T, *k.shape[1:])
    assert all(np.abs(planes[t] - planes[u]).max() > 1e-3
               for t in range(T) for u in range(t))


def test_a_contiguous_cache_holds_a_buffer_for_every_plane(pair):
    """``generate`` (static buffers, plane t * L + i) returns the tokens
    of the uncached forward."""
    model, _ = pair
    ids = paddle.to_tensor(np.stack([tokens(9, seed=1), tokens(9, seed=2)]))
    want = np.asarray(generate_uncached(model, ids, 3)._data)
    assert np.asarray(model.generate(ids, 3)._data).tolist() == want.tolist()
    with pytest.raises(ValueError, match="buffer for every pass and layer"):
        model(ids, kv_caches=make_kv_caches(
            LlamaConfig.tiny(num_hidden_layers=L), 2, 16, jnp.float32))


# -- how many planes ----------------------------------------------------------

def test_one_function_says_how_many_planes_a_config_has():
    for cfg in (LlamaConfig.tiny(), GPTConfig.tiny(), EvaByteConfig.tiny()):
        n = cfg.num_hidden_layers
        assert kv_cache_planes(cfg) == n
        assert len(make_kv_caches(cfg, 1, 8, jnp.float32)) == n
        pools = make_paged_kv_pools(cfg, 5, 4, jnp.float32)
        assert len(pools) == n and pools[0]["k"].shape[0] == 5
    cfg = OuroConfig(dtype="float32", **SIZES)
    assert kv_cache_planes(cfg) == T * L
    assert len(make_kv_caches(cfg, 1, 8, jnp.float32)) == T * L
    pools = make_paged_kv_pools(cfg, 5, 4, jnp.float32)
    # a layer's T planes side by side in one array
    assert len(pools) == L and pools[0]["k"].shape == (T * 5, 4, 4, 32)
    assert kv_cache_bytes_per_token(cfg, "bf16", jnp.bfloat16) \
        == T * L * 2 * 128 * 2
    # the published model: 192 planes, 1.5 MiB a position
    assert kv_cache_planes(OuroConfig()) == 192
    assert kv_cache_bytes_per_token(OuroConfig(), "bf16", jnp.bfloat16) \
        == 1536 * 1024
