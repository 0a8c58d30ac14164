"""DeepSeek-V2's language model (``models/deepseek_v2.py``) against the
plain reference (``perfbench/references/deepseek_v2.py``) at tiny widths
in float32 on seeded weights: the full forward on logits; the two forms
of the cached attention on the same latent cache; the YaRN frequencies
and the softmax scale against numbers written out by hand; the router
against a brute-force choice; the dropless expert layer under a forced
skew; and the shares adding up to the uncut layer."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import generation
from paddle_tpu.distributed import moe_serving
from paddle_tpu.models import DeepseekV2Config, DeepseekV2ForCausalLM
from paddle_tpu.models import deepseek_v2 as dsv2
from perfbench import weights
from perfbench.references import deepseek_v2 as ref

YARN = dict(type="yarn", factor=40, beta_fast=32, beta_slow=1, mscale=0.707,
            mscale_all_dim=0.707, original_max_position_embeddings=64)
# the reference's configuration (a file's keys) of ``DeepseekV2Config.tiny``
SIZES = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=3,
             num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
             qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
             first_k_dense_replace=1, n_routed_experts=16, n_shared_experts=2,
             n_group=4, topk_group=2, num_experts_per_tok=3,
             routed_scaling_factor=16, rms_norm_eps=1e-6, rope_theta=10000,
             rope_scaling=YARN)


def sizes(ep_rank=0, ep_size=1):
    """The file's keys of share ``ep_rank`` of ``ep_size``."""
    return dict(SIZES, n_routed_experts=16 // ep_size, router_experts=16,
                expert_parallel={"rank": ep_rank, "size": ep_size})


def build(ep_rank=0, ep_size=1, seed=7, **overrides):
    """(model, the reference's float32 parameters) of one share, the
    model holding the same seeded values."""
    cfg = sizes(ep_rank, ep_size)
    params = weights.make(ref.param_spec(cfg), seed, jnp.float32)
    model = DeepseekV2ForCausalLM(DeepseekV2Config.tiny(
        ep_rank=ep_rank, ep_size=ep_size, **overrides))
    own = model.named_parameters_dict()
    assert set(own) == set(params)
    for name, p in own.items():
        assert tuple(p.shape) == tuple(params[name].shape), name
        p._data = params[name]
    return model, params


def tokens(n, seed=0):
    return np.random.RandomState(seed).randint(1, 128, n).astype(np.int32)


@pytest.fixture(scope="module")
def pair():
    return build(ep_rank=1, ep_size=4)


def test_the_full_forward_gives_the_references_logits(pair):
    model, params = pair
    ids = tokens(40)
    with paddle.no_grad():
        got = model(paddle.to_tensor(ids[None]))._data[0]
    want = ref.logits(params, jnp.asarray(ids), sizes(1, 4))
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(want).max()) > 0.1


def test_the_uncut_model_is_the_reference_with_every_expert_held():
    model, params = build()
    ids = tokens(24, seed=3)
    with paddle.no_grad():
        got = model(paddle.to_tensor(ids[None]))._data[0]
    want = ref.logits(params, jnp.asarray(ids), sizes())
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("paged", [True, False])
def test_absorbed_and_decompressed_attention_agree_on_the_same_cache(
        pair, paged, monkeypatch):
    """A prompt written through the latent cache in two chunks, then a
    decode step: both forms of the attention, chunk by chunk, against
    the reference's logits; and the cache they leave is the same."""
    model, params = pair
    cfg = model.config
    ids = tokens(29, seed=5)
    want = np.asarray(ref.logits(params, jnp.asarray(ids), sizes(1, 4)))
    run = generation.make_cached_runner(model)
    pb = {**{k: v._data for k, v in model.named_parameters_dict().items()},
          **{k: v._data for k, v in model.named_buffers_dict().items()}}
    left = {}
    for form, below in (("absorbed", 10 ** 9), ("decompressed", 0)):
        # in XLA the shapes decide the form: every call under the bar,
        # or none
        monkeypatch.setattr(generation, "latent_absorb_below",
                            lambda *_, below=below: below)
        if paged:
            caches = generation.make_paged_kv_pools(cfg, 9, 8, jnp.float32)
            bt = jnp.asarray([[3, 5, 7, 2]], jnp.int32)
            caches = [dict(c, bt=bt) for c in caches]
        else:
            caches = generation.make_kv_caches(cfg, 1, 32, jnp.float32)
        got = []
        for start, end in ((0, 17), (17, 28), (28, 29)):
            pos = jnp.asarray([start], jnp.int32) if paged else start
            lg, caches = run(pb, jnp.asarray(ids[None, start:end]), caches,
                             pos)
            caches = [{k: v for k, v in c.items() if k != "route_stats"}
                      for c in caches]
            got.append(np.asarray(lg[0]))
        assert np.abs(np.concatenate(got) - want).max() < 2e-5, form
        left[form] = [np.asarray(c["c"]) for c in caches]
    # the first layer's latents do not pass through attention; the
    # later ones differ by the two forms' float32 rounding
    assert np.array_equal(left["absorbed"][0], left["decompressed"][0])
    for a, b in zip(left["absorbed"], left["decompressed"]):
        assert np.abs(a - b).max() < 1e-5
    # 16 + 4 values a position, the rest of the lane tile is padding
    assert left["absorbed"][0].shape[-1] == 128
    assert not left["absorbed"][0][..., 20:].any()
    assert np.abs(left["absorbed"][0][..., :20]).max() > 0


def test_the_form_follows_the_rows_that_share_their_positions():
    # DeepSeek-V2's widths: decompressing pays from 171 rows on
    assert generation.latent_absorb_below(512, 128, 128) == 171
    # the tiny widths: 16 x 16 / (32 - 16)
    assert generation.latent_absorb_below(16, 8, 8) == 16


def test_yarn_frequencies_and_the_scale_against_numbers_by_hand():
    """DeepSeek-V2's own numbers: 64 rotated dimensions, theta 10000,
    factor 40 over 4096 original positions, beta 32 and 1. The
    correction dimensions are 64 ln(4096 / (32 x 2 pi)) / (2 ln 10000) =
    10.47 -> 10 and 64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> 23:
    frequencies 0-10 unscaled, 23-31 over 40, a ramp of thirteenths
    between."""
    sc = dict(YARN, original_max_position_embeddings=4096)
    for mod in (dsv2, ref):
        inv = np.asarray(mod.yarn_inv_freq(64, 10000.0, sc))
        plain = 10000.0 ** (-np.arange(32) / 32.0)
        assert np.allclose(inv[:11], plain[:11], rtol=1e-6)
        assert np.allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
        # dimension 16 lies 6/13 up the ramp
        want16 = plain[16] * (1 - 6 / 13) + plain[16] / 40 * (6 / 13)
        assert math.isclose(inv[16], want16, rel_tol=1e-5)
        # 0.01 x 7/13 + 0.00025 x 6/13
        assert math.isclose(inv[16], 0.0055, rel_tol=1e-5)
        # m = 0.1 x 0.707 x ln 40 + 1
        assert math.isclose(mod.yarn_mscale(40, 0.707), 1.260804, rel_tol=1e-6)
    big = DeepseekV2Config()
    # 192^-0.5 x 1.260804^2
    assert math.isclose(dsv2.mla_softmax_scale(big), 0.114721, rel_tol=1e-5)
    assert math.isclose(ref.softmax_scale(dict(
        qk_nope_head_dim=128, qk_rope_head_dim=64, rope_scaling=sc)),
        0.114721, rel_tol=1e-5)
    # cos and sin carry mscale / mscale_all_dim = 1
    cos, sin = dsv2._rope_tables(DeepseekV2Config.tiny())
    assert float(cos[0].min()) == 1.0 and float(jnp.abs(sin[0]).max()) == 0.0


def brute_route(p, n_group, topk_group, top_k, scale):
    """One token's experts by hand: the groups by their best member, the
    best ``topk_group`` of them, the ``top_k`` best members left."""
    e = len(p)
    per = e // n_group
    best = sorted(range(n_group), key=lambda g: -max(p[g * per:(g + 1) * per]))
    alive = [i for g in best[:topk_group] for i in range(g * per, (g + 1) * per)]
    chosen = sorted(alive, key=lambda i: -p[i])[:top_k]
    return {i: p[i] * scale for i in chosen}


def test_the_router_against_a_brute_force_choice():
    rng = np.random.RandomState(2)
    x = rng.randn(50, 64).astype(np.float32)
    w = (rng.randn(64, 16) * 0.2).astype(np.float32)
    ids, wts = moe_serving.group_limited_route(
        jnp.asarray(x), jnp.asarray(w), n_group=4, topk_group=2, top_k=3,
        scale=16.0)
    p = np.asarray(jax.nn.softmax(jnp.asarray(x @ w), -1))
    table = np.asarray(ref.route(jnp.asarray(x), jnp.asarray(w), dict(
        n_group=4, topk_group=2, num_experts_per_tok=3,
        routed_scaling_factor=16)))
    masked_high = 0
    for t in range(50):
        want = brute_route(p[t].tolist(), 4, 2, 3, 16.0)
        got = dict(zip(np.asarray(ids[t]).tolist(),
                       np.asarray(wts[t]).tolist()))
        assert set(got) == set(want)
        for i, v in want.items():
            assert math.isclose(got[i], v, rel_tol=1e-5)
            assert math.isclose(table[t, i], v, rel_tol=1e-5)
        assert np.count_nonzero(table[t]) == 3
        # a token whose 3 best experts are not all in its 2 best groups:
        # the group mask, not the plain top-3, decided
        masked_high += set(np.argsort(-p[t])[:3].tolist()) != set(want)
    assert masked_high >= 5


def test_a_high_expert_is_masked_by_its_group():
    """Written out: four groups of two. Expert 6 has the third largest
    probability of all but its group (6, 7) is only the third best by
    its best member, so with two groups kept and three experts a token
    the choice is 0, 2 and 3, never 6."""
    logits = np.log(np.asarray(
        [[0.30, 0.01, 0.20, 0.05, 0.02, 0.03, 0.19, 0.20]], np.float32))
    logits[0, 7] = np.log(0.0001)    # group 3's best is then expert 6
    x = np.eye(8, dtype=np.float32)[:1] * 0 + 1.0 / 8
    w = np.tile(logits, (8, 1)).astype(np.float32)   # x @ w = logits
    ids, wts = moe_serving.group_limited_route(
        jnp.asarray(x), jnp.asarray(w), n_group=4, topk_group=2, top_k=3,
        scale=16.0)
    p = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))[0]
    assert np.argsort(-p)[:3].tolist() == [0, 2, 6]
    assert sorted(np.asarray(ids[0]).tolist()) == [0, 2, 3]
    got = dict(zip(np.asarray(ids[0]).tolist(), np.asarray(wts[0]).tolist()))
    for i in (0, 2, 3):
        assert math.isclose(got[i], 16.0 * p[i], rel_tol=1e-5)


def expert_by_hand(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


@pytest.mark.parametrize("rows", [5, 64])
def test_dropless_under_a_forced_skew_loses_no_pair(rows):
    """Every token to ONE held expert, first choice, and to two absent
    ones: ``rows`` pairs on one expert, none dropped, the absent ones'
    left out and counted."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(rows, 64).astype(np.float32))
    wg, wu = (jnp.asarray(rng.randn(4, 64, 32).astype(np.float32) * 0.1)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(4, 32, 64).astype(np.float32) * 0.1)
    ids = jnp.asarray(np.tile([[6, 1, 13]], (rows, 1)), jnp.int32)
    wts = jnp.asarray(rng.rand(rows, 3).astype(np.float32) + 0.5)
    y, stats = moe_serving.held_expert_ffn(x, ids, wts, wg, wu, wd, first=4)
    want = wts[:, :1] * expert_by_hand(x, wg[2], wu[2], wd[2])
    assert float(jnp.abs(y - want).max()) < 1e-5
    assert dict(zip(moe_serving.ROUTE_STATS, np.asarray(stats).tolist())) \
        == {"pairs_here": rows, "pairs": 3 * rows, "experts_touched": 1,
            "load_max": rows}
    # rows that carry nothing are neither computed nor counted
    live = jnp.arange(rows) % 2 == 0
    y2, stats2 = moe_serving.held_expert_ffn(x, ids, wts, wg, wu, wd,
                                             first=4, live=live)
    n_live = int(live.sum())
    assert np.asarray(stats2).tolist() == [n_live, 3 * n_live, 1, n_live]
    assert float(jnp.abs(jnp.where(live[:, None], y2 - want, y2)).max()) < 1e-5


def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts, with ``Shared`` counted once,
    equal the uncut reference's layer output."""
    cfg = sizes()
    params = weights.make(ref.param_spec(cfg), 9, jnp.float32)
    b = "model.layers.1."
    leaves = tuple(params[b + k] for k in ref.MOE_LEAVES)
    y = jnp.asarray(np.random.RandomState(6).randn(33, 64).astype(np.float32))
    whole = ref.moe(y, leaves, cfg, jnp.matmul)
    shared = ref._swiglu(y, *leaves[4:], jnp.matmul)
    assert float(jnp.abs(whole - shared).max()) > 0.01
    ids, wts = moe_serving.group_limited_route(
        y, leaves[0], n_group=4, topk_group=2, top_k=3, scale=16.0)
    total, here = shared, 0
    for rank in range(4):
        held = slice(4 * rank, 4 * rank + 4)
        part, stats = moe_serving.held_expert_ffn(
            y, ids, wts, leaves[1][held], leaves[2][held], leaves[3][held],
            first=4 * rank)
        # and the reference's own share is the same part
        cut = dict(sizes(rank, 4))
        ref_part = ref.moe(y, (leaves[0], leaves[1][held], leaves[2][held],
                               leaves[3][held]) + leaves[4:], cut,
                           jnp.matmul) - shared
        assert float(jnp.abs(part - ref_part).max()) < 1e-5
        total = total + part
        here += int(stats[0])
        assert int(stats[1]) == 33 * 3
    assert here == 33 * 3
    assert float(jnp.abs(total - whole).max()) < 1e-5


def test_a_cached_position_costs_its_latent_and_nothing_a_head():
    """``kv_cache_bytes_per_token`` and the pools' shape come from the
    configuration: 5,760 B over the benchmark's five layers in bfloat16
    (per-head K and V at these widths: 2 x 128 x 40 x 2 x 5)."""
    big = DeepseekV2Config(num_hidden_layers=5)
    assert generation.latent_cache_width(big) == 576
    assert generation.kv_cache_bytes_per_token(big, "bf16", jnp.bfloat16) \
        == 5760
    tiny_cfg = DeepseekV2Config.tiny()
    assert generation.kv_cache_bytes_per_token(tiny_cfg, "bf16", jnp.float32) \
        == 20 * 4 * 3
    pools = generation.make_paged_kv_pools(tiny_cfg, 9, 8, jnp.float32)
    assert len(pools) == 3 and list(pools[0]) == ["c"]
    assert pools[0]["c"].shape == (9, 8, 128)
    caches = generation.make_kv_caches(tiny_cfg, 2, 32, jnp.float32)
    assert caches[0]["c"].shape == (2, 32, 128)
    for make in (lambda: generation.make_paged_kv_pools(
            tiny_cfg, 9, 8, jnp.float32, "int8"),
            lambda: generation.kv_cache_bytes_per_token(tiny_cfg, "int8")):
        with pytest.raises(ValueError, match="stored unquantized"):
            make()
    # a model that caches per-head K and V is none of this
    from paddle_tpu.models import LlamaConfig
    assert generation.latent_cache_width(LlamaConfig.tiny()) is None


def test_generate_through_the_contiguous_latent_cache_is_greedy(pair):
    model, params = pair
    prompt = tokens(11, seed=8)
    out = np.asarray(model.generate(paddle.to_tensor(prompt[None]),
                                    max_new_tokens=6)._data)[0]
    assert out[:11].tolist() == prompt.tolist()
    lg = np.asarray(ref.logits(params, jnp.asarray(out), sizes(1, 4)))
    assert lg.argmax(-1)[10:16].tolist() == out[11:].tolist()


def test_the_family_loads_with_its_first_use():
    """``import paddle_tpu`` and the other families' engines import
    nothing of it (a subprocess: this one has it loaded)."""
    import subprocess
    import sys

    code = ("import sys, paddle_tpu, paddle_tpu.models, paddle_tpu.serving\n"
            "assert not [m for m in sys.modules if 'deepseek' in m "
            "or 'moe_serving' in m]\n"
            "from paddle_tpu.models import DeepseekV2Config\n"
            "assert 'paddle_tpu.models.deepseek_v2' in sys.modules\n")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(__import__("os").environ,
                                             JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stderr[-2000:]


def test_the_latent_kernel_in_head_groups_is_the_kernel_whole(monkeypatch):
    """A chunk's heads go through the paged kernel in groups, each a
    grid row over the same cache row: the same numbers as one row for
    all heads, and as the dense softmax written out (interpreted)."""
    from paddle_tpu.pallas_kernels import decode_attention as da

    rng = np.random.RandomState(12)
    pool = jnp.asarray(rng.randn(9, 8, 128).astype(np.float32))
    q = jnp.asarray(rng.randn(2, 5, 4, 128).astype(np.float32))
    bt = jnp.asarray([[3, 5, 7, 2], [1, 4, 6, 8]], jnp.int32)
    pos = jnp.asarray([17, 9], jnp.int32)
    how = dict(sm_scale=0.2, v_width=16)
    whole = da.latent_paged_flash_decode_attention(q, pool, bt, pos,
                                                   max_rows=64, **how)
    assert da._latent_head_groups(5, 4, 64) == 1
    assert da._latent_head_groups(5, 4, 5) == 4
    assert da._latent_head_groups(64, 128) == 4
    assert da._latent_head_groups(1, 128) == 1
    split = da.latent_paged_flash_decode_attention(q, pool, bt, pos,
                                                   max_rows=5, **how)
    assert float(jnp.abs(whole - split).max()) < 1e-6
    lat = pool[bt].reshape(2, 32, 128)
    sc = jnp.einsum("bshw,bkw->bhsk", q, lat) * 0.2
    seen = jnp.arange(32)[None, None, :] <= (pos[:, None]
                                             + jnp.arange(5))[:, :, None]
    p = jax.nn.softmax(jnp.where(seen[:, None], sc, -jnp.inf), -1)
    want = jnp.einsum("bhsk,bkr->bshr", p, lat[..., :16])
    assert float(jnp.abs(whole - want).max()) < 1e-5

