"""Every Pallas entry lowers for TPU at production shapes, checked on CPU.

The CPU suite runs the kernels through the Pallas interpreter, which
accepts block shapes and in-kernel ops the TPU lowering refuses. Here
each public kernel entry is lowered for ``platforms=["tpu"]`` through
``jax.export`` with ``_interpret`` patched off, at the head layouts the
serving engine and the trainer actually run (MHA 16x128 = GPT-3 1.3B,
GQA 32/8x128) and the four ResNet-50 conv shapes.

``test_lowers_for_tpu`` catches ONLY the Pallas-to-Mosaic stage (block
shapes against the (8, 128) tile, ops with no TPU lowering rule): the
Mosaic compiler itself runs later, inside the backend compile.
``test_compiles_for_v5e`` (slow, so outside tier-1) runs that too: it
compiles the same cases for a v5e topology description, which libtpu
builds without a chip. Neither executes anything; numerics on the
device are ``chip_smoke.py``'s and the TPU lane's job.
"""

from importlib import import_module

import jax
import jax.numpy as jnp
import pytest

# the package re-exports functions under the modules' own names
decode_attention, flash_attention, fused_conv, quant_matmul = (
    import_module(f"paddle_tpu.pallas_kernels.{m}") for m in (
        "decode_attention", "flash_attention", "fused_conv", "quant_matmul"))

BF16 = jnp.bfloat16
# (heads, kv_heads) at head_dim 128
HEADS = {"mha16": (16, 16), "gqa32_8": (32, 8), "mha32": (32, 32)}
D = 128
# the serving engine's pool geometry: 16 slots x 2048 tokens in
# 16-token blocks, plus the dump block
SLOTS, NB, BS = 16, 128, 16
POOL_DTYPES = {"bf16": BF16, "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}
# paged bundles: (batch rows, q_len, ancestor mask)
BUNDLES = {"decode": (SLOTS, 1, False), "chunk64": (1, 64, False),
           "chunk32": (1, 32, False),   # one prefilling slot's chunk
           # the prefill program the served cells run: the chunks of up
           # to eight slots as its rows, each at its own length
           "chunk8x32": (8, 32, False),
           "spec5": (SLOTS, 5, False), "tree29": (SLOTS, 29, True)}
# the EVA cell (evabyte-6p5b-cut): 20 rows whose table is [72 summary
# blocks | 128 window blocks | 8 more], a pool of 2,816 blocks, the
# step and the 256-token prefill chunk
EVA_BUNDLES = {"eva-step": (20, 1), "eva-chunk256": (1, 256)}
EVA_NB, EVA_POOL = 208, 2816
# the looped cell (ouro-2p6b): 8 rows of up to 672 positions (42 table
# entries), a layer's four passes side by side in one pool array of
# 4 x 336 blocks, the step and the [8, 32] prefill program
LOOP_BUNDLES = {"loop-step": (8, 1), "loop-chunk8x32": (8, 32)}
LOOP_NB, LOOP_POOL = 42, 4 * 336
# the latent cell (deepseek-v2-cut): 64 rows of up to 17,408 positions
# (1,088 table entries) over ONE pool array of 69,632 blocks with no
# kv-heads axis, 640 wide (the 512 + 64 values of a position padded to
# lane tiles), 128 query heads on the one vector, the value its first
# 512 columns; the step and the [8, 32] and [4, 64] prefill programs
LATENT_BUNDLES = {"latent-step": (64, 1), "latent-chunk8x32": (8, 32),
                  "latent-chunk4x64": (4, 64)}
LATENT_NB, LATENT_POOL, LATENT_W, LATENT_V, LATENT_HEADS = (
    1088, 69632, 640, 512, 128)
RESNET50 = [(56, 64), (28, 128), (14, 256), (7, 512)]  # (H=W, channels)


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _paged(heads, fmt, bundle):
    h, kv = HEADS[heads]
    if bundle in EVA_BUNDLES:
        (b, q_len), tree, n, nb = EVA_BUNDLES[bundle], False, EVA_POOL, EVA_NB
    elif bundle in LOOP_BUNDLES:
        (b, q_len), tree, n, nb = (LOOP_BUNDLES[bundle], False, LOOP_POOL,
                                   LOOP_NB)
    else:
        (b, q_len, tree), n, nb = BUNDLES[bundle], SLOTS * NB + 1, NB
    pool = _s((n, BS, kv, D), POOL_DTYPES[fmt])
    args = [_s((b, q_len, h, D), BF16), pool, pool, _s((b, nb), jnp.int32),
            _s((b,), jnp.int32)]
    quant = fmt != "bf16"
    if quant:
        args += [_s((n, BS, kv), jnp.float32)] * 2
    if tree:
        args.append(_s((b, q_len, q_len), jnp.bool_))

    def fn(q, kp, vp, bt, pos, *rest):
        ks, vs = rest[:2] if quant else (None, None)
        return decode_attention.paged_flash_decode_attention(
            q, kp, vp, bt, pos, k_scale=ks, v_scale=vs,
            ancestor_mask=rest[-1] if tree else None)

    return fn, args


def _latent(bundle):
    b, q_len = LATENT_BUNDLES[bundle]
    args = [_s((b, q_len, LATENT_HEADS, LATENT_W), BF16),
            _s((LATENT_POOL, BS, LATENT_W), BF16),
            _s((b, LATENT_NB), jnp.int32), _s((b,), jnp.int32)]

    def fn(q, pool, bt, pos):
        return decode_attention.latent_paged_flash_decode_attention(
            q, pool, bt, pos, sm_scale=0.1147, v_width=LATENT_V)

    return fn, args


def _contiguous(heads, fmt, max_len=NB * BS, block_k=256):
    h, kv = HEADS[heads]
    cache = _s((SLOTS, max_len, kv, D), POOL_DTYPES[fmt])
    args = [_s((SLOTS, 1, h, D), BF16), cache, cache, _s((SLOTS,), jnp.int32)]
    if fmt != "bf16":
        args += [_s((SLOTS, max_len, kv), jnp.float32)] * 2

    def fn(q, kc, vc, pos, ks=None, vs=None):
        return decode_attention.flash_decode_attention(
            q, kc, vc, pos, block_k=block_k, k_scale=ks, v_scale=vs)

    return fn, args


def _flash(d, seq, grad):
    x = _s((1, seq, 4, d), BF16)

    def fwd(q, k, v):
        return flash_attention.flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    return (jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd), [x, x, x]


def _flash_varlen(grad):
    x = _s((1000, 4, D), BF16)  # not a 128 multiple: exercises the pad
    cu = _s((5,), jnp.int32)

    def fwd(q, k, v, cu):
        return flash_attention.flash_attn_varlen(q, k, v, cu)

    def loss(q, k, v, cu):
        return fwd(q, k, v, cu).astype(jnp.float32).sum()

    return (jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd), [x, x, x, cu]


def _quant_matmul(fmt, m):
    n, k = 8192, 2048  # GPT-3 1.3B fc_in
    args = [_s((m, k), BF16), _s((n, k), POOL_DTYPES[fmt]),
            _s((n,), jnp.float32)]
    return quant_matmul.quant_matmul, args


def _conv(hw, c, train):
    x = _s((32, hw, hw, c), BF16)
    w = _s((c, c, 3, 3), BF16)
    vec = _s((c,), jnp.float32)
    if train:
        return fused_conv.fused_conv_bn_train, [x, w, vec, vec]
    return (lambda x, w, s, b: fused_conv.fused_conv_bn_eval(x, w, s, b, True),
            [x, w, vec, vec])


CASES = {f"paged-mha32-bf16-{_b}": (_paged, ("mha32", "bf16", _b))
         for _b in EVA_BUNDLES}
for _b in LOOP_BUNDLES:
    CASES[f"paged-mha16-bf16-{_b}"] = (_paged, ("mha16", "bf16", _b))
for _b in LATENT_BUNDLES:
    CASES[f"paged-{_b}"] = (_latent, (_b,))
for _h in ("mha16", "gqa32_8"):
    for _b in BUNDLES:
        CASES[f"paged-{_h}-bf16-{_b}"] = (_paged, (_h, "bf16", _b))
    for _f in ("int8", "fp8"):
        CASES[f"paged-{_h}-{_f}-decode"] = (_paged, (_h, _f, "decode"))
    CASES[f"paged-{_h}-int8-tree29"] = (_paged, (_h, "int8", "tree29"))
    for _f in POOL_DTYPES:
        CASES[f"contiguous-{_h}-{_f}"] = (_contiguous, (_h, _f))
# a capacity whose only dividing block is below the 8-row tile
CASES["contiguous-gqa32_8-int8-block4"] = (_contiguous,
                                           ("gqa32_8", "int8", 300, 256))
for _d in (64, 128):
    CASES[f"flash-d{_d}-fwd"] = (_flash, (_d, 1024, False))
    CASES[f"flash-d{_d}-fwd+bwd"] = (_flash, (_d, 4096, True))
CASES["flash-varlen-fwd"] = (_flash_varlen, (False,))
CASES["flash-varlen-fwd+bwd"] = (_flash_varlen, (True,))
for _f in ("int8", "fp8"):
    for _m in (4, 16):
        CASES[f"quant_matmul-{_f}-m{_m}"] = (_quant_matmul, (_f, _m))
for _hw, _c in RESNET50:
    CASES[f"fused_conv-{_hw}x{_c}-train"] = (_conv, (_hw, _c, True))
    CASES[f"fused_conv-{_hw}x{_c}-eval"] = (_conv, (_hw, _c, False))


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Every module binds ``_interpret`` by name; patch each binding so
    the entries build the compiled (non-interpret) ``pallas_call``."""
    for mod in (flash_attention, decode_attention, quant_matmul, fused_conv):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


@pytest.mark.parametrize("case", sorted(CASES))
def test_lowers_for_tpu(case, compiled_kernels):
    build, params = CASES[case]
    fn, args = build(*params)
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module()


@pytest.fixture(scope="module")
def v5e_device():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"no TPU topology description available: {e}")
    return topo.devices[0]


@pytest.mark.slow
@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, compiled_kernels, v5e_device):
    build, params = CASES[case]
    fn, args = build(*params)
    sharding = jax.sharding.SingleDeviceSharding(v5e_device)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in args]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
