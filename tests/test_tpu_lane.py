"""On-chip engine coverage for the TPU test lane.

Runs under ``run_shards.py --platform=tpu`` (PADDLE_TPU_TEST_PLATFORM=
tpu): real-chip execution of the train engine with selective remat and
the flash-attention model path — the surfaces bench.py measures, as
correctness tests (reference device-lane discipline: op_test.py:2925
check_output_with_place). On the CPU lane these run on XLA:CPU and stay
cheap.

shard_map-based surfaces (ring attention, per-rank TP) need several
devices and are covered by the virtual CPU mesh lane (tests/conftest.py
default); ``chip_smoke.py`` runs ring attention on a four-chip host.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.engine import ShardedTrainStep
from paddle_tpu.distributed.mesh import ProcessMesh
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, llama_pretrain_loss


def _tiny(flash: bool):
    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    if flash:
        cfg.use_flash_attention = True
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32))
    lab = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32))
    return cfg, model, ids, lab


@pytest.mark.parametrize("remat", [False, "dots_with_no_batch_dims_saveable"])
def test_engine_trains_with_remat(remat):
    cfg, model, ids, lab = _tiny(flash=False)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = ShardedTrainStep(model, llama_pretrain_loss, opt,
                            ProcessMesh(np.arange(1), ["dp"]),
                            dp_axis=None, remat=remat)
    losses = [float(step.step(ids, lab)) for _ in range(4)]
    assert losses[-1] < losses[0], losses


def test_remat_matches_no_remat():
    # rematerialization must not change the math, only the memory
    losses = {}
    for remat in (False, "dots_with_no_batch_dims_saveable"):
        cfg, model, ids, lab = _tiny(flash=False)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        step = ShardedTrainStep(model, llama_pretrain_loss, opt,
                                ProcessMesh(np.arange(1), ["dp"]),
                                dp_axis=None, remat=remat)
        losses[remat] = [float(step.step(ids, lab)) for _ in range(3)]
    np.testing.assert_allclose(losses[False],
                               losses["dots_with_no_batch_dims_saveable"],
                               rtol=2e-4, atol=1e-5)


def test_flash_model_step_trains():
    cfg, model, ids, lab = _tiny(flash=True)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = ShardedTrainStep(model, llama_pretrain_loss, opt,
                            ProcessMesh(np.arange(1), ["dp"]), dp_axis=None)
    losses = [float(step.step(ids, lab)) for _ in range(4)]
    assert losses[-1] < losses[0], losses
