"""Pipeline parallelism.

Parity targets (SURVEY §2.5 #41):
- ``LayerDesc``/``SharedLayerDesc``/``PipelineLayer`` segmentation API
  (reference: fleet/meta_parallel/parallel_layers/pp_layers.py:56,76,257).
- Micro-batch schedules (reference: pipeline_parallel.py FThenB/1F1B).

TPU-native design (SURVEY §7.3 hard part 2): the reference drives PP from
python per micro-batch over NCCL P2P; here the ENTIRE schedule is one
compiled program — a ``lax.scan`` over pipeline ticks inside ``shard_map``
over the ``pp`` mesh axis, with ``ppermute`` moving activations to the
next stage over ICI. Backward is jax.grad through the scan, which yields
exactly the reverse pipeline (the 1F1B memory shape comes from XLA's
scheduling + remat rather than a hand-written interleave). Stage weights
live sharded over ``pp`` (one stage per rank slot).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..nn.layer import Layer


# ---------------------------------------------------------------------------
# Segmentation API (reference pp_layers.py)
# ---------------------------------------------------------------------------


class LayerDesc:
    """Deferred layer construction for stage assignment (reference :56)."""

    def __init__(self, layer_cls, *args, **kwargs):
        self.layer_cls = layer_cls
        self.args = args
        self.kwargs = kwargs

    def build_layer(self):
        return self.layer_cls(*self.args, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    """Layer shared between stages, grads all-reduced across them
    (reference :76 — e.g. tied embeddings)."""

    def __init__(self, key, layer_cls, forward_func=None, shared_weight_attr="weight",
                 *args, **kwargs):
        super().__init__(layer_cls, *args, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class PipelineLayer(Layer):
    """Stage-partitioned sequential model (reference :257).

    Single-process semantics: forward runs ALL stages (the full model) —
    correctness baseline and the source of truth for parameters. The
    compiled pipeline schedule (``gpipe_spmd`` / PipelinedTrainStep) is
    the multi-chip execution path.
    """

    def __init__(self, layers: Sequence, num_stages: Optional[int] = None, topology=None,
                 loss_fn=None, seg_method="uniform", recompute_interval=0, num_virtual_pipeline_stages=None):
        super().__init__()
        descs = list(layers)
        self._loss_fn = loss_fn
        built = []
        for i, d in enumerate(descs):
            if isinstance(d, LayerDesc):
                built.append(d.build_layer())
            elif isinstance(d, Layer):
                built.append(d)
            elif callable(d):
                built.append(_FnLayer(d))
            else:
                raise TypeError(f"unsupported pipeline entry {d!r}")
        from ..nn.layers_common import LayerList

        self.run_function = LayerList(built)
        if topology is not None:
            num_stages = topology.get_dim("pipe")
        self._num_stages = num_stages or 1
        self._segments = self._segment(len(built), self._num_stages, seg_method)

    @staticmethod
    def _segment(n_layers: int, n_stages: int, method: str) -> List[tuple]:
        base = n_layers // n_stages
        extra = n_layers % n_stages
        bounds = [0]
        for s in range(n_stages):
            bounds.append(bounds[-1] + base + (1 if s < extra else 0))
        return [(bounds[i], bounds[i + 1]) for i in range(n_stages)]

    def get_stage_layers(self, stage_id: int) -> List[Layer]:
        lo, hi = self._segments[stage_id]
        return list(self.run_function)[lo:hi]

    @property
    def num_stages(self):
        return self._num_stages

    def forward(self, x):
        for layer in self.run_function:
            x = layer(x)
        return x


class _FnLayer(Layer):
    def __init__(self, fn):
        super().__init__()
        self._fn = fn

    def forward(self, *args, **kwargs):
        return self._fn(*args, **kwargs)


# ---------------------------------------------------------------------------
# Compiled GPipe schedule (shard_map + ppermute + scan)
# ---------------------------------------------------------------------------


def gpipe_spmd(block_fn: Callable, n_stages: int, n_micro: int, pp_axis: str = "pp"):
    """Build the per-rank pipelined program.

    ``block_fn(stage_params, x) -> y``: one stage's computation; all stages
    must share structure (the transformer-stack case). Returns a function
    ``(stacked_params, x_microbatches) -> y_microbatches`` to be run under
    ``shard_map`` with ``stacked_params`` sharded ``P('pp')`` on the
    leading (stage) axis and microbatches replicated.

    Schedule: ``n_micro + n_stages - 1`` ticks; at tick t, rank r computes
    its stage on microbatch ``t - r`` (when in range) and ppermutes the
    activation to rank r+1. This is FThenB/GPipe; jax.grad over it gives
    the reverse schedule.
    """

    def per_rank(stage_params, xmb):
        # stage_params: [1, ...] — this rank's slice of the stacked stages
        sp = jax.tree.map(lambda a: a[0], stage_params)
        rank = jax.lax.axis_index(pp_axis)
        last = n_stages - 1
        T = n_micro + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        ymb0 = jnp.zeros_like(xmb)
        buf0 = jnp.zeros_like(xmb[0])

        def tick(carry, t):
            buf, ymb = carry
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(xmb, mb_idx, 0, keepdims=False)
            inp = jnp.where(rank == 0, fresh, buf)
            out = block_fn(sp, inp)
            # collect on the last rank (microbatch t - last)
            out_idx = t - last
            upd = jax.lax.dynamic_update_index_in_dim(ymb, out, jnp.clip(out_idx, 0, n_micro - 1), 0)
            ymb = jnp.where((rank == last) & (out_idx >= 0), upd, ymb)
            # forward the activation ring
            nxt = jax.lax.ppermute(out, pp_axis, perm)
            return (nxt, ymb), None

        (_, ymb), _ = jax.lax.scan(tick, (buf0, ymb0), jnp.arange(T))
        # replicate the last stage's outputs to every rank
        ymb = jax.lax.psum(jnp.where(rank == last, ymb, jnp.zeros_like(ymb)), pp_axis)
        return ymb

    return per_rank


def pipeline_forward(block_params_stacked, x_microbatches, block_fn, mesh, n_micro: int,
                     pp_axis: str = "pp"):
    """Run the compiled GPipe forward over ``mesh``'s pp axis.

    block_params_stacked: pytree with leading stage axis (len = pp size).
    x_microbatches: [n_micro, micro_batch, ...] array (replicated).
    """
    from jax.sharding import PartitionSpec as P

    from .mesh import ProcessMesh

    jmesh = mesh.jax_mesh if isinstance(mesh, ProcessMesh) else mesh
    n_stages = dict(zip(jmesh.axis_names, jmesh.devices.shape))[pp_axis]
    per_rank = gpipe_spmd(block_fn, n_stages, n_micro, pp_axis)
    f = jax.shard_map(per_rank, mesh=jmesh,
                      in_specs=(P(pp_axis), P()), out_specs=P(),
                      check_vma=False)
    return f(block_params_stacked, x_microbatches)


class PipelinedTrainStep:
    """Compiled pipeline-parallel training step for stacked-block models.

    The model is (embed_fn, block stack, head_loss_fn); block params are
    stacked [n_layers, ...] and split into ``pp`` groups of layers; each
    tick runs a stage = ``layers_per_stage`` blocks via an inner scan.
    Embed/head params are replicated (reference analogue: first/last stage
    owning embedding/head, here GSPMD keeps them where used).
    """

    def __init__(self, embed_fn, block_fn, head_loss_fn, embed_params, stacked_block_params,
                 head_params, mesh, n_micro: int, optimizer,
                 pp_axis: str = "pp", lr: float = 1e-3):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        self.n_micro = n_micro
        jmesh = mesh.jax_mesh
        self.n_stages = dict(zip(jmesh.axis_names, jmesh.devices.shape))[pp_axis]
        n_layers = jax.tree.leaves(stacked_block_params)[0].shape[0]
        assert n_layers % self.n_stages == 0, "layers must divide stages"
        self.layers_per_stage = n_layers // self.n_stages
        self._update = optimizer.update
        self.lr = lr

        pp_sharding = NamedSharding(jmesh, P(pp_axis))
        repl = NamedSharding(jmesh, P())
        # reshape blocks to [n_stages, layers_per_stage, ...] and shard stage axis
        self.block_params = jax.tree.map(
            lambda a: jax.device_put(a.reshape(self.n_stages, self.layers_per_stage, *a.shape[1:]),
                                     pp_sharding),
            stacked_block_params)
        self.embed_params = jax.tree.map(lambda a: jax.device_put(a, repl), embed_params)
        self.head_params = jax.tree.map(lambda a: jax.device_put(a, repl), head_params)
        # optimizer state mirrors the (reshaped, sharded) param tree
        self.opt_state = optimizer.init((self.embed_params, self.block_params, self.head_params))

        lps = self.layers_per_stage

        def stage_fn(stage_params, x):
            # stage = scan over this stage's blocks
            def body(h, layer_params):
                return block_fn(layer_params, h), None

            out, _ = jax.lax.scan(body, x, stage_params)
            return out

        per_rank = gpipe_spmd(stage_fn, self.n_stages, n_micro, pp_axis)

        def loss_fn(params, ids_mb, labels_mb):
            embed_p, block_p, head_p = params
            x_mb = jax.vmap(lambda ids: embed_fn(embed_p, ids))(ids_mb)
            y_mb = jax.shard_map(per_rank, mesh=jmesh,
                                 in_specs=(P(pp_axis), P()),
                                 out_specs=P(), check_vma=False)(block_p, x_mb)
            losses = jax.vmap(lambda y, lab: head_loss_fn(head_p, y, lab))(y_mb, labels_mb)
            return losses.mean()

        def step(params, opt_state, lr, ids_mb, labels_mb):
            loss, grads = jax.value_and_grad(loss_fn)(params, ids_mb, labels_mb)
            new_params, new_state = self._update(grads, opt_state, params, lr)
            return loss, new_params, new_state

        self._step = jax.jit(step, donate_argnums=(0, 1))

    def step(self, ids_microbatches, labels_microbatches) -> float:
        params = (self.embed_params, self.block_params, self.head_params)
        ids = ids_microbatches._data if isinstance(ids_microbatches, Tensor) else jnp.asarray(ids_microbatches)
        labels = labels_microbatches._data if isinstance(labels_microbatches, Tensor) else jnp.asarray(labels_microbatches)
        loss, (self.embed_params, self.block_params, self.head_params), self.opt_state = self._step(
            params, self.opt_state, jnp.asarray(self.lr, jnp.float32), ids, labels)
        return Tensor(loss)
