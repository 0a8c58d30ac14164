"""Sharded training engine: the compiled whole-program training step.

Role in the architecture (SURVEY §7.1): this is the TPU-native analogue of
the reference's StandaloneExecutor Plan/Job + auto_parallel Engine
(auto_parallel/static/engine.py — fit:1544/_parallel_pir:1014): the model
forward + loss + backward + optimizer update is traced ONCE into a single
XLA program, partitioned by GSPMD over the ProcessMesh, and executed per
step with zero python in the loop. Parameters live as sharded device
arrays owned by the engine between steps (the Layer is synced on demand).

Sharding sources:
- parameters carrying ``placements`` (set by TP layers / shard_tensor)
  keep them;
- everything else follows ``default_param_placements`` (replicated, or
  ZeRO-style Shard over the dp axis when ``shard_optimizer_states``);
- the batch is sharded over the dp axis (data parallelism);
- optimizer state follows the parameter sharding, except with
  ``shard_optimizer_states`` (ZeRO-1 semantics: reference
  DygraphShardingOptimizer) where fp32 state shards over dp.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..core.autograd import no_grad
from ..core.tensor import Parameter, Tensor
from ..observability import tracing as _trace
from ..optimizer import functional as fopt
from ..optimizer.lr import LRScheduler
from ..utils.functional import functional_call
from .mesh import Placement, ProcessMesh, Replicate, Shard, named_sharding, placements_to_spec


def _param_sharding(p: Parameter, mesh: ProcessMesh, zero_axis: Optional[str]) -> NamedSharding:
    if getattr(p, "placements", None):
        return named_sharding(p.process_mesh or mesh, p.placements, p.ndim)
    if zero_axis is not None:
        # ZeRO: shard the largest divisible dim over the zero axis
        size = mesh.get_dim_size(zero_axis)
        for d, s in enumerate(p._data.shape):
            if s % size == 0 and s >= size:
                spec = [None] * p.ndim
                spec[d] = zero_axis
                return NamedSharding(mesh.jax_mesh, PartitionSpec(*spec))
    return NamedSharding(mesh.jax_mesh, PartitionSpec())


def _place(arr, sharding) -> jax.Array:
    """Host-complete value -> sharded global array (shared pod data-path
    rule; see distributed.api.put_global)."""
    from .api import put_global

    return put_global(arr, sharding, process_local=False)


class ShardedTrainStep:
    """Build and run a pjit training step for a Layer.

    loss_fn(outputs, *labels) -> scalar Tensor.
    """

    def __init__(self, model, loss_fn: Callable, optimizer, mesh: ProcessMesh,
                 dp_axis: str = "dp", batch_spec: Optional[Sequence] = None,
                 label_spec: Optional[Sequence] = None, grad_clip_norm: Optional[float] = None,
                 shard_optimizer_states: bool = False,
                 remat: "bool | str" = False,
                 donate: bool = True):
        self.model = model
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.dp_axis = dp_axis if dp_axis in mesh.dim_names else None
        self._eager_opt = optimizer
        # optimizer=None: forward/backward machinery only — the caller owns
        # the update (HostOffloadTrainStep keeps state in pinned host
        # memory; eagerly allocating device m/v here would defeat it).
        self._fopt = (fopt.from_eager(optimizer)
                      if optimizer is not None else None)
        self.grad_clip_norm = grad_clip_norm
        if grad_clip_norm is None and getattr(optimizer, "_grad_clip", None) is not None:
            clip = optimizer._grad_clip
            self.grad_clip_norm = getattr(clip, "clip_norm", None)
        if isinstance(remat, str):
            import jax as _jax
            if not hasattr(_jax.checkpoint_policies, remat):
                raise ValueError(
                    f"unknown remat policy {remat!r}; valid: nothing_saveable, "
                    "everything_saveable, dots_saveable, "
                    "dots_with_no_batch_dims_saveable")
        self._remat = remat
        self._donate = donate

        self._param_objs: Dict[str, Parameter] = model.named_parameters_dict()
        self._buffer_objs: Dict[str, Tensor] = model.named_buffers_dict()
        zero_axis = dp_axis if (shard_optimizer_states and self.dp_axis) else None

        self._param_shardings = {
            k: _param_sharding(p, mesh, zero_axis) for k, p in self._param_objs.items()
        }
        self._replicated = NamedSharding(mesh.jax_mesh, PartitionSpec())
        # live sharded state
        self.params = {
            k: _place(p._data, self._param_shardings[k]) for k, p in self._param_objs.items()
        }
        self.buffers = {k: _place(b._data, self._replicated)
                        for k, b in self._buffer_objs.items()}
        self.opt_state = (self._shard_opt_state(self._fopt.init(self.params))
                          if self._fopt is not None else None)
        self._step_fn = None
        # the linear weights whose gradient the traced step makes from
        # factors written once (``_build``), and their share of the
        # parameters
        self._fused = {"fused_leaves": 0, "fused_param_share": 0.0}
        self._batch_spec = batch_spec
        self._label_spec = label_spec
        # HBM-ledger attribution: the engine owns the two big persistent
        # device footprints of a training process. Weakref'd so a dead
        # engine drops out of the ledger instead of pinning its arrays.
        import weakref

        from ..observability import perf as _perf

        ref = weakref.ref(self)

        def _weight_bytes(ref=ref):
            eng = ref()
            if eng is None:
                return None
            return {"bytes": int(sum(v.nbytes for v in eng.params.values())
                                 + sum(v.nbytes
                                       for v in eng.buffers.values()))}

        def _opt_bytes(ref=ref):
            eng = ref()
            if eng is None or eng.opt_state is None:
                return None
            leaves = jax.tree.leaves(eng.opt_state)
            return {"bytes": int(sum(getattr(x, "nbytes", 0)
                                     for x in leaves))}

        _perf.register_memory_component("model_weights", _weight_bytes)
        _perf.register_memory_component("optimizer_state", _opt_bytes)

    # ------------------------------------------------------------------
    def _shard_opt_state(self, state):
        """Place optimizer state explicitly: per-param state follows the
        parameter's sharding (dict subtrees keyed by param name); scalars
        (step counters) are replicated. This is where ZeRO state sharding
        becomes real — with ``shard_optimizer_states`` the param shardings
        carry the dp-axis shard, and fp32 m/v inherit it here."""

        def place(subtree):
            if isinstance(subtree, dict) and set(subtree) == set(self.params):
                return {k: _place(v, self._param_shardings[k]) for k, v in subtree.items()}
            return jax.tree.map(lambda x: _place(x, self._replicated), subtree)

        return {k: place(v) for k, v in state.items()}

    def _data_sharding(self, ndim, spec):
        if spec is not None:
            return NamedSharding(self.mesh.jax_mesh, spec)
        if self.dp_axis is None:
            return self._replicated
        entries = [self.dp_axis] + [None] * (ndim - 1)
        return NamedSharding(self.mesh.jax_mesh, PartitionSpec(*entries))

    def _make_forward_loss(self):
        """The (params, buffers, inputs, labels) -> scalar loss closure,
        remat applied — shared by the standard step and the host-offload
        accumulating step (distributed/offload.py)."""
        model, loss_fn = self.model, self.loss_fn

        def forward_loss(params, buffers, inputs, labels):
            def run(params):
                # no_grad: the outer jax.value_and_grad owns differentiation;
                # letting the eager tape also record would make every op's
                # jax.vjp part of the traced graph — wasted work, and JVP
                # through Pallas kernels (flash attention) is unsupported
                with no_grad():
                    outs = functional_call(model, {**{k: v for k, v in params.items()},
                                                   **{k: v for k, v in buffers.items()}},
                                           *[Tensor(x) for x in inputs])
                    outs_t = outs if isinstance(outs, (list, tuple)) else (outs,)
                    loss = loss_fn(*outs_t, *[Tensor(y) for y in labels])
                return loss._data if isinstance(loss, Tensor) else loss

            if self._remat:
                if isinstance(self._remat, str):
                    # selective policy (reference recompute.py:124 'mode'):
                    # e.g. 'dots_saveable' keeps MXU outputs and recomputes
                    # only elementwise — recovers most of blanket-remat's
                    # MFU loss while bounding activation memory
                    from .fleet.recompute import remat as _remat_policy
                    run = _remat_policy(run, policy=self._remat)
                else:
                    run = jax.checkpoint(run)
            return run(params)

        return forward_loss

    def _one_program_one_device(self) -> bool:
        """What the step that was measured looks like (PERF.md section 6,
        PR 41): AdamW, the mesh shards neither batch nor parameters, no
        gradient is seen whole before its update (no clip by the global
        norm) and nothing is rematerialised. Any other step is traced as
        it always was."""
        from ..optimizer.optimizer import AdamW

        return (isinstance(self._eager_opt, AdamW)
                and self.grad_clip_norm is None and not self._remat
                and self.dp_axis is None and self._batch_spec is None
                and self._label_spec is None
                and not any(getattr(p, "placements", None)
                            for p in self._param_objs.values()))

    def _build(self):
        from ..nn import functional as F

        f = self._fopt
        clip_norm = self.grad_clip_norm
        forward_loss = self._make_forward_loss()
        factors_once = self._one_program_one_device()

        def step(params, opt_state, lr, inputs, labels):
            # ``linear`` reads the slot while it is traced, forward and
            # backward both inside this call
            routed = F._factors_once.routed = [] if factors_once else None
            try:
                loss, grads = jax.value_and_grad(forward_loss)(params, self.buffers, inputs, labels)
            finally:
                F._factors_once.routed = None
            weights = {id(w): w.size for w in routed or ()}
            self._fused = {
                "fused_leaves": len(weights),
                "fused_param_share": sum(weights.values()) / max(1, sum(
                    p.size for p in params.values()))}
            if clip_norm is not None:
                grads, _ = fopt.clip_by_global_norm(grads, clip_norm)
            new_params, new_state = f.update(grads, opt_state, params, lr)
            # keep placements stable across steps
            new_params = {k: jax.lax.with_sharding_constraint(v, self._param_shardings[k])
                          for k, v in new_params.items()}
            return loss, new_params, new_state

        donate = (0, 1) if self._donate else ()
        self._step_fn = jax.jit(step, donate_argnums=donate)

    # ------------------------------------------------------------------
    def _stage_batch(self, inputs, labels):
        """Normalize + device_put one batch with the engine's data specs;
        lazily builds the compiled step.

        Multi-controller (one process per host, the TPU pod execution
        model): each process passes its PROCESS-LOCAL batch shard and the
        global array is assembled with make_array_from_process_local_data
        — jax.device_put cannot target non-addressable devices (reference
        role: fleet's per-rank data feeding into the hybrid program)."""
        inputs = inputs if isinstance(inputs, (list, tuple)) else (inputs,)
        labels = labels if isinstance(labels, (list, tuple)) else (labels,)
        multi = jax.process_count() > 1

        def put(x, spec):
            from .api import put_global

            data = x._data if isinstance(x, Tensor) else jnp.asarray(x)
            sharding = self._data_sharding(data.ndim, spec)
            # a pre-placed DistTensor batch (ShardDataloader) is already
            # global — hand it to jit as-is
            if multi and getattr(data, "sharding", None) == sharding:
                return data
            return put_global(data, sharding, process_local=multi)

        in_datas = tuple(put(x, self._batch_spec) for x in inputs)
        lab_datas = tuple(put(y, self._label_spec) for y in labels)
        if self._step_fn is None:
            self._build()
        return in_datas, lab_datas

    def step(self, inputs, labels) -> Tensor:
        """One optimizer step. inputs/labels: Tensor or tuple of Tensors.

        ``train.dispatch`` (entry to the return of the jitted call:
        staging the batch and the enqueue) is the trainer's one host
        phase inside the program; the step itself runs on the device
        after this returns."""
        _trace.watch_process()   # the lane ``proc``; idempotent
        args = {"step": self._eager_opt._step_count}
        with _trace.profiled_span("train.dispatch", "train", "train", args):
            in_datas, lab_datas = self._stage_batch(inputs, labels)
            lr = jnp.asarray(self._eager_opt.get_lr(), jnp.float32)
            loss, self.params, self.opt_state = self._step_fn(
                self.params, self.opt_state, lr, in_datas, lab_datas)
            args.update(self._fused)    # known once the step has traced
        self._eager_opt._step_count += 1
        if isinstance(self._eager_opt._learning_rate, LRScheduler):
            pass  # user drives scheduler.step() as in eager flow
        return Tensor(loss)

    def eval_step(self, inputs, labels=None):
        raise NotImplementedError("use to_static on the model for eval; engine.step is the train path")

    def _lowered(self, inputs, labels):
        """Lower the step from avals (no device allocation)."""
        in_datas, lab_datas = self._stage_batch(inputs, labels)

        def aval(x):
            sh = getattr(x, "sharding", None)
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

        lr = jax.ShapeDtypeStruct((), jnp.float32)
        return self._step_fn.lower(
            jax.tree.map(aval, self.params), jax.tree.map(aval, self.opt_state),
            lr, jax.tree.map(aval, in_datas), jax.tree.map(aval, lab_datas),
        )

    def lowered_text(self, inputs, labels) -> str:
        """StableHLO text of the train step as jit lowers it for this
        backend — which kernels the step really contains (a Pallas
        kernel shows as a ``tpu_custom_call``). Traces, never compiles."""
        return self._lowered(inputs, labels).as_text()

    def _aot_compiled(self, inputs, labels):
        """AOT-compile the step for the XLA analyses below. Does not
        share jit's dispatch cache, so each call costs one extra compile
        — callers wanting both analyses should reuse the returned
        object."""
        return self._lowered(inputs, labels).compile()

    def memory_analysis(self, inputs, labels):
        """XLA's compiled-program HBM breakdown for the train step (device
        memory_stats is process-cumulative and unavailable on some PJRT
        transports). Returns dict of byte sizes: args/outputs/temps/
        generated_code — extracted through the one shared path in
        ``observability.perf`` (same fallbacks as the serving ledger)."""
        from ..observability.perf import extract_memory_analysis

        return extract_memory_analysis(self._aot_compiled(inputs, labels))

    def cost_analysis(self, inputs, labels):
        """XLA's per-execution cost model for the compiled step (flops /
        bytes accessed). Used by bench.py to compute MFU for conv models
        where the 6N-per-token LLM estimate does not apply. NOTE: for a
        GSPMD-partitioned step the numbers are PER PARTITION (one
        device's share), matching the per-chip MFU convention. Extraction
        routes through ``observability.perf`` — one cost path, one set
        of PJRT-absent fallbacks."""
        from ..observability.perf import extract_cost_analysis

        return extract_cost_analysis(self._aot_compiled(inputs, labels))

    # ------------------------------------------------------------------
    def sync_weights_to_model(self):
        """Copy engine-owned params back onto the Layer (for save/eval).

        Copies, not aliases: the step function donates ``self.params``, so
        handing the live buffers to the Layer would let the next step()
        delete the Layer's weights."""
        for k, p in self._param_objs.items():
            p._data = jnp.copy(self.params[k])
        for k, b in self._buffer_objs.items():
            b._data = jnp.copy(self.buffers[k])

    def sync_weights_from_model(self):
        """Push Layer weights into the engine's live (sharded) params —
        required after set_state_dict, or loaded checkpoints would be
        silently ignored by the compiled step. Optimizer moments are kept
        (matching resume semantics where opt state is loaded separately)."""
        for k, p in self._param_objs.items():
            self.params[k] = _place(jnp.asarray(p._data),
                                    self._param_shardings[k])
        for k, b in self._buffer_objs.items():
            self.buffers[k] = _place(jnp.asarray(b._data), self._replicated)

    def state_dict(self):
        self.sync_weights_to_model()
        return self.model.state_dict()


def parallelize(model, optimizer, loss_fn, mesh: ProcessMesh, **kwargs) -> ShardedTrainStep:
    """Parity entry point (reference: paddle.distributed.to_static /
    DistModel, auto_parallel/api.py:2715): wrap model+optimizer+loss into a
    compiled, mesh-partitioned train step."""
    return ShardedTrainStep(model, loss_fn, optimizer, mesh, **kwargs)
