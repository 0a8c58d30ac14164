"""Host-memory offload for optimizer state (ZeRO-Offload, TPU-native).

Parity: the reference's stage-3 offload and static offload pass —
fleet/meta_parallel/sharding/group_sharded_stage3.py:110,127,187 (param
fp16/fp32-master cpu placement, `offload=True`) and
fleet/meta_optimizers/sharding/offload_helper.py (optimizer-state →
pinned CPU memory with h2d/d2h copies around the update).

TPU design: the state lives in PJRT's ``pinned_host`` memory space
(jax memory kinds) instead of CUDA pinned buffers, and the h2d/d2h
copies are IN-PROGRAM ``jax.device_put`` transfers to/from
``jax.memory.Space.Device`` — XLA's latency-hiding scheduler overlaps
the streaming with the update math. The AdamW math keeps a true fp32
master copy on the host (reference multi_precision semantics), so the
device only ever holds bf16 params, grads, and one parameter's state
in flight.

Measured on v5e: ~12 GB/s sustained host<->device state traffic, so a
2B-param AdamW step (48 GB of fp32 master+m+v traffic) costs ~4 s —
amortized below 20% overhead with >=96k tokens per optimizer step via
gradient accumulation (bench.py big2b point).

Backends whose PJRT plugin lacks in-program memory-space annotation
(XLA:CPU) fall back to eager device_put staging around a plain jitted
update — same semantics and the same host-resident state, less overlap.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.memory import Space
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

__all__ = ["HostOffloadAdamW", "host_sharding", "host_memory_kind",
           "device_memory_kind", "supports_inline_transfers"]


def host_memory_kind() -> str:
    """The backend's host-RAM memory kind for ``device_put`` /
    ``with_memory_kind`` placement — the public discovery helper for
    anything that parks arrays in host memory next to the device
    (optimizer-state offload here; the serving KV tier's pinned-host
    residency planning).

    Returns ``"pinned_host"`` where the backend exposes it (TPU; newer
    CPU jax). Backends without it degrade rather than fail: older
    XLA:CPU only advertises ``"unpinned_host"`` (functionally the same
    host residency), and a backend with a single memory space falls all
    the way back to the device's default kind — so the helper always
    returns a placeable kind, never raises."""
    kinds = [m.kind for m in jax.devices()[0].addressable_memories()]
    if "pinned_host" in kinds:
        return "pinned_host"
    for k in kinds:
        if "host" in k:
            return k
    return jax.devices()[0].default_memory().kind


def device_memory_kind() -> str:
    """The backend's fast (HBM) memory kind — ``"device"`` where the
    backend has distinct device memory (TPU). On single-memory-space
    backends (older XLA:CPU) this equals :func:`host_memory_kind`'s
    fallback: both name the one default space, which is what makes the
    offload paths no-op-safe there."""
    kinds = [m.kind for m in jax.devices()[0].addressable_memories()]
    return "device" if "device" in kinds else jax.devices()[0].default_memory().kind


# internal/back-compat aliases (sharding.py and older callers)
_host_memory_kind = host_memory_kind
_device_memory_kind = device_memory_kind


def host_sharding(sharding=None):
    """The pinned-host twin of a (device) sharding."""
    if sharding is None:
        return SingleDeviceSharding(jax.devices()[0],
                                    memory_kind=_host_memory_kind())
    return sharding.with_memory_kind(_host_memory_kind())


def supports_inline_transfers() -> bool:
    """True when the backend lowers in-program memory-space transfers
    (annotate_device_placement); XLA:CPU currently does not."""
    return jax.default_backend() not in ("cpu",)


def _adamw_math(master, m, v, g, lr, t, beta1, beta2, eps, wd):
    # single source of AdamW truth: optimizer.py's raw update (lr_ratio=1);
    # here `master` IS the fp32 param, so the returned "new param" is the
    # new master
    from ..optimizer.optimizer import _adamw_update_math

    return _adamw_update_math(master, g, m, v, lr, beta1, beta2, eps, t,
                              wd, jnp.float32(1.0))


def make_streamed_update(body, n_host: int, n_rest: int, host_sh, dev_sh,
                         out_host: Sequence[int], out_dev: Sequence[int],
                         donate_rest: Sequence[int] = ()):
    """Compile ``body(*host_args_on_device, *rest) -> outs`` with the first
    ``n_host`` arguments resident in pinned host memory, streamed through
    the device in-program (TPU) or staged eagerly (backends without
    in-program memory-space annotation, e.g. XLA:CPU).

    out_host/out_dev: indices of body outputs that return to host /
    stay on device. Host inputs are always donated (their buffers are
    replaced by the returned state); donate_rest names additional
    ABSOLUTE argument indices the caller promises not to reuse (e.g. the
    old param buffer an eager optimizer overwrites in place).

    The single implementation of the h2d→update→d2h schedule shared by
    HostOffloadAdamW (functional path) and sharding._wrap_adamw_offload
    (eager AdamW path) — reference offload_helper.py's per-param copy
    schedule."""
    donate = tuple(range(n_host)) + tuple(donate_rest)
    if supports_inline_transfers():
        def upd(*args):
            staged = [jax.device_put(a, Space.Device)
                      for a in args[:n_host]]
            outs = list(body(*staged, *args[n_host:]))
            for i in out_host:
                outs[i] = jax.device_put(outs[i], Space.Host)
            return tuple(outs)

        n_out = len(out_host) + len(out_dev)
        out_shardings = tuple(host_sh if i in out_host else dev_sh
                              for i in range(n_out))
        return jax.jit(upd,
                       in_shardings=(host_sh,) * n_host + (None,) * n_rest,
                       out_shardings=out_shardings,
                       donate_argnums=donate)

    # single-memory backends (older XLA:CPU): the host->device staging
    # device_put is an alias, so donating the staged buffer would delete
    # the caller's live array — skip donation there (tests only; TPU has
    # distinct memories and keeps the donate path)
    same_memory = _device_memory_kind() == _host_memory_kind()
    body_jit = jax.jit(body, donate_argnums=() if same_memory else donate)
    dev_stage = host_sh.with_memory_kind(_device_memory_kind())

    def upd_eager(*args):
        staged = [jax.device_put(a, dev_stage) for a in args[:n_host]]
        outs = list(body_jit(*staged, *args[n_host:]))
        for i in out_host:
            outs[i] = jax.device_put(outs[i], host_sh)
        return tuple(outs)

    return upd_eager


class HostOffloadAdamW:
    """AdamW whose fp32 master params + moments live in pinned host
    memory; device keeps only the working-precision params.

    update() walks parameters one-by-one through a per-shape cached
    jitted program (host state streams through the device), bounding
    device-resident state to one parameter at a time — the TPU analogue
    of offload_helper.py's per-param h2d→update→d2h schedule.
    """

    def __init__(self, beta1: float = 0.9, beta2: float = 0.999,
                 epsilon: float = 1e-8, weight_decay: float = 0.01,
                 mesh=None):
        self.beta1, self.beta2, self.eps = beta1, beta2, epsilon
        self.wd = weight_decay
        self._mesh = mesh
        self._fns: Dict = {}
        self._inline = supports_inline_transfers()

    # -- state ----------------------------------------------------------
    def _host_sharding_for(self, arr):
        if self._mesh is not None:
            return NamedSharding(self._mesh, PartitionSpec(),
                                 memory_kind="pinned_host")
        return host_sharding()

    def init(self, params: Dict[str, jax.Array]) -> Dict[str, Dict]:
        """Host-resident {name: {master(f32), m(f32), v(f32)}} + step t."""
        state = {}
        for k, p in params.items():
            sh = self._host_sharding_for(p)
            master = jax.device_put(p.astype(jnp.float32), sh)
            zeros = jnp.zeros(p.shape, jnp.float32)
            state[k] = {"master": master,
                        "m": jax.device_put(zeros, sh),
                        "v": jax.device_put(jnp.zeros(p.shape, jnp.float32), sh)}
        state["@t"] = 0
        return state

    # -- per-shape compiled update -------------------------------------
    def _fn_for(self, shape, pdtype, host_sh, dev_sh):
        # shardings are part of the key: same-shaped params may be placed
        # differently (e.g. an exclude_layer replica next to a dp shard)
        key = (shape, str(pdtype), host_sh, dev_sh, self._inline)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        beta1, beta2, eps, wd = self.beta1, self.beta2, self.eps, self.wd

        def body(master, m, v, g, lr, t):
            master2, m2, v2 = _adamw_math(master, m, v, g, lr, t,
                                          beta1, beta2, eps, wd)
            return master2, m2, v2, master2.astype(pdtype)

        fn = make_streamed_update(body, n_host=3, n_rest=3,
                                  host_sh=host_sh, dev_sh=dev_sh,
                                  out_host=(0, 1, 2), out_dev=(3,))
        self._fns[key] = fn
        return fn

    def update(self, grads: Dict[str, jax.Array],
               state: Dict, params: Dict[str, jax.Array], lr):
        """One AdamW step; returns (new_params, new_state). Host state
        buffers are donated — the caller must drop its references."""
        t = state["@t"] + 1
        t_arr = jnp.asarray(float(t), jnp.float32)
        lr_arr = jnp.asarray(lr, jnp.float32)
        new_params, new_state = {}, {"@t": t}
        for k, p in params.items():
            g = grads[k]
            if g is None:
                new_params[k] = p
                new_state[k] = state[k]
                continue
            st = state[k]
            dev_sh = getattr(p, "sharding", None) or SingleDeviceSharding(
                jax.devices()[0])
            host_sh = st["master"].sharding
            fn = self._fn_for(tuple(p.shape), p.dtype, host_sh, dev_sh)
            master, m, v, new_p = fn(st["master"], st["m"], st["v"], g,
                                     lr_arr, t_arr)
            new_state[k] = {"master": master, "m": m, "v": v}
            new_params[k] = new_p
        return new_params, new_state

    # -- introspection (tests / checkpointing) -------------------------
    @staticmethod
    def state_memory_kinds(state) -> set:
        kinds = set()
        for k, st in state.items():
            if k == "@t":
                continue
            for arr in st.values():
                kinds.add(arr.sharding.memory_kind)
        return kinds


class HostOffloadTrainStep:
    """Gradient-accumulating train step with host-offloaded AdamW state.

    The device holds bf16 params + a grad accumulator; fp32 master/m/v
    live in pinned host memory and stream through the chip once per
    ``accum_steps`` micro-batches — the configuration that fits ~2B
    params on one 16 GB chip (reference analogue: group_sharded stage-3
    `offload=True` + gradient_merge).
    """

    def __init__(self, model, loss_fn, mesh, *, accum_steps: int = 16,
                 learning_rate: float = 1e-4, weight_decay: float = 0.01,
                 remat="dots_with_no_batch_dims_saveable",
                 accum_dtype=jnp.float32):
        from .engine import ShardedTrainStep

        self._engine = ShardedTrainStep(model, loss_fn, None,
                                        mesh, dp_axis=None, remat=remat,
                                        donate=False)
        self.lr = learning_rate
        self.accum_steps = accum_steps
        self.accum_dtype = accum_dtype
        multi = len(mesh.jax_mesh.devices.flat) > 1
        self.opt = HostOffloadAdamW(weight_decay=weight_decay,
                                    mesh=mesh.jax_mesh if multi else None)
        self.params = self._engine.params
        # the engine's copy of the params dict would pin the pre-update
        # buffers forever (a full extra param footprint after step 1)
        self._engine.params = None
        self.opt_state = self.opt.init(self.params)
        self._accum_fn = None
        self._micro = 0
        self.grad_acc = None

    def _build_accum(self):
        forward_loss = self._engine._make_forward_loss()
        scale = 1.0 / float(self.accum_steps)
        acc_dt = self.accum_dtype

        def accum(params, acc, inputs, labels):
            loss, grads = jax.value_and_grad(forward_loss)(
                params, self._engine.buffers, inputs, labels)
            new_acc = jax.tree.map(
                lambda a, g: a + (g * scale).astype(acc_dt), acc, grads)
            return loss, new_acc

        self._accum_fn = jax.jit(accum, donate_argnums=(1,))

    def _zero_acc(self):
        return {k: jnp.zeros(p.shape, self.accum_dtype)
                for k, p in self.params.items()}

    def step(self, inputs, labels):
        """One micro-batch; applies the offloaded update every
        accum_steps calls. Returns the micro-batch loss."""
        in_datas, lab_datas = self._engine._stage_batch(inputs, labels)
        if self._accum_fn is None:
            self._build_accum()
        if self.grad_acc is None:
            self.grad_acc = self._zero_acc()
        loss, self.grad_acc = self._accum_fn(self.params, self.grad_acc,
                                             in_datas, lab_datas)
        self._micro += 1
        if self._micro % self.accum_steps == 0:
            self.params, self.opt_state = self.opt.update(
                self.grad_acc, self.opt_state, self.params, self.lr)
            self.grad_acc = None
            # write back into the model's Parameters: keeps the model
            # live AND releases the pre-update buffers (the Parameter
            # objects are the only remaining reference to them)
            for k, p in self._engine._param_objs.items():
                p._data = self.params[k]
        from ..core.tensor import Tensor

        return Tensor(loss)


