"""DistributedTrainStep: the hybrid-parallel compiled train step.

This is where the reference's whole runtime distributed machinery lands on
TPU: fleet.distributed_model + HybridParallelOptimizer + EagerReducer grad
bucketing + GroupSharded stages + mp/sp collectives (SURVEY §2.3) become ONE
jax.jit over the hybrid mesh with:

- params placed by NamedSharding from Parameter.dist_attr (TP layers set
  column/row specs; sharding stage 3 adds FSDP specs),
- optimizer states sharded over the `sharding` axis (ZeRO-1/2; reference
  DygraphShardingOptimizer dygraph_sharding_optimizer.py:54),
- batch sharded over (dp, sharding) — grad reduction becomes XLA's
  reduce-scatter/all-reduce over ICI, replacing EagerReducer bucketing
  (paddle/fluid/distributed/collective/reducer.cc),
- everything else (clip, AMP, update) inherited from jit.TrainStep.
"""

from __future__ import annotations

import os
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..framework.core import Tensor
from ..jit import TrainStep, _unwrap_pytree
from ..observability import spans as _obs_spans
from . import env as _env

__all__ = ["DistributedTrainStep", "fsdp_spec", "shard_params_for_stage3",
           "host_memory_kind"]


def host_memory_kind(mesh):
    """The memory kind offloaded optimizer states live in: "pinned_host" on
    TPU. XLA:CPU compiles memory-kind placements away — every buffer is
    host RAM, and a compiled program's outputs come back in the default
    kind whatever the program asked for — so on the CPU backend the default
    kind IS the host kind and offload runs the same code path as a no-op
    placement."""
    dev = next(iter(mesh.devices.flat))
    if dev.platform == "cpu":
        return dev.default_memory().kind
    return "pinned_host"


def fsdp_spec(shape, axis="sharding", mesh=None, existing=None):
    """Shard the largest dim divisible by the axis size; replicate otherwise.
    Respects dims already taken by an existing spec (TP)."""
    mesh = mesh or _env.default_mesh()
    size = mesh.shape.get(axis, 1)
    if size <= 1 or not shape:
        return existing
    used = set()
    base = list(existing) if existing is not None else [None] * len(shape)
    while len(base) < len(shape):
        base.append(None)
    for i, s in enumerate(base):
        if s is not None:
            used.add(i)
            # axis already mapped (e.g. stage-3 params feeding _update_spec)
            if s == axis or (isinstance(s, tuple) and axis in s):
                return P(*base)
    # pick largest divisible unused dim
    cands = [
        (shape[i], i) for i in range(len(shape))
        if i not in used and shape[i] % size == 0 and shape[i] >= size
    ]
    if not cands:
        return P(*base) if existing is not None else None
    _, dim = max(cands)
    base[dim] = axis
    return P(*base)


def shard_params_for_stage3(model, axis="sharding", mesh=None):
    """Annotate every parameter with an FSDP spec (GroupShardedStage3 analog,
    reference: group_sharded_stage3.py:85)."""
    for _, p in model.named_parameters():
        existing = getattr(p, "dist_attr", None)
        p.dist_attr = fsdp_spec(tuple(p.shape), axis, mesh, existing)


def _bucket_tag(shardings):
    """Identity on a tuple of param values whose VJP applies the grad's
    reduce-scatter sharding constraint AT THE POINT the backward produces
    the bucket's cotangents — i.e. per-layer inside the backward, where XLA
    can overlap the collective with the remaining backward compute — rather
    than at the step-end consumption site. The optimization_barrier ties the
    bucket's grads together so their reduce-scatters issue as one group
    (EagerReducer bucket semantics, reference reducer.cc)."""

    @jax.custom_vjp
    def tag(*xs):
        return xs

    def tag_fwd(*xs):
        return xs, None

    def tag_bwd(_, gs):
        gs = jax.lax.optimization_barrier(tuple(gs))
        return tuple(jax.lax.with_sharding_constraint(g, s)
                     for g, s in zip(gs, shardings))

    tag.defvjp(tag_fwd, tag_bwd)
    return tag


class DistributedTrainStep(TrainStep):
    def __init__(self, model, loss_fn, optimizer, mesh=None,
                 input_specs=None, label_specs=None, sharding_stage=None,
                 offload=False, batch_axes=("dp", "sharding"),
                 comm_overlap=None, **kw):
        self.mesh = mesh or _env.default_mesh()
        _env.set_global_mesh(self.mesh)
        if sharding_stage is None:
            # group_sharded_parallel() annotates the optimizer
            sharding_stage = getattr(optimizer, "_sharding_stage", 0)
        self.sharding_stage = sharding_stage
        self.offload = offload or getattr(optimizer, "_sharding_offload", False)
        self.batch_axes = tuple(a for a in batch_axes if self.mesh.shape.get(a, 1) >= 1)
        self.input_specs = input_specs
        self.label_specs = label_specs
        # comm_overlap (default on; PADDLE_TPU_COMM_OVERLAP=0 restores the
        # exposed-collective step for A/B runs): in-backward reduce-scatter
        # bucket tags + in-program offload streaming + overlap-attributed
        # host transfers. Fixed at construction — it shapes the compiled
        # program, so an A/B needs two instances, not a flag flip.
        if comm_overlap is None:
            comm_overlap = os.environ.get("PADDLE_TPU_COMM_OVERLAP", "1") != "0"
        self.comm_overlap = bool(comm_overlap)
        self._host_kind = host_memory_kind(self.mesh)
        self._bucket_plan = None
        # MoE a2a records registered by THIS step's traces: __call__ marks
        # the registry before each dispatch, _post_dispatch claims whatever
        # that call's (re)trace registered — a shape-change retrace
        # replaces the emitted set instead of leaving it stale, and records
        # from another model's build can never land in this step's window
        from . import moe_comm as _moe_comm

        self._moe_a2a = None
        self._moe_pre = _moe_comm.trace_marker()
        self._moe_t0 = 0
        if sharding_stage == 3:
            shard_params_for_stage3(model, mesh=self.mesh)
        super().__init__(model, loss_fn, optimizer, **kw)
        self._place_state()

    # ------------------------------------------------------------------ #

    def _param_spec(self, name):
        p = self._state.params[name]
        spec = getattr(p, "dist_attr", None)
        if spec is None:
            spec = P()
        return spec

    def _opt_state_spec(self, name, state_key, arr):
        pspec = self._param_spec(name)
        pshape = tuple(self._state.params[name].shape)
        if tuple(arr.shape) == pshape:
            # moment tensors follow the param layout, plus ZeRO sharding
            if self.sharding_stage in (1, 2) and self.mesh.shape.get("sharding", 1) > 1:
                s = fsdp_spec(tuple(arr.shape), "sharding", self.mesh, pspec)
                return s if s is not None else pspec
            return pspec
        return P()

    def _update_spec(self, name):
        """The spec the optimizer update runs under: the grad's owner shard
        (reference: GroupShardedStage2 reduce-scatter-to-rank,
        group_sharded_stage2.py:47)."""
        pspec = self._param_spec(name)
        if self.sharding_stage in (2, 3) and self.mesh.shape.get("sharding", 1) > 1:
            s = fsdp_spec(tuple(self._state.params[name].shape),
                          "sharding", self.mesh, pspec)
            if s is not None:
                return s
        return pspec

    def _shard_grad(self, name, g):
        spec = self._update_spec(name)
        if spec == self._param_spec(name):
            return g
        # XLA lowers this to a reduce-scatter over ICI instead of the
        # all-reduce the replicated-grad path would use
        return jax.lax.with_sharding_constraint(g, self._sharding(spec))

    def _shard_param_for_update(self, name, pv):
        spec = self._update_spec(name)
        if spec == self._param_spec(name):
            return pv
        return jax.lax.with_sharding_constraint(pv, self._sharding(spec))

    def _restore_param(self, name, np_):
        # all-gather fresh shards back to the param layout (stage 2; stage 3
        # params stay sharded because _param_spec == _update_spec there)
        return jax.lax.with_sharding_constraint(
            np_, self._sharding(self._param_spec(name)))

    # -- comm/compute overlap: in-backward grad reduce-scatter ----------- #

    def _grad_bucket_plan(self):
        """[(param names, bucket tag fn)] in REVERSE topological order (the
        order the backward pass produces grads), bucketed by cumulative
        bytes (PADDLE_TPU_RS_BUCKET_MB, default 25 — the EagerReducer
        bucket size). Only params whose update layout differs from their
        param layout are tagged; the rest have no reduce-scatter to place."""
        if self._bucket_plan is not None:
            return self._bucket_plan
        plan = []
        if (self.comm_overlap and self.sharding_stage in (2, 3)
                and self.mesh.shape.get("sharding", 1) > 1):
            cap = float(os.environ.get("PADDLE_TPU_RS_BUCKET_MB", "25")) * 1e6
            names, shards, size = [], [], 0.0
            for name in reversed(list(self._state.params)):
                spec = self._update_spec(name)
                if spec == self._param_spec(name):
                    continue  # grad already produced in its update layout
                p = self._state.params[name]
                names.append(name)
                shards.append(self._sharding(spec))
                size += (int(np.prod(p.shape))
                         * jnp.dtype(p.dtype).itemsize)
                if size >= cap:
                    plan.append((tuple(names), _bucket_tag(tuple(shards))))
                    names, shards, size = [], [], 0.0
            if names:
                plan.append((tuple(names), _bucket_tag(tuple(shards))))
        self._bucket_plan = plan
        return plan

    def _tag_grad_buckets(self, p):
        plan = self._grad_bucket_plan()
        if not plan:
            return p
        p = dict(p)
        for names, tag in plan:
            for name, v in zip(names, tag(*(p[n] for n in names))):
                p[name] = v
        return p

    # -- comm/compute overlap: offload state streaming ------------------- #

    def _offload_streaming(self):
        """In-program host<->device streaming of the optimizer states: the
        compiled program itself device_puts them in at the start and back to
        host memory per-param after each update, so XLA overlaps the copies
        with compute instead of the host serializing them around the step."""
        return self.offload and self.comm_overlap

    def _fetch_opt_states(self, opt_states):
        if not self._offload_streaming():
            return opt_states
        return {
            k: {sk: jax.device_put(
                    sv, self._sharding(self._opt_state_spec(k, sk, sv)))
                if hasattr(sv, "shape") else sv
                for sk, sv in st.items()}
            for k, st in opt_states.items()
        }

    def _emit_opt_state(self, name, st):
        if not self._offload_streaming():
            return st
        return {sk: jax.device_put(
                    sv, self._sharding(self._opt_state_spec(name, sk, sv),
                                       host=True))
                if hasattr(sv, "shape") else sv
                for sk, sv in st.items()}

    def _post_dispatch(self):
        # MoE expert-parallel a2a accounting: the traced MoE fast path
        # registered its per-step dispatch/combine all-to-all volume during
        # this program's trace (moe_comm.note_a2a); any (re)trace inside
        # THIS call's window replaces the claimed set, and every call
        # re-emits it as collective_{calls,bytes}_total{op="all_to_all"} +
        # estimated comm_task(kind="a2a") intervals — anchored inside this
        # step's compute span (floored at the dispatch start), mirroring
        # how the chunked schedule overlaps them on device.
        from . import moe_comm as _moe_comm

        fresh = _moe_comm.drain_since(self._moe_pre)
        if fresh or self._moe_a2a is None:
            self._moe_a2a = fresh
        _moe_comm.emit_step(self._moe_a2a, floor_ns=self._moe_t0)

    def _sharding(self, spec, host=False):
        kind = self._host_kind if host else None
        return NamedSharding(self.mesh, spec if spec is not None else P(),
                             memory_kind=kind)

    def _place_state(self):
        """device_put params/opt-states/buffers with their shardings; with
        offload=True the optimizer states (and master weights) live in host
        memory between steps (reference: GroupSharded cpu offload,
        group_sharded_stage3.py offload params / sharding_optimizer)."""
        for k, v in self.params.items():
            self.params[k] = jax.device_put(v, self._sharding(self._param_spec(k)))
        for k, st in self.opt_states.items():
            for sk, sv in st.items():
                if hasattr(sv, "shape"):
                    st[sk] = jax.device_put(
                        sv, self._sharding(self._opt_state_spec(k, sk, sv),
                                           host=self.offload)
                    )
        for k, v in self.buffers.items():
            self.buffers[k] = jax.device_put(v, self._sharding(P()))

    def _batch_spec(self, arr):
        axes = tuple(a for a in self.batch_axes if self.mesh.shape.get(a, 1) > 1)
        if not axes or arr.ndim == 0:
            return P()
        n = int(np.prod([self.mesh.shape[a] for a in axes]))
        if arr.shape[0] % n != 0:
            return P()
        return P(axes if len(axes) > 1 else axes[0])

    def _move_opt_states(self, host):
        for k, st in self.opt_states.items():
            for sk, sv in st.items():
                if hasattr(sv, "shape"):
                    st[sk] = jax.device_put(
                        sv, self._sharding(self._opt_state_spec(k, sk, sv),
                                           host=host))

    # span paths: train_step > place_inputs, dispatch > compiled, offload
    _compiled_span = "compiled"

    def __call__(self, inputs, labels):
        # `step_num`, so that XProf groups the trace by training step
        with _obs_spans.span("train_step", step_num=self._step + 1):
            return self._call(inputs, labels)

    def _call(self, inputs, labels):
        from . import comm_watchdog

        streaming = self._offload_streaming()
        if self.offload and not streaming:
            # host-side move barrier (comm_overlap off): stream
            # optimizer states host→device for the update (reference:
            # GroupSharded offload=True keeping the moments on CPU between
            # steps, group_sharded_stage3.py offload). With streaming the
            # compiled program carries these transfers itself.
            with _obs_spans.span("offload", to="device"), \
                    comm_watchdog.comm_task("offload/h2d", kind="comm"):
                self._move_opt_states(host=False)
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        if not isinstance(labels, (list, tuple)):
            labels = [labels]
        raw_in = [_unwrap_pytree(i if isinstance(i, Tensor) else Tensor(jnp.asarray(np.asarray(i)))) for i in inputs]
        raw_lb = [_unwrap_pytree(l if isinstance(l, Tensor) else Tensor(jnp.asarray(np.asarray(l)))) for l in labels]
        in_specs = self.input_specs or [self._batch_spec(a) for a in raw_in]
        lb_specs = self.label_specs or [self._batch_spec(a) for a in raw_lb]
        # the previous step's program still executing (async dispatch) means
        # this step's input h2d is genuinely pipelined behind device compute.
        # The credit is conservative: is_ready() (a non-blocking peek) must
        # report busy BOTH before and after the placement window, or no
        # compute span is recorded — a program finishing mid-window drops
        # the whole credit rather than inflating overlap_fraction.
        prev = getattr(self, "_inflight", None)
        pipelined = (self.comm_overlap and prev is not None
                     and hasattr(prev, "is_ready") and not prev.is_ready())
        with _obs_spans.span("place_inputs"), \
                comm_watchdog.comm_task("h2d/inputs", kind="comm"):
            t0 = time.perf_counter_ns() if pipelined else 0
            placed_in = [jax.device_put(a, self._sharding(s)) for a, s in zip(raw_in, in_specs)]
            placed_lb = [jax.device_put(a, self._sharding(s)) for a, s in zip(raw_lb, lb_specs)]
            if pipelined and not prev.is_ready():
                _obs_spans.record_span("train_step/prev_step_inflight",
                                       t0, time.perf_counter_ns(),
                                       kind="compute")
        # a2a-accounting window for this dispatch (see _post_dispatch):
        # registry mark scopes retraces to this call; the timestamp floors
        # the estimated intervals inside the step's compute span
        from . import moe_comm as _moe_comm

        self._moe_pre = _moe_comm.trace_marker()
        self._moe_t0 = time.perf_counter_ns()
        with _obs_spans.span("dispatch"):
            loss = super().__call__([Tensor(a) for a in placed_in], [Tensor(a) for a in placed_lb])
        self._inflight = loss._value
        if self.offload and not streaming:
            # comm_overlap off: the d2h restream runs as an exposed
            # post-step barrier (streaming carries it inside the program)
            with _obs_spans.span("offload", to="host"), \
                    comm_watchdog.comm_task("offload/d2h", kind="comm"):
                self._move_opt_states(host=True)
        return loss
