"""jit / to_static: the traced execution path.

Reference analog: paddle.jit.to_static (python/paddle/jit/api.py:197) backed by
AST transforms + SOT bytecode tracing (python/paddle/jit/sot/translate.py) that
build a static Program run by the PirInterpreter. On TPU the entire pipeline
collapses into jax.jit: user Layers execute once under a tracer (module-state
swap — parameters temporarily wrap tracers), producing one XLA program with
guard-based retrace on new input signatures, which is exactly the SOT
guard-cache contract.

Two entry points:
- to_static(fn): trace-and-guard jit of any Tensor->Tensor callable (params
  captured as constants; inference / frozen-weight use).
- TrainStep(model, loss, optimizer): the whole train step (fwd, bwd, optimizer
  update, buffer updates, AMP) as ONE compiled+donated XLA program — replacing
  the reference's per-op dispatch AND its fused optimizer kernels.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import random as rnd
from ..framework.core import Parameter, Tensor, no_grad, to_tensor, tracing_guard
from ..nn.layer.layers import Layer

__all__ = ["to_static", "TrainStep", "functional_call", "save", "load", "not_to_static", "ignore_module", "InputSpec", "TranslatedLayer"]


def _unwrap_pytree(obj):
    if isinstance(obj, Tensor):
        return obj._value
    if isinstance(obj, (list, tuple)):
        t = [_unwrap_pytree(o) for o in obj]
        return type(obj)(t) if not isinstance(obj, tuple) else tuple(t)
    if isinstance(obj, dict):
        return {k: _unwrap_pytree(v) for k, v in obj.items()}
    return obj


def _wrap_pytree(obj):
    if isinstance(obj, (jax.Array, np.ndarray)):
        return Tensor(obj)
    if isinstance(obj, (list, tuple)):
        t = [_wrap_pytree(o) for o in obj]
        return type(obj)(t) if not isinstance(obj, tuple) else tuple(t)
    if isinstance(obj, dict):
        return {k: _wrap_pytree(v) for k, v in obj.items()}
    return obj


class _ModuleState:
    """Swap a Layer tree's param/buffer values for traced values and restore."""

    def __init__(self, layer: Layer):
        self.layer = layer
        self.params = dict(layer.named_parameters())
        self.buffers = dict(layer.named_buffers())

    def values(self):
        return (
            {k: p._value for k, p in self.params.items()},
            {k: b._value for k, b in self.buffers.items()},
        )

    def swap_in(self, param_vals, buffer_vals):
        saved_p = {k: p._value for k, p in self.params.items()}
        saved_b = {k: b._value for k, b in self.buffers.items()}
        for k, v in (param_vals or {}).items():
            self.params[k]._value = v
        for k, v in (buffer_vals or {}).items():
            self.buffers[k]._value = v
        return saved_p, saved_b

    def read_buffers(self):
        return {k: b._value for k, b in self.buffers.items()}

    def restore(self, saved):
        saved_p, saved_b = saved
        for k, v in saved_p.items():
            self.params[k]._value = v
        for k, v in saved_b.items():
            self.buffers[k]._value = v


def functional_call(layer: Layer, param_vals, buffer_vals, args, kwargs=None, train=None, rng_key=None):
    """Run layer(*args) with the given raw param/buffer values, purely.

    Returns (outputs_raw, new_buffer_vals). Works under jax tracing: the
    module-state swap makes user Layer code (written against the eager API)
    execute as a pure jax function — the TPU-native replacement for the
    reference's dy2static AST rewriting.
    """
    kwargs = kwargs or {}
    state = _ModuleState(layer)
    saved = state.swap_in(param_vals, buffer_vals)
    prev_training = layer.training
    if train is not None:
        layer.train() if train else layer.eval()
    saved_rng = rnd.get_rng_state()
    if rng_key is not None:
        rnd.set_rng_state((rng_key,))
    try:
        with tracing_guard(True):
            wrapped_args = [_wrap_pytree(a) if not isinstance(a, Tensor) else a for a in args]
            out = layer(*wrapped_args, **kwargs)
        new_bufs = state.read_buffers()
        return _unwrap_pytree(out), new_bufs
    finally:
        state.restore(saved)
        rnd.set_rng_state(saved_rng)
        if train is not None:
            layer.train() if prev_training else layer.eval()


def _is_trace_ineligible(e) -> bool:
    """Errors meaning 'this Python frame cannot be traced' — data-dependent
    control flow / shapes (the reference SOT's ineligible-frame set,
    python/paddle/jit/sot/translate.py BreakGraphError)."""
    import jax.errors as jerr

    return isinstance(e, (jerr.TracerBoolConversionError,
                          jerr.ConcretizationTypeError,
                          jerr.TracerArrayConversionError,
                          jerr.TracerIntegerConversionError,
                          jerr.NonConcreteBooleanIndexError))


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    """Decorator/wrapper: jit a Tensor-level callable or a Layer's forward.

    Shape-signature guarding comes from jax.jit's tracing cache — a new input
    (shape, dtype) signature triggers a retrace, matching the reference SOT
    guard semantics (python/paddle/jit/sot/translate.py:97-106). Frames the
    tracer cannot swallow (data-dependent Python control flow, concretized
    shapes) fall back to SOT GRAPH-BREAK CAPTURE (jit/sot.py): the frame is
    re-run once eagerly while recording, split at the concrete-value sync
    points, and thereafter executes as compiled subgraphs around the breaks
    — the reference SOT's partial-graph behavior (translate.py
    BreakGraphError path) rather than losing all compilation.
    """
    if function is None:
        return lambda f: to_static(f, input_spec=input_spec)

    if isinstance(function, Layer):
        layer = function
        orig_forward = layer.forward

        compiled = _make_layer_jit(layer, orig_forward)
        layer.forward = compiled
        layer._to_static_origin = orig_forward
        return layer

    fn = function

    @jax.jit
    def traced(raw_args):
        with tracing_guard(True):
            args = _wrap_pytree(raw_args)
            out = fn(*args)
        return _unwrap_pytree(out)

    fell_back = [False]
    sot = [None]

    @functools.wraps(fn)
    def wrapper(*args):
        if fell_back[0]:
            return sot[0](*args)
        raw = _unwrap_pytree(list(args))
        try:
            out = traced(raw)
        except Exception as e:
            if not _is_trace_ineligible(e):
                raise
            # graph-break capture: compiled subgraphs around the dynamic
            # control flow instead of a permanent whole-frame eager fallback
            from .sot import SOTCapture

            fell_back[0] = True
            sot[0] = SOTCapture(fn)
            return sot[0](*args)
        return _wrap_pytree(out)

    wrapper._original_fn = fn
    wrapper._sot_fallen_back = fell_back
    wrapper._sot_capture = sot
    return wrapper


def _make_layer_jit(layer, orig_forward):
    """jit a Layer's forward: params/buffers become traced args so weight
    updates don't trigger recompiles; buffers update functionally."""
    jit_cache = {}
    fell_back = [False]
    sot = [{}]  # training-mode -> SOTCapture

    def forward(*args, **kwargs):
        if kwargs:
            # kwargs would be baked into the trace as constants
            return orig_forward(*args, **kwargs)
        if fell_back[0]:
            # one capture per training mode: recorded segments bake the
            # train/eval branch (dropout, BN stat source)
            from .sot import SOTCapture

            mode = bool(layer.training)
            if sot[0].get(mode) is None:
                sot[0][mode] = SOTCapture(orig_forward)
            return sot[0][mode](*args)
        state = _ModuleState(layer)
        p_vals, b_vals = state.values()
        training = layer.training

        key = "train" if training else "eval"
        if key not in jit_cache:
            @functools.partial(jax.jit, static_argnums=())
            def step(p, b, rng, raw_args):
                saved = state.swap_in(p, b)
                saved_rng = rnd.get_rng_state()
                rnd.set_rng_state((rng,))
                try:
                    with tracing_guard(True):
                        out = orig_forward(*_wrap_pytree(raw_args), **kwargs)
                    return _unwrap_pytree(out), state.read_buffers()
                finally:
                    state.restore(saved)
                    rnd.set_rng_state(saved_rng)

            jit_cache[key] = step
        raw_args = _unwrap_pytree(list(args))
        try:
            out, new_bufs = jit_cache[key](p_vals, b_vals, rnd.next_key(), raw_args)
        except Exception as e:
            if not _is_trace_ineligible(e):
                raise
            from .sot import SOTCapture

            fell_back[0] = True
            mode = bool(layer.training)
            sot[0][mode] = SOTCapture(orig_forward)
            return sot[0][mode](*args)
        for k, v in new_bufs.items():
            state.buffers[k]._value = v
        return _wrap_pytree(out)

    forward._sot_fallen_back = fell_back
    forward._sot_capture = sot
    return forward


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    return None


class TrainStep:
    """One compiled train step: loss, grads, clip, optimizer update, buffer
    (BN stat) updates — fused into a single donated XLA program.

    Replaces, in one object: the reference's dygraph per-op dispatch, AMP
    autocast pass, ClipGradByGlobalNorm kernel, and the fused/multi_tensor
    optimizer kernels (paddle/phi/kernels/fusion/gpu/fused_adam_kernel.cu).

    Usage:
        step = TrainStep(model, loss_fn, optimizer)
        loss = step(x, y)            # all device-side
        step.sync_weights()          # write back into model Tensors
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer, amp_level=None, amp_dtype="bfloat16", donate=True):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.amp_level = amp_level
        self.amp_dtype = amp_dtype
        self._state = _ModuleState(model)
        p_vals, b_vals = self._state.values()
        self.params = p_vals
        self.buffers = b_vals
        self.opt_states = {k: optimizer.init_state(v) for k, v in p_vals.items()}
        self._step = 0
        self._compiled = None
        self._donate = donate

    def _build(self):
        model, loss_fn, opt = self.model, self.loss_fn, self.optimizer
        state = self._state
        amp_level, amp_dtype = self.amp_level, self.amp_dtype
        grad_clip = opt._grad_clip
        wd = opt._decay_coeff()
        # per-param regularizers (ParamAttr(regularizer=...)): applied to
        # the grads inside the compiled program, and they REPLACE the
        # optimizer-level weight_decay for their params (same semantics as
        # the eager Optimizer.step / reference append_regularization_ops)
        reg_specs = {}
        for _k, _prm in state.params.items():
            _r = getattr(_prm, "regularizer", None)
            if _r is not None:
                from ..regularizer import L1Decay

                reg_specs[_k] = ("l1" if isinstance(_r, L1Decay) else "l2",
                                 float(_r._coeff))

        # models that must see the loss inside their compiled schedule (1F1B
        # pipelining: the last stage seeds its own backward) expose
        # forward_loss(inputs..., labels..., criterion) — reference analog:
        # PipelineParallel owns the loss in train_batch (pipeline_parallel
        # .py:940) rather than the user loop
        fused_loss = (getattr(model, "forward_loss", None)
                      if getattr(model, "pp_schedule", None) == "1f1b" else None)

        def compute_loss(p, b, rng, batch):
            # grad-overlap hook: DistributedTrainStep tags params with
            # custom-VJP bucket identities whose backward applies the
            # reduce-scatter sharding constraint where the grad is PRODUCED
            # (per-layer, against remaining backward compute) instead of at
            # the step-end consumption site
            p = self._tag_grad_buckets(p)
            saved = state.swap_in(p, b)
            saved_rng = rnd.get_rng_state()
            rnd.set_rng_state((rng,))
            try:
                with tracing_guard(True):
                    ctx = _amp_ctx(amp_level, amp_dtype)
                    with ctx:
                        if fused_loss is not None:
                            loss = fused_loss(
                                *_wrap_pytree(list(batch["inputs"])),
                                *_wrap_pytree(list(batch["labels"])),
                                loss_fn)
                        else:
                            out = model(*_wrap_pytree(list(batch["inputs"])))
                            outs = out if isinstance(out, (list, tuple)) else [out]
                            loss = loss_fn(*outs, *_wrap_pytree(list(batch["labels"])))
                return loss._value.astype(jnp.float32), state.read_buffers()
            finally:
                state.restore(saved)
                rnd.set_rng_state(saved_rng)

        def train_step(p, opt_states, b, rng, step_i, lr, batch):
            # offload streaming: host-resident optimizer states enter the
            # program through in-program device_puts (overlappable h2d
            # copies scheduled by XLA) instead of a host-side move barrier
            opt_states = self._fetch_opt_states(opt_states)
            (loss, new_b), grads = jax.value_and_grad(compute_loss, has_aux=True)(p, b, rng, batch)
            if reg_specs:
                grads = dict(grads)
                for k, (kind, coeff) in reg_specs.items():
                    gk = grads[k].astype(jnp.float32)
                    pk = p[k].astype(jnp.float32)
                    add = coeff * (jnp.sign(pk) if kind == "l1" else pk)
                    grads[k] = (gk + add).astype(grads[k].dtype)
            # global-norm clip (fused into the same program)
            if grad_clip is not None:
                clip_norm = getattr(grad_clip, "clip_norm", None)
                if clip_norm is not None:
                    gnorm = jnp.sqrt(
                        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
                    )
                    scale = clip_norm / jnp.maximum(gnorm, clip_norm)
                    grads = jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads)
            new_p, new_states = {}, {}
            # the optimizer's name ("adamw") on its operations in a trace
            with jax.named_scope(type(opt).__name__.lower()):
                for k in p:
                    ctx = {"step": step_i,
                           "weight_decay": 0.0 if k in reg_specs else wd}
                    st = opt_states[k]
                    master = st.get("master")
                    pv = master if master is not None else p[k]
                    # sharding-stage hooks (ZeRO-2/3): reduce-scatter the grad to
                    # its owner shard and compute the update sharded, then
                    # all-gather the fresh params (DistributedTrainStep overrides)
                    gv = self._shard_grad(k, grads[k].astype(pv.dtype))
                    pv = self._shard_param_for_update(k, pv)
                    rule_state = {kk: vv for kk, vv in st.items() if kk != "master"}
                    np_, ns_ = opt.update(pv, gv, rule_state, lr, ctx)
                    if master is not None:
                        ns_ = dict(ns_)
                        ns_["master"] = np_
                        np_ = np_.astype(p[k].dtype)
                    new_p[k] = self._restore_param(k, np_)
                    # per-param d2h emission point: under offload streaming the
                    # fresh states head back to host memory HERE, pipelined
                    # against the remaining params' updates
                    new_states[k] = self._emit_opt_state(k, ns_)
            return loss, new_p, new_states, new_b

        donate = (0, 1, 2) if self._donate else ()
        out_sh = self._train_out_shardings()
        kw = {"out_shardings": out_sh} if out_sh is not None else {}
        self._compiled = jax.jit(train_step, donate_argnums=donate, **kw)

        def eval_step(p, b, rng, batch):
            loss, _ = compute_loss(p, b, rng, batch)
            return loss

        self._compiled_eval = jax.jit(eval_step)

    # the span around the compiled call; DistributedTrainStep, whose
    # `train_step` span is this one's parent, shortens it to "compiled"
    _compiled_span = "train_step/compiled"

    # sharding-stage hooks; identity here, overridden by DistributedTrainStep
    def _shard_grad(self, name, g):
        return g

    def _shard_param_for_update(self, name, pv):
        return pv

    def _restore_param(self, name, np_):
        return np_

    # comm-overlap hooks; identity here, overridden by DistributedTrainStep
    def _tag_grad_buckets(self, p):
        return p

    def _fetch_opt_states(self, opt_states):
        return opt_states

    def _emit_opt_state(self, name, st):
        return st

    def _post_dispatch(self):
        """Runs inside the step's compute span, right after the compiled
        call returns (the device is still executing asynchronously) — the
        overlap point for host-issued follow-up transfers."""

    def _train_out_shardings(self):
        """Optional out_shardings for (loss, new_p, new_states, new_b) —
        used by the offload path to keep optimizer states host-resident."""
        return None

    def __call__(self, inputs, labels):
        if self._compiled is None:
            # multi-precision: seed master copies
            if self.optimizer._multi_precision:
                for k, v in self.params.items():
                    if v.dtype in (jnp.bfloat16, jnp.float16):
                        self.opt_states[k]["master"] = v.astype(jnp.float32)
            self._build()
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        if not isinstance(labels, (list, tuple)):
            labels = [labels]
        self._step += 1
        batch = {
            "inputs": [_unwrap_pytree(i if isinstance(i, Tensor) else to_tensor(i)) for i in inputs],
            "labels": [_unwrap_pytree(l if isinstance(l, Tensor) else to_tensor(l)) for l in labels],
        }
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        step_i = jnp.asarray(self._step, jnp.int32)
        from ..observability import spans as _obs_spans

        # kind="compute": the step's compute interval for the overlap
        # accounting (overlap_stats). The span covers the async dispatch and
        # _post_dispatch — transfers issued there run while the device is
        # still executing this step's program.
        with _obs_spans.span(self._compiled_span, kind="compute"):
            loss, self.params, self.opt_states, self.buffers = self._compiled(
                self.params, self.opt_states, self.buffers, rnd.next_key(), step_i, lr, batch
            )
            self._post_dispatch()
        return Tensor(loss)

    def evaluate(self, inputs, labels):
        if self._compiled is None:
            self._build()
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        if not isinstance(labels, (list, tuple)):
            labels = [labels]
        was_training = self.model.training
        self.model.eval()
        try:
            batch = {
                "inputs": [_unwrap_pytree(i if isinstance(i, Tensor) else to_tensor(i)) for i in inputs],
                "labels": [_unwrap_pytree(l if isinstance(l, Tensor) else to_tensor(l)) for l in labels],
            }
            loss = self._compiled_eval(self.params, self.buffers, rnd.next_key(), batch)
            return Tensor(loss)
        finally:
            if was_training:
                self.model.train()

    @no_grad()
    def sync_weights(self):
        """Write device-side params/buffers back into the model's Tensors."""
        for k, v in self.params.items():
            self._state.params[k]._value = v
        for k, v in self.buffers.items():
            self._state.buffers[k]._value = v

    @no_grad()
    def sync_optimizer(self):
        """Write device-side optimizer state back into the Optimizer so
        optimizer.state_dict() reflects training (checkpoint correctness)."""
        for k, st in self.opt_states.items():
            param = self._state.params[k]
            self.optimizer._states[id(param)] = dict(st)
        self.optimizer._step_count = self._step


def _amp_ctx(level, dtype):
    import contextlib

    if level in ("O1", "O2"):
        from ..amp import auto_cast

        return auto_cast(True, level=level, dtype=dtype)
    return contextlib.nullcontext()


class InputSpec:
    """Shape/dtype spec for traced export (reference: paddle.static.InputSpec)."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name

    def _sds(self, scope=None):
        from ..framework import dtype as dtype_mod

        dt = jnp.dtype(dtype_mod.convert_dtype(self.dtype))
        if any(d is None for d in self.shape):
            # dynamic dims (the reference's None batch dims) -> jax.export
            # symbolic shapes; one shared scope per save() call
            from jax import export as jexport

            names = iter("abcdefghijklmnop")
            dims = ",".join(str(d) if d is not None else next(names)
                            for d in self.shape)
            shape = jexport.symbolic_shape(dims, scope=scope)
            return jax.ShapeDtypeStruct(shape, dt)
        return jax.ShapeDtypeStruct(self.shape, dt)


def save(layer, path, input_spec=None, **configs):
    """paddle.jit.save (reference: python/paddle/jit/api.py) — persist weights
    AND, when input_spec is given, the traced program itself: the forward is
    traced to StableHLO via jax.export (params captured as constants) and
    serialized to `path`.pdmodel — the analog of the reference's saved
    Program/PIR artifact. Weights always go to `path`.pdparams."""
    from ..framework.io import save as fsave

    if not isinstance(layer, Layer):
        raise TypeError("jit.save expects a Layer")
    state = layer.state_dict()
    fsave({"state_dict": state, "class": type(layer).__qualname__}, path + ".pdparams")
    if input_spec is not None:
        from jax import export as jexport

        params = {k: p._value for k, p in layer.named_parameters()}
        buffers = {k: b._value for k, b in layer.named_buffers()}

        def fwd(*xs):
            out, _ = functional_call(layer, params, buffers,
                                     [Tensor(x) for x in xs], train=False)
            return out

        from jax import export as _jexp

        scope = _jexp.SymbolicScope()
        sds = [s._sds(scope) if isinstance(s, InputSpec) else
               jax.ShapeDtypeStruct(tuple(s.shape), jnp.dtype(s.dtype))
               for s in input_spec]
        # the serving artifact is a SINGLE-device program: a lingering
        # global training mesh (DistributedTrainStep sets one) must not
        # leak into the export, or the saved model demands that device
        # count at load time (jax.export records nr_devices)
        from ..distributed import env as _dist_env

        prev_mesh = _dist_env.get_global_mesh()
        _dist_env.set_global_mesh(None)
        try:
            exported = jexport.export(jax.jit(fwd))(*sds)
        finally:
            _dist_env.set_global_mesh(prev_mesh)
        with open(path + ".pdmodel", "wb") as f:
            f.write(exported.serialize())


class TranslatedLayer(Layer):
    """A loaded saved-program (reference: TranslatedLayer from paddle.jit.load
    running a deserialized Program on the executor) — here a deserialized
    StableHLO program invoked through jax.export."""

    def __init__(self, exported, state=None):
        super().__init__()
        self._exported = exported
        self._state = state or {}

    def forward(self, *args):
        raw = [a._value if isinstance(a, Tensor) else jnp.asarray(a) for a in args]
        out = self._exported.call(*raw)
        return _wrap_pytree(out)

    def state_dict(self, *a, **kw):
        return dict(self._state)

    @property
    def input_shapes(self):
        return [tuple(a.shape) for a in self._exported.in_avals]


def load(path, **configs):
    """paddle.jit.load — with a .pdmodel program file returns a runnable
    TranslatedLayer; otherwise returns the saved dict (weights-only load)."""
    import os

    from jax import export as jexport

    from ..framework.io import load as fload

    payload = fload(path + ".pdparams")
    if os.path.exists(path + ".pdmodel"):
        with open(path + ".pdmodel", "rb") as f:
            exported = jexport.deserialize(f.read())
        return TranslatedLayer(exported, payload.get("state_dict"))
    return payload
