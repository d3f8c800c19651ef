"""Compiled pipeline parallelism over a `pp` mesh axis.

Reference analog: python/paddle/distributed/fleet/meta_parallel/
pipeline_parallel.py — the hand-scheduled 1F1B loop (forward_backward_pipeline
:684) where pp ranks are processes exchanging activations via batched NCCL
send/recv (pp_utils/p2p_communication.py).

TPU-native redesign: the schedule is *compiled into one XLA program*. Stages
live on the `pp` axis of the device mesh; each tick of a `lax.scan` applies
the local stage to its current microbatch and `ppermute`s the activations one
stage forward over ICI. Stage 0 injects a fresh microbatch per tick; the last
stage's outputs are collected from the scan ys. With `jax.checkpoint` around
the stage body the backward pass recomputes stage activations per microbatch,
which gives 1F1B's peak-memory behavior while XLA owns the overlap of
compute and collective-permute DMA — the steady-state overlap the reference
schedules by hand in Python.

Three schedules, all compiled (selected via DistributedStrategy
pp_configs["schedule_mode"], fleet/model.py:160-185 in the reference):

- `pipeline_spmd` — GPipe/FThenB: forward scan over T = M + S - 1 ticks,
  backward generated by XLA autodiff of the scan (reverse schedule).
  Bubble fraction (S-1)/T.
- `pipeline_1f1b` — true 1F1B (reference forward_backward_pipeline :684):
  ONE scan whose every tick does a forward microbatch AND a backward
  microbatch on each stage; stage inputs are held in a ring buffer of only
  W = min(M, 2S-1) entries (vs the T residuals the autodiff schedule
  stashes) and gradients are accumulated in-schedule. Exposed through
  jax.custom_vjp so it composes with outer autodiff (embedding / lm-head
  outside the pipelined region).
- `pipeline_interleaved` — VPP (reference :1308): stage-chunked params
  [S*V, ...]; each microbatch rides the ppermute RING (last stage wraps to
  first) V times, chunk v of microbatch m hitting stage s at tick
  v*M + m + s — bubble shrinks to (S-1)/(V*M + S - 1). Differentiable like
  pipeline_spmd.

Only the `pp` axis is manual (shard_map axis_names={'pp'}); dp/mp/sharding
remain auto axes, so GSPMD still inserts TP/DP collectives inside the stage
body from the usual sharding constraints.
"""

from __future__ import annotations

import weakref

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def stack_pytrees(trees):
    """Stack a list of identical-structure pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def unstack_leading(tree, n):
    """Inverse of stack_pytrees: one pytree per leading index."""
    return [jax.tree.map(lambda a, i=i: a[i], tree) for i in range(n)]


def _double_buffer_default():
    import os

    return os.environ.get("PADDLE_TPU_PP_DOUBLE_BUFFER") == "1"


def pipeline_spmd(stage_fn, stacked_params, inputs_mb, *, mesh, axis="pp",
                  remat=True, double_buffer=None):
    """Run microbatches through a compiled stage pipeline.

    Args:
      stage_fn: (stage_params, inputs) -> outputs. `stage_params` is
        `stacked_params` with the leading stage dim removed. `outputs` must
        have the same pytree structure/shapes/dtypes as `inputs` (they feed
        the next stage); constants that later stages need (position ids,
        masks) should ride along inside `inputs` and be returned unchanged.
      stacked_params: pytree whose leaves have leading dim S (= pp size),
        leaf i holding stage i's params.
      inputs_mb: pytree whose leaves have leading dim M (microbatches).
      mesh: the hybrid jax.sharding.Mesh containing `axis`.
      remat: wrap stage_fn in jax.checkpoint (recompute activations in bwd).
      double_buffer: hold each ppermute in flight for a FULL tick (a
        prefetch carry slot) so the tick-t send overlaps the tick-t+1 stage
        GEMMs instead of serializing send -> next-GEMM. Per-microbatch math
        is identical; the skew doubles, so the bubble grows to 2(S-1)/T —
        worth it when the permute latency is comparable to a stage tick
        (steady state becomes max(gemm, permute), not gemm+permute).
        Default: env PADDLE_TPU_PP_DOUBLE_BUFFER=1, else off.

    Returns outputs pytree with leading dim M, replicated over `axis`.
    """
    S = mesh.shape[axis]
    if double_buffer is None:
        double_buffer = _double_buffer_default()
    if S <= 1:
        # degenerate pipeline: sequential scan over the single stage's params
        def apply_one(mb):
            p = jax.tree.map(lambda a: a[0], stacked_params)
            return stage_fn(p, mb)

        return _vmap_microbatches(apply_one, inputs_mb)

    skew = 2 if double_buffer else 1
    leaves = jax.tree.leaves(inputs_mb)
    pad = [jnp.zeros((skew * (S - 1),) + l.shape[1:], l.dtype)
           for l in leaves]
    inputs_pad = jax.tree.unflatten(
        jax.tree.structure(inputs_mb),
        [jnp.concatenate([l, p], axis=0) for l, p in zip(leaves, pad)],
    )
    pipelined = _build_pipelined(
        stage_fn, mesh, axis, remat, bool(double_buffer),
        jax.tree.structure(stacked_params), jax.tree.structure(inputs_pad),
    )
    # the shard_map must go through jit: jax 0.9's un-jitted partial-manual
    # spec-matching path (_unmatch) builds full-axes specs and rejects the
    # manual subset — this bites in eager AND inside vjp traces. Under an
    # outer jit the nested jit inlines; eagerly the cache above makes repeat
    # calls with a stable stage_fn hit the compiled program.
    return _jitted(pipelined)(stacked_params, inputs_pad)


# jitted-wrapper caches. Keyed so repeated eager calls with a STABLE stage_fn
# (models memoize theirs, e.g. GPTForCausalLMPipe) hit the jit cache instead
# of retracing per call; fresh-closure callers just pay what they paid before.
_BUILD_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_JIT_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _jitted(pipelined):
    jitted = _JIT_CACHE.get(pipelined)
    if jitted is None:
        jitted = jax.jit(pipelined)
        _JIT_CACHE[pipelined] = jitted
    return jitted


def _build_pipelined(stage_fn, mesh, axis, remat, double_buffer, ptreedef,
                     xtreedef):
    per_fn = _BUILD_CACHE.setdefault(stage_fn, {})
    key = (mesh, axis, remat, double_buffer, ptreedef, xtreedef)
    if key in per_fn:
        return per_fn[key]

    S = mesh.shape[axis]
    skew = 2 if double_buffer else 1
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    def body(params_block, xs_pad):
        # manual over pp only: each leaf arrives as [1, ...] — stage-local slice
        p_local = jax.tree.map(lambda a: a[0], params_block)
        idx = jax.lax.axis_index(axis)
        fwd_perm = [(i, i + 1) for i in range(S - 1)]
        recv0 = jax.tree.map(lambda a: jnp.zeros(a.shape[1:], a.dtype), xs_pad)

        def step(recv, x_t):
            inp = jax.tree.map(lambda a, b: jnp.where(idx == 0, a, b), x_t, recv)
            out = fn(p_local, inp)
            send = jax.tree.map(
                lambda a: jax.lax.ppermute(a, axis, fwd_perm), out
            )
            return send, out

        def step_db(carry, x_t):
            # prefetch carry slot: `ready` arrived LAST tick — this tick's
            # send has a whole tick in flight to overlap the next stage
            # GEMMs (stage s consumes microbatch m at tick m + 2s)
            ready, inflight = carry
            inp = jax.tree.map(
                lambda a, b: jnp.where(idx == 0, a, b), x_t, ready)
            out = fn(p_local, inp)
            send = jax.tree.map(
                lambda a: jax.lax.ppermute(a, axis, fwd_perm), out
            )
            return (inflight, send), out

        if double_buffer:
            _, ys = jax.lax.scan(step_db, (recv0, recv0), xs_pad)
        else:
            _, ys = jax.lax.scan(step, recv0, xs_pad)
        # outputs are valid on the last stage at ticks t >= skew*(S-1)
        outs = jax.tree.map(lambda a: a[skew * (S - 1):], ys)
        # replicate the last stage's outputs over pp (everyone else adds zeros)
        mask = (idx == S - 1)
        outs = jax.tree.map(
            lambda a: jax.lax.psum(
                jnp.where(mask, a, jnp.zeros_like(a)), axis
            ),
            outs,
        )
        return outs

    n_p = ptreedef.num_leaves
    n_x = xtreedef.num_leaves
    pspecs = jax.tree.unflatten(ptreedef, [P(axis)] * n_p)
    xspecs = jax.tree.unflatten(xtreedef, [P()] * n_x)
    ospecs = jax.tree.unflatten(xtreedef, [P()] * n_x)
    pipelined = jax.shard_map(
        body, mesh=mesh, in_specs=(pspecs, xspecs), out_specs=ospecs,
        axis_names=frozenset({axis}), check_vma=False,
    )
    per_fn[key] = pipelined
    return pipelined


# --------------------------------------------------------------------------- #
# 1F1B: one scan, every tick does a fwd microbatch AND a bwd microbatch
# --------------------------------------------------------------------------- #


def _guarded_store(buf_tree, val_tree, slot, keep):
    """buf[slot] = val where keep, else unchanged — per leaf. The no-op arm
    rewrites the slot with its own current value so the scan carry keeps a
    single static shape (None leaves pass through untouched)."""
    def store(buf, v):
        if buf is None:
            return None
        cur = jax.lax.dynamic_index_in_dim(buf, slot, 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            buf, jnp.where(keep, v, cur), slot, 0)

    return jax.tree.map(store, buf_tree, val_tree,
                        is_leaf=lambda x: x is None)


def _is_inexact(x):
    return jnp.issubdtype(jnp.result_type(x), jnp.inexact)


def _split_diff(tree):
    """Partition a pytree into (diff, rider): inexact leaves keep values in
    `diff` (None in rider) and vice versa. Same treedef on both sides."""
    diff = jax.tree.map(lambda a: a if _is_inexact(a) else None, tree)
    rider = jax.tree.map(lambda a: None if _is_inexact(a) else a, tree)
    return diff, rider


def _merge_diff(diff, rider):
    return jax.tree.map(
        lambda d, r: d if r is None else r, diff, rider,
        is_leaf=lambda x: x is None,
    )


def _float0_zeros(tree):
    import numpy as np

    return jax.tree.map(
        lambda a: np.zeros(jnp.shape(a), jax.dtypes.float0), tree)


def pipeline_1f1b(stage_fn, loss_fn, stacked_params, loss_params, inputs_mb,
                  *, mesh, axis="pp"):
    """Compiled 1F1B schedule (reference forward_backward_pipeline :684).

    Per tick t, stage s forwards microbatch mf = t - s and backwards
    microbatch mb = t - 2(S-1) + s (the last stage seeds its own backward
    from loss_fn the same tick its forward completes). Stage inputs wait in
    a W = min(M, 2S-1)-slot ring buffer; the backward recomputes the stage
    body from the buffered input (remat), so peak memory is W microbatch
    activations per stage instead of the T = M + S - 1 residuals the
    autodiff'd GPipe scan stashes. Gradients accumulate in-schedule.

    Args:
      stage_fn: (stage_params, x) -> y with y the same pytree structure as x.
      loss_fn: (loss_params, y) -> scalar microbatch loss (the last-stage
        head/criterion; targets ride inside x/y as non-differentiable leaves).
      stacked_params: leaves [S, ...]; loss_params: any pytree;
      inputs_mb: leaves [M, ...].

    Returns the mean microbatch loss. Differentiable w.r.t. stacked_params,
    loss_params, and inputs_mb through jax.custom_vjp — the schedule itself
    computed the grads; outer autodiff just scales them by the cotangent.
    """
    S = mesh.shape[axis]
    M = jax.tree.leaves(inputs_mb)[0].shape[0]
    if S <= 1:
        # degenerate: sequential fwd+bwd per microbatch via plain autodiff
        def one(mb):
            p = jax.tree.map(lambda a: a[0], stacked_params)
            return loss_fn(loss_params, stage_fn(p, mb))

        def step(acc, mb):
            return acc + one(mb) / M, None

        loss, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), inputs_mb)
        return loss

    runner = _build_1f1b(
        stage_fn, loss_fn, mesh, axis, M,
        jax.tree.structure(stacked_params),
        jax.tree.structure(loss_params, is_leaf=lambda x: x is None),
        jax.tree.structure(inputs_mb),
    )
    return runner(stacked_params, loss_params, inputs_mb)


_B1F1B_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _build_1f1b(stage_fn, loss_fn, mesh, axis, M, sp_def, lp_def, in_def):
    per_fn = _B1F1B_CACHE.setdefault(stage_fn, {})
    key = (loss_fn, mesh, axis, M, sp_def, lp_def, in_def)
    if key in per_fn:
        return per_fn[key]

    S = mesh.shape[axis]
    W = min(M, 2 * S - 1)
    T = M + 2 * (S - 1)

    def body(params_block, lp, xs_mb):
        p_local_full = jax.tree.map(lambda a: a[0], params_block)
        p_diff, p_rider = _split_diff(p_local_full)
        lp_diff, lp_rider = _split_diff(lp)
        idx = jax.lax.axis_index(axis)
        is_first = idx == 0
        is_last = idx == S - 1
        fwd_perm = [(i, i + 1) for i in range(S - 1)]
        bwd_perm = [(i + 1, i) for i in range(S - 1)]

        x1 = jax.tree.map(lambda a: jnp.zeros(a.shape[1:], a.dtype), xs_mb)
        x1_diff, _ = _split_diff(x1)
        # pad the stream to T ticks (stage 0 consumes xs_pad[t] for t < M)
        xs_pad = jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((T - M,) + a.shape[1:], a.dtype)]), xs_mb)

        def zeros_diff(tree):
            return jax.tree.map(
                lambda a: None if a is None else jnp.zeros_like(a), tree,
                is_leaf=lambda x: x is None)

        carry0 = dict(
            recv_f=x1,
            recv_b=zeros_diff(x1_diff),
            xbuf=jax.tree.map(
                lambda a: jnp.zeros((W,) + a.shape, a.dtype), x1),
            gacc=zeros_diff(p_diff),
            glacc=zeros_diff(lp_diff),
            dxacc=jax.tree.map(
                lambda a: None if a is None else
                jnp.zeros((M,) + a.shape, a.dtype), x1_diff,
                is_leaf=lambda x: x is None),
            lacc=jnp.zeros((), jnp.float32),
        )

        def tick(carry, tx):
            t, x_t = tx
            mf = t - idx
            mb = t - 2 * (S - 1) + idx
            valid_f = (mf >= 0) & (mf < M)
            valid_b = (mb >= 0) & (mb < M)

            inp = jax.tree.map(
                lambda a, b: jnp.where(is_first, a, b), x_t, carry["recv_f"])
            # ring-buffer write, guarded so drain-phase garbage never
            # clobbers a slot whose backward is still pending
            slot_w = jnp.where(valid_f, mf % W, 0)
            xbuf = _guarded_store(carry["xbuf"], inp, slot_w, valid_f)

            # ---- forward work unit ----
            # UNCONDITIONAL: the stage body carries auto-axis (mp/sharding)
            # collectives, and a lax.cond predicate that differs across pp
            # ranks would strand half the mesh inside a branch collective
            # while the other half proceeds to the pipeline ppermute — a
            # rendezvous deadlock observed on the hybrid mesh. Bubble ticks
            # therefore compute on zeros and mask, like the GPipe schedule.
            out = stage_fn(p_local_full, inp)

            # issue the forward send IMMEDIATELY: the backward work unit
            # below is anchored after it (optimization_barrier on its
            # inputs), so the ppermute DMA is in flight while the bwd vjp
            # GEMMs run — the tick's send hides behind its own backward
            # compute instead of serializing before the next tick
            recv_f = jax.tree.map(
                lambda a: jax.lax.ppermute(a, axis, fwd_perm), out)
            anchors = jax.tree.leaves(recv_f)

            def after_send(tree):
                # tie a pytree's (non-None) leaves after the fwd-send issue
                return jax.tree.map(
                    lambda a: None if a is None else
                    jax.lax.optimization_barrier((a, *anchors))[0],
                    tree, is_leaf=lambda x: x is None)

            # ---- backward work unit (recompute-from-buffer + vjp) ----
            slot_r = jnp.where(valid_b, mb % W, 0)
            xb = jax.tree.map(
                lambda buf: jax.lax.dynamic_index_in_dim(
                    buf, slot_r, 0, keepdims=False), xbuf)
            _, xb_rider = _split_diff(xb)

            # last stage: loss of THIS tick's forward microbatch seeds its
            # own backward (unconditional for the same reason as above —
            # loss_fn may reshard over auto axes)
            out_diff, out_rider = _split_diff(out)
            out_diff = after_send(out_diff)

            def lfn(lpd, yd):
                return loss_fn(_merge_diff(lpd, lp_rider),
                               _merge_diff(yd, out_rider))

            loss_m, lvjp = jax.vjp(lfn, lp_diff, out_diff)
            loss_m = loss_m.astype(jnp.float32)
            dlp, g_seed = lvjp(jnp.ones_like(loss_m) / M)
            g_in = jax.tree.map(
                lambda a, b: None if a is None else jnp.where(is_last, a, b),
                g_seed, carry["recv_b"], is_leaf=lambda x: x is None)

            def sfn(pd, xd):
                y = stage_fn(_merge_diff(pd, p_rider),
                             _merge_diff(xd, xb_rider))
                yd, _ = _split_diff(y)
                return yd

            xb_diff, _ = _split_diff(xb)
            xb_diff = after_send(xb_diff)
            _, svjp = jax.vjp(sfn, p_diff, xb_diff)
            dp, dxb = svjp(g_in)

            def acc(a, d, pred):
                # bubble-tick garbage must not reach the accumulators
                return jax.tree.map(
                    lambda x, y: None if x is None
                    else x + jnp.where(pred, y, jnp.zeros_like(y)), a, d,
                    is_leaf=lambda x: x is None)

            gacc = acc(carry["gacc"], dp, valid_b)
            glacc = acc(carry["glacc"], dlp, is_last & valid_b)
            lacc = carry["lacc"] + jnp.where(
                is_last & valid_b, loss_m, 0.0) / M
            # stage 0's dx is the cotangent of inputs_mb[mb]
            slot_d = jnp.where(valid_b, mb, 0)
            dxacc = _guarded_store(carry["dxacc"], dxb, slot_d,
                                   is_first & valid_b)

            # Deterministic collective order: every backward send is tied
            # after ALL forward sends (the `anchors` barrier), and the
            # backward sends are chained among themselves —
            # data-independent collectives otherwise run concurrently, and
            # devices joining two rendezvous in opposite orders deadlock
            # (observed on the hybrid CPU mesh before the cond removal; the
            # barriers keep the schedule deterministic).
            dxb_leaves, dxb_def = jax.tree.flatten(
                dxb, is_leaf=lambda x: x is None)
            sent = []
            prev = None
            for a in dxb_leaves:
                if a is None:
                    sent.append(None)
                    continue
                deps = anchors + ([prev] if prev is not None else [])
                a = jax.lax.optimization_barrier((a, *deps))[0]
                s = jax.lax.ppermute(a, axis, bwd_perm)
                prev = s
                sent.append(s)
            recv_b = jax.tree.unflatten(dxb_def, sent)

            return dict(recv_f=recv_f, recv_b=recv_b, xbuf=xbuf, gacc=gacc,
                        glacc=glacc, dxacc=dxacc, lacc=lacc), None

        carry, _ = jax.lax.scan(
            tick, carry0, (jnp.arange(T, dtype=jnp.int32), xs_pad))

        def only(pred, tree):
            return jax.tree.map(
                lambda a: None if a is None else jax.lax.psum(
                    jnp.where(pred, a, jnp.zeros_like(a)), axis), tree,
                is_leaf=lambda x: x is None)

        loss = jax.lax.psum(carry["lacc"], axis)  # nonzero on last stage only
        glacc = only(is_last, carry["glacc"])
        dxacc = only(is_first, carry["dxacc"])
        # restore the [1, ...] per-device leading dim so P(axis) re-stacks
        gacc = jax.tree.map(
            lambda a: None if a is None else a[None], carry["gacc"],
            is_leaf=lambda x: x is None)
        return loss, gacc, glacc, dxacc

    shmapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(), P()),
        out_specs=(P(), P(axis), P(), P()),
        axis_names=frozenset({axis}), check_vma=False,
    )

    @jax.custom_vjp
    def run(sp, lp, xs):
        loss, _, _, _ = shmapped(sp, lp, xs)
        return loss

    def run_fwd(sp, lp, xs):
        loss, gs, gl, gx = shmapped(sp, lp, xs)
        return loss, (gs, gl, gx, sp, lp, xs)

    def run_bwd(res, gbar):
        gs, gl, gx, sp, lp, xs = res

        def scale(g, primal):
            return jax.tree.map(
                lambda gg, pp: gg * gbar if gg is not None
                else _float0_zeros([pp])[0],
                g, primal, is_leaf=lambda x: x is None)

        return scale(gs, sp), scale(gl, lp), scale(gx, xs)

    run.defvjp(run_fwd, run_bwd)
    jitted = jax.jit(run)
    per_fn[key] = jitted
    return jitted


# --------------------------------------------------------------------------- #
# interleaved VPP: circular schedule over stage-chunked params
# --------------------------------------------------------------------------- #


def pipeline_interleaved(stage_fn, chunked_params, inputs_mb, *, mesh,
                         axis="pp", num_chunks, remat=True,
                         double_buffer=None):
    """Interleaved virtual-pipeline schedule (reference interleaved 1F1B,
    pipeline_parallel.py:1308; VPP in fleet model.py:160-185).

    `chunked_params` leaves must use the `pack_chunked` layout
    `[V, S, ...]` (dim 0 = chunk, dim 1 = physical stage — the shard_map
    in_spec P(None, axis) shards dim 1 over pp); virtual
    stage u = v*S + s lives on physical stage s = u % S. Microbatch m's
    chunk-v pass hits stage s at tick v*M + m + s; the ppermute ring wraps
    S-1 -> 0, and stage 0 holds wrapped activations in an (M - S + 1)-slot
    ring until chunk v+1's scheduled tick. Total T = V*M + S - 1 ticks, so
    the bubble shrinks from (S-1)/(M+S-1) to (S-1)/(V*M+S-1). Differentiable
    (XLA autodiff reverses the scan). Requires M >= S.

    double_buffer: as in pipeline_spmd — a prefetch carry slot gives every
    ring hop a full tick in flight to overlap the next tick's GEMMs (skew
    2, needs M >= 2S-1; an env-default request silently falls back below
    that, an explicit double_buffer=True raises).
    """
    S = mesh.shape[axis]
    V = num_chunks
    M = jax.tree.leaves(inputs_mb)[0].shape[0]
    explicit_db = double_buffer
    if double_buffer is None:
        double_buffer = _double_buffer_default()
    if double_buffer and M < 2 * S - 1:
        if explicit_db:
            raise ValueError(
                f"double-buffered interleaved schedule needs microbatches "
                f">= 2*pp-1 ({M} < {2 * S - 1})")
        double_buffer = False
    if S <= 1:
        def apply_one(mb):
            h = mb
            for u in range(V):
                p = jax.tree.map(lambda a, u=u: a[u], chunked_params)
                h = stage_fn(p, h)
            return h

        return _vmap_microbatches(apply_one, inputs_mb)
    if M < S:
        raise ValueError(
            f"interleaved schedule needs microbatches >= pp degree "
            f"({M} < {S})")
    for leaf in jax.tree.leaves(chunked_params):
        if leaf.ndim < 2 or leaf.shape[0] != V or leaf.shape[1] != S:
            raise ValueError(
                f"chunked_params leaves must be [V={V}, S={S}, ...] "
                f"(pack_chunked layout); got {leaf.shape}")

    runner = _build_interleaved(
        stage_fn, mesh, axis, V, M, remat, bool(double_buffer),
        jax.tree.structure(chunked_params), jax.tree.structure(inputs_mb),
    )
    return runner(chunked_params, inputs_mb)


_BVPP_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _build_interleaved(stage_fn, mesh, axis, V, M, remat, double_buffer,
                       cp_def, in_def):
    per_fn = _BVPP_CACHE.setdefault(stage_fn, {})
    key = (mesh, axis, V, M, remat, double_buffer, cp_def, in_def)
    if key in per_fn:
        return per_fn[key]

    S = mesh.shape[axis]
    # skew: ticks between a send and its consumption downstream. 2 =
    # double-buffered (a prefetch slot holds the hop in flight for a full
    # tick); stage s runs schedule index r at tick r + skew*s, the wrap
    # ring absorbs the deeper skew (hence the M >= 2S-1 gate upstream).
    skew = 2 if double_buffer else 1
    T = V * M + skew * (S - 1)
    Bw = max(M - skew * (S - 1), 1)
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    def body(params_block, xs_mb):
        # leading dim arrives as V*S/S = V chunks per device, chunk v of this
        # stage at local index v (P(axis) shards contiguously: global index
        # u = v*S + s maps to device u % S ... we instead lay out chunked
        # params as [V, S, ...] flattened so that P(axis) on dim 1 gives each
        # device its V chunks contiguously — see pack_chunked.
        p_chunks = params_block  # leaves [V, 1, ...] after shard_map split
        idx = jax.lax.axis_index(axis)
        is_first = idx == 0
        is_last = idx == S - 1
        ring = [(i, (i + 1) % S) for i in range(S)]

        x1 = jax.tree.map(lambda a: jnp.zeros(a.shape[1:], a.dtype), xs_mb)
        xs_pad = jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((T - M,) + a.shape[1:], a.dtype)]), xs_mb)

        carry0 = dict(
            recv=x1,
            wrap=jax.tree.map(
                lambda a: jnp.zeros((Bw,) + a.shape, a.dtype), x1),
            out=jax.tree.map(
                lambda a: jnp.zeros((M,) + a.shape, a.dtype), x1),
        )
        if double_buffer:
            carry0["recv_in"] = x1  # in-flight prefetch slot

        def tick(carry, tx):
            t, x_t = tx
            r = t - skew * idx
            valid = (r >= 0) & (r < V * M)
            v = jnp.clip(jnp.where(valid, r // M, 0), 0, V - 1)
            m = jnp.where(valid, r % M, 0)

            # stage 0 input: external stream for chunk 0, wrap ring after
            wrap_in = jax.tree.map(
                lambda buf: jax.lax.dynamic_index_in_dim(
                    buf, jnp.where(r >= M, (r - M) % Bw, 0), 0,
                    keepdims=False), carry["wrap"])
            first_in = jax.tree.map(
                lambda a, b: jnp.where(r < M, a, b), x_t, wrap_in)
            inp = jax.tree.map(
                lambda a, b: jnp.where(is_first, a, b), first_in,
                carry["recv"])

            p_v = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, v, 0, keepdims=False)[0], p_chunks)
            # unconditional: a pp-divergent lax.cond around a stage body
            # whose auto-axis (mp/sharding) collectives span the mesh
            # deadlocks the rendezvous (see pipeline_1f1b); bubble ticks
            # compute on zeros and are masked at the collection points
            out = fn(p_v, inp)

            sent = jax.tree.map(lambda a: jax.lax.ppermute(a, axis, ring), out)
            # stage 0 stores what wrapped around from stage S-1 this tick:
            # the sender's schedule index a = t - skew*(S-1), consumed at
            # tick a + M (the wrap ring is where the skew slack lives)
            a_idx = t - skew * (S - 1)
            keep_w = is_first & (a_idx >= 0) & (a_idx < (V - 1) * M)
            slot_w = jnp.where(keep_w, a_idx % Bw, 0)
            wrap = _guarded_store(carry["wrap"], sent, slot_w, keep_w)

            # last chunk's outputs leave from stage S-1
            keep_o = is_last & valid & (v == V - 1)
            slot_o = jnp.where(keep_o, m, 0)
            outbuf = _guarded_store(carry["out"], out, slot_o, keep_o)

            nxt = dict(wrap=wrap, out=outbuf)
            if double_buffer:
                # this tick consumes LAST tick's arrival; `sent` waits one
                # full tick in the prefetch slot, overlapping the next
                # tick's stage GEMMs
                nxt["recv"] = carry["recv_in"]
                nxt["recv_in"] = sent
            else:
                nxt["recv"] = sent
            return nxt, None

        carry, _ = jax.lax.scan(
            tick, carry0, (jnp.arange(T, dtype=jnp.int32), xs_pad))
        outs = jax.tree.map(
            lambda a: jax.lax.psum(
                jnp.where(is_last, a, jnp.zeros_like(a)), axis),
            carry["out"])
        return outs

    shmapped = jax.shard_map(
        body, mesh=mesh, in_specs=(P(None, axis), P()),
        out_specs=P(), axis_names=frozenset({axis}),
        check_vma=False,
    )
    jitted = jax.jit(shmapped)
    per_fn[key] = jitted
    return jitted


def pack_chunked(stacked, S, V):
    """[S*V, ...] virtual-stage-major params -> [V, S, ...] layout whose dim 1
    shards over pp so device s holds chunks v*S+s for all v (paddle VPP
    chunk-to-stage assignment, pp_layers.py get_stage_from_index)."""
    return jax.tree.map(
        lambda a: a.reshape((V, S) + a.shape[1:]), stacked)


def _vmap_microbatches(apply_one, inputs_mb):
    """Sequential microbatch application (scan keeps memory flat like the
    pipelined path so pp=1 vs pp>1 behave alike)."""
    def step(carry, mb):
        return carry, apply_one(mb)

    _, ys = jax.lax.scan(step, 0, inputs_mb)
    return ys


def microbatch(tree, num_microbatches):
    """Split leading batch dim B into [M, B/M, ...] on every leaf."""
    def split(a):
        B = a.shape[0]
        if B % num_microbatches != 0:
            raise ValueError(
                f"batch {B} not divisible by {num_microbatches} microbatches"
            )
        return a.reshape((num_microbatches, B // num_microbatches) + a.shape[1:])

    return jax.tree.map(split, tree)


def unmicrobatch(tree):
    """Inverse of microbatch: [M, mb, ...] -> [M*mb, ...]."""
    return jax.tree.map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]), tree
    )
