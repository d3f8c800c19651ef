"""Fused RMSNorm / LayerNorm for TPU, in Pallas.

Reference analogs: paddle/phi/kernels/fusion/gpu/fused_layernorm_kernel.cu
and gpu/rms_norm_kernel.cu (the fused_rms_norm / fused_layer_norm python
APIs) — re-designed for the TPU memory hierarchy rather than translated:

- The norm is memory-bound: the entire job is streaming each [rows, N]
  activation tile through VMEM exactly once per pass. The forward runs ONE
  fused stream per tile — f32 upcast + square/sum (or sum + square-sum for
  LayerNorm) + rsqrt + scale (+ shift) + downcast — instead of the separate
  reduce/normalize/affine passes the unfused lax path can decompose into
  between flash-attention calls (the non-attention residency the gpt3/llama
  bench rungs sit in).
- Stats are computed in f32 regardless of input dtype, like the reference
  kernels; LayerNorm variance is the two-pass (x - mean)^2 form (the
  one-pass E[x^2]-E[x]^2 cancels catastrophically in f32 for
  mean-dominated inputs) with padded lanes masked out of the centered sum.
- The rows axis is tiled by an AUTOTUNED block (autotune.pick_block_sizes,
  kernels "fused_rms_norm"/"fused_layer_norm"); the feature axis is never
  split — the row statistics need the whole row. The default block is sized
  from the backward's working set so it stays under Mosaic's default 16 MiB
  scoped-VMEM limit (8-row blocks fit up to N ~ 36k f32 lanes).
- backward: dx is a second fused Pallas stream recomputing x_hat from the
  saved rstd (and mean) — the recompute-not-store trade, same as the flash
  backward. dweight/dbias are plain jnp row reductions (a single XLA
  reduce over an operand the backward already touches; a Pallas kernel
  would add nothing). Wired as jax.custom_vjp; the block size and
  weight/bias arity ride the nondiff statics so forward and backward can
  never disagree on tiling.

All entry points pad rows to block multiples and lanes to 128 multiples and
mask/slice the padding, so any shape works with static shapes. The
PADDLE_TPU_FUSED_NORM toggle (read by the functional dispatch, captured at
trace time) selects between these kernels and the lax composite for A/B.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from . import interpret_mode, named_pallas_call
from .partition import shard_plan

# kind -> the kernel's word in the autotune registry and the trace
_KERNEL_OF = {"rms": "fused_rms_norm", "ln": "fused_layer_norm"}

__all__ = ["fused_norm_on", "rms_norm_fwd", "layer_norm_fwd"]


def fused_norm_on() -> bool:
    """PADDLE_TPU_FUSED_NORM toggle, default ON. Read once per forward
    trace by the functional dispatch (nn/functional/norm.py, incubate) and
    captured into the traced closure — like the PR-7 safe-softmax capture,
    an env flip between forward and backward tracing cannot mix paths,
    because the backward is this module's custom VJP, not a re-dispatch."""
    return os.environ.get("PADDLE_TPU_FUSED_NORM", "1") != "0"


def _pad_lanes(n):
    return max(128, -(-n // 128) * 128)


def _pad2(x, br, nl):
    r, n = x.shape
    pr, pn = (-r) % br, nl - n
    if pr or pn:
        x = jnp.pad(x, ((0, pr), (0, pn)))
    return x


def _vec_spec_and_arg(v, nl, grid_rank=1):
    """BlockSpec + operand for a per-feature vector (weight/bias): [8, Nl]
    with 8 replicated sublanes (Mosaic wants last-two block dims divisible
    by (8, 128)); kernels read row 0 and broadcast."""
    v = v.astype(jnp.float32)
    if nl > v.shape[0]:
        v = jnp.pad(v, (0, nl - v.shape[0]))
    arg = jnp.broadcast_to(v[None, :], (8, nl))
    spec = pl.BlockSpec((8, nl), lambda i: (0, 0))
    return spec, arg


# Mosaic's default scoped-VMEM limit is 16 MiB on every TPU generation; the
# row block is sized to leave it a third of that as headroom.
_VMEM_BUDGET = 10 * 1024 * 1024


def _row_bytes(nl, itemsize):
    """VMEM bytes one row of a block costs in the BACKWARD, the larger pass
    (the forward shares its block size): x, dy and dx row tiles, each
    double-buffered by the pipeline, plus ~3 f32 row temporaries. Measured
    on the v5e: 256 rows x 2048 f32 lanes needed 16.18 MiB (PR 22)."""
    return nl * (6 * itemsize + 3 * 4)


def _row_block(r, nl, itemsize=4):
    """Default rows-per-block: the largest power-of-two block whose backward
    working set fits _VMEM_BUDGET, clamped to the padded row count."""
    cap = 1024
    while cap > 8 and cap * _row_bytes(nl, itemsize) > _VMEM_BUDGET:
        cap //= 2
    return max(8, min(cap, -(-max(8, r) // 8) * 8))


def _row_candidates(r, nl, default, itemsize=4):
    cands = {default}
    for br in (64, 128, 256, 512, 1024):
        if (br <= -(-max(8, r) // 8) * 8
                and br * _row_bytes(nl, itemsize) <= _VMEM_BUDGET):
            cands.add((br, nl))
    return sorted(cands)


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #


def _fwd_kernel(x_ref, *rest, kind, eps, n, has_w, has_b):
    i = iter(rest)
    w_ref = next(i) if has_w else None
    b_ref = next(i) if has_b else None
    o_ref = next(i)
    rstd_ref = next(i)
    mean_ref = next(i) if kind == "ln" else None

    x = x_ref[...].astype(jnp.float32)
    inv_n = 1.0 / n
    if kind == "ln":
        # two-pass (x - mean)^2 — the E[x^2]-E[x]^2 one-pass form
        # catastrophically cancels in f32 when |mean| >> std (x ~ 1e4 puts
        # both moments at ~1e8 and their difference below f32 resolution).
        # The whole row is already in VMEM, so the second pass is free;
        # padded lanes (zeros, which would contribute mean^2 each) are
        # masked out of the centered sum — statically elided when N needs
        # no lane padding.
        mean = jnp.sum(x, axis=-1, keepdims=True) * inv_n
        centered = x - mean
        if n != x.shape[-1]:
            lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
            centered = jnp.where(lane < n, centered, 0.0)
        var = jnp.sum(centered * centered, axis=-1, keepdims=True) * inv_n
        rstd = jax.lax.rsqrt(var + eps)
        out = centered * rstd
        mean_ref[...] = mean
    else:
        var = jnp.sum(x * x, axis=-1, keepdims=True) * inv_n
        rstd = jax.lax.rsqrt(var + eps)
        out = x * rstd
    if w_ref is not None:
        out = out * w_ref[0:1, :]
    if b_ref is not None:
        out = out + b_ref[0:1, :]
    o_ref[...] = out.astype(o_ref.dtype)
    rstd_ref[...] = rstd


def _norm_fwd(x2, w, b, kind, eps, br):
    """x2: [R, N] (leading dims pre-flattened). Returns (out [R, N],
    xp [Rp, Nl], rstd [Rp, 1], mean [Rp, 1]|None) — padded residuals for
    the backward kernel."""
    r, n = x2.shape
    nl = _pad_lanes(n)
    xp = _pad2(x2, br, nl)
    rp = xp.shape[0]
    grid = (rp // br,)
    in_specs = [pl.BlockSpec((br, nl), lambda i: (i, 0))]
    args = [xp]
    for v, flag in ((w, w is not None), (b, b is not None)):
        if flag:
            spec, arg = _vec_spec_and_arg(v, nl)
            in_specs.append(spec)
            args.append(arg)
    out_specs = [
        pl.BlockSpec((br, nl), lambda i: (i, 0)),
        pl.BlockSpec((br, 1), lambda i: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((rp, nl), x2.dtype),
        jax.ShapeDtypeStruct((rp, 1), jnp.float32),
    ]
    if kind == "ln":
        out_specs.append(pl.BlockSpec((br, 1), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((rp, 1), jnp.float32))
    kernel = functools.partial(
        _fwd_kernel, kind=kind, eps=eps, n=n,
        has_w=w is not None, has_b=b is not None)
    outs = named_pallas_call(
        f"{_KERNEL_OF[kind]}_fwd", kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret_mode(),
    )(*args)
    if kind == "ln":
        op, rstd, mean = outs
    else:
        (op, rstd), mean = outs, None
    return op[:r, :n], xp, rstd, mean


# --------------------------------------------------------------------------- #
# backward (dx kernel; dw/db are jnp row reductions)
# --------------------------------------------------------------------------- #


def _bwd_kernel(x_ref, *rest, kind, n, has_w):
    i = iter(rest)
    w_ref = next(i) if has_w else None
    dy_ref = next(i)
    rstd_ref = next(i)
    mean_ref = next(i) if kind == "ln" else None
    dx_ref = next(i)

    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    rstd = rstd_ref[...]
    g = dy * w_ref[0:1, :] if w_ref is not None else dy
    inv_n = 1.0 / n
    if kind == "ln":
        xhat = (x - mean_ref[...]) * rstd
        c1 = jnp.sum(g, axis=-1, keepdims=True) * inv_n
        c2 = jnp.sum(g * xhat, axis=-1, keepdims=True) * inv_n
        dx = rstd * (g - c1 - xhat * c2)
    else:
        xhat = x * rstd
        c = jnp.sum(g * xhat, axis=-1, keepdims=True) * inv_n
        dx = rstd * (g - xhat * c)
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _norm_bwd_dx(xp, w, dyp, rstd, mean, kind, n, br):
    rp, nl = xp.shape
    grid = (rp // br,)
    row = pl.BlockSpec((br, nl), lambda i: (i, 0))
    col = pl.BlockSpec((br, 1), lambda i: (i, 0))
    in_specs = [row]
    args = [xp]
    if w is not None:
        spec, arg = _vec_spec_and_arg(w, nl)
        in_specs.append(spec)
        args.append(arg)
    in_specs += [row, col]
    args += [dyp, rstd]
    if kind == "ln":
        in_specs.append(col)
        args.append(mean)
    kernel = functools.partial(_bwd_kernel, kind=kind, n=n,
                               has_w=w is not None)
    return named_pallas_call(
        f"{_KERNEL_OF[kind]}_bwd", kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((rp, nl), xp.dtype),
        interpret=interpret_mode(),
    )(*args)


# --------------------------------------------------------------------------- #
# custom VJP over (x, weight, bias) — absent weight/bias ride as None
# --------------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _fused_norm(operands, kind, eps, br):
    out, _ = _fused_norm_fwd_res(operands, kind, eps, br)
    return out


def _fused_norm_fwd_res(operands, kind, eps, br):
    x, w, b = operands
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out2, xp, rstd, mean = _norm_fwd(x2, w, b, kind, eps, br)
    # b rides the residuals only for its arity/dtype (the cotangent pytree
    # must mirror the primal operands)
    return out2.reshape(shape), (xp, w, b, rstd, mean)


def _fused_norm_vjp_fwd(operands, kind, eps, br):
    return _fused_norm_fwd_res(operands, kind, eps, br)


def _fused_norm_vjp_bwd(kind, eps, br, res, dout):
    # (br, kind, weight arity) are the FORWARD's statics — recomputing the
    # block size here could pad the grid differently and leave rows unwritten
    xp, w, b, rstd, mean = res
    shape = dout.shape
    n = shape[-1]
    r = 1
    for d in shape[:-1]:
        r *= d
    dy2 = dout.reshape(r, n)
    dyp = _pad2(dy2, br, xp.shape[1])
    dxp = _norm_bwd_dx(xp, w, dyp, rstd, mean, kind, n, br)
    dx = dxp[:r, :n].reshape(shape)
    dw = db = None
    if w is not None:
        x32 = xp[:r, :n].astype(jnp.float32)
        dy32 = dy2.astype(jnp.float32)
        rs = rstd[:r]
        xhat = (x32 - mean[:r]) * rs if kind == "ln" else x32 * rs
        dw = jnp.sum(dy32 * xhat, axis=0).astype(w.dtype)
    if b is not None:
        db = jnp.sum(dy2.astype(jnp.float32), axis=0).astype(b.dtype)
    return ((dx, dw, db),)


_fused_norm.defvjp(_fused_norm_vjp_fwd, _fused_norm_vjp_bwd)


def _tuned_row_block(kernel_name, x2, w, b, kind, eps):
    """Row-block size for this signature, autotuned when
    PADDLE_TPU_AUTOTUNE=1 (reference: phi/kernels/autotune cache). The
    feature width is pinned (row stats need whole rows), so candidates vary
    only the row block; the recorded tile is (rows, padded_lanes)."""
    from .autotune import pick_block_sizes

    r, n = x2.shape
    nl = _pad_lanes(n)
    itemsize = jnp.dtype(x2.dtype).itemsize
    default = (_row_block(r, nl, itemsize), nl)

    def run_with(br, _bk):
        out, _, _, _ = _norm_fwd(x2, w, b, kind, eps, br)
        out.block_until_ready()

    concrete = not any(
        isinstance(v, jax.core.Tracer) for v in (x2, w, b) if v is not None)
    br, _ = pick_block_sizes(
        kernel_name, r, nl, default, run_with, allow_measure=concrete,
        signature=(n, str(x2.dtype), w is not None, b is not None),
        candidates=_row_candidates(r, nl, default, itemsize))
    return br


def _norm_entry(kernel_name, kind, x, weight, bias, eps):
    """Shared entry: under a multi-device mesh the kernel runs per shard
    (partition.py) — the rows are independent, so the leading dimension is
    split over the data axes and a [B, S, N] input's sequence over sep; the
    feature axis and weight/bias stay whole."""
    def local(x, *wb):
        it = iter(wb)
        w = next(it) if weight is not None else None
        b = next(it) if bias is not None else None
        x2 = x.reshape(-1, x.shape[-1])
        br = _tuned_row_block(kernel_name, x2, w, b, kind, eps)
        return _fused_norm((x, w, b), kind, eps, br)

    wb = [v for v in (weight, bias) if v is not None]
    plan = shard_plan(x) if x.ndim >= 2 else None
    if plan is None:
        return local(x, *wb)
    dims = [plan.axes("batch", x.shape[0])]
    if x.ndim >= 3:
        dims.append(plan.axes("seq", x.shape[1]))
    xs = P(*dims, *([None] * (x.ndim - len(dims))))
    return plan.run(local, [x, *wb], [xs] + [P(None)] * len(wb), xs)


def rms_norm_fwd(x, weight=None, epsilon=1e-6, bias=None):
    """Fused RMSNorm: x [..., N] normalized over the last axis, f32 stats,
    optional weight/bias [N]. Differentiable (custom VJP, fused dx kernel).
    Reference API: python/paddle/incubate/nn/functional/fused_rms_norm.py."""
    return _norm_entry("fused_rms_norm", "rms", x, weight, bias,
                       float(epsilon))


def layer_norm_fwd(x, weight=None, bias=None, epsilon=1e-5):
    """Fused LayerNorm over the last axis (two-pass masked (x-mean)^2
    variance, f32 stats), optional weight/bias [N]. Differentiable (custom
    VJP, fused dx kernel). Reference: fusion/gpu/fused_layernorm_kernel.cu."""
    return _norm_entry("fused_layer_norm", "ln", x, weight, bias,
                       float(epsilon))
