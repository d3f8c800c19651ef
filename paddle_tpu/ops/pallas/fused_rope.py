"""Fused rotary position embedding for TPU, in Pallas.

Reference analog: paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu (+
fused_rope_utils.h for the neox-vs-interleaved pairing) — re-designed for
the TPU memory hierarchy rather than translated:

- RoPE is pure elementwise traffic; the entire job is streaming q and k
  through VMEM exactly once, applying cos/sin in the same stream. The
  hazard on TPU is the HALF-ROTATION: both pairings split the head dim on
  the lane axis at non-128-aligned offsets (D/2, or even/odd lanes), which
  Mosaic cannot slice. The kernel never slices — rotate_half is expressed
  as a LANE ROLL (pltpu.roll) with the pairing's signs folded into
  precomputed full-width sin tables:

      out = x * CF + roll(x, s1) * S1 [+ roll(x, s2) * S2]

  neox  (pairs (i, i+D/2)): one roll by D/2 (its own inverse mod D),
        CF = [cos, cos], S1 = [-sin, sin].
  GPT-J (interleaved pairs (2i, 2i+1)): rolls by 1 and D-1 with
        even/odd-masked sin tables (the mask is IN the table — zero
        coefficient kills the cross-pair lanes the circular roll drags in).

- One pallas_call applies the same tables to q, k (and v when the caller
  rotates it) in a single grid sweep — the reference kernel's "one launch
  for the whole qkv group". Tables are [.., S, D] f32, tiny next to the
  activations, and ride per-sequence-block; batch-invariant tables (no
  position_ids) stay [1, S, D] and are index-mapped, not broadcast.
- The sequence axis is tiled by an AUTOTUNED block (kernel "fused_rope");
  heads and head_dim stay whole per block, so the block's last-two dims
  (H, D) are the natural Mosaic tile.
- backward: a rotation is orthogonal and linear, so the VJP is the SAME
  kernel with the sin tables negated (for both pairings the adjoint's
  shifted-table terms reduce to exactly that). No activations are saved —
  only the tables ride the residuals. Wired as jax.custom_vjp; tables get
  zero cotangents (they are position data, not parameters).

The PADDLE_TPU_FUSED_ROPE toggle (read by the functional dispatch at trace
time) selects between this kernel and the lax composite for A/B.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from . import interpret_mode, named_pallas_call
from .partition import shard_plan

__all__ = ["fused_rope_on", "apply_fused_rope"]


def fused_rope_on() -> bool:
    """PADDLE_TPU_FUSED_ROPE toggle, default ON. Read once per forward trace
    by the functional dispatch and captured into the traced closure; the
    backward is this module's custom VJP, so an env flip between forward and
    backward tracing cannot mix kernel and composite gradients."""
    return os.environ.get("PADDLE_TPU_FUSED_ROPE", "1") != "0"


def _roll_lanes(x, shift):
    """Circular shift along the last (lane) axis: out[.., l] = x[.., l-shift].
    pltpu.roll is the Mosaic lane-rotate; the interpreter takes the same
    path (it lowers to jnp.roll semantics)."""
    return pltpu.roll(x, shift=shift, axis=x.ndim - 1)


def _rope_tables_full(c, s, d, interleaved):
    """Expand half-width cos/sin [.., S, D/2] into the kernel's full-width
    coefficient tables (cf, (s1, s2?)) [.., S, D] f32, signs and pair masks
    folded in (see module docstring)."""
    c = c.astype(jnp.float32)
    s = s.astype(jnp.float32)
    if interleaved:
        cf = jnp.repeat(c, 2, axis=-1)
        zero = jnp.zeros_like(s)
        # even lanes pull x[l+1] (roll d-1): coeff -sin; odd lanes 0
        sa = jnp.stack([-s, zero], axis=-1).reshape(*s.shape[:-1], d)
        # odd lanes pull x[l-1] (roll 1): coeff +sin; even lanes 0
        sb = jnp.stack([zero, s], axis=-1).reshape(*s.shape[:-1], d)
        return cf, (sa, sb), (d - 1, 1)
    cf = jnp.concatenate([c, c], axis=-1)
    s1 = jnp.concatenate([-s, s], axis=-1)
    return cf, (s1,), (d // 2,)


def _rope_kernel(*refs, nt, shifts):
    ns = len(shifts)
    cf = refs[nt][0].astype(jnp.float32)                     # [bs, D]
    sins = [refs[nt + 1 + j][0].astype(jnp.float32) for j in range(ns)]
    for t in range(nt):
        x = refs[t][0].astype(jnp.float32)                   # [bs, H, D]
        out = x * cf[:, None, :]
        for shift, sv in zip(shifts, sins):
            out = out + _roll_lanes(x, shift) * sv[:, None, :]
        o_ref = refs[nt + 1 + ns + t]
        o_ref[0] = out.astype(o_ref.dtype)


def _pad_rows(x, bs):
    pad = (-x.shape[1]) % bs
    if pad:
        width = [(0, 0)] * x.ndim
        width[1] = (0, pad)
        x = jnp.pad(x, width)
    return x


def _rope_run(tensors, cf, sins, shifts, bs):
    """tensors: tuple of [B, S, Hi, D]; cf/sins: [Bt, S, D] (Bt in {1, B})."""
    b, s = tensors[0].shape[0], tensors[0].shape[1]
    d = tensors[0].shape[-1]
    tp = [_pad_rows(t, bs) for t in tensors]
    sp = tp[0].shape[1]
    cfp = _pad_rows(cf, bs)
    sinsp = [_pad_rows(sv, bs) for sv in sins]
    bt = cf.shape[0]
    grid = (b, sp // bs)

    def tmap(bi, i, _bt=bt):
        return (bi if _bt > 1 else 0, i, 0)

    in_specs = [
        pl.BlockSpec((1, bs, t.shape[2], d), lambda bi, i: (bi, i, 0, 0))
        for t in tp
    ]
    in_specs.append(pl.BlockSpec((1, bs, d), tmap))
    in_specs += [pl.BlockSpec((1, bs, d), tmap) for _ in sinsp]
    out_specs = [
        pl.BlockSpec((1, bs, t.shape[2], d), lambda bi, i: (bi, i, 0, 0))
        for t in tp
    ]
    out_shape = [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tp]
    kernel = functools.partial(_rope_kernel, nt=len(tp), shifts=shifts)
    outs = named_pallas_call(
        "fused_rope", kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret_mode(),
    )(*tp, cfp, *sinsp)
    if len(tp) == 1:
        outs = (outs,) if not isinstance(outs, (tuple, list)) else tuple(outs)
    return tuple(o[:, :s] for o in outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rope(tensors, tables, shifts, bs):
    cf, sins = tables
    return _rope_run(tensors, cf, sins, shifts, bs)


def _rope_vjp_fwd(tensors, tables, shifts, bs):
    cf, sins = tables
    return _rope_run(tensors, cf, sins, shifts, bs), tables


def _rope_vjp_bwd(shifts, bs, tables, douts):
    # adjoint of an orthogonal rotation = same kernel, sin tables negated
    # (both pairings: the shift set is closed under lane-negation and the
    # rolled sign tables map onto each other with a sign flip)
    cf, sins = tables
    dtens = _rope_run(tuple(douts), cf, tuple(-sv for sv in sins), shifts,
                      bs)
    zeros = (jnp.zeros_like(cf), tuple(jnp.zeros_like(sv) for sv in sins))
    return (dtens, zeros)


_rope.defvjp(_rope_vjp_fwd, _rope_vjp_bwd)


def _seq_block(s, heads, d):
    """Default sequence block: power of two keeping the per-step working set
    (sum of all tensor tiles, in + out, f32) near 8MB."""
    per_row = max(1, heads) * d * 4 * 2
    cap = 1024
    while cap > 8 and cap * per_row > 8 * 1024 * 1024:
        cap //= 2
    return max(8, min(cap, -(-max(8, s) // 8) * 8))


def _tuned_seq_block(tensors, cf, sins, shifts):
    """Sequence-block size for this signature, autotuned when
    PADDLE_TPU_AUTOTUNE=1. The head/head-dim axes stay whole (they are the
    Mosaic tile), so candidates vary only the sequence block; the recorded
    tile is (seq_rows, head_dim)."""
    from .autotune import pick_block_sizes

    b, s = tensors[0].shape[0], tensors[0].shape[1]
    d = tensors[0].shape[-1]
    heads = sum(t.shape[2] for t in tensors)
    default = (_seq_block(s, heads, d), d)
    per_row = heads * d * 4 * 2
    cands = sorted({default} | {
        (c, d) for c in (64, 128, 256, 512, 1024)
        if c <= -(-max(8, s) // 8) * 8 and c * per_row <= 12 * 1024 * 1024})

    def run_with(bs, _bk):
        outs = _rope_run(tensors, cf, sins, shifts, bs)
        jax.block_until_ready(outs)

    concrete = not any(isinstance(t, jax.core.Tracer)
                       for t in (*tensors, cf, *sins))
    bs, _ = pick_block_sizes(
        "fused_rope", s, d, default, run_with, allow_measure=concrete,
        signature=(b, heads, d, str(tensors[0].dtype), len(shifts)),
        candidates=cands)
    return bs


def apply_fused_rope(tensors, cos_half, sin_half, interleaved=False):
    """Apply rotary embedding to 1..3 tensors [B, S, Hi, D] in ONE kernel
    pass. cos_half/sin_half: [B|1, S, D/2] position tables (data — zero
    cotangent). Differentiable w.r.t. the tensors (custom VJP). Requires
    even D; callers gate on that and fall back to the composite. Under a
    multi-device mesh the kernel runs per shard (partition.py): batch over
    the data axes, heads over mp."""
    tensors = tuple(tensors)

    def local(cos_half, sin_half, *tensors):
        d = tensors[0].shape[-1]
        cf, sins, shifts = _rope_tables_full(cos_half, sin_half, d,
                                             interleaved)
        bs = _tuned_seq_block(tensors, cf, sins, shifts)
        return _rope(tensors, (cf, tuple(sins)), shifts, bs)

    plan = shard_plan(*tensors)
    if plan is None:
        return local(cos_half, sin_half, *tensors)
    nb = tensors[0].shape[0]
    b = plan.axes("batch", nb)
    h = plan.axes("heads", math.gcd(*(t.shape[2] for t in tensors)))
    ts = P(b, None, h, None)
    tab = P(b if cos_half.shape[0] == nb else None, None, None)
    return plan.run(local, [cos_half, sin_half, *tensors],
                    [tab, tab] + [ts] * len(tensors),
                    tuple(ts for _ in tensors))
