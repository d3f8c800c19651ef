"""Flash attention for TPU, in Pallas.

Reference analog: the dynloaded flash-attention library the reference wraps
(paddle/phi/kernels/gpu/flash_attn_kernel.cu:832, dynload
paddle/phi/backends/dynload/flashattn.cc) — re-designed for the TPU memory
hierarchy rather than translated:

- grid (batch, heads, q-blocks, kv-blocks), kv innermost: softmax running
  state (l, acc) lives in VMEM scratch that persists across the sequential
  TPU grid steps, so no atomics / split-k reduction pass is needed.
- K is fed TRANSPOSED ([B, H, D, S]) so the QK^T contraction runs in the
  MXU's native layout (lhs lane x rhs sublane). The round-5 on-chip A/B
  measured the nt form (both contractions on lane dims) at 2.4x slower —
  Mosaic inserts a relayout for it.
- the [bq, bk] f32 logits tile cannot live in vector registers, so EVERY
  separate elementwise pass over it is a full VMEM round trip; chained ops
  fuse into one stream and are effectively free (measured: 1 op == 16 ops).
  The kernel therefore runs ONE fused stream per tile: exp(clamp(s)) +
  row-sum + bf16 cast, with NO separate running-max reduce. Softmax
  shift-invariance makes the unshifted form exact while row max < _CLAMP
  (=60: sum bounded by 2048*e^60 ~ 2e29, far inside f32); rows with logits
  >= 60 saturate to equal weights instead of overflowing. Measured on a
  v5e: 1.9x forward speedup over the online-softmax form.
  PADDLE_TPU_FLASH_SAFE_SOFTMAX=1 restores the classic running-max kernel
  (exact for any logit magnitude).
- causal blocks strictly above the diagonal are skipped via pl.when, blocks
  fully below it skip ALL mask work; only diagonal-crossing blocks build a
  mask (1-D iotas broadcast against each other).
- GQA/MQA: kv heads indexed via the BlockSpec index_map (no head repetition
  materialized in the forward).
- backward = two kernels (dq; dk/dv) recomputing logits from the saved
  softmax LSE — the standard recompute-not-store flash backward, wired as
  jax.custom_vjp, with the same transposed K/V layout for the recomputes.

All entry points pad the sequence to block multiples and mask the padding, so
any length works with static shapes.
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

from . import interpret_mode, mxu_dot, named_pallas_call
from .partition import shard_plan

__all__ = ["flash_attention_fwd", "flash_attention", "flash_window_fwd"]

NEG_INF = -1e30
# unshifted-softmax saturation bound: exact below, equal-weight above (see
# module docstring); 2048-wide rows sum to <= 2e29 << f32 max.
#
# The matching LOWER bound: f32 exp underflows to 0 for arguments below
# ~-87.3 (ln(2^-126)), so in fast mode any key whose logit sits more than
# ~87 below the row's lse contributes exactly 0 weight — in particular a
# fully-masked row (all logits NEG_INF, l=0 -> lse=0 by the l_safe guard)
# produces an all-zero output row rather than NaN. Between the two bounds
# the unshifted form is exact; outside them it saturates (high side) or
# truncates the tail (low side). PADDLE_TPU_FLASH_SAFE_SOFTMAX=1 selects
# the running-max kernel, exact for any magnitude.
_CLAMP = 60.0


def _safe_softmax():
    """Read the safe/fast softmax toggle. Captured ONCE per forward trace
    (flash_attention_fwd) and threaded through the custom-VJP static args —
    the backward must never re-read the env var, or a toggle between
    forward and backward tracing silently corrupts gradients (the two
    kernels disagree on the lse convention: running-max base vs 0)."""
    return os.environ.get("PADDLE_TPU_FLASH_SAFE_SOFTMAX") == "1"


def _block_sizes(sq, skv, d=None):
    """Default tile sizes. Large blocks matter more than MXU-perfect ones on
    TPU: the grid is executed sequentially per core, and the per-tile VMEM
    streaming rate is the binding constraint — 512x1024 uses <6MB of VMEM.
    Head dims >=256 halve the cap to stay inside VMEM with double buffering.

    PADDLE_TPU_FLASH_BLOCK=<n> overrides the cap (hardware escape hatch —
    e.g. =128 restores the round-2 tiling without a code change)."""
    try:
        env_cap = int(os.environ.get("PADDLE_TPU_FLASH_BLOCK", "0"))
    except ValueError:
        env_cap = 0
    if env_cap > 0:
        # explicit override: round to a legal sublane multiple, clamp >= 8
        capq = capk = max(8, env_cap // 8 * 8)
    else:
        capq, capk = 512, 1024
        if d is not None and d >= 256:
            capq, capk = 256, 256  # VMEM headroom for wide heads
    bq = min(capq, -(-max(8, sq) // 8) * 8)  # round up to sublane multiple
    bk = min(capk, -(-max(8, skv) // 8) * 8)
    return bq, bk


def _block_mask(q_start, k_start, bq, bk, off, causal, pad_k, skv,
                pad_q=False, sq=None):
    """Bool keep-mask for one [bq, bk] tile, built from 1-D iotas broadcast
    against each other (a 2-D iota per operand costs two full VPU
    materializations; the broadcast compare is one)."""
    row = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    col = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    mask = None
    if causal:
        mask = col <= row + off
    if pad_k:  # kv padding tail (only when skv % bk != 0)
        m2 = jnp.broadcast_to(col < skv, (bq, bk))
        mask = m2 if mask is None else mask & m2
    if pad_q and sq is not None:  # q padding tail (dkv kernel)
        m3 = jnp.broadcast_to(row < sq, (bq, bk))
        mask = m3 if mask is None else mask & m3
    return mask


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #


def _band_first_block(q_start, off, window, bk):
    """The first key block a query block starting at `q_start` can see under
    a causal window: the one holding column q_start + off - window + 1."""
    return jnp.maximum(q_start + off - window + 1, 0) // bk


def _fwd_kernel(q_ref, kt_ref, v_ref, *rest_refs,
                scale, causal, sq, skv, bq, bk, nk, safe, has_kbias,
                window=None, has_sink=False):
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest_refs[-5:]
    kb_ref = rest_refs[0] if has_kbias else None
    sink_ref = rest_refs[-6] if has_sink else None
    i = pl.program_id(2)
    j = pl.program_id(3)

    q_start = i * bq
    if window is None:
        k_start = j * bk
    else:
        # the grid's last axis walks the band only: its step j is key block
        # first + j (`_fwd`'s index maps fetch the same block)
        k_start = (_band_first_block(q_start, skv - sq, window, bk) + j) * bk
    # bottom-right-aligned causal (flash-attn convention): query at true row r
    # attends to cols <= r + (skv - sq), so decode (sq=1) sees the whole cache
    off = skv - sq
    pad_k = (skv % bk) != 0  # static: no padding -> no padding mask at all

    @pl.when(j == 0)
    def _init():
        if safe:
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _logits():
        # q [bq, D] x kT [D, bk]: contraction lhs-lane x rhs-sublane — the
        # MXU-native form (the nt form costs a Mosaic relayout, 2.4x slower)
        q = q_ref[0, 0]
        kt = kt_ref[0, 0]
        out = mxu_dot(
            q, kt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        if kb_ref is not None:
            # additive per-key bias (padding mask): one fused VPU add —
            # free, since the stream over s is already being paid for.
            # the bias rides as [B, 8, Skv] (8 replicated sublanes — Mosaic
            # needs last-two block dims divisible by (8, 128)); row 0 is
            # broadcast over the tile
            out = out + kb_ref[0, :1].astype(jnp.float32)
        return out

    def _update_fast(s, v):
        # ONE fused VMEM stream: clamp + exp + row-sum + bf16 cast. No
        # running max — softmax shift invariance (see module docstring).
        p = jnp.exp(jnp.minimum(s, _CLAMP))
        l_scr[:, :1] = l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = mxu_dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] + pv

    def _update_safe(s, v):
        # classic online softmax: an extra full pass over the tile for the
        # running-max reduce, exact for any logit magnitude
        m_prev = m_scr[:, :1]  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [bq, bk]
        l_scr[:, :1] = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
        pv = mxu_dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    def _update(s, v):
        if safe:
            _update_safe(s, v)
        else:
            _update_fast(s, v)

    if window is not None:
        # causal band: query row r sees columns r + off - window + 1 ..
        # r + off. Blocks wholly inside it skip the mask, blocks wholly
        # outside it (beyond the diagonal, past the keys) skip everything
        interior = ((k_start + bk - 1 <= q_start + off)
                    & (k_start >= q_start + bq - 1 + off - window + 1)
                    & (k_start + bk <= skv))
        needed = ((k_start <= q_start + bq - 1 + off)
                  & (k_start + bk - 1 >= q_start + off - window + 1)
                  & (k_start < skv))

        @pl.when(interior)
        def _compute_inside():
            _update(_logits(), v_ref[0, 0])

        @pl.when(needed & ~interior)
        def _compute_edge():
            s = _logits()
            row = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            col = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            mask = ((col <= row + off) & (col > row + off - window)
                    & (col < skv))
            _update(jnp.where(mask, s, NEG_INF), v_ref[0, 0])
    elif causal:
        # three-way block split: interior blocks (fully below the diagonal)
        # skip ALL mask work — only diagonal-crossing blocks pay for it
        interior = k_start + bk - 1 <= q_start + off
        needed = k_start <= q_start + bq - 1 + off
        if pad_k:
            interior = interior & (j < nk - 1)

        @pl.when(interior)
        def _compute_interior():
            _update(_logits(), v_ref[0, 0])

        @pl.when(needed & ~interior)
        def _compute_diagonal():
            s = _logits()
            mask = _block_mask(q_start, k_start, bq, bk, off, True, pad_k,
                               skv)
            _update(jnp.where(mask, s, NEG_INF), v_ref[0, 0])
    elif pad_k:
        @pl.when(j < nk - 1)
        def _compute_inner():
            _update(_logits(), v_ref[0, 0])

        @pl.when(j == nk - 1)
        def _compute_tail():
            s = _logits()
            mask = _block_mask(q_start, k_start, bq, bk, off, False, True,
                               skv)
            _update(jnp.where(mask, s, NEG_INF), v_ref[0, 0])
    else:
        _update(_logits(), v_ref[0, 0])

    # last block for this row: nk-1 in general; for causal the last needed one
    if window is not None:
        last = nk - 1
    elif causal:
        last = jnp.clip((q_start + bq - 1 + off) // bk, 0, nk - 1)
    else:
        last = nk - 1

    @pl.when(j == last)
    def _finish():
        l = l_scr[:, :1]
        if sink_ref is not None:
            # the head's sink: one more term of the denominator, under the
            # maximum (safe) or the clamp (fast) the other terms have
            b = sink_ref[0, :1, :1]
            l = l + (jnp.exp(b - m_scr[:, :1]) if safe
                     else jnp.exp(jnp.minimum(b, _CLAMP)))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # lse rides as [B, H, Sq, 1]: a trailing singleton keeps the block's
        # last-two dims (bq, 1) legal under Mosaic's tiling rule (a [.., bq]
        # block would put the H axis second-to-last with block size 1)
        base = m_scr[:, :1] if safe else 0.0
        lse_ref[0, 0] = base + jnp.log(l_safe)


def _fwd(q, k, v, scale, causal, sq, skv, bq=None, bk=None, kbias=None,
         safe=None, window=None, sink=None):
    """With `window` (causal, no key bias) the kernel runs as
    `flash_fwd_window`: the grid's last axis is as long as the widest band of
    key blocks a query block can see, not as the keys, and the index maps
    start each query block at its band's first key block. The values may be
    of another width than q and k (`Dv`, a latent-attention prefill's 128
    beside 192): the value and output blocks and the accumulator are `Dv`
    wide, the body is the same. `sink` [H] f32: a logit a head that joins
    the softmax's denominator at the end and brings no value."""
    B, H, Sqp, D = q.shape
    _, Hkv, Skvp, _ = k.shape
    Dv = v.shape[-1]
    if bq is None or bk is None:
        bq, bk = _block_sizes(Sqp, Skvp, d=D)
    if safe is None:
        safe = _safe_softmax()
    nq = Sqp // bq
    nk = Skvp // bk
    group = H // Hkv
    kt = jnp.swapaxes(k, 2, 3)  # [B, Hkv, D, Skv]: MXU-native QK^T layout

    if window is None:
        def kblock(i, j):
            return j
    else:
        # a band spans window + bq - 1 columns, which touch at most this
        # many key blocks; a step past the keys refetches the last block
        # (no DMA) and computes nothing
        blocks = nk
        nk = min(nk, (window + bq - 2) // bk + 2)

        def kblock(i, j):
            return jnp.minimum(
                _band_first_block(i * bq, skv - sq, window, bk) + j,
                blocks - 1)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, sq=sq, skv=skv,
        bq=bq, bk=bk, nk=nk, safe=safe,
        has_kbias=kbias is not None, window=window,
        has_sink=sink is not None,
    )
    in_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, D, bk),
                     lambda b, h, i, j, g=group: (b, h // g, 0, kblock(i, j))),
        pl.BlockSpec((1, 1, bk, Dv),
                     lambda b, h, i, j, g=group: (b, h // g, kblock(i, j), 0)),
    ]
    args = [q, kt, v]
    if kbias is not None:  # [B, Skvp] additive per-key bias (padding mask)
        spec, arg = _kbias_spec_and_arg(kbias, B, bk,
                                        lambda b, h, i, j: (b, 0, j))
        in_specs.append(spec)
        args.append(arg)
    if sink is not None:
        # one (8, 128) f32 tile a head, the logit in every place of it
        in_specs.append(
            pl.BlockSpec((1, 8, 128), lambda b, h, i, j: (h, 0, 0)))
        args.append(jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None, None], (H, 8, 128)))
    out, lse = named_pallas_call(
        "flash_fwd" if window is None else "flash_fwd_window", kernel,
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sqp, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sqp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(*args)
    return out, lse


# --------------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------------- #


def _recompute_p(q_ref, kt_ref, lse_ref, scale, safe, kb_ref=None):
    """One fused stream: s = q@kT (MXU) then exp(s - lse) (VPU). The fast
    forward clamps logits at _CLAMP, so its backward must clamp identically
    for gradient consistency. kb_ref: optional [1, bk] additive key bias
    (padding mask) — folded in before the clamp like the forward.

    Returns (p, ds_gate): ds_gate is None in safe mode; in fast mode it is
    the boolean clamp mask — where the forward SATURATED (s >= _CLAMP),
    d p/d s is exactly 0 (the clamp is flat), so ds must be zeroed there.
    p itself stays ungated: dv = p^T @ do is correct with the saturated
    weights."""
    s = mxu_dot(
        q_ref[0, 0], kt_ref[0, 0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32
    ) * scale
    if kb_ref is not None:
        s = s + kb_ref[0, :1].astype(jnp.float32)
    if not safe:
        gate = s < _CLAMP
        return jnp.exp(jnp.minimum(s, _CLAMP) - lse_ref[0, 0]), gate
    return jnp.exp(s - lse_ref[0, 0]), None


def _bwd_dq_kernel(q_ref, kt_ref, vt_ref, k_ref, *rest_refs, scale, causal,
                   sq, skv, bq, bk, nk, safe, has_kbias):
    if has_kbias:
        kb_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = rest_refs
    else:
        do_ref, lse_ref, delta_ref, dq_ref, dq_scr = rest_refs
        kb_ref = None
    i = pl.program_id(2)
    j = pl.program_id(3)
    q_start = i * bq
    k_start = j * bk
    off = skv - sq
    pad_k = (skv % bk) != 0

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _accum(masked):
        p, gate = _recompute_p(q_ref, kt_ref, lse_ref, scale, safe, kb_ref)
        if masked:
            mask = _block_mask(q_start, k_start, bq, bk, off, causal, pad_k,
                               skv)
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
        do = do_ref[0, 0]
        # dp = do @ v^T — vT input makes this MXU-native like the recompute
        dp = mxu_dot(
            do, vt_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, 0]) * scale
        if gate is not None:  # fast path: zero ds where the clamp saturated
            ds = jnp.where(gate, ds, 0.0)
        ds = ds.astype(k_ref.dtype)
        dq_scr[:] = dq_scr[:] + mxu_dot(
            ds, k_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )

    if causal:
        interior = k_start + bk - 1 <= q_start + off
        needed = k_start <= q_start + bq - 1 + off
        if pad_k:
            interior = interior & (j < nk - 1)

        @pl.when(interior)
        def _compute_interior():
            _accum(masked=False)

        @pl.when(needed & ~interior)
        def _compute_masked():
            _accum(masked=True)
    elif pad_k:
        @pl.when(j < nk - 1)
        def _compute_inner():
            _accum(masked=False)

        @pl.when(j == nk - 1)
        def _compute_tail():
            _accum(masked=True)
    else:
        _accum(masked=False)

    if causal:
        last = jnp.clip((q_start + bq - 1 + off) // bk, 0, nk - 1)
    else:
        last = nk - 1

    @pl.when(j == last)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, kt_ref, vt_ref, *rest_refs, scale, causal, sq,
                    skv, bq, bk, nq, safe, has_kbias):
    if has_kbias:
        (kb_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr,
         dv_scr) = rest_refs
    else:
        do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest_refs
        kb_ref = None
    j = pl.program_id(2)  # kv block
    i = pl.program_id(3)  # q block
    q_start = i * bq
    k_start = j * bk
    off = skv - sq
    pad_k = (skv % bk) != 0
    pad_q = (sq % bq) != 0

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accum(masked):
        p, gate = _recompute_p(q_ref, kt_ref, lse_ref, scale, safe, kb_ref)
        if masked:
            mask = _block_mask(q_start, k_start, bq, bk, off, causal, pad_k,
                               skv, pad_q=pad_q, sq=sq)
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
        do = do_ref[0, 0]
        dv_scr[:] = dv_scr[:] + mxu_dot(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        dp = mxu_dot(
            do, vt_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, 0]) * scale
        if gate is not None:  # fast path: zero ds where the clamp saturated
            ds = jnp.where(gate, ds, 0.0)
        ds = ds.astype(q_ref.dtype)
        dk_scr[:] = dk_scr[:] + mxu_dot(
            ds, q_ref[0, 0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )

    # causal: q block needed iff q_end + off >= k_start; interior q blocks
    # (whole block past the diagonal) need no causal mask
    if causal:
        interior = k_start + bk - 1 <= q_start + off
        needed = q_start + bq - 1 + off >= k_start
        if pad_k:
            interior = interior & (j < pl.num_programs(2) - 1)
        if pad_q:
            interior = interior & (i < nq - 1)

        @pl.when(interior)
        def _compute_interior():
            _accum(masked=False)

        @pl.when(needed & ~interior)
        def _compute_masked():
            _accum(masked=True)
    elif pad_k or pad_q:
        tail = jnp.bool_(False)
        if pad_k:
            tail = tail | (j == pl.num_programs(2) - 1)
        if pad_q:
            tail = tail | (i == nq - 1)

        @pl.when(~tail)
        def _compute_inner():
            _accum(masked=False)

        @pl.when(tail)
        def _compute_tail():
            _accum(masked=True)
    else:
        _accum(masked=False)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(scale, causal, sq, skv, residuals, dout, bq, bk, safe,
         kbias=None):
    # (bq, bk, safe) are the FORWARD's (possibly autotuned) block sizes and
    # softmax mode, threaded through the VJP's static args — recomputing
    # them here could diverge from the forward (padding mismatch leaving
    # grid rows unwritten; an env-var toggle flipping the lse convention
    # between forward and backward, silently corrupting gradients)
    q, k, v, out, lse = residuals
    B, H, Sqp, D = q.shape
    _, Hkv, Skvp, _ = k.shape
    nq = Sqp // bq
    nk = Skvp // bk
    group = H // Hkv
    kt = jnp.swapaxes(k, 2, 3)  # [B, Hkv, D, Skv]
    vt = jnp.swapaxes(v, 2, 3)

    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B, H, Sqp, 1] like lse

    dq_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, D, bk), lambda b, h, i, j, g=group: (b, h // g, 0, j)),
        pl.BlockSpec((1, 1, D, bk), lambda b, h, i, j, g=group: (b, h // g, 0, j)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
    ]
    dq_args = [q, kt, vt, k]
    if kbias is not None:
        spec, arg = _kbias_spec_and_arg(kbias, B, bk,
                                        lambda b, h, i, j: (b, 0, j))
        dq_specs.append(spec)
        dq_args.append(arg)
    dq_specs += [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
    ]
    dq = named_pallas_call(
        "flash_bwd_dq",
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          sq=sq, skv=skv, bq=bq, bk=bk, nk=nk, safe=safe,
                          has_kbias=kbias is not None),
        grid=(B, H, nq, nk),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sqp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret_mode(),
    )(*dq_args, dout, lse, delta)

    # dk/dv over expanded heads, then group-sum for GQA
    dkv_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, D, bk), lambda b, h, j, i, g=group: (b, h // g, 0, j)),
        pl.BlockSpec((1, 1, D, bk), lambda b, h, j, i, g=group: (b, h // g, 0, j)),
    ]
    dkv_args = [q, kt, vt]
    if kbias is not None:
        spec, arg = _kbias_spec_and_arg(kbias, B, bk,
                                        lambda b, h, j, i: (b, 0, j))
        dkv_specs.append(spec)
        dkv_args.append(arg)
    dkv_specs += [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
    ]
    dk, dv = named_pallas_call(
        "flash_bwd_dkv",
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          sq=sq, skv=skv, bq=bq, bk=bk, nq=nq, safe=safe,
                          has_kbias=kbias is not None),
        grid=(B, H, nk, nq),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Skvp, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Skvp, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(*dkv_args, dout, lse, delta)

    if group > 1:
        dk = dk.reshape(B, Hkv, group, Skvp, D).sum(axis=2)
        dv = dv.reshape(B, Hkv, group, Skvp, D).sum(axis=2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------------- #
# public entry: [B, S, H, D] paddle layout, custom VJP
# --------------------------------------------------------------------------- #


def _pad_seq(x, block):
    s = x.shape[2]
    pad = (-s) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, bq, bk, safe):
    out, _ = _flash_fwd_res(q, k, v, causal, scale, bq, bk, safe)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_kb(q, k, v, kbias, causal, scale, bq, bk, safe):
    """Variant with an additive per-key bias [B, Skv] (padding mask).

    The bias is treated as DATA: its cotangent is zero (callers with a
    trainable bias must use the composite path — the functional dispatch
    checks stop_gradient for exactly this)."""
    out, _ = _flash_kb_fwd_res(q, k, v, kbias, causal, scale, bq, bk, safe)
    return out


def _kbias_spec_and_arg(kbias, B, bk, index_map):
    """BlockSpec + operand for the key bias: [B, 8, Skvp] with 8 replicated
    sublanes (Mosaic wants last-two block dims divisible by (8, 128));
    kernels read row 0 and broadcast. ONE definition — the fwd and both bwd
    kernels must stay tiled identically."""
    spec = pl.BlockSpec((1, 8, bk), index_map)
    arg = jnp.broadcast_to(kbias[:, None, :], (B, 8, kbias.shape[1]))
    return spec, arg


def _pad_kbias(kbias, skv, block):
    pad = (-skv) % block
    if pad:
        # padded key columns must stay masked even without the pad_k mask
        kbias = jnp.pad(kbias, ((0, 0), (0, pad)), constant_values=NEG_INF)
    return kbias


def _flash_kb_fwd_res(q, k, v, kbias, causal, scale, bq, bk, safe):
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    qp = _pad_seq(q, bq)
    kp = _pad_seq(k, bk)
    vp = _pad_seq(v, bk)
    kbp = _pad_kbias(kbias.astype(jnp.float32), Skv, bk)
    out, lse = _fwd(qp, kp, vp, scale, causal, Sq, Skv, bq=bq, bk=bk,
                    kbias=kbp, safe=safe)
    return out[:, :, :Sq], (qp, kp, vp, kbp, out, lse)


def _flash_kb_vjp_fwd(q, k, v, kbias, causal, scale, bq, bk, safe):
    out, res = _flash_kb_fwd_res(q, k, v, kbias, causal, scale, bq, bk,
                                 safe)
    return out, (res, q.shape[2], k.shape[2])


def _flash_kb_vjp_bwd(causal, scale, bq, bk, safe, saved, dout):
    (qp, kp, vp, kbp, outp, lse), sq, skv = saved
    dop = jnp.pad(dout, ((0, 0), (0, 0), (0, qp.shape[2] - sq), (0, 0)))
    dq, dk, dv = _bwd(scale, causal, sq, skv, (qp, kp, vp, outp, lse), dop,
                      bq, bk, safe, kbias=kbp)
    # the mask is data, not a trained parameter — zero cotangent; primal
    # kbias is f32 by construction (entry casts), so dtypes always match
    return (dq[:, :, :sq], dk[:, :, :skv], dv[:, :, :skv],
            jnp.zeros((kbp.shape[0], skv), jnp.float32))


_flash_kb.defvjp(_flash_kb_vjp_fwd, _flash_kb_vjp_bwd)


def _tuned_blocks(q, k, v, causal, scale):
    """Forward block sizes, autotuned per (seq, kv-seq) signature when
    PADDLE_TPU_AUTOTUNE=1 (reference: phi/kernels/autotune cache). Always
    goes through pick_block_sizes — disabled runs return the default fast
    but still land the chosen tile in the telemetry registry
    (autotune.chosen_tiles), so the step-timeline JSONL and bench perf line
    can attribute MFU movement to tile choices."""
    from .autotune import pick_block_sizes

    sq, skv = q.shape[2], k.shape[2]
    default = _block_sizes(sq, skv, d=q.shape[-1])

    def run_with(bq, bk):
        out, _ = _fwd(_pad_seq(q, bq), _pad_seq(k, bk), _pad_seq(v, bk),
                      scale, causal, sq, skv, bq=bq, bk=bk)
        out.block_until_ready()

    concrete = not any(isinstance(x, jax.core.Tracer) for x in (q, k, v))
    B, H, _, D = q.shape
    # a value width of its own is part of the signature; at equal widths
    # the signature is what it was
    widths = (D,) if v.shape[-1] == D else (D, v.shape[-1])
    return pick_block_sizes(
        "flash_fwd", sq, skv, default, run_with, allow_measure=concrete,
        signature=(B, H, k.shape[1], *widths, str(q.dtype), bool(causal)))


def _flash_fwd_res(q, k, v, causal, scale, bq, bk, safe):
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    qp = _pad_seq(q, bq)
    kp = _pad_seq(k, bk)
    vp = _pad_seq(v, bk)
    out, lse = _fwd(qp, kp, vp, scale, causal, Sq, Skv, bq=bq, bk=bk,
                    safe=safe)
    return out[:, :, :Sq], (qp, kp, vp, out, lse)


def _flash_vjp_fwd(q, k, v, causal, scale, bq, bk, safe):
    out, res = _flash_fwd_res(q, k, v, causal, scale, bq, bk, safe)
    return out, (res, q.shape[2], k.shape[2])


def _flash_vjp_bwd(causal, scale, bq, bk, safe, saved, dout):
    (qp, kp, vp, outp, lse), sq, skv = saved
    if vp.shape[-1] != qp.shape[-1]:
        raise NotImplementedError(
            "flash attention with a value width of its own is forward only")
    dop = jnp.pad(dout, ((0, 0), (0, 0), (0, qp.shape[2] - sq), (0, 0)))
    dq, dk, dv = _bwd(scale, causal, sq, skv, (qp, kp, vp, outp, lse), dop,
                      bq, bk, safe)
    return dq[:, :, :sq], dk[:, :, :skv], dv[:, :, :skv]


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention_fwd(q, k, v, causal=False, scale=None, key_bias=None):
    """Paddle-layout entry: q [B,Sq,H,D], k/v [B,Skv,Hkv,D] → [B,Sq,H,D]
    (v may be [B,Skv,Hkv,Dv], the output then [B,Sq,H,Dv]: forward only).

    key_bias: optional [B, Skv] ADDITIVE per-key bias (the padding-mask
    case — encoder models), fused into the kernel's logits stream.
    Differentiable (custom VJP, flash backward). Under a multi-device mesh
    the kernel runs per shard: batch over the data axes, heads over mp
    (ops/pallas/partition.py). Reference API:
    python/paddle/nn/functional/flash_attention.py:358."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    plan = shard_plan(q, k, v)
    if plan is not None:
        b = plan.axes("batch", q.shape[0])
        h = plan.axes("heads", math.gcd(q.shape[2], k.shape[2]))
        qkv = P(b, None, h, None)
        args, specs = [q, k, v], [qkv, qkv, qkv]
        if key_bias is not None:
            args.append(key_bias)
            specs.append(P(b if key_bias.shape[0] == q.shape[0] else None,
                           None))
        return plan.run(
            lambda q, k, v, *kb: _flash_local(q, k, v, causal, scale,
                                              kb[0] if kb else None),
            args, specs, qkv)
    return _flash_local(q, k, v, causal, scale, key_bias)


def _flash_local(q, k, v, causal, scale, key_bias):
    # the kernels feed the MXU raw operands, so mixed q/kv dtypes must be
    # normalized here (promote everything to q's dtype)
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    qt = jnp.swapaxes(q, 1, 2)  # [B,H,S,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    bq, bk = _tuned_blocks(qt, kt, vt, causal, scale)
    # softmax mode is captured HERE, at forward trace time, and rides the
    # custom-VJP static args: fwd and bwd kernels always agree on the lse
    # convention even if the env toggle flips between their traces
    safe = _safe_softmax()
    if key_bias is not None:
        # f32 primal by construction: the zero cotangent in the VJP is f32
        out = _flash_kb(qt, kt, vt, key_bias.astype(jnp.float32), causal,
                        scale, bq, bk, safe)
    else:
        out = _flash(qt, kt, vt, causal, scale, bq, bk, safe)
    return jnp.swapaxes(out, 1, 2)


def flash_window_fwd(q, k, v, window, scale=None, sink=None):
    """Causal sliding-window attention forward (`flash_fwd_window`),
    paddle layout: q [B, S, H, D], k/v [B, S, Hkv, D] -> [B, S, H, D] (v may
    be [B, S, Hkv, Dv], the output then [B, S, H, Dv]); query
    i sees keys j with 0 <= i - j < window. `sink` [H]: a learned logit a
    head whose exponential joins the softmax's denominator and brings no
    value. Blocks wholly behind the window
    are neither fetched nor computed: the grid walks each query block's band
    only. Inference only (no VJP); single device."""
    from .autotune import pick_block_sizes

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k.astype(q.dtype), 1, 2)
    vt = jnp.swapaxes(v.astype(q.dtype), 1, 2)
    B, H, S, D = qt.shape
    # the tile follows from the shapes and is the tuner's only candidate:
    # nothing sweeps inside a serving process
    bq, bk = _block_sizes(S, S, d=D)
    # a key block no wider than the window needs (its next power of two, a
    # lane tile at least): a band of 128 keys walked in blocks of 1024
    # would compute eight times what it sees
    default = (bq, min(bk, max(128, 1 << (int(window) - 1).bit_length())))
    bq, bk = pick_block_sizes(
        "flash_fwd_window", S, S, default, lambda bq, bk: None,
        allow_measure=False,
        signature=(B, H, kt.shape[1], D, str(q.dtype), int(window)),
        candidates=[default])
    out, _ = _fwd(_pad_seq(qt, bq), _pad_seq(kt, bk), _pad_seq(vt, bk),
                  scale, True, S, S, bq=bq, bk=bk, safe=_safe_softmax(),
                  window=int(window), sink=sink)
    return jnp.swapaxes(out[:, :, :S], 1, 2)


flash_attention = flash_attention_fwd
