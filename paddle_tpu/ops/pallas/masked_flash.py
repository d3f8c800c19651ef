"""Flashmask + varlen flash attention for TPU, in Pallas.

Reference analogs:
- flashmask: python/paddle/nn/functional/flash_attention.py:1299
  (flashmask_attention) backed by the flashmask params of the dynloaded
  flash-attention kernel (paddle/phi/kernels/gpu/flash_attn_kernel.cu:832).
- varlen: flash_attn_unpadded (flash_attention.py) / flash_attn varlen
  kernels — ragged packed batches.

TPU-native design (not a translation):
- Same online-softmax running state in VMEM scratch as the dense kernel
  (flash_attention.py in this package), kv innermost on the sequential grid.
- flashmask's per-column row ranges ride in as a [B, Hm, n, Sk] operand
  sliced per kv block; the keep-mask is computed on the VPU from the loaded
  index columns, and a whole (q-block, kv-block) tile is SKIPPED (no MXU
  work) when its keep-mask is empty — the block-sparsity win the reference
  gets from its flashmask CUDA kernel.
- varlen uses segment ids + in-segment positions (the TPU-idiomatic ragged
  encoding: static shapes, no dynamic slicing); blocks whose q/k segment
  ranges cannot intersect are skipped.
- backward recomputes logits from the saved LSE (flash backward), with the
  same skip conditions; wired as jax.custom_vjp.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from . import interpret_mode, mxu_dot, named_pallas_call
from .partition import shard_plan
from .flash_attention import NEG_INF, _block_sizes, _pad_seq

__all__ = ["flashmask_attention_fwd", "varlen_flash_attention_fwd"]


# --------------------------------------------------------------------------- #
# flashmask keep-mask from startend_row_indices columns
# --------------------------------------------------------------------------- #


def _flashmask_keep(idx_blk, row, col, sq, skv, causal, n):
    """keep[bq, bk] from idx columns [n, bk]; row/col are absolute indices.

    Encoding (reference flashmask_attention docstring):
      causal n=1: rows >= start masked;  causal n=2: [start, end) masked
      non-causal n=2: (LTS, UTE) -> rows >= LTS or < UTE masked
      non-causal n=4: [LTS, LTE) and [UTS, UTE) masked
    """
    keep = (col < skv) & (row < sq)
    if causal:
        keep = keep & (col <= row)  # flashmask is top-left causal (sq == skv)
        start = idx_blk[0][None, :]
        if n == 1:
            masked = row >= start
        else:
            end = idx_blk[1][None, :]
            masked = (row >= start) & (row < end)
    else:
        if n == 2:
            lts = idx_blk[0][None, :]
            ute = idx_blk[1][None, :]
            masked = (row >= lts) | (row < ute)
        else:
            lts = idx_blk[0][None, :]
            lte = idx_blk[1][None, :]
            uts = idx_blk[2][None, :]
            ute = idx_blk[3][None, :]
            masked = ((row >= lts) & (row < lte)) | ((row >= uts) & (row < ute))
    return keep & ~masked


def _fm_fwd_kernel(q_ref, kt_ref, v_ref, idx_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr,
                   *, scale, causal, n, sq, skv, bq, bk, nk):
    i = pl.program_id(2)
    j = pl.program_id(3)
    q_start = i * bq
    k_start = j * bk

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # static causal skip (strictly above the diagonal)
    needed = k_start <= q_start + bq - 1 if causal else True

    row = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    idx_blk = idx_ref[0, 0].astype(jnp.int32)  # [n, bk]
    keep = _flashmask_keep(idx_blk, row, col, sq, skv, causal, n)

    @pl.when(needed & jnp.any(keep))
    def _compute():
        # feed the MXU native dtypes (bf16 under AMP — an f32 upcast would
        # cost ~4x MXU passes); accumulation is f32 via preferred_element_type
        q = q_ref[0, 0]
        kt = kt_ref[0, 0]  # [D, bk]: MXU-native QK^T (see flash_attention.py)
        s = mxu_dot(
            q, kt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(keep, p, 0.0)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)

        v = v_ref[0, 0]
        pv = mxu_dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # [bq, 1] layout (trailing singleton keeps Mosaic tiling legal,
        # see flash_attention.py _fwd_kernel)
        lse_ref[0, 0] = jnp.where(l == 0.0, NEG_INF,
                                  m_scr[:, :1] + jnp.log(l_safe))


def _fm_bwd_dq_kernel(q_ref, kt_ref, vt_ref, k_ref, idx_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dq_scr,
                      *, scale, causal, n, sq, skv, bq, bk, nk):
    i = pl.program_id(2)
    j = pl.program_id(3)
    q_start = i * bq
    k_start = j * bk

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    needed = k_start <= q_start + bq - 1 if causal else True
    row = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    idx_blk = idx_ref[0, 0].astype(jnp.int32)
    keep = _flashmask_keep(idx_blk, row, col, sq, skv, causal, n)

    @pl.when(needed & jnp.any(keep))
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # [bq, 1]
        delta = delta_ref[0, 0]
        s = mxu_dot(
            q, kt_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        ) * scale
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        dp = mxu_dot(
            do, vt_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_scr[:] = dq_scr[:] + mxu_dot(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _fm_bwd_dkv_kernel(q_ref, kt_ref, vt_ref, idx_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                       *, scale, causal, n, sq, skv, bq, bk, nq):
    j = pl.program_id(2)  # kv block
    i = pl.program_id(3)  # q block
    q_start = i * bq
    k_start = j * bk

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    needed = q_start + bq - 1 >= k_start if causal else True
    row = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    idx_blk = idx_ref[0, 0].astype(jnp.int32)
    keep = _flashmask_keep(idx_blk, row, col, sq, skv, causal, n)

    @pl.when(needed & jnp.any(keep))
    def _compute():
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # [bq, 1]
        delta = delta_ref[0, 0]
        s = mxu_dot(
            q, kt_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        ) * scale
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        dv_scr[:] = dv_scr[:] + mxu_dot(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        dp = mxu_dot(
            do, vt_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_scr[:] = dk_scr[:] + mxu_dot(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _fm_specs(B, H, Hm, Hkv, n, bq, bk, D):
    """[q, kT, v, idx] input specs (K rides TRANSPOSED [B,Hkv,D,S] so the
    QK^T contraction is MXU-native — see flash_attention.py)."""
    group = H // Hkv
    gm = H // Hm
    return [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, D, bk), lambda b, h, i, j, g=group: (b, h // g, 0, j)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        pl.BlockSpec((1, 1, n, bk), lambda b, h, i, j, g=gm: (b, h // g, 0, j)),
    ]


def _fm_fwd(q, k, v, idx, scale, causal, sq, skv, bq, bk):
    B, H, Sqp, D = q.shape
    _, Hkv, Skvp, _ = k.shape
    Hm, n = idx.shape[1], idx.shape[2]
    nq, nk = Sqp // bq, Skvp // bk

    kernel = functools.partial(
        _fm_fwd_kernel, scale=scale, causal=causal, n=n, sq=sq, skv=skv,
        bq=bq, bk=bk, nk=nk)
    return named_pallas_call(
        "flashmask_fwd", kernel,
        grid=(B, H, nq, nk),
        in_specs=_fm_specs(B, H, Hm, Hkv, n, bq, bk, D),
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sqp, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sqp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(q, jnp.swapaxes(k, 2, 3), v, idx)


def _fm_bwd(scale, causal, sq, skv, residuals, dout, bq, bk):
    # (bq, bk) are the FORWARD's block sizes threaded through the custom-VJP
    # statics — recomputing here could diverge (env override changing
    # mid-run) and leave bwd grid rows unwritten
    q, k, v, idx, out, lse = residuals
    B, H, Sqp, D = q.shape
    _, Hkv, Skvp, _ = k.shape
    Hm, n = idx.shape[1], idx.shape[2]
    nq, nk = Sqp // bq, Skvp // bk
    group = H // Hkv

    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B, H, Sqp, 1] like lse
    kt = jnp.swapaxes(k, 2, 3)  # [B, Hkv, D, Skv]: MXU-native recomputes
    vt = jnp.swapaxes(v, 2, 3)
    gm = H // Hm

    dq = named_pallas_call(
        "flashmask_bwd_dq",
        functools.partial(_fm_bwd_dq_kernel, scale=scale, causal=causal, n=n,
                          sq=sq, skv=skv, bq=bq, bk=bk, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, D, bk), lambda b, h, i, j, g=group: (b, h // g, 0, j)),
            pl.BlockSpec((1, 1, D, bk), lambda b, h, i, j, g=group: (b, h // g, 0, j)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, n, bk), lambda b, h, i, j, g=gm: (b, h // g, 0, j)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sqp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret_mode(),
    )(q, kt, vt, k, idx, dout, lse, delta)

    dk, dv = named_pallas_call(
        "flashmask_bwd_dkv",
        functools.partial(_fm_bwd_dkv_kernel, scale=scale, causal=causal, n=n,
                          sq=sq, skv=skv, bq=bq, bk=bk, nq=nq),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, D, bk), lambda b, h, j, i, g=group: (b, h // g, 0, j)),
            pl.BlockSpec((1, 1, D, bk), lambda b, h, j, i, g=group: (b, h // g, 0, j)),
            pl.BlockSpec((1, 1, n, bk), lambda b, h, j, i, g=gm: (b, h // g, 0, j)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
        ],
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
    )(q, kt, vt, idx, dout, lse, delta)

    if group > 1:
        dk = dk.reshape(B, Hkv, group, Skvp, D).sum(axis=2)
        dv = dv.reshape(B, Hkv, group, Skvp, D).sum(axis=2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flashmask(q, k, v, idx, causal, scale, bq, bk):
    out, _ = _flashmask_fwd_res(q, k, v, idx, causal, scale, bq, bk)
    return out


def _flashmask_fwd_res(q, k, v, idx, causal, scale, bq, bk):
    sq, skv = q.shape[2], k.shape[2]
    qp = _pad_seq(q, bq)
    kp = _pad_seq(k, bk)
    vp = _pad_seq(v, bk)
    pad_k = kp.shape[2] - skv
    # padded key columns are dropped by the (col < skv) term in the keep mask,
    # so the pad value for idx does not matter
    idxp = jnp.pad(idx, ((0, 0), (0, 0), (0, 0), (0, pad_k)))
    out, lse = _fm_fwd(qp, kp, vp, idxp, scale, causal, sq, skv, bq, bk)
    return out[:, :, :sq], (qp, kp, vp, idxp, out, lse)


def _flashmask_vjp_fwd(q, k, v, idx, causal, scale, bq, bk):
    out, res = _flashmask_fwd_res(q, k, v, idx, causal, scale, bq, bk)
    return out, (res, q.shape[2], k.shape[2])


def _flashmask_vjp_bwd(causal, scale, bq, bk, saved, dout):
    res, sq, skv = saved
    qp = res[0]
    dop = jnp.pad(dout, ((0, 0), (0, 0), (0, qp.shape[2] - sq), (0, 0)))
    dq, dk, dv = _fm_bwd(scale, causal, sq, skv, res, dop, bq, bk)
    return dq[:, :, :sq], dk[:, :, :skv], dv[:, :, :skv], None


_flashmask.defvjp(_flashmask_vjp_fwd, _flashmask_vjp_bwd)


def _tuned_blocks_fm(q, k, v, idx, causal, scale):
    """Forward block sizes for the flashmask kernel ([B,H,S,D] layout),
    autotuned per signature when PADDLE_TPU_AUTOTUNE=1 — previously this
    kernel ran fixed _block_sizes defaults and the tuning env var silently
    did nothing for it. Under a jit trace only the cache is consulted
    (allow_measure=False); misses are counted as fallbacks and warned."""
    from .autotune import pick_block_sizes

    sq, skv = q.shape[2], k.shape[2]
    default = _block_sizes(sq, skv, d=q.shape[-1])

    def run_with(bq, bk):
        qp = _pad_seq(q, bq)
        kp = _pad_seq(k, bk)
        vp = _pad_seq(v, bk)
        idxp = jnp.pad(idx, ((0, 0), (0, 0), (0, 0),
                             (0, kp.shape[2] - skv)))
        out, _ = _fm_fwd(qp, kp, vp, idxp, scale, causal, sq, skv, bq, bk)
        out.block_until_ready()

    concrete = not any(isinstance(x, jax.core.Tracer)
                       for x in (q, k, v, idx))
    return pick_block_sizes(
        "flashmask_fwd", sq, skv, default, run_with, allow_measure=concrete,
        signature=(q.shape[0], q.shape[1], k.shape[1], idx.shape[1],
                   idx.shape[2], q.shape[-1], str(q.dtype), bool(causal)))


def flashmask_attention_fwd(q, k, v, startend_row_indices, causal=True,
                            scale=None):
    """Paddle-layout entry: q [B,Sq,H,D], k/v [B,Skv,Hkv,D],
    startend_row_indices [B,Hm,Skv,n] -> [B,Sq,H,D]. Differentiable. Under
    a multi-device mesh the kernel runs per shard (partition.py): batch
    over the data axes, heads — and the mask's heads, when it has its own —
    over mp."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    plan = shard_plan(q, k, v)
    if plan is not None:
        hm = startend_row_indices.shape[1]
        b = plan.axes("batch", q.shape[0])
        h = plan.axes("heads", math.gcd(math.gcd(q.shape[2], k.shape[2]),
                                        hm if hm > 1 else 0))
        qkv = P(b, None, h, None)
        idx_spec = P(b if startend_row_indices.shape[0] == q.shape[0]
                     else None, h if hm > 1 else None, None, None)
        return plan.run(
            lambda q, k, v, idx: _flashmask_local(q, k, v, idx, causal,
                                                  scale),
            [q, k, v, startend_row_indices], [qkv, qkv, qkv, idx_spec], qkv)
    return _flashmask_local(q, k, v, startend_row_indices, causal, scale)


def _flashmask_local(q, k, v, startend_row_indices, causal, scale):
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    idx = jnp.moveaxis(startend_row_indices.astype(jnp.int32), 2, 3)  # [B,Hm,n,Sk]
    bq, bk = _tuned_blocks_fm(qt, kt, vt, idx, causal, scale)
    out = _flashmask(qt, kt, vt, idx, causal, scale, bq, bk)
    return jnp.swapaxes(out, 1, 2)


# --------------------------------------------------------------------------- #
# varlen (packed ragged batches, segment-id encoding)
# --------------------------------------------------------------------------- #


def _vl_keep(sq_blk, sk_blk, pq_blk, pk_blk, causal, tq, tk, q_start, k_start,
             bq, bk):
    """sq/pq ride as [bq, 1] columns, sk/pk as [1, bk] rows (2-D layouts —
    1-D s32 operands trip the XLA-vs-Mosaic tiling mismatch on real TPUs);
    plain broadcasting then forms the [bq, bk] mask."""
    row = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = (row < tq) & (col < tk)
    keep = keep & (sq_blk == sk_blk)
    if causal:
        keep = keep & (pq_blk >= pk_blk)
    return keep


def _vl_fwd_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, pq_ref, pk_ref,
                   o_ref, lse_ref, m_scr, l_scr, acc_scr,
                   *, scale, causal, tq, tk, bq, bk, nk):
    i = pl.program_id(1)
    j = pl.program_id(2)
    q_start = i * bq
    k_start = j * bk

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    keep = _vl_keep(sq_ref[...].astype(jnp.int32), sk_ref[...].astype(jnp.int32),
                    pq_ref[...].astype(jnp.int32), pk_ref[...].astype(jnp.int32),
                    causal, tq, tk, q_start, k_start, bq, bk)

    @pl.when(jnp.any(keep))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = mxu_dot(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = mxu_dot(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l == 0.0, NEG_INF,
                               m_scr[:, :1] + jnp.log(l_safe))


def _vl_bwd_dq_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, pq_ref, pk_ref,
                      do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
                      *, scale, causal, tq, tk, bq, bk, nk):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    keep = _vl_keep(sq_ref[...].astype(jnp.int32), sk_ref[...].astype(jnp.int32),
                    pq_ref[...].astype(jnp.int32), pk_ref[...].astype(jnp.int32),
                    causal, tq, tk, i * bq, j * bk, bq, bk)

    @pl.when(jnp.any(keep))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]  # [bq, 1]
        delta = delta_ref[0]
        s = mxu_dot(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        dp = mxu_dot(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dq_scr[:] = dq_scr[:] + mxu_dot(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _vl_bwd_dkv_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, pq_ref, pk_ref,
                       do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                       dk_scr, dv_scr,
                       *, scale, causal, tq, tk, bq, bk, nq):
    j = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    keep = _vl_keep(sq_ref[...].astype(jnp.int32), sk_ref[...].astype(jnp.int32),
                    pq_ref[...].astype(jnp.int32), pk_ref[...].astype(jnp.int32),
                    causal, tq, tk, i * bq, j * bk, bq, bk)

    @pl.when(jnp.any(keep))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]  # [bq, 1]
        delta = delta_ref[0]
        s = mxu_dot(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        dv_scr[:] = dv_scr[:] + mxu_dot(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = mxu_dot(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dk_scr[:] = dk_scr[:] + mxu_dot(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pad_tokens(x, block):
    pad = (-x.shape[1]) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _pad_vec(x, block, fill):
    pad = (-x.shape[0]) % block
    if pad:
        x = jnp.pad(x, (0, pad), constant_values=fill)
    return x


def _vl_specs(bq, bk, D, group, transpose_grid=False):
    if transpose_grid:  # grid (H, nk, nq)
        qm = lambda h, j, i: (h, i, 0)
        km = lambda h, j, i, g=group: (h // g, j, 0)
        sqm = lambda h, j, i: (i, 0)
        skm = lambda h, j, i: (0, j)
    else:  # grid (H, nq, nk)
        qm = lambda h, i, j: (h, i, 0)
        km = lambda h, i, j, g=group: (h // g, j, 0)
        sqm = lambda h, i, j: (i, 0)
        skm = lambda h, i, j: (0, j)
    return [
        pl.BlockSpec((1, bq, D), qm),
        pl.BlockSpec((1, bk, D), km),
        pl.BlockSpec((1, bk, D), km),
        pl.BlockSpec((bq, 1), sqm),
        pl.BlockSpec((1, bk), skm),
        pl.BlockSpec((bq, 1), sqm),
        pl.BlockSpec((1, bk), skm),
    ]


def _vl_fwd(q, k, v, seg_q, seg_k, pos_q, pos_k, scale, causal, tq, tk,
            bq, bk):
    H, Tqp, D = q.shape
    Hkv, Tkp, _ = k.shape
    nq, nk = Tqp // bq, Tkp // bk
    group = H // Hkv
    return named_pallas_call(
        "varlen_fwd",
        functools.partial(_vl_fwd_kernel, scale=scale, causal=causal,
                          tq=tq, tk=tk, bq=bq, bk=bk, nk=nk),
        grid=(H, nq, nk),
        in_specs=_vl_specs(bq, bk, D, group),
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, i, j: (h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((H, Tqp, D), q.dtype),
            jax.ShapeDtypeStruct((H, Tqp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(q, k, v, seg_q, seg_k, pos_q, pos_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _varlen(q, k, v, seg_q, seg_k, pos_q, pos_k, causal, scale, bq, bk):
    out, _ = _varlen_fwd_res(q, k, v, seg_q, seg_k, pos_q, pos_k, causal,
                             scale, bq, bk)
    return out


def _varlen_fwd_res(q, k, v, seg_q, seg_k, pos_q, pos_k, causal, scale,
                    bq, bk):
    tq, tk = q.shape[1], k.shape[1]
    qp = _pad_tokens(q, bq)
    kp = _pad_tokens(k, bk)
    vp = _pad_tokens(v, bk)
    # pad segments with distinct sentinels so padding never matches;
    # q-side metadata rides as [Tq, 1] columns, k-side as [1, Tk] rows
    sqp = _pad_vec(seg_q.astype(jnp.int32), bq, -1)[:, None]
    skp = _pad_vec(seg_k.astype(jnp.int32), bk, -2)[None, :]
    pqp = _pad_vec(pos_q.astype(jnp.int32), bq, 0)[:, None]
    pkp = _pad_vec(pos_k.astype(jnp.int32), bk, 0)[None, :]
    out, lse = _vl_fwd(qp, kp, vp, sqp, skp, pqp, pkp, scale, causal, tq,
                       tk, bq, bk)
    return out[:, :tq], (qp, kp, vp, sqp, skp, pqp, pkp, out, lse)


def _varlen_vjp_fwd(q, k, v, seg_q, seg_k, pos_q, pos_k, causal, scale,
                    bq, bk):
    out, res = _varlen_fwd_res(q, k, v, seg_q, seg_k, pos_q, pos_k, causal,
                               scale, bq, bk)
    return out, (res, q.shape[1], k.shape[1])


def _varlen_vjp_bwd(causal, scale, bq, bk, saved, dout):
    # forward's block sizes arrive as custom-VJP statics — never recomputed
    (qp, kp, vp, sqp, skp, pqp, pkp, outp, lse), tq, tk = saved
    H, Tqp, D = qp.shape
    Hkv, Tkp, _ = kp.shape
    nq, nk = Tqp // bq, Tkp // bk
    group = H // Hkv
    dop = jnp.pad(dout, ((0, 0), (0, Tqp - tq), (0, 0)))
    delta = jnp.sum(dop.astype(jnp.float32) * outp.astype(jnp.float32),
                    axis=-1, keepdims=True)

    dq = named_pallas_call(
        "varlen_bwd_dq",
        functools.partial(_vl_bwd_dq_kernel, scale=scale, causal=causal,
                          tq=tq, tk=tk, bq=bq, bk=bk, nk=nk),
        grid=(H, nq, nk),
        in_specs=_vl_specs(bq, bk, D, group) + [
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, i, j: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, Tqp, D), qp.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret_mode(),
    )(qp, kp, vp, sqp, skp, pqp, pkp, dop, lse, delta)

    dk, dv = named_pallas_call(
        "varlen_bwd_dkv",
        functools.partial(_vl_bwd_dkv_kernel, scale=scale, causal=causal,
                          tq=tq, tk=tk, bq=bq, bk=bk, nq=nq),
        grid=(H, nk, nq),
        in_specs=_vl_specs(bq, bk, D, group, transpose_grid=True) + [
            pl.BlockSpec((1, bq, D), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, j, i: (h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, j, i: (h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((H, Tkp, D), jnp.float32),
            jax.ShapeDtypeStruct((H, Tkp, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(qp, kp, vp, sqp, skp, pqp, pkp, dop, lse, delta)

    if group > 1:
        dk = dk.reshape(Hkv, group, Tkp, D).sum(axis=1)
        dv = dv.reshape(Hkv, group, Tkp, D).sum(axis=1)
    return (dq[:, :tq], dk[:, :tk].astype(kp.dtype), dv[:, :tk].astype(vp.dtype),
            None, None, None, None)


_varlen.defvjp(_varlen_vjp_fwd, _varlen_vjp_bwd)


def _tuned_blocks_vl(q, k, v, seg_q, seg_k, pos_q, pos_k, causal, scale):
    """Forward block sizes for the varlen kernel ([H,T,D] packed layout),
    autotuned per signature when PADDLE_TPU_AUTOTUNE=1 (cache-only under
    trace, fallback-counted on miss — see autotune.pick_block_sizes)."""
    from .autotune import pick_block_sizes

    tq, tk = q.shape[1], k.shape[1]
    default = _block_sizes(tq, tk, d=q.shape[-1])

    def run_with(bq, bk):
        qp = _pad_tokens(q, bq)
        kp = _pad_tokens(k, bk)
        vp = _pad_tokens(v, bk)
        sqp = _pad_vec(seg_q, bq, -1)[:, None]
        skp = _pad_vec(seg_k, bk, -2)[None, :]
        pqp = _pad_vec(pos_q, bq, 0)[:, None]
        pkp = _pad_vec(pos_k, bk, 0)[None, :]
        out, _ = _vl_fwd(qp, kp, vp, sqp, skp, pqp, pkp, scale, causal, tq,
                         tk, bq, bk)
        out.block_until_ready()

    concrete = not any(isinstance(x, jax.core.Tracer)
                       for x in (q, k, v, seg_q, seg_k))
    return pick_block_sizes(
        "varlen_fwd", tq, tk, default, run_with, allow_measure=concrete,
        signature=(q.shape[0], k.shape[0], q.shape[-1], str(q.dtype),
                   bool(causal)))


def varlen_flash_attention_fwd(q, k, v, cu_seqlens_q, cu_seqlens_k, scale,
                               causal=False):
    """Packed varlen entry: q [Tq,H,D], k/v [Tk,Hkv,D], cu_seqlens [B+1].
    Differentiable w.r.t. q/k/v. Under a multi-device mesh the kernel runs
    per shard with heads over mp (partition.py; the packed token axis
    carries the segments and stays whole). Reference: flash_attn_unpadded."""
    plan = shard_plan(q, k, v)
    if plan is not None:
        h = plan.axes("heads", math.gcd(q.shape[1], k.shape[1]))
        qkv = P(None, h, None)
        return plan.run(
            lambda q, k, v, cq, ck: _varlen_local(q, k, v, cq, ck, scale,
                                                  causal),
            [q, k, v, cu_seqlens_q, cu_seqlens_k],
            [qkv, qkv, qkv, P(None), P(None)], qkv)
    return _varlen_local(q, k, v, cu_seqlens_q, cu_seqlens_k, scale, causal)


def _varlen_local(q, k, v, cu_seqlens_q, cu_seqlens_k, scale, causal):
    Tq, Tk = q.shape[0], k.shape[0]
    cq = cu_seqlens_q.astype(jnp.int32)
    ck = cu_seqlens_k.astype(jnp.int32)
    seg_q = jnp.cumsum(jnp.zeros(Tq, jnp.int32).at[cq[1:-1]].add(1))
    seg_k = jnp.cumsum(jnp.zeros(Tk, jnp.int32).at[ck[1:-1]].add(1))
    pos_q = jnp.arange(Tq, dtype=jnp.int32) - jnp.take(cq, seg_q)
    pos_k = jnp.arange(Tk, dtype=jnp.int32) - jnp.take(ck, seg_k)
    qt = jnp.swapaxes(q, 0, 1)  # [H, T, D]
    kt = jnp.swapaxes(k, 0, 1)
    vt = jnp.swapaxes(v, 0, 1)
    bq, bk = _tuned_blocks_vl(qt, kt, vt, seg_q, seg_k, pos_q, pos_k,
                              causal, scale)
    out = _varlen(qt, kt, vt, seg_q, seg_k, pos_q, pos_k, causal, scale,
                  bq, bk)
    return jnp.swapaxes(out, 0, 1)
