"""Attention functionals (reference: python/paddle/nn/functional/flash_attention.py:
flash_attention :358, scaled_dot_product_attention :1139, flashmask_attention :1299).

Paddle layout: q/k/v are [batch, seq, num_heads, head_dim].

Dispatch: on TPU these route to the Pallas flash-attention kernel
(paddle_tpu.ops.pallas.flash_attention) — the analog of the reference's
dynloaded flashattn library (paddle/phi/kernels/gpu/flash_attn_kernel.cu);
elsewhere (CPU tests) they fall back to the jnp reference implementation.
GQA/MQA (fewer kv heads) is supported by head repetition in the reference
path and natively in the kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework.core import Tensor, run_op, to_tensor

__all__ = [
    "scaled_dot_product_attention",
    "flash_attention",
    "flash_attn_unpadded",
    "flashmask_attention",
    "sdp_kernel",
]

_USE_PALLAS = True


def _use_pallas_kernel():
    from ...ops.pallas import kernels_available

    return _USE_PALLAS and kernels_available()


def _t(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def _ref_attention(q, k, v, mask=None, causal=False, scale=None, dropout=0.0, dropout_key=None):
    """jnp reference: q/k/v [B, S, H, D] -> [B, S, H, D]; f32 softmax."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    Hkv = k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = scale if scale is not None else 1.0 / (D ** 0.5)
    # [B,H,Sq,Skv]
    logits = jnp.einsum("bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32) * s
    if causal:
        cm = jnp.tril(jnp.ones((Sq, Skv), bool), k=Skv - Sq)
        logits = jnp.where(cm[None, None], logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    if dropout > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), 0.0)
    out = jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v)
    return out.astype(q.dtype)


def scaled_dot_product_attention(
    query,
    key,
    value,
    attn_mask=None,
    dropout_p=0.0,
    is_causal=False,
    training=True,
    name=None,
):
    ins = [_t(query), _t(key), _t(value)]
    has_mask = attn_mask is not None
    if has_mask:
        ins.append(_t(attn_mask))
    dkey = None
    if dropout_p > 0.0 and training:
        from ...framework import random as rnd

        dkey = rnd.next_key()

    # a [B,1,1,Skv]-broadcastable mask is a per-KEY padding mask — the
    # encoder-model case (BERT/ERNIE) — and rides the Pallas kernel as a
    # fused additive key bias instead of forcing the S^2-materializing
    # composite (round-5: this was BERT's bottleneck)
    key_padding = False
    if has_mask:
        mshape = tuple(ins[3].shape)
        key_padding = (len(mshape) == 4 and mshape[1] == 1 and mshape[2] == 1
                       and mshape[3] == ins[1].shape[1]
                       and mshape[0] in (1, ins[0].shape[0])
                       # a LEARNED bias needs its gradient, which the
                       # kernel's key-bias path does not produce — keep the
                       # exact composite for trainable masks
                       and getattr(ins[3], "stop_gradient", True))

    if (_use_pallas_kernel() and dropout_p == 0.0
            and (not has_mask or key_padding)):
        from ...ops.pallas.flash_attention import flash_attention_fwd

        def fnp(q, k, v, *rest):
            kb = None
            if rest:
                m = rest[0].reshape(rest[0].shape[0], -1)
                if m.dtype == jnp.bool_:
                    kb = jnp.where(m, 0.0, -1e30).astype(jnp.float32)
                else:
                    kb = m.astype(jnp.float32)
                if kb.shape[0] == 1 and q.shape[0] > 1:
                    kb = jnp.broadcast_to(kb, (q.shape[0], kb.shape[1]))
            return flash_attention_fwd(q, k, v, causal=is_causal,
                                       key_bias=kb)

        return run_op("flash_attention", fnp, ins)

    def fn(q, k, v, *rest):
        mask = rest[0] if has_mask else None
        return _ref_attention(
            q, k, v, mask=mask, causal=is_causal,
            dropout=dropout_p if training else 0.0, dropout_key=dkey,
        )

    return run_op("sdpa", fn, ins)


def flash_attention(
    query,
    key,
    value,
    dropout=0.0,
    causal=False,
    return_softmax=False,
    fixed_seed_offset=None,
    rng_name="",
    training=True,
    name=None,
):
    """reference: flash_attention (flash_attention.py:358). Returns
    (out, softmax_lse_placeholder) tuple like the reference API."""
    out = scaled_dot_product_attention(
        query, key, value, None, dropout, causal, training
    )
    if return_softmax:
        return out, None
    return out, None


def flash_attn_unpadded(
    query,
    key,
    value,
    cu_seqlens_q,
    cu_seqlens_k,
    max_seqlen_q,
    max_seqlen_k,
    scale,
    dropout=0.0,
    causal=False,
    return_softmax=False,
    fixed_seed_offset=None,
    rng_name="",
    training=True,
    name=None,
):
    """Varlen attention over packed sequences (reference: flash_attn_unpadded).
    q/k/v: [total_tokens, H, D]; cu_seqlens: [B+1] prefix sums. Implemented by
    segment ids over the packed layout — the TPU-idiomatic ragged encoding;
    on TPU it runs the Pallas varlen kernel (block-sparse: tiles whose q/k
    segments cannot intersect are skipped), elsewhere a dense jnp fallback."""
    ins = [_t(query), _t(key), _t(value), _t(cu_seqlens_q), _t(cu_seqlens_k)]

    if _use_pallas_kernel() and dropout == 0.0:
        from ...ops.pallas.masked_flash import varlen_flash_attention_fwd

        def fnp(q, k, v, cq, ck):
            return varlen_flash_attention_fwd(q, k, v, cq, ck, scale,
                                              causal=causal)

        out = run_op("flash_attn_unpadded", fnp, ins)
        return out, None

    def fn(q, k, v, cq, ck):
        Tq, H, D = q.shape
        Tk = k.shape[0]
        seg_q = jnp.cumsum(
            jnp.zeros(Tq, jnp.int32).at[cq.astype(jnp.int32)[1:-1]].add(1)
        )
        seg_k = jnp.cumsum(
            jnp.zeros(Tk, jnp.int32).at[ck.astype(jnp.int32)[1:-1]].add(1)
        )
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            pos_q = jnp.arange(Tq) - jnp.take(cq.astype(jnp.int32), seg_q)
            pos_k = jnp.arange(Tk) - jnp.take(ck.astype(jnp.int32), seg_k)
            mask = mask & (pos_q[:, None] >= pos_k[None, :])
        logits = jnp.einsum("qhd,khd->hqk", q, k, preferred_element_type=jnp.float32) * scale
        logits = jnp.where(mask[None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v)
        return out.astype(q.dtype)

    out = run_op("flash_attn_unpadded", fn, ins)
    return out, None


def flashmask_attention(
    query,
    key,
    value,
    startend_row_indices=None,
    dropout=0.0,
    causal=False,
    window_size=None,
    return_softmax_lse=False,
    return_seed_offset=False,
    fixed_seed_offset=None,
    rng_name="",
    training=True,
    name=None,
):
    """Sparse block-mask attention (reference: flashmask_attention,
    flash_attention.py:1299). startend_row_indices [B, Hm, Sk, 1|2|4] encodes,
    per key column, the query-row range that is MASKED OUT:
    - causal + last-dim 1: rows >= start masked (below the band)
    - causal + last-dim 2: [start, end) masked
    - non-causal + 2: (LTS, UTE) — rows >= LTS or < UTE masked
    - non-causal + 4: (LTS, LTE, UTS, UTE) — [LTS,LTE) and [UTS,UTE) masked
    """
    ins = [_t(query), _t(key), _t(value)]
    has_idx = startend_row_indices is not None
    if has_idx:
        ins.append(_t(startend_row_indices))

    if (_use_pallas_kernel() and has_idx and dropout == 0.0
            and window_size is None and not return_softmax_lse):
        from ...ops.pallas.masked_flash import flashmask_attention_fwd

        def fnp(q, k, v, idx):
            return flashmask_attention_fwd(q, k, v, idx, causal=causal)

        out = run_op("flashmask_attention", fnp, ins)
        if return_seed_offset:
            return (out, *([None] * int(return_seed_offset)))
        return out

    def fn(q, k, v, *rest):
        B, Sq, H, D = q.shape
        Sk = k.shape[1]
        if k.shape[2] != H:  # GQA/MQA: repeat kv heads
            rep_kv = H // k.shape[2]
            k = jnp.repeat(k, rep_kv, axis=2)
            v = jnp.repeat(v, rep_kv, axis=2)
        rows = jnp.arange(Sq)[:, None]  # query row
        mask_keep = jnp.ones((B, 1, Sq, Sk), bool)
        if has_idx:
            idx = rest[0].astype(jnp.int32)  # [B, Hm, Sk, n]
            n = idx.shape[-1]
            idxb = jnp.moveaxis(idx, 2, -1)  # [B, Hm, n, Sk]
            if causal:
                if n == 1:
                    start = idxb[:, :, 0][:, :, None, :]  # [B,Hm,1,Sk]
                    masked = rows[None, None] >= start
                else:
                    start = idxb[:, :, 0][:, :, None, :]
                    end = idxb[:, :, 1][:, :, None, :]
                    masked = (rows[None, None] >= start) & (rows[None, None] < end)
            else:
                if n == 2:
                    lts = idxb[:, :, 0][:, :, None, :]
                    ute = idxb[:, :, 1][:, :, None, :]
                    masked = (rows[None, None] >= lts) | (rows[None, None] < ute)
                else:
                    lts = idxb[:, :, 0][:, :, None, :]
                    lte = idxb[:, :, 1][:, :, None, :]
                    uts = idxb[:, :, 2][:, :, None, :]
                    ute = idxb[:, :, 3][:, :, None, :]
                    masked = ((rows[None, None] >= lts) & (rows[None, None] < lte)) | (
                        (rows[None, None] >= uts) & (rows[None, None] < ute)
                    )
            mask_keep = ~masked  # [B, Hm, Sq, Sk]
        if causal:
            cm = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
            mask_keep = mask_keep & cm[None, None]
        Hm = mask_keep.shape[1]
        scale = 1.0 / (D ** 0.5)
        logits = jnp.einsum("bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32) * scale
        if Hm == 1:
            m = mask_keep
        else:
            rep = H // Hm
            m = jnp.repeat(mask_keep, rep, axis=1)
        logits = jnp.where(m, logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v)
        return out.astype(q.dtype)

    out = run_op("flashmask_attention", fn, ins)
    if return_softmax_lse or return_seed_offset:
        extra = [None] * (int(return_softmax_lse) + int(return_seed_offset))
        return (out, *extra)
    return out


class sdp_kernel:
    """Context manager selecting attention backends (API parity with the
    reference's sdp_kernel; on TPU the Pallas kernel is always preferred)."""

    def __init__(self, enable_flash=True, enable_math=True, enable_mem_efficient=True):
        self.enable_flash = enable_flash

    def __enter__(self):
        global _USE_PALLAS
        self._saved = _USE_PALLAS
        _USE_PALLAS = self.enable_flash
        return self

    def __exit__(self, *exc):
        global _USE_PALLAS
        _USE_PALLAS = self._saved
        return False


def ring_flash_attention(query, key, value, causal=True, axis="sep", name=None):
    """Context-parallel exact attention: sequence sharded over the `sep` mesh
    axis, K/V blocks rotating on the ICI ring with online-softmax accumulation
    (paddle_tpu.parallel.ring). The reference snapshot has no ring attention
    (SURVEY §5.7) — this is the TPU-native long-context upgrade over its bare
    SEP-axis plumbing (fleet/meta_parallel/segment_parallel.py:26).

    Falls back to dense reference attention when no mesh is active or the
    axis degree is 1, so models are portable across parallel configs.
    """
    from ...distributed import env as _env
    from ...parallel.ring import ring_attention_spmd

    mesh = _env.get_global_mesh()
    use_ring = mesh is not None and mesh.shape.get(axis, 1) > 1

    def fn(q, k, v):
        if use_ring:
            return ring_attention_spmd(q, k, v, mesh, axis=axis, causal=causal)
        return _ref_attention(q, k, v, causal=causal)

    return run_op("ring_flash_attention", fn, [_t(query), _t(key), _t(value)])


__all__.append("ring_flash_attention")
