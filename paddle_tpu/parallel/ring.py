"""Ring attention: context parallelism over the `sep` mesh axis.

The reference snapshot has NO ring attention (SURVEY §5.7 — its long-context
story is the bare SEP mesh axis, segment_parallel.py:26, with attention
resharding left to user model code). This module is the TPU-native upgrade:
sequence-sharded exact attention where K/V blocks rotate around the ICI ring
(`ppermute`) while each device keeps a running online-softmax accumulator —
so peak memory is O(L_local) and the ring hop overlaps with the block GEMMs.

Math (online softmax, identical to flash attention's outer loop):
  per incoming block: m' = max(m, rowmax(S)); acc = acc*e^{m-m'} + e^{S-m'}V;
  l = l*e^{m-m'} + rowsum(e^{S-m'}); out = acc / l.

Causal masking is by GLOBAL chunk position: a device holding query chunk i
attends fully to K/V chunks j<i, diagonally (tril) to j==i, not at all to
j>i. Shapes follow the paddle layout [B, S, H, D], S sharded over `sep`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_NEG = -1e30


def _local_ring_attention(q, k, v, *, axis, n, causal, scale):
    """shard_map body: q [B, L, H, D], k/v [B, L, Hkv, D] (seq-sharded over
    `axis`). K/V rotate UNEXPANDED — GQA groups broadcast in the einsums, so
    each ppermute hop moves Hkv (not H) heads of bytes."""
    B, L, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv  # query heads per kv head; head order matches jnp.repeat
    idx = jax.lax.axis_index(axis)
    qf = q.astype(jnp.float32).reshape(B, L, Hkv, G, D)
    rows = jnp.arange(L)
    perm = [(i, (i + 1) % n) for i in range(n)]

    @jax.checkpoint
    def block_update(carry_mla, ks, vs, s):
        """Online-softmax update with the K/V block that came from chunk
        (idx - s) mod n."""
        m, l, acc = carry_mla
        src = (idx - s) % n
        logits = jnp.einsum("bihgd,bjhd->bhgij", qf, ks.astype(jnp.float32)) * scale
        if causal:
            grow = idx * L + rows[:, None]   # global query row
            gcol = src * L + rows[None, :]   # global key col
            logits = jnp.where(gcol <= grow, logits, _NEG)
        m_new = jnp.maximum(m, logits.max(-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgij,bjhd->bhgid", p, vs.astype(jnp.float32))
        return m_new, l_new, acc_new

    def step(carry, s):
        ks, vs, mla = carry
        # permute FIRST: n-1 hops total, the last block is consumed in place
        ks = jax.lax.ppermute(ks, axis, perm)
        vs = jax.lax.ppermute(vs, axis, perm)
        return (ks, vs, block_update(mla, ks, vs, s)), None

    m0 = jnp.full((B, Hkv, G, L), _NEG, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, L), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, L, D), jnp.float32)
    mla = block_update((m0, l0, a0), k, v, jnp.int32(0))  # local block, no hop
    if n > 1:
        (_, _, mla), _ = jax.lax.scan(step, (k, v, mla), jnp.arange(1, n))
    m, l, acc = mla
    out = acc / jnp.maximum(l, 1e-30)[..., None]        # [B, Hkv, G, L, D]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, L, H, D)
    return out.astype(q.dtype)


@functools.lru_cache(maxsize=32)
def _build(mesh, axis, causal, scale, jit):
    n = mesh.shape[axis]
    body = functools.partial(_local_ring_attention, axis=axis, n=n,
                             causal=causal, scale=scale)
    spec = P(None, axis, None, None)
    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=frozenset({axis}), check_vma=False,
    )
    # through jit for the same partial-manual reason as pipeline_spmd
    return jax.jit(mapped) if jit else mapped


def ring_attention_spmd(q, k, v, mesh, axis="sep", causal=True, scale=None):
    """Raw-array entry: q/k/v [B, S, H, D] with S divisible by mesh.shape[axis]."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    # nested inside another partial-manual shard_map region (e.g. the pp
    # pipeline body): shard_map must be built on the CONTEXT abstract mesh,
    # and without a jit wrapper (the trace is already inside one)
    try:
        ctx = jax.sharding.get_abstract_mesh()
    except Exception:
        ctx = None
    if ctx is not None and not ctx.empty and ctx.manual_axes:
        if axis in ctx.manual_axes:
            raise ValueError(f"ring attention axis {axis!r} is already manual here")
        return _build(ctx, axis, bool(causal), float(scale), False)(q, k, v)
    return _build(mesh, axis, bool(causal), float(scale), True)(q, k, v)


__all__ = ["ring_attention_spmd"]
