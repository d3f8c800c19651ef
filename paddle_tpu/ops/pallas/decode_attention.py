"""Paged-KV and dense-cache decode attention for TPU, in Pallas.

Reference analogs: block_multihead_attention's paged decode path
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu,
block_attn.h) and masked_multihead_attention
(fusion/gpu/masked_multihead_attention_kernel.cu, mmha_util.cu.h).

TPU-native design: decode is HBM-bound — the entire job is streaming the KV
cache through VMEM exactly once per step. The paged variant prefetches the
block table as a scalar operand (pltpu.PrefetchScalarGridSpec) so the
per-page physical index is resolved in the BlockSpec index_map: the pipeline
DMAs each logical page straight from its physical slot, no gathered copy of
the cache is ever materialized (the jnp composite's `kc[tables]` gather is
exactly what XLA does badly — SURVEY §7 hard parts). Pages past a row's
length are skipped (no DMA cost model change, but no MXU/VPU work), and the
final page is masked per-slot. GQA: grid is (batch, kv_head, page) and each
step attends the head-group [g, D] block against one [page, D] page.

Single-token decode (q = one step per row), inference only (no VJP).

Quantized fast path: with `kv_scales`, the caches are int8 page payloads and
`kv_scales` the per-(page, head) f32 dequant scales (`x ≈ q * scale`,
`BlockPool(quantized=True)` layout). The same grid loads the int8 page into
VMEM, dequantizes there (one scalar multiply per page), and accumulates in
f32 exactly like the full-precision kernel — decode is HBM-bound, so
halving/quartering the streamed bytes is the whole win and the dequant
multiply rides the VPU for free. The scale rides beside its page as the
(8, Hkv) tile of the [n_pages, Hkv] scale array that holds it — a (1, 1)
block of a 2-D array is not a shape Mosaic tiles — and the kernel selects
its (page % 8, head) entry with an iota mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode, named_pallas_call
from .flash_attention import NEG_INF

__all__ = ["paged_decode_attention", "dense_decode_attention",
           "paged_kv_write", "paged_kv_write_q8", "KV_QMAX"]

# symmetric int8 range for KV pages: ±127 (not -128) so the running-max
# rescale in paged_kv_write_q8 can never overflow the negative extreme
KV_QMAX = 127.0
# rows of the [n_pages, Hkv] f32 scale array fetched per grid step: one f32
# sublane tile
_SCALE_ROWS = 8


def _decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
                   scale, ps, np_, g, paged, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]
    base = p * ps
    valid_page = base < length
    if paged:
        valid_page = valid_page & (tables_ref[b, p] >= 0)

    def _page_scales():
        """This (page, head)'s K and V scales as (1, 1) arrays, picked out
        of their (8, Hkv) tiles with one mask (padding rows past n_pages
        never match)."""
        phys = jnp.maximum(tables_ref[b, p], 0)
        row = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape, 1)
        pick = (row == phys % _SCALE_ROWS) & (col == h)

        def one(sc_ref):
            lane = jnp.sum(jnp.where(pick, sc_ref[...], 0.0), axis=1,
                           keepdims=True)
            return jnp.sum(lane, axis=0, keepdims=True)

        return one(ks_ref), one(vs_ref)

    # scratch rows are padded to >=8 for TPU tiling; compute on the first g
    @pl.when(valid_page)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)      # [g, D]
        k = k_ref[0, 0].astype(jnp.float32)      # [ps, D]
        if quantized:
            k_scale, v_scale = _page_scales()
            k = k * k_scale                      # dequant in VMEM
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                # [g, ps]
        slot = base + jax.lax.broadcasted_iota(jnp.int32, (g, ps), 1)
        s = jnp.where(slot < length, s, NEG_INF)

        m_prev = m_scr[0:g, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new)
        pr = jnp.where(slot < length, pr, 0.0)
        l_scr[0:g, :] = jnp.broadcast_to(
            alpha * l_scr[0:g, 0:1] + jnp.sum(pr, axis=-1, keepdims=True),
            (g, l_scr.shape[1]))
        v = v_ref[0, 0].astype(jnp.float32)      # [ps, D]
        if quantized:
            v = v * v_scale
        pv = jax.lax.dot_general(
            pr, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[0:g, :] = acc_scr[0:g, :] * alpha + pv
        m_scr[0:g, :] = jnp.broadcast_to(m_new, (g, m_scr.shape[1]))

    @pl.when(p == np_ - 1)
    def _finish():
        l = l_scr[0:g, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[0:g, :] / l_safe).astype(o_ref.dtype)


def _default_dense_ps(s_max):
    """Dense-cache sequence tile: largest power of two <= 256 dividing the
    static cache capacity."""
    ps = min(256, s_max)
    while s_max % ps:
        ps //= 2
    return ps


def _run_decode(q, kc, vc, tables, lengths, scale, paged, ps=None,
                kv_scales=None):
    """q: [B, Hkv, g, D]; kc/vc paged [n_pages, Hkv, ps, D] or dense
    [B, Hkv, S_max, D] (viewed as ps-sized pages). tables: [B, P] (paged) or
    a dummy [B, 1] (dense). For the dense layout `ps` selects the sequence
    tile (autotunable); paged `ps` IS the cache's physical page size.
    kv_scales: (k_scale, v_scale) per-(page, head) f32 [n_pages, Hkv] for
    int8 caches (paged only) — dequant is fused into the page load."""
    B, Hkv, g, D = q.shape
    quantized = kv_scales is not None
    if paged:
        _, _, ps, _ = kc.shape
        P = tables.shape[1]

        def kmap(b, h, p, tabs, lens):
            t = tabs[b, p]
            return (jnp.where(t < 0, 0, t), h, 0, 0)

        def smap(b, h, p, tabs, lens):
            return (jnp.maximum(tabs[b, p], 0) // _SCALE_ROWS, 0)
    else:
        assert not quantized, "quantized cache is paged-only"
        S_max = kc.shape[2]
        if ps is None:
            ps = _default_dense_ps(S_max)
        P = S_max // ps

        def kmap(b, h, p, tabs, lens):
            return (b, h, p, 0)

    kernel = functools.partial(
        _decode_kernel, scale=scale, ps=ps, np_=P, g=g, paged=paged,
        quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, 1, g, D), lambda b, h, p, tabs, lens: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, ps, D), kmap),
        pl.BlockSpec((1, 1, ps, D), kmap),
    ]
    operands = [q, kc, vc]
    if quantized:
        # the (8, Hkv) scale tile holding this page's row, beside the page
        sc_spec = pl.BlockSpec((_SCALE_ROWS, Hkv), smap)
        in_specs += [sc_spec, sc_spec]
        operands += [kv_scales[0].astype(jnp.float32),
                     kv_scales[1].astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, D),
                               lambda b, h, p, tabs, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((max(g, 8), 128), jnp.float32),
            pltpu.VMEM((max(g, 8), 128), jnp.float32),
            pltpu.VMEM((max(g, 8), D), jnp.float32),
        ],
    )
    # paged: cache already [n_pages, Hkv, ps, D]; dense: the index_map views
    # the [B, Hkv, S_max, D] cache as ps-sized blocks of the sequence axis
    name = ("decode_dense" if not paged
            else "decode_paged_q8" if quantized else "decode_paged")
    out = named_pallas_call(
        name, kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        interpret=interpret_mode(),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
    return out


def _split_heads(q, Hkv):
    B, H, D = q.shape
    g = H // Hkv
    return q.reshape(B, Hkv, g, D), g


def paged_decode_attention(q, key_cache, value_cache, block_tables, lengths,
                           scale=None, kv_scales=None):
    """q: [B, H, D] (one decode step); key/value_cache:
    [n_pages, Hkv, page_size, D]; block_tables: [B, P] physical page ids
    (-1 unused); lengths: [B] valid tokens incl. the current one (caller has
    already written the step's K/V into the cache). With `kv_scales`
    (= (k_scale, v_scale) f32 [n_pages, Hkv]) the caches are int8 payloads
    and dequantization is fused into the page load. Returns [B, H, D]."""
    B, H, D = q.shape
    Hkv = key_cache.shape[1]
    if scale is None:
        scale = D ** -0.5
    q4, g = _split_heads(q, Hkv)
    _consult_tuner_paged(q4, key_cache, block_tables,
                         quantized=kv_scales is not None)
    out = _run_decode(q4, key_cache, value_cache, block_tables, lengths,
                      scale, paged=True, kv_scales=kv_scales)
    return out.reshape(B, H, D)


def _consult_tuner_paged(q4, kc, tables, quantized=False):
    """The paged kernel's tile (page_size, D) is the cache POOL's physical
    layout — tunable at pool construction, not per launch — so the only
    candidate is the layout itself. Consulting the tuner anyway keeps all
    the Pallas kernels uniform in telemetry: the tile lands in
    chosen_tiles() / the step-timeline record as source "fixed" (the
    single-candidate consult never sweeps and never counts a fallback).
    The dequant-fused int8 variant records under its own tuner name so the
    telemetry distinguishes which decode path actually ran."""
    from .autotune import pick_block_sizes

    B, Hkv, g, D = q4.shape
    ps = kc.shape[2]
    pick_block_sizes(
        "decode_paged_q8" if quantized else "decode_paged",
        1, ps, (ps, D), lambda bq, bk: None,
        allow_measure=False,
        signature=(B, Hkv, g, D, str(q4.dtype), tables.shape[1]),
        candidates=[(ps, D)])


def paged_kv_write(cache, new, block_tables, lengths):
    """Scatter one decode step's K (or V) rows into the paged cache.

    cache: [n_pages, Hkv, page_size, D]; new: [B, Hkv, D] (this step's
    projection per row); block_tables: [B, P] physical page ids (-1 unused);
    lengths: [B] tokens already present per row — the write lands at logical
    slot `lengths[b]`, i.e. physical (tables[b, lengths[b]//ps],
    lengths[b]%ps). Rows whose target table entry is -1 (parked/batch-pad
    rows) are routed to physical page 0, the pool's reserved null page, which
    no live block table ever references. Pure/jittable; owns the page layout
    so callers never index the cache themselves."""
    B = new.shape[0]
    ps = cache.shape[2]
    lengths = lengths.astype(jnp.int32)
    page = block_tables[jnp.arange(B), lengths // ps]
    page = jnp.where(page < 0, 0, page)
    return cache.at[page, :, lengths % ps].set(new.astype(cache.dtype))


def paged_kv_write_q8(cache, scales, new, block_tables, lengths):
    """Quantized-append analog of `paged_kv_write`: scatter one decode
    step's K (or V) rows into an int8 paged cache with per-(page, head)
    scales.

    cache: int8 [n_pages, Hkv, page_size, D]; scales: f32 [n_pages, Hkv]
    (dequant = int8 * scale); new: [B, Hkv, D]. The page scale is a RUNNING
    abs-max: if this step's row exceeds the page's current abs-max, the
    scale grows and the page's existing payload is requantized under the new
    scale in the same scatter (ratio multiply + round — exact when the scale
    is unchanged, one bounded rounding step when it grows). A write at slot 0
    restarts the running max (and zeroes the rest of the page): appends are
    strictly sequential, so slot 0 is always a page's first write, and a
    page recycled through the free list must not inherit the previous
    tenant's scale. The whole update is therefore a function of the page's
    appended history only, so page content is bit-identical across
    scheduling, COW, and spill/resume orders — the invariance the
    quantized-engine tests pin.
    Parked rows (table entry -1) land on null page 0 like the f32 path.
    Returns (cache, scales); pure/jittable."""
    B = new.shape[0]
    ps = cache.shape[2]
    lengths = lengths.astype(jnp.int32)
    page = block_tables[jnp.arange(B), lengths // ps]
    page = jnp.where(page < 0, 0, page)
    slot = lengths % ps

    new32 = new.astype(jnp.float32)                        # [B, Hkv, D]
    row_scale = jnp.max(jnp.abs(new32), axis=-1) / KV_QMAX  # [B, Hkv]
    # slot 0 is always a page's FIRST write (appends are sequential; a
    # boundary crossing allocates a fresh page), so it restarts the running
    # max — a recycled free-list page must not seed its scale (or payload,
    # zeroed below via ratio == 0) from the previous tenant's leftovers
    old_scale = jnp.where(slot[:, None] == 0, 0.0, scales[page])  # [B, Hkv]
    new_scale = jnp.maximum(old_scale, row_scale)
    safe = jnp.where(new_scale == 0.0, 1.0, new_scale)
    # requantize prior payload under the (possibly grown) scale; ratio == 1
    # (bit-exact no-op) unless this row raised the page abs-max
    ratio = old_scale / safe                                # <= 1
    pg = cache[page].astype(jnp.float32)                    # [B, Hkv, ps, D]
    pg = jnp.round(pg * ratio[:, :, None, None])
    q_row = jnp.clip(jnp.round(new32 / safe[:, :, None]), -KV_QMAX, KV_QMAX)
    at_slot = (jax.lax.broadcasted_iota(jnp.int32, (B, 1, ps, 1), 2)
               == slot[:, None, None, None])
    pg = jnp.where(at_slot, q_row[:, :, None, :], pg)
    # live rows never share a write page (COW guarantees); only parked rows
    # collide — all on null page 0, where last-writer-wins is harmless
    cache = cache.at[page].set(pg.astype(jnp.int8))
    scales = scales.at[page].set(new_scale)
    return cache, scales


def _tuned_dense_ps(q4, kc, vc, lengths, scale):
    """Dense-decode sequence tile, autotuned per signature when
    PADDLE_TPU_AUTOTUNE=1 — candidates are the powers of two dividing the
    static cache capacity (decode streams the whole cache once; the tile
    trades DMA granularity against grid overhead). Cache-only under trace."""
    from .autotune import pick_block_sizes

    B, Hkv, g, D = q4.shape
    S_max = kc.shape[2]
    default = (_default_dense_ps(S_max), D)
    cands = sorted({default} | {
        (p, D) for p in (8, 16, 32, 64, 128, 256, 512) if S_max % p == 0})
    dummy = jnp.zeros((B, 1), jnp.int32)

    def run_with(ps, _d):
        out = _run_decode(q4, kc, vc, dummy, lengths, scale, paged=False,
                          ps=ps)
        out.block_until_ready()

    concrete = not any(isinstance(x, jax.core.Tracer)
                       for x in (q4, kc, lengths))
    ps, _ = pick_block_sizes(
        "decode_dense", 1, S_max, default, run_with,
        allow_measure=concrete, signature=(B, Hkv, g, D, str(q4.dtype)),
        candidates=cands)
    return ps


def dense_decode_attention(q, key_cache, value_cache, lengths, scale=None):
    """MMHA analog on a dense cache: q [B, H, D]; key/value_cache
    [B, Hkv, S_max, D]; lengths [B] valid tokens incl. current. -> [B, H, D]."""
    B, H, D = q.shape
    Hkv = key_cache.shape[1]
    if scale is None:
        scale = D ** -0.5
    q4, g = _split_heads(q, Hkv)
    ps = _tuned_dense_ps(q4, key_cache, value_cache, lengths, scale)
    dummy_tables = jnp.zeros((B, 1), jnp.int32)
    out = _run_decode(q4, key_cache, value_cache, dummy_tables, lengths,
                      scale, paged=False, ps=ps)
    return out.reshape(B, H, D)
