"""Paged-KV and dense-cache decode attention for TPU, in Pallas.

Reference analogs: block_multihead_attention's paged decode path
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu,
block_attn.h) and masked_multihead_attention
(fusion/gpu/masked_multihead_attention_kernel.cu, mmha_util.cu.h).

TPU-native design: decode is HBM-bound — the entire job is streaming the KV
cache through VMEM exactly once per step — so a grid step does a DMA's worth
of work, not one head of one page.

Paged (`decode_paged`, `decode_paged_q8`): a STEP is all KV heads of N
consecutive table slots of one row: the pool's layout is
[n_pages, Hkv, page_size, D], so a page of all heads is one contiguous DMA.
The pool goes into the `pallas_call` as it is, N times, one
`BlockSpec((1, Hkv, page_size, D))` per page slot whose index_map reads the
physical page out of a prefetched scalar table (PrefetchScalarGridSpec): no
gathered copy of the cache is ever materialized (the jnp composite's
`kc[tables]` gather is exactly what XLA does badly — SURVEY §7 hard parts).
The grid is ONE flat axis over a WORK LIST of the live steps, as long as the
work (`work_list`): a step is live when any of its N slots is (a slot is dead
past the row's length, at a -1 hole, in a free row, in the padding behind P),
and the live steps of all rows are compacted in row-major order into the front
of prefetched arrays (the step's row, its index within the row, whether it is
the row's first and its last) with a cumulative sum and one scatter; their
count, a traced scalar, is the grid's bound. A step without work does not
exist: a table as wide as the longest request costs a short row nothing, and
a free row nothing at all. The fetch table is built over the compacted steps:
a dead SLOT inside a live step names the page the same slot held at the last
live step, so its block index does not change and the pipeline issues no DMA
for it. The list follows from the block table and the lengths alone, so the
layers of a decode program that share a table share one list (XLA merges the
identical computations). The accumulators start at a row's first live step
and the output is normalised and written at its last; a row the grid never
visits has no output block written, and the wrapper returns zeros for it. N
follows from the shapes (`pages_per_step`: the largest of 16..1 that fits the
VMEM budget and the table) and is recorded through the tuner as the tile
(N * page_size, D); nothing is swept, so a serving process pays no
measurement at set-up. N need divide neither P nor a row's page count: the
last step is masked. A step makes ONE online-softmax update over its
N * page_size keys for all heads: `q.K` and `p.V` are dots batched over the
KV heads ([Hkv, g, D] x [Hkv, N * ps, D]); running maximum, sum and
accumulator are f32 VMEM scratch. Operand
types: with q and the pool both bf16 the dots take bf16 operands and
accumulate in f32 — `q.K` is then exact, and p (f32) goes in as three bf16
addends that carry its whole mantissa, so nothing is rounded that the
f32-cast dots of the old kernel kept; any other mix (f32 pool, int8 pool,
f32 q) casts both operands to f32 and follows the ambient matmul precision
as before.

The KV append (`paged_kv_write`, `paged_kv_write_q8`) rewrites each row's
whole target page: XLA's TPU scatter wants the scattered dimensions major, so
only a scatter of whole pages leaves the pool where it lies.

Windowed (`decode_window`): a sliding-window layer's decode is the paged
kernel's body under its own name, with one more mask (keys before
`length - window`) and a table that starts at the row's first cached page
and is `ceil(window / page_size) + 1` wide whatever the longest sequence is.
A page wholly before the window is a dead slot even while the table still
names it, so a step of such pages is not in the work list.

Two widths and a sink. The values may be narrower than the keys (K pages
[.., Dk], V pages [.., Dv], q [B, H, Dk] -> [B, H, Dv]: 192 and 128): the
K and V blocks, the accumulator and the output take each its own width and
the body is the same. `sink` [H] (f32 logits, one a query head: a sliding
layer's learned sink) adds ONE term to the softmax's denominator that brings
no value: the running softmax of a row starts from `m = sink`, `l = 1` (the
sink's own term under its own maximum) where it otherwise starts from
`-inf`, `0`, and every later step rescales that term with the rest. Both are
static at trace time: with `Dv == Dk` and no sink the traced kernel is the
one it was.

Latent (`decode_latent`): a latent-attention layer caches per token ONE
normed latent and ONE rotated key that all the heads share, side by side in a
row of the pool's `[n_pages, page_size, W]` array (`block_pool.LatentKV`: W =
640 for 512 + 64, zero behind them). With the key and value projections
absorbed into the query and the output, a row's H heads are H queries `[ql ;
q_pe ; 0]` against the SAME keys, and the value of a token is the first
`latent_dim` of its key: the kernel takes the paged kernel's work list and
grid, reads each page ONCE, and uses it twice, `s = q . page^T` over all W
and `o += p . page[:, :latent_dim]`. 2 x H x (576 + 512) FLOP a cached token
against 1,152 bytes is 121 FLOP a byte at 64 heads, half the chip's balance,
so both products go to the MXU with bf16 operands and f32 accumulation
(p rounded to bf16 once, as the flash kernels do); an f32 pool takes f32
operands.

Dense (`decode_dense`): the cache is contiguous, the grid stays (batch,
kv_head, block) with one [block, D] tile of one head a step and the sequence
tile autotuned. It shares the online-softmax update with the paged kernel
and nothing else.

Single-token decode (q = one step per row), inference only (no VJP).

Quantized pool: with `kv_scales`, the caches are int8 page payloads and
`kv_scales` the per-(page, head) f32 dequant scales (`x ≈ q * scale`,
`BlockPool(quantized=True)` layout). It is the same kernel body on the same
work list: a page is dequantized in VMEM after its load (its [Hkv] row of scales,
one multiply per head) and everything after is the f32 path — decode is
HBM-bound, so halving/quartering the streamed bytes is the whole win. The
scales ride beside their page as the (8, Hkv) tile of the [n_pages, Hkv]
scale array that holds the page's row — a (1, Hkv) block of a 2-D array is
not a shape Mosaic tiles — and the kernel reads row `page % 8` of it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode, mxu_dot, named_pallas_call
from .flash_attention import NEG_INF

__all__ = ["paged_decode_attention", "dense_decode_attention",
           "latent_decode_attention", "pages_per_step",
           "latent_pages_per_step", "WorkList", "work_list",
           "live_step_count", "paged_kv_write", "paged_kv_write_q8",
           "latent_kv_write", "KV_QMAX"]

# symmetric int8 range for KV pages: ±127 (not -128) so the running-max
# rescale in paged_kv_write_q8 can never overflow the negative extreme
KV_QMAX = 127.0
# rows of the [n_pages, Hkv] f32 scale array fetched per grid step: one f32
# sublane tile
_SCALE_ROWS = 8


def _softmax_update(s, live, m_prev, l_prev):
    """One online-softmax step over the keys of `s` (last axis), `live`
    marking the keys that exist. Returns (m_new, alpha, p, l_new): the new
    running maximum, the factor that rescales what was accumulated under the
    old one, the unnormalised probabilities (0 where not live) and the new
    running sum. Every array is f32; m and l keep a last axis of 1."""
    s = jnp.where(live, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    return m_new, alpha, p, l_new


def _dense_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                  acc_scr, *, scale, ps, np_, g):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]
    base = p * ps

    # scratch rows are padded to >=8 for TPU tiling; compute on the first g
    @pl.when(base < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)      # [g, D]
        k = k_ref[0, 0].astype(jnp.float32)      # [ps, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                # [g, ps]
        slot = base + jax.lax.broadcasted_iota(jnp.int32, (g, ps), 1)
        m_new, alpha, pr, l_new = _softmax_update(
            s, slot < length, m_scr[0:g, 0:1], l_scr[0:g, 0:1])
        l_scr[0:g, :] = jnp.broadcast_to(l_new, (g, l_scr.shape[1]))
        v = v_ref[0, 0].astype(jnp.float32)      # [ps, D]
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


def _run_dense(q, kc, vc, lengths, scale, ps):
    """q: [B, Hkv, g, D]; kc/vc [B, Hkv, S_max, D], which the index_map
    views as `ps`-sized blocks of the sequence axis (`ps` divides S_max)."""
    B, Hkv, g, D = q.shape
    P = kc.shape[2] // ps
    kernel = functools.partial(_dense_kernel, scale=scale, ps=ps, np_=P, g=g)
    kv_spec = pl.BlockSpec((1, 1, ps, D), lambda b, h, p, lens: (b, h, p, 0))
    q_spec = pl.BlockSpec((1, 1, g, D), lambda b, h, p, lens: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, P),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((max(g, 8), 128), jnp.float32),
            pltpu.VMEM((max(g, 8), 128), jnp.float32),
            pltpu.VMEM((max(g, 8), D), jnp.float32),
        ],
    )
    return named_pallas_call(
        "decode_dense", kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        interpret=interpret_mode(),
    )(lengths.astype(jnp.int32), q, kc, vc)


# ---- paged ----------------------------------------------------------------

# What a grid step of the paged kernel may hold in VMEM: the double-buffered
# K and V blocks of its N pages plus two pages' worth of f32 temporaries per
# page. Under the compiler's default scoped limit of 16 MiB, so no
# `vmem_limit_bytes` is asked for.
_VMEM_BUDGET = 12 * 2 ** 20
_PAGES_PER_STEP = (16, 8, 4, 2, 1)


def pages_per_step(Hkv, ps, D, P, itemsize, Dv=None):
    """N, the pages of one row a grid step of the paged kernel takes: the
    largest of 16, 8, 4, 2, 1 that is no larger than the table's width and
    whose VMEM need fits `_VMEM_BUDGET`. From shapes alone: nothing is
    measured, so nothing sweeps inside a serving process. `D`: the keys'
    width, `Dv` the values' (None: the same)."""
    page = Hkv * ps * (D + (D if Dv is None else Dv))   # K and V
    for n in _PAGES_PER_STEP:
        blocks = 2 * n * page * itemsize   # double-buffered
        temporaries = n * page * 4
        if n <= P and blocks + temporaries <= _VMEM_BUDGET:
            return n
    return 1


class WorkList(NamedTuple):
    """What the paged decode kernels' grid walks, as scalar-prefetch
    operands. A STEP is `n` consecutive table slots of one row; it is LIVE
    when any of its slots is. The live steps of all rows, in row-major
    order, fill the front of the `[B * steps]` arrays; `count` says how
    many there are, and the grid is that long.

    - `fetch` [B * steps, n]: what slot j of the w-th live step fetches and
      whether it counts. An entry >= 0 is a live slot's physical page; an
      entry < 0 is a dead slot, and `~entry` the page the same slot held at
      the last live step at which it was live: the pipeline sees an
      unchanged block index and fetches nothing;
    - `row`, `step` [B * steps]: the live step's row and its index within
      the row's `steps` (its key positions start at `step * n * ps`);
    - `first`, `last` [B * steps], 0 or 1: the live step is its row's first
      (the accumulators start) or its last (the output is written);
    - `count` []: the number of live steps;
    - `visited` [B] bool: the row has a live step. The output block of a
      row that has none is never written."""
    fetch: jax.Array
    row: jax.Array
    step: jax.Array
    first: jax.Array
    last: jax.Array
    count: jax.Array
    visited: jax.Array


def work_list(tables, lengths, ps, n, window=None) -> WorkList:
    """The live steps of `tables` [B, P] at `n` slots a step. A slot is
    live when its table entry is not -1 and its page starts before the
    row's length (with `window`, and ends behind `length - window`: a page
    wholly before the window is dead even while the table still names it);
    dead are a slot past the length, a -1 hole, a free row, the padding
    behind P. The live steps are compacted with a cumulative sum and ONE
    scatter of their rows (a sort of these few thousand keys compiles for
    seconds on the chip). It depends on `tables` and `lengths` alone, so the
    layers of a decode program that share a table share one list."""
    B, P = tables.shape
    steps = -(-P // n)
    total = B * steps
    t = jnp.pad(tables.astype(jnp.int32), ((0, 0), (0, steps * n - P)),
                constant_values=-1)
    lengths = lengths.astype(jnp.int32)
    first_tok = jnp.arange(steps * n, dtype=jnp.int32) * ps
    live = (t >= 0) & (first_tok[None, :] < lengths[:, None])
    if window is not None:
        live = live & (first_tok[None, :] + ps > lengths[:, None] - window)
    t, live = t.reshape(total, n), live.reshape(total, n)
    live_step = jnp.any(live, axis=1)
    s = jnp.arange(total, dtype=jnp.int32)
    before = jnp.cumsum(live_step.astype(jnp.int32))   # live steps up to s
    count = before[-1]
    # a stable partition: the live steps to the front in their order, the
    # dead ones behind them, so every target is written once
    target = jnp.where(live_step, before - 1, count + s - before)
    packed = jnp.concatenate(
        [jnp.where(live, t, -1), s[:, None]], axis=1)
    packed = jnp.zeros_like(packed).at[target].set(
        packed, unique_indices=True)
    t, s_of = packed[:, :n], packed[:, n]
    live = t >= 0
    # a dead slot names the page it held when it last was live
    held = _last_known(jnp.where(live, t, 0), live)
    row = s_of // steps
    is_live = s < count
    edge = jnp.full((1,), -1, jnp.int32)
    first = is_live & (row != jnp.concatenate([edge, row[:-1]]))
    last = is_live & ((s == count - 1)
                      | (row != jnp.concatenate([row[1:], edge])))
    return WorkList(
        fetch=jnp.where(live, t, ~held), row=row, step=s_of % steps,
        first=first.astype(jnp.int32), last=last.astype(jnp.int32),
        count=count, visited=jnp.any(live_step.reshape(B, steps), axis=1))


def _last_known(value, known):
    """`value` [steps, n] with every entry that is not `known` replaced by
    the last known one above it in its column (0 where there is none): a
    scan of log2(steps) shifts and selects. A gather of these scalars
    (`take_along_axis` at a running maximum's indices) costs the chip 10 ns
    apiece, a millisecond for a 512-wide table of 224 rows."""
    size, d = value.shape[0], 1
    while d < size:
        above_value = jnp.pad(value, ((d, 0), (0, 0)))[:size]
        above_known = jnp.pad(known, ((d, 0), (0, 0)))[:size]
        value = jnp.where(known, value, above_value)
        known = known | above_known
        d *= 2
    return value


def live_step_count(lengths, ps, n, window=None) -> int:
    """`work_list(...).count` on the host, for rows whose tables have no
    hole before their length (the engine's): numpy over `lengths` [rows],
    each row's valid tokens counted from its table's first slot. A row of
    length 0 has no live step."""
    lengths = np.asarray(lengths, np.int64)
    last_page = (lengths - 1) // ps
    first_page = (np.zeros_like(lengths) if window is None
                  else np.maximum(lengths - window, 0) // ps)
    per_row = last_page // n - first_page // n + 1
    return int(np.where(lengths > 0, per_row, 0).sum())


def _page_of(entry):
    """The physical page a `WorkList.fetch` entry names, live or dead."""
    return jnp.where(entry < 0, ~entry, entry)


def _split_bf16(x):
    """f32 `x` as three bf16 addends, highest first: together they carry
    all 24 bits of an f32 mantissa, so a bf16 MXU pass over each loses
    nothing."""
    parts = []
    for _ in range(3):
        hi = x.astype(jnp.bfloat16)
        parts.append(hi)
        x = x - hi.astype(jnp.float32)
    return parts


def _paged_kernel(fetch_ref, row_ref, step_ref, first_ref, last_ref,
                  lens_ref, *refs, scale, ps, n, g, quantized, dot_dtype,
                  window=None, sink=False):
    """One grid step, the w-th LIVE step of the work list: all KV heads of
    `n` pages of row `row[w]`. refs: n K blocks [1, Hkv, ps, Dk], n V blocks
    [1, Hkv, ps, Dv]; q [1, Hkv, g, Dk]; `sink`: the heads' sink logits
    [Hkv, g, 1] f32; quantized: n K-scale and n V-scale tiles [8, Hkv]; the
    output [1, Hkv, g, Dv]; scratch m, l [Hkv, g, 1] and acc [Hkv, g, Dv],
    f32."""
    k_refs, v_refs, q_ref = refs[:n], refs[n:2 * n], refs[2 * n]
    ks_refs, vs_refs = refs[2 * n + 1:3 * n + 1], refs[3 * n + 1:4 * n + 1]
    o_ref, m_scr, l_scr, acc_scr = refs[-4:]
    w = pl.program_id(0)
    i = step_ref[w]
    Hkv = q_ref.shape[1]

    @pl.when(first_ref[w] == 1)
    def _init():
        if sink:   # the sink's term, under its own maximum
            m_scr[...] = refs[2 * n + 1][...]
            l_scr[...] = jnp.ones_like(l_scr)
        else:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[row_ref[w]]
    slot_live = [fetch_ref[w * n + j] >= 0 for j in range(n)]

    def pages(refs, scale_refs):
        """The step's n pages as one [Hkv, n * ps, D] array of `dot_dtype`,
        an int8 page times its [Hkv] row of scales on the way."""
        out = []
        for j, ref in enumerate(refs):
            if not quantized:
                out.append(ref[0].astype(dot_dtype))
                continue
            phys = _page_of(fetch_ref[w * n + j])
            row = scale_refs[j][pl.ds(phys % _SCALE_ROWS, 1), :]   # [1, Hkv]
            head = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
            # a head's scale as a (1, 1) array by way of a masked sum: the
            # only (1, 1) that Mosaic broadcasts over a [ps, D] page
            out.append(jnp.stack([
                ref[0, h].astype(jnp.float32)
                * jnp.sum(jnp.where(head == h, row, 0.0), axis=1,
                          keepdims=True)
                for h in range(Hkv)]))
        return out[0] if n == 1 else jnp.concatenate(out, axis=1)

    # every step of the list has a live slot: there is no step to skip
    q = q_ref[0].astype(dot_dtype)                       # [Hkv, g, D]
    s = mxu_dot(q, pages(k_refs, ks_refs),
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale  # [Hkv, g, T]
    # a key counts if it lies before the row's length, in a live slot
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n * ps), 2)
    live = (i * (n * ps) + lane) < length
    if window is not None:   # the query at length - 1 sees `window` keys
        live = live & ((i * (n * ps) + lane) >= length - window)
    for j, alive in enumerate(slot_live):
        in_slot = (lane >= j * ps) & (lane < (j + 1) * ps)
        live = live & (alive | jnp.logical_not(in_slot))
    m_new, alpha, p, l_new = _softmax_update(
        s, live, m_scr[...], l_scr[...])
    v = pages(v_refs, vs_refs)                           # [Hkv, T, D]
    pv_dims = (((2,), (1,)), ((0,), (0,)))
    if dot_dtype == jnp.bfloat16:
        pv = sum(mxu_dot(part, v, pv_dims,
                         preferred_element_type=jnp.float32)
                 for part in _split_bf16(p))
    else:
        pv = mxu_dot(p, v, pv_dims, preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(last_ref[w] == 1)
    def _finish():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def _run_paged(q, kc, vc, tables, lengths, scale, n, kv_scales=None,
               window=None, sink=None):
    """q: [B, Hkv, g, D]; kc/vc: the pool's [n_pages, Hkv, ps, D] arrays as
    they are (vc may be [.., Dv], the output then [B, Hkv, g, Dv]), each
    passed `n` times, once per page slot of a grid step;
    tables: [B, P]; kv_scales: (k_scale, v_scale) f32 [n_pages, Hkv] for
    int8 pools. With `window` the same body runs as `decode_window`: slot 0
    of a row's table is the page of its FIRST cached position, positions and
    `lengths` count from there, and keys before `length - window` are
    masked. `sink`: [Hkv, g, 1] f32, the heads' sink logits."""
    B, Hkv, g, D = q.shape
    ps, Dv = kc.shape[2], vc.shape[3]
    quantized = kv_scales is not None
    lengths = lengths.astype(jnp.int32)
    work = work_list(tables, lengths, ps, n, window)

    def page_spec(j, width):
        return pl.BlockSpec(
            (1, Hkv, ps, width),
            lambda w, fetch, *_: (_page_of(fetch[w * n + j]), 0, 0, 0))

    def scale_spec(j):
        # the (8, Hkv) tile of the [n_pages, Hkv] scales that holds the
        # page's row: a (1, Hkv) block is not a shape Mosaic tiles
        return pl.BlockSpec(
            (_SCALE_ROWS, Hkv),
            lambda w, fetch, *_: (
                _page_of(fetch[w * n + j]) // _SCALE_ROWS, 0))

    def row_spec(width):
        return pl.BlockSpec((1, Hkv, g, width),
                            lambda w, fetch, row, *_: (row[w], 0, 0, 0))

    in_specs = ([page_spec(j, D) for j in range(n)]
                + [page_spec(j, Dv) for j in range(n)] + [row_spec(D)])
    operands = [kc] * n + [vc] * n + [q]
    if sink is not None:
        if quantized:
            raise NotImplementedError("a sink over an int8 pool")
        in_specs.append(pl.BlockSpec((Hkv, g, 1), lambda w, *_: (0, 0, 0)))
        operands.append(sink)
    if quantized:
        scale_slots = [scale_spec(j) for j in range(n)]
        in_specs += scale_slots + scale_slots
        operands += ([kv_scales[0].astype(jnp.float32)] * n
                     + [kv_scales[1].astype(jnp.float32)] * n)
    dot_dtype = (jnp.bfloat16
                 if q.dtype == kc.dtype == jnp.bfloat16 else jnp.float32)
    kernel = functools.partial(
        _paged_kernel, scale=scale, ps=ps, n=n, g=g, quantized=quantized,
        dot_dtype=dot_dtype, window=window, sink=sink is not None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(work.count,),
        in_specs=in_specs,
        out_specs=row_spec(Dv),
        scratch_shapes=[
            pltpu.VMEM((Hkv, g, 1), jnp.float32),
            pltpu.VMEM((Hkv, g, 1), jnp.float32),
            pltpu.VMEM((Hkv, g, Dv), jnp.float32),
        ],
    )
    out = named_pallas_call(
        _paged_name(quantized, window), kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, Dv), q.dtype),
        interpret=interpret_mode(),
    )(*_prefetched(work), lengths, *operands)
    return _zero_unvisited(out, work)


def _prefetched(work):
    """The work list's scalar-prefetch operands, `fetch` as ONE row: SMEM
    pads a 2-D array's minor dimension to 128 words, which a table of `n`
    slots a row would pay 8 to 128 times over."""
    return (work.fetch.reshape(-1), work.row, work.step, work.first,
            work.last)


def _zero_unvisited(out, work):
    """The grid never comes to a row without a live step, so nothing wrote
    its output block: such a row's output is zero by definition."""
    seen = work.visited.reshape((-1,) + (1,) * (out.ndim - 1))
    return jnp.where(seen, out, jnp.zeros_like(out))


def _paged_name(quantized, window):
    """The paged kernel's name, in the trace and in the tuner's registry."""
    if window is not None:
        if quantized:
            raise NotImplementedError("a window over an int8 pool")
        return "decode_window"
    return "decode_paged_q8" if quantized else "decode_paged"


# ---- latent ---------------------------------------------------------------


def latent_pages_per_step(ps, W, P, itemsize):
    """N for the latent kernel, as `pages_per_step`: one array's blocks,
    double-buffered, plus two pages' worth of f32 temporaries per page."""
    page = ps * W
    for n in _PAGES_PER_STEP:
        if n <= P and 2 * n * page * itemsize + 2 * n * page * 4 <= _VMEM_BUDGET:
            return n
    return 1


def _latent_kernel(fetch_ref, row_ref, step_ref, first_ref, last_ref,
                   lens_ref, *refs, scale, ps, n, dv, dot_dtype):
    """One grid step, the w-th LIVE step of the work list: `n` latent pages
    of row `row[w]`, each [1, ps, W], used as keys (all W) and as values
    (the first `dv`). refs: the n page blocks; q [1, H, W]; the output [1,
    H, dv]; scratch m, l [H, 1], acc [H, dv]."""
    page_refs, q_ref = refs[:n], refs[n]
    o_ref, m_scr, l_scr, acc_scr = refs[-4:]
    w = pl.program_id(0)
    i = step_ref[w]

    @pl.when(first_ref[w] == 1)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[row_ref[w]]
    slot_live = [fetch_ref[w * n + j] >= 0 for j in range(n)]

    # every step of the list has a live slot: there is no step to skip
    q = q_ref[0].astype(dot_dtype)                        # [H, W]
    pages = [ref[0].astype(dot_dtype) for ref in page_refs]
    kv = pages[0] if n == 1 else jnp.concatenate(pages, axis=0)  # [T, W]
    s = mxu_dot(q, kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # [H, T]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n * ps), 1)
    live = (i * (n * ps) + lane) < length
    for j, alive in enumerate(slot_live):
        in_slot = (lane >= j * ps) & (lane < (j + 1) * ps)
        live = live & (alive | jnp.logical_not(in_slot))
    m_new, alpha, p, l_new = _softmax_update(
        s, live, m_scr[...], l_scr[...])
    pv = mxu_dot(p.astype(dot_dtype), kv[:, :dv],
                 (((1,), (0,)), ((), ())),
                 preferred_element_type=jnp.float32)           # [H, dv]
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(last_ref[w] == 1)
    def _finish():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def latent_decode_attention(q, pages, block_tables, lengths, latent_dim,
                            scale):
    """One decode step of latent attention in its absorbed form
    (`decode_latent`). q: [B, H, W], each head's `[ql ; q_pe]` (the query
    with the key projection absorbed, then its rotated part) padded with
    zeros to the pool's row width W; pages: the pool's [n_pages, page_size,
    W] array, a token's normed latent and rotated key side by side;
    block_tables: [B, P] (-1 unused); lengths: [B] valid tokens incl. the
    current one (already written). Returns [B, H, latent_dim]: per head the
    softmax-weighted sum of the cached LATENTS, to which the caller applies
    the absorbed value projection. A free row (all -1) comes out zero."""
    from .autotune import pick_block_sizes

    B, H, W = q.shape
    ps, P = pages.shape[1], block_tables.shape[1]
    tile = (latent_pages_per_step(ps, W, P, pages.dtype.itemsize) * ps, W)
    # the tile follows from the shapes and is the tuner's only candidate:
    # nothing sweeps inside a serving process
    tile = pick_block_sizes(
        "decode_latent", 1, P * ps, tile, lambda bq, bk: None,
        allow_measure=False, signature=(B, H, W, str(q.dtype), P),
        candidates=[tile])
    n = tile[0] // ps
    lengths = lengths.astype(jnp.int32)
    work = work_list(block_tables, lengths, ps, n)

    def page_spec(j):
        return pl.BlockSpec(
            (1, ps, W),
            lambda w, fetch, *_: (_page_of(fetch[w * n + j]), 0, 0))

    def row_spec(width):
        return pl.BlockSpec((1, H, width),
                            lambda w, fetch, row, *_: (row[w], 0, 0))

    dot_dtype = (jnp.bfloat16
                 if q.dtype == pages.dtype == jnp.bfloat16 else jnp.float32)
    kernel = functools.partial(
        _latent_kernel, scale=scale, ps=ps, n=n, dv=latent_dim,
        dot_dtype=dot_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(work.count,),
        in_specs=[page_spec(j) for j in range(n)] + [row_spec(W)],
        out_specs=row_spec(latent_dim),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, latent_dim), jnp.float32),
        ],
    )
    out = named_pallas_call(
        "decode_latent", kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, latent_dim), q.dtype),
        interpret=interpret_mode(),
    )(*_prefetched(work), lengths, *([pages] * n), q)
    return _zero_unvisited(out, work)


def _split_heads(q, Hkv):
    B, H, D = q.shape
    g = H // Hkv
    return q.reshape(B, Hkv, g, D), g


def paged_decode_attention(q, key_cache, value_cache, block_tables, lengths,
                           scale=None, kv_scales=None, window=None,
                           sink=None):
    """q: [B, H, D] (one decode step); key/value_cache:
    [n_pages, Hkv, page_size, D] (the values may be [.., Dv]: the output is
    then [B, H, Dv]); block_tables: [B, P] physical page ids
    (-1 unused); lengths: [B] valid tokens incl. the current one (caller has
    already written the step's K/V into the cache). With `kv_scales`
    (= (k_scale, v_scale) f32 [n_pages, Hkv]) the caches are int8 payloads
    and dequantization is fused into the page load. With `window` (a
    sliding-window layer, `decode_window`): the row's query, at position
    `lengths - 1`, sees keys `lengths - window .. lengths - 1` only, and
    `block_tables` [B, ceil(window / ps) + 1] starts at the page of the row's
    first CACHED position, from which `lengths` counts too (the engine
    releases the pages before it: `inference/paged/block_pool.WindowKV`), so
    its width does not grow with the longest sequence. `sink` [H] f32: one
    logit a query head that joins the softmax's denominator and brings no
    value (a row the grid visits then has a denominator of at least the
    sink's term; a row without a live key still comes out zero). Returns
    [B, H, D]."""
    B, H, D = q.shape
    Hkv = key_cache.shape[1]
    if scale is None:
        scale = D ** -0.5
    q4, g = _split_heads(q, Hkv)
    n = _consult_tuner_paged(q4, key_cache, block_tables,
                             _paged_name(kv_scales is not None, window),
                             value_cache.shape[-1])
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(Hkv, g, 1)
    out = _run_paged(q4, key_cache, value_cache, block_tables, lengths,
                     scale, n, kv_scales=kv_scales, window=window, sink=sink)
    return out.reshape(B, H, value_cache.shape[-1])


def _consult_tuner_paged(q4, kc, tables, name="decode_paged", Dv=None):
    """N, the pages a grid step takes, by way of the tuner. N follows from
    the shapes (`pages_per_step`) and the page size is the POOL's physical
    layout, so the tile (N * page_size, D) is the tuner's only candidate:
    it never sweeps (a serving process must not) and never counts a
    fallback, and the tile lands in chosen_tiles() / the step-timeline
    record with its `consults`. The int8 pool and the windowed kernel record
    under their own tuner names, so the telemetry tells which decode path
    ran."""
    from .autotune import pick_block_sizes

    B, Hkv, g, D = q4.shape
    ps, P = kc.shape[2], tables.shape[1]
    widths = () if Dv in (None, D) else (Dv,)   # the values' own width
    tile = (pages_per_step(Hkv, ps, D, P, kc.dtype.itemsize, *widths) * ps,
            D)
    tile = pick_block_sizes(
        name, 1, P * ps, tile, lambda bq, bk: None,
        allow_measure=False,
        signature=(B, Hkv, g, D, str(q4.dtype), P) + widths,
        candidates=[tile])
    return tile[0] // ps


def paged_kv_write(cache, new, block_tables, lengths):
    """Write one decode step's K (or V) rows into the paged cache.

    cache: [n_pages, Hkv, page_size, D]; new: [B, Hkv, D] (this step's
    projection per row); block_tables: [B, P] physical page ids (-1 unused);
    lengths: [B] tokens already present per row — the write lands at logical
    slot `lengths[b]`, i.e. physical (tables[b, lengths[b]//ps],
    lengths[b]%ps). Rows whose target table entry is -1 (parked/batch-pad
    rows) are routed to physical page 0, the pool's reserved null page, which
    no live block table ever references. Pure/jittable; owns the page layout
    so callers never index the cache themselves.

    The form is a read-modify-write of each row's WHOLE target page: a
    scatter over the pool's major dimension alone is done in place, where
    `cache.at[page, :, slot].set(new)` has the TPU compiler re-lay the whole
    pool round the scatter. Live rows never share a write page (the engine's
    copy-on-write); parked rows collide on page 0 only, where one writer's
    page stays and nothing reads it."""
    B = new.shape[0]
    ps = cache.shape[2]
    lengths = lengths.astype(jnp.int32)
    page = block_tables[jnp.arange(B), lengths // ps]
    page = jnp.where(page < 0, 0, page)
    at_slot = (jax.lax.broadcasted_iota(jnp.int32, (B, 1, ps, 1), 2)
               == (lengths % ps)[:, None, None, None])
    pg = jnp.where(at_slot, new.astype(cache.dtype)[:, :, None, :],
                   cache[page])
    return cache.at[page].set(pg)


def latent_kv_write(pages, new, block_tables, lengths):
    """Write one decode step's latent rows into the latent pages.

    pages: [n_pages, page_size, W]; new: [B, W] (this step's `[c ; k_pe ;
    0]` per row); block_tables, lengths and the null page as `paged_kv_write`.
    Here the page and the slot ARE the array's two major dimensions, so the
    pool is seen as [n_pages * page_size, W] rows (no data moves) and each
    row's ONE token row is scattered over the major dimension alone, in
    place: the write moves B rows of W values whatever the page size, where
    the whole-page form moves B pages. Parked rows collide on the null
    page's slots, where one writer's row stays and nothing reads it."""
    B = new.shape[0]
    n_pages, ps, W = pages.shape
    lengths = lengths.astype(jnp.int32)
    page = block_tables[jnp.arange(B), lengths // ps]
    page = jnp.where(page < 0, 0, page)
    rows = pages.reshape(n_pages * ps, W)
    rows = rows.at[page * ps + lengths % ps].set(new.astype(pages.dtype))
    return rows.reshape(pages.shape)


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

    def run_with(ps, _d):
        _run_dense(q4, kc, vc, lengths, scale, ps).block_until_ready()

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
    out = _run_dense(q4, key_cache, value_cache, lengths, scale, ps)
    return out.reshape(B, H, D)
