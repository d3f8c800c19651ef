"""Grouped (ragged) GEMM for MoE expert compute, in Pallas.

Reference analog: paddle/phi/kernels/fusion/cutlass/fused_moe_kernel.cu —
the cutlass grouped GEMM that runs every expert's FFN over its own ragged
row range in one launch. TPU redesign (the megablox formulation):

- ONE kernel body over row groups given by each group's FIRST ROW and SIZE
  (scalar prefetch). The grid walks (N tile, visit): a VISIT is one (row
  tile, group) pair whose rows meet, listed group after group
  (`plan_visits`, `jax.numpy` on a few hundred integers), at most
  `tiles + groups - 1` of them. A group of r rows is visited in every tile
  it has a row in and an empty group nowhere; a tile that two groups share
  is visited once for each, the other's rows masked (the later visit finds
  the earlier one's rows in VMEM and keeps them). A tile no group touches
  gets one visit that writes zeros, a visit past the real count does
  nothing, and neither fetches: their index maps name the blocks the last
  live visit left in VMEM.
- The N tile is the OUTER grid dimension: inside one N tile the visits of a
  group follow each other, so its [K, bn] weight block is fetched once
  however many row tiles the group spans, and the visits of a row tile
  follow each other, so its output block is written back once. A call reads
  the stacked weights once and its live rows N / bn times.
- Two layouts, the same body. `ragged_matmul`: groups END TO END, each
  starting where the one before ended (`jax.lax.ragged_dot`'s semantics;
  the held experts' layer, `moe/held_moe.py`). `grouped_matmul`: the
  UNIFORM STRIDE, `lhs` [E * R, K] with group e at rows [e*R, (e+1)*R)
  (`MoELayer`'s dispatch scatters into it; the stride is what makes the
  expert dim a mesh-shardable axis: under expert parallelism the same
  kernel runs per ep-shard on [E/ep * R, K]), as `starts = e * R` with each
  size rounded up to whole row tiles.
- Whole-K blocks and f32 accumulation (`preferred_element_type`) whatever
  the input dtype, like every other kernel in the ladder: a row's dot is
  one MXU dot whatever tile it lies in.

Semantics: every row of a group is its row times the group's matrix; every
row of NO group is exactly zero, never garbage. For `grouped_matmul`
(pinned by tests/test_moe.py::TestGroupedGemm) that reads: rows inside a
partially-live tile are still computed (they cost nothing extra: the MXU
runs whole tiles); rows in fully-dead tiles are zero. Callers that scatter
zeros into dead rows (the MoE layer does) therefore get exact parity with
the dense batched-GEMM formulation.

Backward (custom VJPs): dlhs reuses THIS kernel with the weights transposed
(the same visits); the group weights' gradient is `ragged_dot`'s own for
groups end to end, and for the uniform stride a batched jnp matmul masked
to the rows the forward computed. Autotune: tuner name "grouped_gemm";
`grouped_matmul` offers the family (bm over the row stride, bn over N),
`ragged_matmul`'s callers bring the one tile their shapes give.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from . import interpret_mode, mxu_dot, named_pallas_call
from .partition import shard_plan

__all__ = ["grouped_matmul", "ragged_matmul", "plan_visits",
           "end_to_end_visits", "Visits", "default_tiles", "row_stride"]


def _pad_to(n, m):
    return -(-n // m) * m


def row_stride(max_rows: int) -> int:
    """The uniform per-group row stride for `max_rows` live rows per group,
    padded so every autotune bm candidate that divides it tiles cleanly.
    Small groups quantize to 16 — the bf16 sublane minimum, so the
    sub-f32 bm bump in grouped_matmul always has a legal divisor — larger
    ones to the MXU row (128) so the (128, bn) candidates stay legal."""
    q = 16 if max_rows <= 64 else 128
    return _pad_to(max(max_rows, 1), q)


def default_tiles(R, K, N):
    """(bm, bn): bm the largest power-of-two row tile dividing R (<=128),
    bn capped so the lhs + rhs + out f32 working set stays well under
    VMEM with double buffering."""
    bm = 8
    while bm * 2 <= min(R, 128) and R % (bm * 2) == 0:
        bm *= 2
    bn = 128
    while bn * 2 <= min(N, 512) and (bm + bn * 2) * K * 4 < 6 * 1024 * 1024:
        bn *= 2
    return bm, bn


def _tile_candidates(R, K, N, default):
    cands = {default}
    for bm in (8, 16, 32, 64, 128, 256):
        if bm > R or R % bm:
            continue
        for bn in (128, 256, 512):
            if bn > _pad_to(N, 128):
                continue
            if (bm + bn) * K * 4 > 10 * 1024 * 1024:
                continue
            cands.add((bm, bn))
    return sorted(cands)


# --------------------------------------------------------------------------- #
# the walk over (row tile, group) visits
# --------------------------------------------------------------------------- #


class Visits(NamedTuple):
    """What the kernel's grid walks, as scalar-prefetch operands. A VISIT is
    one (row tile, group) pair whose rows meet; `group`, `lhs_tile` and
    `out_tile` are [tiles + groups - 1] int32, one entry a visit:

    - visits 0 .. n[0] - 1 are LIVE, in group order and inside a group in
      tile order, so one group's weights and one row tile's output stay in
      VMEM over the visits that share them;
    - visits n[0] .. n[1] - 1 are the row tiles no group touches, which are
      written as zeros: `group` and `lhs_tile` still name the last live
      visit's blocks, so nothing is fetched;
    - visits from n[1] on do nothing and name the blocks of visit n[1] - 1.

    `starts`, `ends` [groups]: each group's first row and the row behind its
    last."""
    group: jax.Array
    lhs_tile: jax.Array
    out_tile: jax.Array
    starts: jax.Array
    ends: jax.Array
    n: jax.Array


def plan_visits(starts, sizes, rows, bm) -> Visits:
    """The visits of groups `[starts[g], starts[g] + sizes[g])` (ascending,
    none overlapping) over the `rows // bm` row tiles of `bm` rows. A group
    of r rows is visited in every tile it has a row in (at most
    ceil(r / bm) + 1 of them), an empty group nowhere, a tile two groups
    share once for each: at most tiles + groups - 1 visits with the dead
    tiles' one each, whatever the sizes. All of it is `jax.numpy` on a few
    hundred integers."""
    tiles, groups = rows // bm, sizes.shape[0]
    starts, sizes = starts.astype(jnp.int32), sizes.astype(jnp.int32)
    ends = starts + sizes
    first = starts // bm
    per_group = jnp.where(sizes > 0, (ends - 1) // bm - first + 1, 0)
    before = jnp.cumsum(per_group)
    n_live = before[-1]
    v = jnp.arange(tiles + groups - 1, dtype=jnp.int32)
    # a dead visit reads the entries of the last live one
    at = jnp.clip(v, 0, jnp.maximum(n_live - 1, 0))
    group = jnp.minimum(jnp.searchsorted(before, at, side="right"),
                        groups - 1).astype(jnp.int32)
    tile = jnp.clip(first[group] + at - (before[group] - per_group[group]),
                    0, tiles - 1)
    touched = jnp.zeros(tiles, jnp.int32).at[
        jnp.where(v < n_live, tile, tiles)].add(1, mode="drop") > 0
    untouched = jnp.argsort(touched, stable=True).astype(jnp.int32)
    n_all = n_live + tiles - jnp.sum(touched)
    last = jnp.minimum(v, n_all - 1)
    out_tile = jnp.where(last < n_live, tile[last],
                         untouched[jnp.clip(last - n_live, 0, tiles - 1)])
    return Visits(group, tile, out_tile, starts, ends,
                  jnp.stack([n_live, n_all]).astype(jnp.int32))


def end_to_end_visits(group_sizes, rows, bm) -> Visits:
    """`plan_visits` for groups that start where the one before ended, over
    `rows` rows padded to whole tiles of `bm`."""
    sizes = group_sizes.astype(jnp.int32)
    return plan_visits(jnp.cumsum(sizes) - sizes, sizes, _pad_to(rows, bm), bm)


# --------------------------------------------------------------------------- #
# kernel
# --------------------------------------------------------------------------- #


def _gg_kernel(group_ref, lhs_tile_ref, out_tile_ref, starts_ref, ends_ref,
               n_ref, lhs_ref, rhs_ref, o_ref, *, bm):
    del lhs_tile_ref                      # the index maps' alone
    v = pl.program_id(1)
    tile = out_tile_ref[v]
    # the first visit of a row tile writes all of it; a later one (the tile
    # holds the end of one group and the start of the next) only its rows
    fresh = jnp.logical_or(
        v == 0, out_tile_ref[jnp.maximum(v - 1, 0)] != tile)

    @pl.when(v < n_ref[0])
    def _():
        group = group_ref[v]
        acc = mxu_dot(
            lhs_ref[...], rhs_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        row = tile * bm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = jnp.logical_and(row >= starts_ref[group],
                               row < ends_ref[group])

        @pl.when(fresh)
        def _():
            o_ref[...] = jnp.where(mine, acc, 0.0).astype(o_ref.dtype)

        @pl.when(jnp.logical_not(fresh))
        def _():
            o_ref[...] = jnp.where(
                mine, acc, o_ref[...].astype(jnp.float32)).astype(o_ref.dtype)

    @pl.when(jnp.logical_and(v >= n_ref[0], v < n_ref[1]))
    def _():
        # a tile no group touches: zeros, not garbage — downstream
        # reductions (dweight batched matmuls, combine gathers) must never
        # meet uninitialized VMEM
        o_ref[...] = jnp.zeros_like(o_ref)


def _gg_call(lhs, rhs, visits, bm, bn):
    """lhs [M, K] (bm divides M), rhs [G, K, N], the visits of the G groups
    over lhs's row tiles -> [M, N]: a group's rows times its matrix, zeros
    in every row of no group."""
    G, K, N = rhs.shape
    M = lhs.shape[0]
    Kp, Np = max(128, _pad_to(K, 128)), max(128, _pad_to(N, 128))
    bn = min(bn, Np)
    if Np % bn:
        bn = Np
    if lhs.shape != (M, Kp):
        lhs = jnp.pad(lhs, ((0, 0), (0, Kp - K)))
    if rhs.shape != (G, Kp, Np):
        rhs = jnp.pad(rhs, ((0, 0), (0, Kp - K), (0, Np - N)))
    # the column tile is the OUTER grid dimension: inside one column tile
    # the visits come group after group, so a group's [K, bn] block is
    # fetched once however many row tiles the group spans, and the visits
    # of one row tile follow each other, so its output block is written
    # back once. A visit that is not live names blocks already in VMEM.
    out = named_pallas_call(
        "grouped_gemm", functools.partial(_gg_kernel, bm=bm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(visits),
            grid=(Np // bn, visits.group.shape[0]),
            in_specs=[
                pl.BlockSpec((bm, Kp),
                             lambda j, v, group, lhs_tile, *_: (lhs_tile[v], 0)),
                pl.BlockSpec((1, Kp, bn),
                             lambda j, v, group, *_: (group[v], 0, j)),
            ],
            out_specs=pl.BlockSpec(
                (bm, bn),
                lambda j, v, group, lhs_tile, out_tile, *_: (out_tile[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, Np), lhs.dtype),
        interpret=interpret_mode(),
    )(*visits, lhs, rhs)
    return out[:, :N]


def _uniform_visits(sizes, E, R, bm):
    """The uniform stride as ragged groups: group e starts at row e * R and
    is its live rows rounded up to whole row tiles (a partially-live tile
    is computed whole)."""
    sizes = jnp.minimum(-(-sizes.astype(jnp.int32) // bm) * bm, R)
    return plan_visits(jnp.arange(E, dtype=jnp.int32) * R, sizes, E * R, bm)


# --------------------------------------------------------------------------- #
# custom VJP
# --------------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_matmul(lhs, rhs, sizes, bm, bn):
    E = rhs.shape[0]
    return _gg_call(lhs, rhs, _uniform_visits(sizes, E, lhs.shape[0] // E, bm),
                    bm, bn)


def _gmm_fwd(lhs, rhs, sizes, bm, bn):
    return _grouped_matmul(lhs, rhs, sizes, bm, bn), (lhs, rhs, sizes)


def _gmm_bwd(bm, bn, res, dout):
    lhs, rhs, sizes = res
    E, K, N = rhs.shape
    R = lhs.shape[0] // E
    # dlhs: the same grouped kernel against the transposed weights — dead
    # tiles write zeros, matching the forward's "dead rows are zero" output
    # semantics exactly
    dlhs = _gg_call(dout, jnp.swapaxes(rhs, 1, 2),
                    _uniform_visits(sizes, E, R, bm), bm,
                    min(bn, max(128, _pad_to(K, 128))))
    # drhs[e] = lhs_e^T @ dout_e over the rows the forward COMPUTED —
    # live tiles in full (partially-live tiles run whole), dead tiles not
    # at all. The uniform stride makes this one batched matmul; masking to
    # computed rows keeps the op's own semantics exact even for callers
    # that leave garbage in dead rows.
    computed = jnp.minimum((-(-sizes // bm)) * bm, R)  # ceil(live/bm)*bm
    row = jax.lax.broadcasted_iota(jnp.int32, (E, R), 1)
    live = (row < computed[:, None])[..., None]
    lhs3 = jnp.where(live, lhs.reshape(E, R, K), 0).astype(jnp.float32)
    dout3 = jnp.where(live, dout.reshape(E, R, N), 0).astype(jnp.float32)
    drhs = jnp.einsum("erk,ern->ekn", lhs3, dout3).astype(rhs.dtype)
    dsizes = np.zeros(sizes.shape, jax.dtypes.float0)
    return dlhs.astype(lhs.dtype), drhs, dsizes


_grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def _tuned_tiles(lhs, rhs, sizes, R):
    """Consult the autotuner for this signature (tuner name
    "grouped_gemm"): candidates vary the row tile over divisors of the
    layout stride R and the N tile; the winner is cached per
    (E, R, K, N, dtype). Under a trace the consult is cache-only (the
    standard priming rule — call grouped_matmul with concrete arrays of
    the production shape to fill the cache, ops/pallas/README.md)."""
    from .autotune import pick_block_sizes

    E, K, N = rhs.shape
    default = default_tiles(R, K, N)

    def run_with(bm, bn):
        out = _gg_call(lhs, rhs, _uniform_visits(sizes, E, R, bm), bm, bn)
        out.block_until_ready()

    concrete = not any(isinstance(v, jax.core.Tracer)
                       for v in (lhs, rhs, sizes))
    return pick_block_sizes(
        "grouped_gemm", lhs.shape[0], N, default, run_with,
        allow_measure=concrete,
        signature=(E, R, K, N, str(lhs.dtype)),
        candidates=_tile_candidates(R, K, N, default))


def ragged_matmul(lhs, rhs, group_sizes, block, visits=None):
    """Groups that lie END TO END: out[r] = lhs[r] @ rhs[g] for the rows r of
    group g, which starts where group g - 1 ended (group 0 at row 0) and has
    `group_sizes[g]` rows; rows behind the last group come back zero
    (`jax.lax.ragged_dot`'s semantics, which is what the tests hold it to).

    lhs: [M, K]; rhs: [G, K, N]; group_sizes: [G] int32, their sum at most M.
    `block` = (bm, bn): M is padded to whole row tiles, so choose a bm that
    divides it. `visits`: `end_to_end_visits` of these groups at this bm,
    for a caller that makes several calls over the same groups or counts the
    visits. Differentiable in lhs and rhs: dlhs is this kernel against the
    transposed weights, drhs is `jax.lax.ragged_dot`'s own."""
    bm, bn = block
    M = lhs.shape[0]
    if visits is None:
        visits = end_to_end_visits(group_sizes, M, bm)
    if M % bm:
        lhs = jnp.pad(lhs, ((0, -M % bm), (0, 0)))
    return _ragged_matmul(lhs, rhs, group_sizes.astype(jnp.int32), visits,
                          bm, bn)[:M]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ragged_matmul(lhs, rhs, sizes, visits, bm, bn):
    return _gg_call(lhs, rhs, visits, bm, bn)


def _rmm_fwd(lhs, rhs, sizes, visits, bm, bn):
    return _gg_call(lhs, rhs, visits, bm, bn), (lhs, rhs, sizes, visits)


def _rmm_bwd(bm, bn, res, dout):
    lhs, rhs, sizes, visits = res
    K = rhs.shape[1]
    dlhs = _gg_call(dout, jnp.swapaxes(rhs, 1, 2), visits, bm,
                    min(bn, max(128, _pad_to(K, 128))))
    _, pull = jax.vjp(
        lambda w: jax.lax.ragged_dot(
            lhs, w, sizes, preferred_element_type=jnp.float32), rhs)
    (drhs,) = pull(dout.astype(jnp.float32))

    def no_grad(a):
        return np.zeros(a.shape, jax.dtypes.float0)

    return (dlhs.astype(lhs.dtype), drhs, no_grad(sizes),
            jax.tree.map(no_grad, visits))


_ragged_matmul.defvjp(_rmm_fwd, _rmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, block=None):
    """Ragged grouped GEMM: out[r] = lhs[r] @ rhs[r // R] with
    R = lhs.shape[0] // rhs.shape[0] the uniform group stride.

    lhs: [E*R, K] rows pre-sorted by group; rhs: [E, K, N] stacked group
    weights; group_sizes: [E] int32 live rows per group. Rows past
    `group_sizes[g]` in a fully-dead row tile come back zero; rows inside
    a partially-live tile are computed (MXU tiles are all-or-nothing).
    Differentiable in lhs/rhs (custom VJP; group_sizes gets a symbolic
    zero). `block` overrides the autotuned (bm, bn). Under a multi-device
    mesh the kernel runs per shard (partition.py): the groups over ep (the
    uniform stride keeps each shard's rows contiguous), the output columns
    over the data axes."""
    plan = shard_plan(lhs, rhs)
    if plan is not None:
        e = plan.axes("experts", rhs.shape[0])
        c = plan.axes("cols", rhs.shape[2])
        return plan.run(
            lambda l, r, s: _grouped_matmul_local(l, r, s, block),
            [lhs, rhs, group_sizes], [P(e, None), P(e, None, c), P(e)],
            P(e, c))
    return _grouped_matmul_local(lhs, rhs, group_sizes, block)


def _grouped_matmul_local(lhs, rhs, group_sizes, block):
    E = rhs.shape[0]
    G = lhs.shape[0]
    if G % E:
        raise ValueError(
            f"lhs rows {G} not a multiple of the group count {E} — the "
            f"uniform-stride layout needs rows padded per group "
            f"(see row_stride())")
    R = G // E
    bm, bn = block if block is not None else _tuned_tiles(
        lhs, rhs, group_sizes, R)
    if R % bm:
        raise ValueError(f"row tile {bm} does not divide group stride {R}")
    # sub-f32 dtypes need a 16-sublane minimum tile on real Mosaic (the
    # interpreter doesn't care); row_stride() quantizes small strides to 16
    # so the bump always has a legal divisor — a hand-built layout that
    # doesn't gets a clear error instead of a Mosaic lowering failure
    if jnp.dtype(lhs.dtype).itemsize < 4 and bm < 16:
        if R % 16:
            raise ValueError(
                f"sub-f32 grouped_matmul needs a 16-divisible group stride "
                f"(got R={R}); lay rows out with row_stride()")
        bm = 16
    return _grouped_matmul(lhs, rhs, group_sizes.astype(jnp.int32), bm, bn)
