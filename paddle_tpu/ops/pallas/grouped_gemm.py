"""Grouped (ragged) GEMM for MoE expert compute, in Pallas.

Reference analog: paddle/phi/kernels/fusion/cutlass/fused_moe_kernel.cu —
the cutlass grouped GEMM that runs every expert's FFN over its own ragged
row range in one launch. TPU redesign (the megablox formulation):

- Rows are pre-sorted by expert into a UNIFORM-STRIDE layout: `lhs` is
  [E * R, K] where group e owns rows [e*R, (e+1)*R) and only the first
  `group_sizes[e]` of them are live (the MoE dispatch scatters tokens into
  exactly this layout; R is padded to the row-tile multiple). The uniform
  stride is what makes the expert dim a real mesh-shardable axis — under
  expert parallelism the same kernel runs per ep-shard on [E/ep * R, K]
  with no layout change.
- The grid walks (row tile, N tile); each row tile belongs to exactly one
  group (bm divides R), so the group's weight block rides an ordinary
  BlockSpec index map — no scalar-dependent DMA. `group_sizes` is a
  scalar-prefetch operand: tiles whose row offset is past the group's live
  rows SKIP the MXU work entirely and write zeros (compute scales with
  routed tokens rounded to bm, not with capacity — the ragged half of
  "grouped/ragged"), and their index maps name the blocks already in VMEM,
  so a dead tile fetches neither rows nor weights: a group's weights are
  read once per LIVE row tile, whatever the stride.
- Accumulation is f32 (`preferred_element_type`) whatever the input dtype,
  like every other kernel in the ladder.

Semantics (pinned by tests/test_moe.py::TestGroupedGemm): rows inside a
partially-live tile are still computed (they cost nothing extra — the MXU
runs whole tiles); rows in fully-dead tiles are zero. Callers that scatter
zeros into dead rows (the MoE layer does) therefore get exact parity with
the dense batched-GEMM formulation.

Backward (custom VJP): dlhs reuses THIS kernel with the weights transposed
(same tile skipping — dead tiles have zero cotangent by the same
semantics); dgroup weights are a batched jnp matmul over the uniform
stride, masked to the rows the forward actually computed. Autotune: tuner
name "grouped_gemm", tile family (bm over the row stride, bn over N).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from . import interpret_mode, mxu_dot, named_pallas_call
from .partition import shard_plan

__all__ = ["grouped_matmul", "default_tiles", "row_stride"]


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
# kernel
# --------------------------------------------------------------------------- #


def _gg_kernel(sizes_ref, lhs_ref, rhs_ref, o_ref, *, bm, tiles_per_group):
    i = pl.program_id(0)
    group = i // tiles_per_group
    off = (i % tiles_per_group) * bm
    live = sizes_ref[group]

    @pl.when(live > off)
    def _():
        o_ref[...] = mxu_dot(
            lhs_ref[...], rhs_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)

    @pl.when(live <= off)
    def _():
        # dead tile: zeros, not garbage — downstream reductions (dweight
        # batched matmuls, combine gathers) must never meet uninitialized
        # VMEM
        o_ref[...] = jnp.zeros_like(o_ref)


def _gg_call(lhs, rhs, sizes, bm, bn):
    """lhs [E*R, K], rhs [E, K, N], sizes [E] -> [E*R, N]."""
    E, K, N = rhs.shape
    G = lhs.shape[0]
    R = G // E
    Kp, Np = max(128, _pad_to(K, 128)), max(128, _pad_to(N, 128))
    bn = min(bn, Np)
    if Np % bn:
        bn = Np
    if lhs.shape != (G, Kp):
        lhs = jnp.pad(lhs, ((0, 0), (0, Kp - K)))
    if rhs.shape != (E, Kp, Np):
        rhs = jnp.pad(rhs, ((0, 0), (0, Kp - K), (0, Np - N)))
    tiles_per_group = R // bm
    grid = (E * tiles_per_group, Np // bn)
    kernel = functools.partial(_gg_kernel, bm=bm,
                               tiles_per_group=tiles_per_group)

    # a dead tile computes nothing, so it fetches nothing either: its steps
    # name the blocks the step before them held (the group's last live row
    # tile, the last N tile), and the pipeline issues no DMA for an
    # unchanged block index. Only the zeros it must write cost anything.
    def lhs_block(i, j, szs):
        group, tile = i // tiles_per_group, i % tiles_per_group
        live_tiles = (szs[group] + bm - 1) // bm
        last_live = group * tiles_per_group + jnp.maximum(live_tiles - 1, 0)
        return jnp.where(tile < live_tiles, i, last_live), 0

    def rhs_block(i, j, szs):
        group, tile = i // tiles_per_group, i % tiles_per_group
        return group, 0, jnp.where(szs[group] > tile * bm, j, Np // bn - 1)

    out = named_pallas_call(
        "grouped_gemm", kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, Kp), lhs_block),
                pl.BlockSpec((1, Kp, bn), rhs_block),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, szs: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((G, Np), lhs.dtype),
        interpret=interpret_mode(),
    )(sizes.astype(jnp.int32), lhs, rhs)
    return out[:, :N]


# --------------------------------------------------------------------------- #
# custom VJP
# --------------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_matmul(lhs, rhs, sizes, bm, bn):
    return _gg_call(lhs, rhs, sizes, bm, bn)


def _gmm_fwd(lhs, rhs, sizes, bm, bn):
    return _gg_call(lhs, rhs, sizes, bm, bn), (lhs, rhs, sizes)


def _gmm_bwd(bm, bn, res, dout):
    lhs, rhs, sizes = res
    E, K, N = rhs.shape
    R = lhs.shape[0] // E
    # dlhs: the same grouped kernel against the transposed weights — dead
    # tiles write zeros, matching the forward's "dead rows are zero" output
    # semantics exactly
    dlhs = _gg_call(dout, jnp.swapaxes(rhs, 1, 2), sizes, bm,
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
        out = _gg_call(lhs, rhs, sizes, bm, bn)
        out.block_until_ready()

    concrete = not any(isinstance(v, jax.core.Tracer)
                       for v in (lhs, rhs, sizes))
    return pick_block_sizes(
        "grouped_gemm", lhs.shape[0], N, default, run_with,
        allow_measure=concrete,
        signature=(E, R, K, N, str(lhs.dtype)),
        candidates=_tile_candidates(R, K, N, default))


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
