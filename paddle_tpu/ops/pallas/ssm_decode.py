"""One decode step of a Mamba-2 (SSD) recurrence over every decode row, in
place on the rows' state, in Pallas.

Per row b and head h, with state S of shape [P, N] (P = head width, N = state
width), this step's decay a = exp(dt * A), input u = dt * x and the row's B
and C vectors:

    S <- a * S + u (outer) B          y = S C

The step is bound by HBM: a row reads and writes its whole state (2 MB a
layer at 128 heads x 64 x 128 in bf16) and does four operations a value. So
the kernel exists for one thing: the state array goes in and comes out as
THE SAME buffer (`input_output_aliases`), a row's block is read once and
written once, and a dead row costs no DMA at all. XLA's own update of such
an array round a scatter re-lays the array out (PERF.md, PR 28, S15).

Layout. The state is kept as [rows, N, H * P]: the state width on the
sublanes, heads x head width on the lanes. What varies along the lanes (a
and u, [H * P] a row) then goes in as plain row vectors, and what varies
along the sublanes (B and C, N values a row) as [N, 128] tiles broadcast
over the lanes by the caller: a column of N values is no shape Mosaic
tiles, and at 64 KB a row and vector it is 3 % of the state's bytes. The
natural [H, P, N] layout would need a and u as columns, 4 MB a row.

Grid (lane blocks, rows), rows innermost. A dead row's state block is the
block of the last live row before it (of the first live row, for dead rows
ahead of all live ones): the pipeline sees an unchanged block index and
moves nothing, and the body is skipped. Arithmetic is f32 whatever the
state's dtype; the state is rounded once, when it is stored.

Inference only (no VJP).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode, kernels_available, named_pallas_call

__all__ = ["ssm_decode", "ssm_decode_reference", "lane_block"]

LANES = 128         # width of the B and C tiles the caller broadcasts
_CHUNK = 512        # lanes the body handles at a time: f32 temporaries of
#                     [N, 512] stay in a few dozen vregs' worth of VMEM
_VMEM_BUDGET = 10 * 1024 * 1024
_LIVE, _DEAD, _LEADING_DEAD = 1, 0, 2


def lane_block(n_state, lanes, itemsize):
    """Lanes of one row's state a grid step takes: the largest of the whole
    row, a half, a quarter ... (multiples of 128) whose blocks (state in and
    out, double-buffered) fit the VMEM budget. From shapes alone."""
    cb = lanes
    while (cb % 2 == 0 and (cb // 2) % LANES == 0
           and 4 * n_state * cb * itemsize > _VMEM_BUDGET):
        cb //= 2
    return cb


def _kernel(src_ref, live_ref, s_ref, a_ref, u_ref, b_ref, c_ref,
            s_out, y_out, *, cb):
    r = pl.program_id(1)
    live = live_ref[r]

    @pl.when(live == _LIVE)
    def _():
        reps = min(_CHUNK, cb) // LANES
        bcol = jnp.concatenate([b_ref[0].astype(jnp.float32)] * reps,
                               axis=1)                     # [N, chunk]
        ccol = jnp.concatenate([c_ref[0].astype(jnp.float32)] * reps, axis=1)
        for lo in range(0, cb, _CHUNK):
            w = min(_CHUNK, cb - lo)
            sl = pl.ds(lo, w)
            s = s_ref[0, :, sl].astype(jnp.float32)     # [N, w]
            a = a_ref[0, :, sl].astype(jnp.float32)     # [1, w]
            u = u_ref[0, :, sl].astype(jnp.float32)
            s = s * a + bcol[:, :w] * u
            s_out[0, :, sl] = s.astype(s_out.dtype)
            y_out[0, :, sl] = jnp.sum(s * ccol[:, :w], axis=0,
                                      keepdims=True).astype(y_out.dtype)

    @pl.when(live != _LIVE)
    def _():
        y_out[...] = jnp.zeros_like(y_out)

    @pl.when(live == _LEADING_DEAD)
    def _():
        # the block is the first live row's (or row 0's when no row is
        # live), fetched and not yet updated: pass it through, so that what
        # the pipeline writes back is what it read
        s_out[...] = s_ref[...]


def _fetch_rows(live):
    """(src, code) int32 [rows]: the row whose state block each grid row
    names, and _LIVE / _DEAD / _LEADING_DEAD."""
    rows = live.shape[0]
    idx = jnp.arange(rows, dtype=jnp.int32)
    last_live = jax.lax.cummax(jnp.where(live, idx, -1), axis=0)
    first_live = jnp.argmax(live).astype(jnp.int32)   # 0 when none is live
    src = jnp.where(last_live >= 0, last_live, first_live)
    code = jnp.where(live, _LIVE,
                     jnp.where(last_live >= 0, _DEAD, _LEADING_DEAD))
    return src.astype(jnp.int32), code.astype(jnp.int32)


def _call(state, a, u, bcol, ccol, live, cb):
    rows, n_state, lanes = state.shape
    src, code = _fetch_rows(live)

    def state_spec():
        return pl.BlockSpec((1, n_state, cb),
                            lambda j, r, src, code: (src[r], 0, j))

    def row_spec():
        return pl.BlockSpec((1, 1, cb), lambda j, r, src, code: (r, 0, j))

    def col_spec():
        return pl.BlockSpec((1, n_state, bcol.shape[2]),
                            lambda j, r, src, code: (r, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lanes // cb, rows),
        in_specs=[state_spec(), row_spec(), row_spec(), col_spec(),
                  col_spec()],
        out_specs=[state_spec(), row_spec()],
    )
    new_state, y = named_pallas_call(
        "ssm_decode", functools.partial(_kernel, cb=cb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((rows, 1, lanes), jnp.float32)],
        # operand 2 (after the two prefetched scalars) is the state
        input_output_aliases={2: 0},
        interpret=interpret_mode(),
    )(src, code, state, a[:, None, :], u[:, None, :], bcol, ccol)
    return new_state, y[:, 0]


def _tuned_lane_block(state):
    """The lane block by way of the tuner, as the paged-decode kernel takes
    its pages a step: it follows from shapes, so it is the only candidate,
    nothing is swept, and `chosen_tiles()["ssm_decode"]` counts the
    consults."""
    from .autotune import pick_block_sizes

    rows, n_state, lanes = state.shape
    tile = (n_state, lane_block(n_state, lanes, state.dtype.itemsize))
    tile = pick_block_sizes(
        "ssm_decode", rows, lanes, tile, lambda bq, bk: None,
        allow_measure=False,
        signature=(rows, n_state, lanes, str(state.dtype)),
        candidates=[tile])
    return tile[1]


def ssm_decode_reference(state, a, u, b, c, live):
    """The same step in plain jax.numpy: what the kernel is tested against,
    and what runs where no kernel can (a bare CPU backend)."""
    s = state.astype(jnp.float32)
    new = (s * a[:, None, :].astype(jnp.float32)
           + b.astype(jnp.float32)[:, :, None]
           * u[:, None, :].astype(jnp.float32))
    y = jnp.einsum("rnl,rn->rl", new, c.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    keep = live[:, None, None]
    return (jnp.where(keep, new.astype(state.dtype), state),
            jnp.where(live[:, None], y, 0.0))


def ssm_decode(state, a, u, b, c, live):
    """One recurrence step for every live row.

    state: [rows, N, L] (L = heads x head width, a multiple of 128 on the
    chip), any float dtype, updated in place when the caller donates it;
    a, u: [rows, L] f32, the decay exp(dt A) and the input dt x of each
    lane; b, c: [rows, N]; live: [rows] bool. Returns (state, y [rows, L]
    f32); a dead row's state is untouched and its y is 0."""
    if not kernels_available():
        return ssm_decode_reference(state, a, u, b, c, live)
    rows, n_state, lanes = state.shape
    if lanes % LANES:
        raise ValueError(f"ssm_decode needs heads x head width ({lanes}) to "
                         f"be a multiple of {LANES}")
    cb = _tuned_lane_block(state)
    bcol = jnp.broadcast_to(b.astype(jnp.float32)[:, :, None],
                            (rows, n_state, LANES))
    ccol = jnp.broadcast_to(c.astype(jnp.float32)[:, :, None],
                            (rows, n_state, LANES))
    return _call(state, a.astype(jnp.float32), u.astype(jnp.float32),
                 bcol, ccol, live, cb)
