"""Direct correctness coverage for ops/pallas/decode_attention.py — the
paged/dense decode kernels vs a numpy oracle under interpret mode (the
serving engines exercise them end-to-end; these pin the kernel contract
itself: GQA head groups, partially-filled final pages, -1 unused
block-table entries, and the `l == 0` zero-length-row guard in _finish)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import autotune
from paddle_tpu.ops.pallas import decode_attention as da
from paddle_tpu.ops.pallas.decode_attention import (
    dense_decode_attention,
    paged_decode_attention,
    paged_kv_write,
)


@pytest.fixture(autouse=True)
def _interpret_mode(pallas_interpret_unless_hw):
    pass


def _ref_attend(q_bh, keys, vals, L, scale):
    """One (row, head): softmax(q·K[:L]) @ V[:L] in f64-ish numpy."""
    if L == 0:
        return np.zeros_like(q_bh)
    s = keys[:L] @ q_bh * scale
    p = np.exp(s - s.max())
    p /= p.sum()
    return p @ vals[:L]


def _ref_paged(q, kc, vc, tables, lengths):
    B, H, D = q.shape
    _, Hkv, ps, _ = kc.shape
    P = tables.shape[1]
    S = P * ps
    g = H // Hkv
    kc, vc = np.asarray(kc, np.float32), np.asarray(vc, np.float32)
    out = np.zeros((B, H, D), np.float32)
    for b in range(B):
        keys = np.zeros((S, Hkv, D), np.float32)
        vals = np.zeros_like(keys)
        for j in range(P):
            t = int(tables[b, j])
            if t >= 0:
                keys[j * ps:(j + 1) * ps] = kc[t].transpose(1, 0, 2)
                vals[j * ps:(j + 1) * ps] = vc[t].transpose(1, 0, 2)
        for h in range(H):
            out[b, h] = _ref_attend(np.asarray(q)[b, h], keys[:, h // g],
                                    vals[:, h // g], int(lengths[b]),
                                    D ** -0.5)
    return out


def _make_case(B, H, Hkv, D, ps, P, lengths, seed=0, n_pages=None,
               dead=(), shared=0, cache_dtype=jnp.float32):
    """Random paged cache + per-row block tables covering `lengths` tokens;
    entries past each row's last page are -1. Rows in `dead` keep their
    length and get a table of -1 alone (what the engine sends for a free
    row: length 0 + 1). With `shared`, every later row's first `shared`
    table entries are row 0's (a prefix hit: two rows, one physical page)."""
    rng = np.random.default_rng(seed)
    need = [-(-L // ps) if L else 0 for L in lengths]
    if n_pages is None:
        n_pages = 1 + sum(need)  # page 0 = null
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((n_pages, Hkv, ps, D)), cache_dtype)
    vc = jnp.asarray(rng.standard_normal((n_pages, Hkv, ps, D)), cache_dtype)
    tables = np.full((B, P), -1, np.int32)
    nxt = 1
    for b, m in enumerate(need):
        if b in dead:
            continue
        for j in range(m):
            tables[b, j] = nxt
            nxt += 1
        if b:
            tables[b, :shared] = tables[0, :shared]
    return q, kc, vc, jnp.asarray(tables), jnp.asarray(
        np.asarray(lengths, np.int32))


def _case(*shape, **extra):
    B, H, Hkv, D, ps, P, lengths = shape
    return pytest.param(*shape, extra, id="-".join(
        [f"B{B}H{H}kv{Hkv}D{D}ps{ps}P{P}"] + sorted(extra)))


CASES = [
    # B, H, Hkv, D, ps, P, lengths
    _case(2, 4, 4, 32, 16, 4, [64, 32]),          # MHA, full pages
    _case(2, 4, 2, 32, 16, 4, [48, 16]),          # GQA head groups
    _case(3, 4, 1, 16, 8, 8, [13, 27, 5]),        # MQA, partial final pages
    _case(2, 2, 2, 16, 16, 2, [17, 31]),          # partial fill + -1 tail entries
    # the serving cell's head shape, fewer rows: one page exactly, one token,
    # a ragged middle
    _case(3, 16, 16, 128, 32, 8, [32, 1, 150]),
    _case(2, 4, 2, 32, 8, 6, [40, 17]),           # P no multiple of N (4)
    # N = 16 of P = 20: 18 and 6 pages, neither a multiple of N
    _case(2, 2, 2, 16, 8, 20, [141, 41]),
    # a free row as the engine sends it: length 0 + 1, table -1
    _case(3, 4, 2, 32, 8, 4, [30, 1, 9], dead=(1,)),
    # a prefix hit: rows 1 and 2 read row 0's first two physical pages
    _case(3, 4, 2, 32, 8, 8, [37, 24, 50], shared=2),
    _case(2, 4, 2, 32, 16, 4, [48, 19], cache_dtype=jnp.bfloat16),
    _case(2, 8, 2, 32, 8, 8, [50, 23]),           # GQA g = 4, N = 8
]


@pytest.mark.parametrize("B,H,Hkv,D,ps,P,lengths,extra", CASES)
def test_paged_decode_matches_reference(B, H, Hkv, D, ps, P, lengths, extra):
    q, kc, vc, tables, lens = _make_case(B, H, Hkv, D, ps, P, lengths,
                                         **extra)
    out = paged_decode_attention(q, kc, vc, tables, lens)
    ref = _ref_paged(q, kc, vc, np.asarray(tables), np.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=2e-5)


def test_bf16_operands_lose_nothing_to_the_f32_path():
    """q and the pool both bf16 (the serving cell): the dots take bf16
    operands, q.K exactly and p.V with p split into three bf16 terms, so
    the result is the f32-operand path's, rounded once to bf16."""
    q, kc, vc, tables, lens = _make_case(
        2, 8, 2, 32, 8, 8, [50, 23], cache_dtype=jnp.bfloat16)
    q = q.astype(jnp.bfloat16)
    out = paged_decode_attention(q, kc, vc, tables, lens)
    assert out.dtype == jnp.bfloat16
    f32_path = paged_decode_attention(q.astype(jnp.float32), kc, vc, tables,
                                      lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(f32_path), rtol=2 ** -8, atol=1e-6)


def test_cell_shapes_take_several_pages_a_step():
    """The tile of the serving cell's shapes (B 64, 16 heads of 128, pages
    of 32, a table 64 wide, bf16 pool) is N > 1 pages, and chosen_tiles()
    records it with its consults."""
    q4 = jax.ShapeDtypeStruct((64, 16, 1, 128), jnp.bfloat16)
    kc = jax.ShapeDtypeStruct((1746, 16, 32, 128), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((64, 64), jnp.int32)
    before = autotune.chosen_tiles().get("decode_paged", {}).get("consults", 0)
    n = da._consult_tuner_paged(q4, kc, tables)
    assert n > 1 and n == da.pages_per_step(16, 32, 128, 64, 2)
    rec = autotune.chosen_tiles()["decode_paged"]
    assert (rec["bq"], rec["bk"]) == (n * 32, 128)
    assert rec["consults"] == before + 1
    # never wider than the table, and a page too large for the budget goes alone
    assert da.pages_per_step(16, 32, 128, 3, 2) == 2
    assert da.pages_per_step(64, 256, 256, 64, 4) == 1


def test_dead_slots_refetch_nothing():
    """_fetch_table: a live slot carries its physical page; a dead one (past
    the length, a -1 hole, a free row, the padding behind P) carries ~(the
    page the same slot held one grid step earlier), so its block index does
    not change and the pipeline issues no DMA for it."""
    tables = np.array([[3, 4, 5, -1, -1, -1],
                       [-1, -1, -1, -1, -1, -1],     # a free row
                       [6, -1, 7, -1, -1, -1]], np.int32)   # a hole
    lengths = np.array([20, 1, 24], np.int32)
    n, ps = 4, 8
    fetch = np.asarray(da._fetch_table(jnp.asarray(tables),
                                       jnp.asarray(lengths), ps, n))
    assert fetch.shape == (3, 8)
    live = fetch >= 0
    want_live = np.zeros((3, 8), bool)
    want_live[0, :3] = True
    want_live[2, [0, 2]] = True
    np.testing.assert_array_equal(live, want_live)
    np.testing.assert_array_equal(fetch[live], [3, 4, 5, 6, 7])
    pages = np.where(live, fetch, ~fetch).reshape(-1, n)    # [step, slot]
    for step in range(1, len(pages)):
        dead = ~live.reshape(-1, n)[step]
        np.testing.assert_array_equal(pages[step][dead],
                                      pages[step - 1][dead])


def test_zero_length_row_outputs_zeros():
    """The `l == 0` guard in _paged_kernel._finish: a row with no valid
    tokens (every page skipped) must return zeros, not NaN from 0/0."""
    q, kc, vc, tables, lens = _make_case(3, 4, 2, 16, 8, 4, [16, 0, 9])
    out = np.asarray(paged_decode_attention(q, kc, vc, tables, lens))
    assert np.all(np.isfinite(out))
    np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))
    ref = _ref_paged(q, kc, vc, np.asarray(tables), np.asarray(lens))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-5)


def test_unused_table_entries_are_skipped():
    """-1 entries (and whatever stale page ids would sit behind them) must
    not contribute: truncating a row's table to -1 changes nothing vs a
    shorter reference, even though the physical pages still hold data."""
    q, kc, vc, tables, lens = _make_case(1, 2, 2, 16, 8, 4, [16])
    tables = np.asarray(tables).copy()
    # leave garbage pages allocated beyond the valid range; table says -1
    out = paged_decode_attention(q, kc, vc, jnp.asarray(tables), lens)
    ref = _ref_paged(q, kc, vc, tables, np.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=2e-5)


def _dense_case():
    rng = np.random.default_rng(3)
    B, H, Hkv, D, S = 2, 4, 2, 32, 64
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    lens = np.asarray([37, 64], np.int32)
    g = H // Hkv
    ref = np.zeros((B, H, D), np.float32)
    for b in range(B):
        for h in range(H):
            ref[b, h] = _ref_attend(
                np.asarray(q)[b, h],
                np.asarray(kc)[b, h // g], np.asarray(vc)[b, h // g],
                int(lens[b]), D ** -0.5)
    return q, kc, vc, jnp.asarray(lens), ref


def test_dense_decode_matches_reference():
    q, kc, vc, lens, ref = _dense_case()
    out = dense_decode_attention(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("ps", [16, 64])
def test_dense_decode_at_a_given_tile(ps):
    """The dense kernel at a sequence tile of its own (several blocks a row,
    and the whole cache in one): it shares nothing with the paged grid."""
    q, kc, vc, lens, ref = _dense_case()
    q4, _ = da._split_heads(q, kc.shape[1])
    out = da._run_dense(q4, kc, vc, lens, q.shape[-1] ** -0.5, ps)
    np.testing.assert_allclose(np.asarray(out).reshape(ref.shape), ref,
                               rtol=1e-4, atol=2e-5)


class TestPagedKvWrite:
    def test_write_lands_at_next_slot(self):
        B, Hkv, D, ps, P, n_pages = 2, 2, 8, 4, 4, 6
        kc = jnp.zeros((n_pages, Hkv, ps, D), jnp.float32)
        tables = np.full((B, P), -1, np.int32)
        tables[0, :2] = [1, 2]
        tables[1, :1] = [3]
        lengths = np.asarray([5, 2], np.int32)  # slots (page 2, 1), (page 3, 2)
        new = jnp.asarray(
            np.arange(B * Hkv * D, dtype=np.float32).reshape(B, Hkv, D) + 1.0)
        out = np.array(paged_kv_write(kc, new, jnp.asarray(tables),
                                      jnp.asarray(lengths)))
        np.testing.assert_array_equal(out[2, :, 1], np.asarray(new)[0])
        np.testing.assert_array_equal(out[3, :, 2], np.asarray(new)[1])
        # nothing else touched
        out[2, :, 1] = 0
        out[3, :, 2] = 0
        assert not out.any()

    def test_parked_rows_hit_null_page(self):
        """Rows whose table entry is -1 (inactive program rows) write page 0
        — the reserved null page — and corrupt nothing allocatable."""
        B, Hkv, D, ps, P, n_pages = 2, 1, 4, 4, 2, 4
        kc = jnp.zeros((n_pages, Hkv, ps, D), jnp.float32)
        tables = np.full((B, P), -1, np.int32)
        tables[0, 0] = 1
        lengths = np.asarray([1, 0], np.int32)
        new = jnp.ones((B, Hkv, D), jnp.float32)
        out = np.asarray(paged_kv_write(kc, new, jnp.asarray(tables),
                                        jnp.asarray(lengths)))
        assert out[1, :, 1].any()          # live row wrote its slot
        assert out[0, :, 0].any()          # parked row landed on null page
        assert not out[2:].any()           # no allocatable page touched

    # (lengths, first table entries) per row; a row with no entries is parked
    SCENES = {
        # slot 0 and slot ps - 1 of first and later pages
        "page-boundaries": [(0, [5]), (3, [6]), (4, [1, 7]), (11, [2, 3, 8])],
        # the engine's parked rows: table -1, length 0
        "all-parked": [(0, []), (0, []), (0, [])],
        # parked rows (one at a slot of its own) beside live ones
        "parked-beside-live": [(0, []), (6, [4, 2]), (0, []), (9, []),
                               (3, [9])],
    }

    @pytest.mark.parametrize("scene", sorted(SCENES))
    @pytest.mark.parametrize("hkv", [1, 8, 16])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                             ids=["bf16", "f32"])
    def test_equals_a_numpy_loop_over_the_whole_pool(self, dtype, hkv, scene):
        """The docstring's semantics as a plain loop: every live row's slot
        written, every other element of the pool as it was, bit for bit.
        Parked rows all go to page 0, where ONE of them leaves its write."""
        rows = self.SCENES[scene]
        B, D, ps, P, n_pages = len(rows), 8, 4, 4, 10
        rng = np.random.default_rng(hkv)
        tables = np.full((B, P), -1, np.int32)
        for b, (_, pages) in enumerate(rows):
            tables[b, :len(pages)] = pages
        lengths = np.asarray([n for n, _ in rows], np.int32)
        pool = jnp.asarray(rng.standard_normal((n_pages, hkv, ps, D)), dtype)
        new = jnp.asarray(rng.standard_normal((B, hkv, D)), jnp.float32)
        before = np.array(pool)
        cast = np.asarray(new.astype(dtype))

        write = jax.jit(paged_kv_write, donate_argnums=(0,))
        out = np.asarray(write(pool, new, jnp.asarray(tables),
                               jnp.asarray(lengths)))
        assert out.dtype == before.dtype

        want = before.copy()
        null_pages = []   # what page 0 may hold: one parked row's write
        for b in range(B):
            page, slot = tables[b, lengths[b] // ps], lengths[b] % ps
            if page < 0:
                null_pages.append(before[0].copy())
                null_pages[-1][:, slot] = cast[b]
            else:
                want[page, :, slot] = cast[b]
        bits = lambda a: a.view(np.uint16 if a.itemsize == 2 else np.uint32)
        np.testing.assert_array_equal(bits(out[1:]), bits(want[1:]))
        assert any((bits(out[0]) == bits(pg)).all()
                   for pg in null_pages or [before[0]])
