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


def _hand_work_list(tables, lengths, ps, n, window=None):
    """The live steps by plain loops: [(row, step, [page or None] * n)] in
    the grid's order, a slot's page None where the slot is dead."""
    B, P = tables.shape
    out = []
    for b in range(B):
        for i in range(-(-P // n)):
            slots = []
            for p in range(i * n, (i + 1) * n):
                page = int(tables[b, p]) if p < P else -1
                live = page >= 0 and p * ps < lengths[b] and (
                    window is None or (p + 1) * ps > lengths[b] - window)
                slots.append(page if live else None)
            if any(x is not None for x in slots):
                out.append((b, i, slots))
    return out


def _work(tables, lengths, ps, n, window=None):
    work = da.work_list(jnp.asarray(np.asarray(tables, np.int32)),
                        jnp.asarray(np.asarray(lengths, np.int32)), ps, n,
                        window)
    return jax.tree.map(np.asarray, work)


WORK_CASES = {
    # a free row between two live ones; a -1 hole inside a live step; the
    # padding behind P (6 slots, 4 a step)
    "free-row-and-hole": dict(
        tables=[[3, 4, 5, -1, -1, -1], [-1] * 6, [6, -1, 7, -1, -1, -1]],
        lengths=[20, 1, 24], ps=8, n=4,
        steps=[(0, 0), (2, 0)], first=[1, 1], last=[1, 1],
        visited=[True, False, True]),
    # a hole as wide as a step: the row's steps 0 and 2 are live, 1 is not;
    # the next row's dead slots name what the slot held at the last LIVE step
    "hole-of-a-whole-step": dict(
        tables=[[1, 2, -1, -1, 3, 4], [5, -1, -1, -1, -1, 6]],
        lengths=[24, 24], ps=4, n=2,
        steps=[(0, 0), (0, 2), (1, 0), (1, 2)], first=[1, 0, 1, 0],
        last=[0, 1, 0, 1], visited=[True, True]),
    "a-row-of-one-page": dict(
        tables=[[-1] * 4, [7, -1, -1, -1], [-1] * 4], lengths=[1, 1, 1],
        ps=8, n=2, steps=[(1, 0)], first=[1], last=[1],
        visited=[False, True, False]),
    # every step of every row is live: the list is the old grid
    "rows-that-fill-the-table": dict(
        tables=[[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]],
        lengths=[48, 41], ps=8, n=4,
        steps=[(0, 0), (0, 1), (1, 0), (1, 1)], first=[1, 0, 1, 0],
        last=[0, 1, 0, 1], visited=[True, True]),
    # window 4 over pages of 4: row 0 (16 tokens) sees keys 12-15 only, so
    # its step 0 has passed though the table still names the pages; row 1
    # (6 tokens) sees keys 2-5 in pages 0 and 1; row 2 (14 tokens, window
    # start inside page 2) keeps pages 2 and 3
    "a-window-that-has-passed-pages": dict(
        tables=[[1, 2, 3, 4], [5, 6, -1, -1], [7, 8, 9, 10]],
        lengths=[16, 6, 14], ps=4, n=2, window=4,
        steps=[(0, 1), (1, 0), (2, 1)], first=[1, 1, 1], last=[1, 1, 1],
        visited=[True, True, True]),
    "no-live-row": dict(
        tables=[[-1] * 4, [-1] * 4], lengths=[1, 0], ps=8, n=2, steps=[],
        first=[], last=[], visited=[False, False]),
}


@pytest.mark.parametrize("case", sorted(WORK_CASES))
def test_work_list_matches_a_hand_count(case):
    """`work_list`: the live steps in the grid's order at the front of the
    arrays, with their rows, their steps, the first and last flags and the
    count, against the counts written out above and against plain loops."""
    c = dict(WORK_CASES[case])
    tables = np.asarray(c.pop("tables"), np.int32)
    lengths, ps, n = c.pop("lengths"), c.pop("ps"), c.pop("n")
    window = c.pop("window", None)
    work = _work(tables, lengths, ps, n, window)
    total = tables.shape[0] * -(-tables.shape[1] // n)
    assert work.fetch.shape == (total, n)
    assert all(getattr(work, f).shape == (total,)
               for f in ("row", "step", "first", "last"))
    count = int(work.count)
    assert count == len(c["steps"])
    assert list(zip(work.row[:count].tolist(),
                    work.step[:count].tolist())) == c["steps"]
    assert work.first[:count].tolist() == c["first"]
    assert work.last[:count].tolist() == c["last"]
    # behind the count no step starts or ends a row
    assert not work.first[count:].any() and not work.last[count:].any()
    assert work.visited.tolist() == c["visited"]
    hand = _hand_work_list(tables, lengths, ps, n, window)
    assert [(b, i) for b, i, _ in hand] == c["steps"]
    held = [0] * n      # what each slot's block index holds, step by step
    for w, (_, _, slots) in enumerate(hand):
        for j, page in enumerate(slots):
            if page is not None:
                assert work.fetch[w, j] == page
                held[j] = page
            else:
                # a dead slot of a live step names the page the slot held
                # at the previous LIVE step: nothing is fetched for it
                assert work.fetch[w, j] == ~held[j]
    # the host's count, for tables without holes
    if case not in ("free-row-and-hole", "hole-of-a-whole-step"):
        seen = [L if (t >= 0).any() else 0
                for L, t in zip(lengths, tables)]
        assert da.live_step_count(seen, ps, n, window) == count


def test_dead_slots_refetch_nothing():
    """`work_list`'s fetch table: a live slot carries its physical page; a
    dead one (past the length, a -1 hole, the padding behind P) carries
    ~(the page the same slot held one grid step earlier), so its block index
    does not change and the pipeline issues no DMA for it. A free row has no
    step in the list at all."""
    tables = np.array([[3, 4, 5, -1, -1, -1],
                       [-1, -1, -1, -1, -1, -1],     # a free row
                       [6, -1, 7, -1, -1, -1]], np.int32)   # a hole
    lengths = np.array([20, 1, 24], np.int32)
    n, ps = 4, 8
    work = _work(tables, lengths, ps, n)
    assert work.fetch.shape == (6, 4) and work.count == 2
    fetch = work.fetch[:2]
    live = fetch >= 0
    want_live = np.zeros((2, 4), bool)
    want_live[0, :3] = True
    want_live[1, [0, 2]] = True
    np.testing.assert_array_equal(live, want_live)
    np.testing.assert_array_equal(fetch[live], [3, 4, 5, 6, 7])
    pages = np.where(live, fetch, ~fetch)    # [step, slot]
    for step in range(1, len(pages)):
        dead = ~live[step]
        np.testing.assert_array_equal(pages[step][dead],
                                      pages[step - 1][dead])


def _ref_window(q, kc, vc, tables, lengths, window):
    """`_ref_paged` for a table that starts at the row's first cached page:
    the query at `length - 1` sees the last `window` keys."""
    B, H, D = q.shape
    Hkv = kc.shape[1]
    out = np.zeros((B, H, D), np.float32)
    for b in range(B):
        L = int(lengths[b])
        pages = [p for p in tables[b] if p >= 0]
        if not pages:
            continue
        keys = np.concatenate([np.asarray(kc)[p] for p in pages], 1)
        vals = np.concatenate([np.asarray(vc)[p] for p in pages], 1)
        lo = max(0, L - window)
        for h in range(H):
            out[b, h] = _ref_attend(np.asarray(q)[b, h],
                                    keys[h // (H // Hkv), lo:L],
                                    vals[h // (H // Hkv), lo:L], L - lo,
                                    D ** -0.5)
    return out


def _ref_latent(q, pages, tables, lengths, latent_dim, scale):
    B, H, _ = q.shape
    out = np.zeros((B, H, latent_dim), np.float32)
    for b in range(B):
        held = [p for p in tables[b] if p >= 0]
        if not held:
            continue
        kv = np.concatenate([np.asarray(pages)[p] for p in held])[
            :int(lengths[b])]
        s = np.asarray(q)[b] @ kv.T * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b] = (p / p.sum(-1, keepdims=True)) @ kv[:, :latent_dim]
    return out


# rows x [tokens cached]: most steps dead (short rows and free rows in a
# wide table), none dead (every row fills the table), no live row at all
SCENES = {"mostly-dead": [9, 0, 0, 200, 0, 17, 0, 0],
          "none-dead": [384, 384, 384],
          "no-live-row": [0, 0, 0, 0]}


def _run_kernel(kernel, lengths):
    """(what the kernel returns, its reference, the list it walked, the
    steps its table holds) over a table 48 pages of 8 wide: three steps a
    row at 16 pages a step."""
    ps, P, n_pages = 8, 48, 150
    rng = np.random.default_rng(len(lengths))
    B = len(lengths)
    tables = np.full((B, P), -1, np.int32)
    free = iter(rng.permutation(np.arange(1, n_pages)))
    for b, L in enumerate(lengths):
        for slot in range(-(-L // ps)):
            tables[b, slot] = next(free)
    lens = np.asarray([max(L, 1) for L in lengths], np.int32)  # free: 0 + 1
    if kernel == "decode_latent":
        pages = rng.normal(size=(n_pages, ps, 128)).astype(np.float32)
        q = rng.normal(size=(B, 4, 128)).astype(np.float32)
        got = da.latent_decode_attention(
            jnp.asarray(q), jnp.asarray(pages), jnp.asarray(tables),
            jnp.asarray(lens), 96, 0.3)
        ref = _ref_latent(q, pages, tables, lens, 96, 0.3)
        n, window = da.latent_pages_per_step(ps, 128, P, 4), None
    else:
        window = 24 if kernel == "decode_window" else None
        kc = rng.normal(size=(n_pages, 2, ps, 16)).astype(np.float32)
        vc = rng.normal(size=(n_pages, 2, ps, 16)).astype(np.float32)
        q = rng.normal(size=(B, 4, 16)).astype(np.float32)
        got = paged_decode_attention(
            jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
            jnp.asarray(tables), jnp.asarray(lens), window=window)
        ref = (_ref_window(q, kc, vc, tables, lens, window) if window
               else _ref_paged(q, kc, vc, tables, lens))
        n = da.pages_per_step(2, ps, 16, P, 4)
    work = _work(tables, lens, ps, n, window)
    return np.asarray(got), ref, work, B * -(-P // n)


@pytest.mark.parametrize("scene", sorted(SCENES))
@pytest.mark.parametrize("kernel", ["decode_paged", "decode_window",
                                    "decode_latent"])
def test_kernels_walk_the_live_steps_alone(kernel, scene):
    """The three paged kernels against their references where most steps of
    the table are dead, where none is and where no row is live (zeros): the
    grid is as long as the work."""
    got, ref, work, walked_before = _run_kernel(kernel, SCENES[scene])
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=2e-5)
    if scene == "mostly-dead":
        # rows of 2, 25 and 3 pages: 1 + 2 + 1 steps of 24; under the
        # window row 3 keeps its second step alone
        assert work.count == (3 if kernel == "decode_window" else 4)
        assert walked_before == 24
        assert not got[[1, 2, 4, 6, 7]].any()       # the free rows
    elif scene == "none-dead" and kernel != "decode_window":
        assert work.count == walked_before
    elif scene == "no-live-row":
        assert work.count == 0 and not got.any()


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
