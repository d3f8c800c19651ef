"""The window-and-full-attention decoder (`models/afmoe.py`: sliding-window
layers beside full ones, gated QK-normed heads, sigmoid-routed experts of
which a chip holds a share) through the paged engine, against the plain
float32 reference (`benchmark/reference/afmoe.py`) at a tiny size on the CPU
(window 32, pages of 8). Logits are compared, not tokens: with random weights
the largest logit changes on rounding.

Tolerances. Model and reference are both float32 here (conftest sets
`highest` matmuls), so they differ by the order of summation alone. A row of
logits has a standard deviation of about 0.2 at this size: 2e-5 absolute is
twenty times what was seen (1e-6) and a ten-thousandth of a spread, where a
wrong page, a window off by one, a missing rotation or a wrong expert moves
a row by a good part of one.
"""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import afmoe as reference
from paddle_tpu.incubate.distributed.models.moe import HeldExpertsMoE
from paddle_tpu.incubate.distributed.models.moe import held_moe
from paddle_tpu.inference.paged import (BlockPool, PagedKV,
                                        PagedServingEngine, WindowKV)
from paddle_tpu.inference.paged.block_pool import page_layout
from paddle_tpu.models import GPTForCausalLM, gpt3_tiny
from paddle_tpu.models.afmoe import AfmoeForCausalLM, afmoe_tiny
from paddle_tpu.models.granite_hybrid import (GraniteHybridForCausalLM,
                                              granite_hybrid_tiny)
from paddle_tpu.observability.metrics import default_registry
from paddle_tpu.ops.pallas import autotune
from paddle_tpu.ops.pallas.decode_attention import paged_decode_attention
from paddle_tpu.ops.pallas.flash_attention import flash_window_fwd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-5
W, PS = 32, 8       # afmoe_tiny's window; the engines' page size here


@pytest.fixture(autouse=True)
def _interpret_mode(pallas_interpret_unless_hw):
    pass


def _as_config_dict(cfg):
    """The model's config under the configuration file's keys, as the
    reference reads them."""
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    out["num_hidden_layers"] = cfg.num_layers
    out["layer_types"] = list(cfg.layer_types)
    return out


def _build(seed=7, **kw):
    paddle.seed(seed)
    m = AfmoeForCausalLM(afmoe_tiny(**kw))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _build()


def _params(m):
    out = {k: p._value for k, p in m.named_parameters()}
    out.update({k: b._value for k, b in m.named_buffers()})
    return out


def _reference_logits(m, ids, **kw):
    return np.asarray(reference.logits(
        _params(m), ids, _as_config_dict(m.config), m.config.held_experts,
        **kw))


def _engine(m, **kw):
    kw = {"max_batch_size": 4, "max_seq_len": 160, "page_size": PS, **kw}
    return PagedServingEngine(m, **kw)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


# -- 1. the whole forward --------------------------------------------------- #

def test_full_forward_matches_the_reference(model):
    """Two periods of three sliding layers and a full one, two leading dense
    layers, all experts held, contexts to three windows, a batch of two."""
    ids = np.random.default_rng(0).integers(1, 256, (2, 100)).astype(np.int32)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids))._value)
    for b in range(2):
        assert np.abs(got[b] - _reference_logits(model, ids[b])).max() < ATOL


def test_a_cast_part_leaves_no_float32_form_behind():
    """`_cast` returns with the replaced arrays deleted, so that what is
    sized from the device's free memory next sees the bf16 model alone."""
    from paddle_tpu.models import afmoe

    layer = afmoe._linear(afmoe_tiny(), 8, 16)
    f32 = layer.weight._value
    assert afmoe._cast(layer, "bfloat16") is layer
    bf16 = layer.weight._value
    assert f32.is_deleted() and bf16.dtype == jnp.bfloat16
    afmoe._cast(layer, "bfloat16")               # nothing to replace
    assert layer.weight._value is bf16 and not bf16.is_deleted()
    m = _build(dtype="bfloat16")
    assert {str(p.dtype) for p in m.parameters()} <= {
        "paddle.bfloat16", "bfloat16"}


def test_a_held_share_matches_the_reference_given_the_same_share():
    m = _build(seed=9, held_experts=(4, 4))
    ids = _prompt(50, 1)
    with paddle.no_grad():
        got = np.asarray(m(paddle.to_tensor(ids[None]))._value[0])
    assert np.abs(got - _reference_logits(m, ids)).max() < ATOL


# -- 2, 3. prefill, then decode through the window boundary ------------------ #

def _serve_logit_for_logit(m, prompts, new_tokens):
    """Serve `prompts` together and hold EVERY decode tick's logits of every
    live row against the reference's one forward over what the row has seen.
    Returns the engine and the finished requests."""
    eng = _engine(m)
    for p in prompts:
        eng.add_request(p, max_new_tokens=new_tokens)
    worst = 0.0
    while eng.has_work():
        seen = {i: (r, r.output_ids.copy()) for i, r in enumerate(eng.active)
                if r is not None}
        eng.step()
        logits = np.asarray(eng.last_logits)
        for i, (req, ids) in seen.items():
            if len(req.generated) == len(ids) - len(req.prompt):
                continue   # admitted this tick: no decode row of its own yet
            # causal: zero padding behind the row is unseen, and one length
            # is one compile of the reference
            padded = np.zeros(160, np.int32)
            padded[:len(ids)] = ids
            want = _reference_logits(m, padded, rows=[len(ids) - 1])[0]
            worst = max(worst, np.abs(logits[i] - want).max())
    assert worst < ATOL, worst
    return eng


def test_prefill_then_decode_through_the_window_boundary(model):
    """Prompts inside their buckets on both sides of the window (20 and 28:
    the rows START inside it and leave it while decoding; 50 and 90: past it
    at admission, the window groups take the prompt's last window only), 45
    tokens each, so contexts reach four windows; every tick logit for
    logit."""
    eng = _serve_logit_for_logit(
        model, [_prompt(n, n) for n in (20, 50, 90, 28)], 45)
    assert eng.pool.pages_free == eng.pool.pages_total
    assert eng._window_released > 0


def test_first_token_comes_from_the_prompts_last_position(model):
    """The prefill's head runs on ONE row: the last real position inside the
    padded bucket."""
    eng = _engine(model)
    p = _prompt(37, 5)
    eng.add_request(p, max_new_tokens=1)
    (done,) = eng.run()
    want = _reference_logits(model, p, rows=[len(p) - 1])[0]
    assert done.generated == [int(want.argmax())]


# -- 4. pages are released exactly when the row passes them ------------------ #

def test_window_pages_are_released_as_the_row_passes_them(model):
    eng = _engine(model)
    eng.add_request(_prompt(20, 3), max_new_tokens=100)
    spec = next(g.spec for g in eng.groups if g.window)
    assert spec.table_width(PS) == W // PS + 1 == eng.group_tables[1].shape[1]
    assert eng.group_tables[0].shape[1] == eng.P == 20
    total = eng.pool.pages_total
    while eng.has_work():
        eng.step()
        if eng.active[0] is None:
            break
        # tokens cached once the tick in flight is done
        L = int(eng.lengths[0]) + 1
        # the tick's query sat at L - 1 and saw keys L - W .. L - 1: the
        # pages the row holds are exactly those from the one holding key
        # L - W (never one fewer) to the one it wrote into (never one more
        # kept: a page wholly before the window is gone)
        first = max(0, L - W) // PS
        held = (L - 1) // PS - first + 1
        assert eng.window_start[0] == first
        for group, table in zip(eng.groups, eng.group_tables):
            pages = (table[0] >= 0).sum()
            assert pages == (held if group.window else -(-L // PS))
            assert (table[0, :pages] >= 0).all()      # left-aligned
        assert held <= spec.table_width(PS)
        live = sum((t >= 0).sum() for t in eng.group_tables)
        assert eng.pool.pages_free == total - live
    # retirement returns everything, the released pages were not double freed
    assert eng.pool.pages_free == total
    assert not eng.pool.ref.any()
    m = default_registry().get("serving_window_pages_released_total")
    assert m is not None and m.value() >= eng._window_released > 0


def test_window_pages_are_never_shared_and_full_pages_are(model):
    """Two requests with one prompt: the full group's pages are shared by
    prefix key, the window groups' are each row's own."""
    eng = _engine(model)
    p = _prompt(40, 11)
    eng.add_request(p, max_new_tokens=3)
    eng.add_request(p, max_new_tokens=3)
    eng.step()
    full, *windows = eng.group_tables
    assert (full[0, :4] == full[1, :4]).all() and (full[0, :4] >= 0).all()
    for t in windows:
        assert not set(t[0][t[0] >= 0]) & set(t[1][t[1] >= 0])
    a, b = eng.run()
    assert a.generated == b.generated
    assert eng.pool.pages_free == eng.pool.pages_total


def test_admission_charges_both_kinds(model):
    """`pick` charges a prompt what the full group AND the window groups
    need; the watermark is a page per live row and group."""
    eng = _engine(model)
    # 90 tokens: 12 full pages, and of each window group the pages holding
    # positions 59 .. 89: 7 .. 11, five
    assert eng._prompt_pages(90) == 12 + 3 * 5
    assert eng._prompt_pages(20) == 3 + 3 * 3
    assert eng._held_pages(160) == 20 + 3 * 5
    assert eng.sched.groups == 4 and eng.sched._watermark(3) == 12
    small = _engine(model, num_pages=40)      # 39 pages
    small.add_request(_prompt(90, 1), max_new_tokens=4)   # 27 + watermark 4
    small.add_request(_prompt(20, 2), max_new_tokens=4)   # 12 more: no room
    small.step()
    assert small.live_count == 1 and small.sched.waiting_prefill == 1
    small.run()
    assert small.pool.pages_free == small.pool.pages_total


# -- 5. preemption past the window ------------------------------------------- #

def test_a_preempted_row_past_the_window_resumes_token_for_token(model):
    prompts = [_prompt(60, 21), _prompt(44, 22)]
    calm = _engine(model)
    for p in prompts:
        calm.add_request(p, max_new_tokens=50)
    want = {tuple(r.prompt): r.generated for r in calm.run()}
    # a pool that cannot hold both rows to their ends: the newer is spilled
    # past the window (its window groups' pages and start go along)
    eng = _engine(model, num_pages=52, watermark_pages=0)
    for p in prompts:
        eng.add_request(p, max_new_tokens=50)
    done = eng.run()
    assert sum(r.preemptions for r in done) >= 1
    for r in done:
        assert r.generated == want[tuple(r.prompt)]
    assert eng.pool.pages_free == eng.pool.pages_total


def test_spill_and_restore_move_pages_in_power_of_two_buckets():
    """`read_pages` / `restore_pages` / `write_prompt_pages` go through ONE
    gather and ONE scatter program that take their page ids as data, padded
    to powers of two: walking every count compiles a program a bucket."""
    pool = BlockPool(2, 2, 4, page_size=4, num_pages=40)
    for count in range(1, 20):
        pages = list(range(1, count + 1))
        host = pool.read_pages(pages)
        assert host[0][0].shape == (count, 2, 4, 4)
        pool.restore_pages(pages, host, list(range(count)))
    assert pool._gather._cache_size() == 6      # 1, 2, 4, 8, 16, 32
    assert pool._scatter._cache_size() == 6
    data = np.arange(3 * 2 * 4 * 4, dtype=np.float32).reshape(3, 2, 4, 4)
    pool.write_prompt_pages([5, 6, 7], [True, False, True], [data, data],
                            [data + 1, data + 1])
    got = pool.read_pages([5, 6, 7])
    assert np.array_equal(got[1][0][[0, 2]], data[[0, 2]])
    assert np.array_equal(got[0][1][[0, 2]], data[[0, 2]] + 1)
    assert not got[0][0][1].any()                # the masked page: untouched
    # copy-on-write is the two programs' one-page forms: nothing is lowered
    from jax._src import test_util as jtu

    with jtu.count_jit_and_pmap_lowerings() as lowered:
        pool.copy_page(5, 9)
        assert np.array_equal(pool.read_pages([9])[0][0][0], data[0])
    assert lowered() == 0


def test_page_groups_of_equal_depth_share_one_free_list(model):
    specs = model.cache_specs()
    groups, entry_of, group_of = page_layout(specs)
    full = PagedKV(2, 16)
    window = WindowKV(2, 16, 32)
    assert [(g.spec, g.layers) for g in groups] == [
        (full, (3, 7)), (window, (0, 1)), (window, (2, 4)), (window, (5, 6))]
    assert entry_of == [0, 1, 0, 0, 1, 0, 1, 1]
    assert group_of == [1, 1, 2, 0, 2, 3, 3, 0]
    pool = BlockPool(8, 2, 16, page_size=8, num_pages=10, specs=specs)
    assert pool.depth == 2 and len(pool.kv) == 2
    assert pool.bytes_per_page == BlockPool.page_nbytes(2, 2, 16, 8)
    # one kind of paged layer: a group, an entry a layer, as before
    plain, entries, _ = page_layout([full] * 3)
    assert len(plain) == 1 and entries == [0, 1, 2]
    # twice the heads at equal widths is two adjacent pages since PR 39
    # (tests/test_mimo_v2.py); another width is still refused
    assert [g.span for g in page_layout([full, PagedKV(4, 16)])[0]] == [1, 2]
    with pytest.raises(ValueError):
        page_layout([full, PagedKV(2, 32)])


# -- 6, 7, 8. the expert layer ----------------------------------------------- #

def _moe(held, seed=3, **kw):
    paddle.seed(seed)
    layer = HeldExpertsMoE(32, 16, 8, 4, held=held, gate="sigmoid",
                           route_scale=2.826, **kw)
    layer.expert_bias._value = jnp.asarray(
        np.random.default_rng(seed).normal(0, 0.05, 8), jnp.float32)
    return layer


def _layer_reference(x, params, first, top_k=4, scale=2.826):
    sizes = {"low": False, "top_k": top_k, "scale": scale, "first": first}
    shared = {"shared_experts.gate_up_proj.weight": jnp.zeros((32, 2)),
              "shared_experts.down_proj.weight": jnp.zeros((1, 32))}
    return np.asarray(reference._experts(jnp.asarray(x),
                                         {**params, **shared}, sizes))


def test_the_two_halves_and_the_shared_expert_add_up_to_the_whole_layer():
    """Experts 0-3 and 4-7 of the tiny model's first expert layer, each
    through `HeldExpertsMoE`, plus the shared expert counted once, against
    the uncut reference layer."""
    m = _build(seed=5)
    layer = m.layers[2]
    x = np.random.default_rng(2).normal(size=(40, 64)).astype(np.float32)
    prefix = "layers.2."
    params = {k[len(prefix):]: v for k, v in _params(m).items()
              if k.startswith(prefix)}
    cfg = m.config
    sizes = {"low": False, "top_k": cfg.num_experts_per_tok,
             "scale": cfg.route_scale, "first": 0}
    whole = np.asarray(reference._experts(jnp.asarray(x), params, sizes))
    total = np.asarray(layer.shared_experts(paddle.to_tensor(x))._value)
    for first in (0, 4):
        half = HeldExpertsMoE(64, 32, 8, 4, held=(first, 4), gate="sigmoid",
                              route_scale=cfg.route_scale)
        half.router._value = layer.moe.router._value
        half.expert_bias._value = layer.moe.expert_bias._value
        half.w_in._value = layer.moe.w_in._value[first:first + 4]
        half.w_out._value = layer.moe.w_out._value[first:first + 4]
        total = total + np.asarray(half(paddle.to_tensor(x))._value)
    assert np.abs(total - whole).max() < 1e-5


def test_the_bias_decides_a_pick_and_never_a_weight():
    layer = _moe((0, 8))
    x = np.random.default_rng(4).normal(size=(64, 32)).astype(np.float32)
    params = {"moe.router": layer.router._value,
              "moe.expert_bias": layer.expert_bias._value,
              "moe.w_in": layer.w_in._value, "moe.w_out": layer.w_out._value}
    with_bias = np.asarray(layer(paddle.to_tensor(x))._value)
    assert np.abs(with_bias - _layer_reference(x, params, 0)).max() < 1e-5
    score = jax.nn.sigmoid(jnp.asarray(x) @ layer.router._value)
    plain = np.asarray(jax.lax.top_k(score, 4)[1])
    biased = np.asarray(jax.lax.top_k(score + layer.expert_bias._value, 4)[1])
    moved = (np.sort(plain, -1) != np.sort(biased, -1)).any(-1)
    assert moved.any() and not moved.all()
    # where the bias changed no pick it changed nothing: it is in the choice
    # only, never in the weight
    layer.expert_bias._value = jnp.zeros(8, jnp.float32)
    without = np.asarray(layer(paddle.to_tensor(x))._value)
    assert np.abs(with_bias - without)[~moved].max() < 1e-6
    assert np.abs(with_bias - without)[moved].max() > 1e-4


def test_the_softmax_gate_is_the_layer_granite_has():
    """The gate is part of the layer's definition; the default is the one
    the layer had: a softmax over the picked logits."""
    paddle.seed(1)
    layer = HeldExpertsMoE(32, 16, 8, 4)
    assert layer.gate == "softmax" and not hasattr(layer, "expert_bias")
    assert [k for k, _ in layer.named_buffers()] == []
    with pytest.raises(ValueError):
        HeldExpertsMoE(32, 16, 8, 4, gate="tanh")


_MASKS = {None: None, "sparse": lambda i: i % 7 != 0,
          "prefix": lambda i: i < 40}


@pytest.mark.parametrize("tokens,live,chunk", [
    (96, None, 32), (100, "sparse", 32), (64, "sparse", 32),
    (96, "prefix", 32),        # a bucket's tail: the third chunk is dead
    # two full passes through the grouped GEMM's 256-row tiles (2048 pairs
    # a pass), then a padded last pass of 76 tokens
    (1100, None, 512), (1100, "sparse", 512)])
def test_chunked_calls_equal_the_unchunked_layer(monkeypatch, tokens, live,
                                                 chunk):
    """A call of more tokens than a pass takes runs as a scan over passes
    (here 32 or 512 for 4096) and gives what one pass gives, its stats summed
    over the call. Every held pair has its row in its pass, so no pair is
    dropped; a pass with no live token is skipped."""
    layer = _moe((2, 4))
    x = np.random.default_rng(6).normal(size=(tokens, 32)).astype(np.float32)
    mask = (paddle.to_tensor(_MASKS[live](np.arange(tokens))) if live
            else None)
    one, stats1 = layer(paddle.to_tensor(x), live=mask, with_stats=True)
    monkeypatch.setattr(held_moe, "CHUNK_TOKENS", chunk)
    layer._fns.clear()
    assert held_moe.chunks_for(tokens) == -(-tokens // chunk)
    many, stats2 = layer(paddle.to_tensor(x), live=mask, with_stats=True)
    assert np.abs(np.asarray(one._value) - np.asarray(many._value)).max() < 1e-6
    stats1, stats2 = np.asarray(stats1._value), np.asarray(stats2._value)
    # pairs, the largest group, the groups' rows, dropped: those of the call
    assert np.array_equal(stats1[:4], stats2[:4])
    assert int(stats2[3]) == 0                   # no pair is ever dropped
    # the row tiles are each pass's own: they cover the groups' rows, and
    # leave at most a tile open at either end of a group
    names = held_moe.STAT_NAMES
    rows, tiled = names.index("expert_rows_sum"), names.index("tile_rows")
    for stats, passes in ((stats1, 1), (stats2, held_moe.chunks_for(tokens))):
        bm = held_moe._row_tile(min(tokens, 4096 if passes == 1 else chunk)
                                * 4, 8)
        assert stats[rows] <= stats[tiled] <= stats[rows] + passes * 4 * 2 * bm
        assert stats[tiled] % bm == 0
    if chunk == 512:    # the passes went through the kernel's tiles
        tiles = autotune.chosen_tiles()["grouped_gemm"]
        assert tiles["consults"] > 0


def _dense_reference(layer, x, live):
    """Every expert over every token, then each token's gated picks of the
    HELD experts summed in pick order: what the layer is, with no sort, no
    layout and no kernel."""
    first, held = layer.held
    k = layer.top_k
    logits = x @ np.asarray(layer.router._value, np.float64)
    if layer.gate == "sigmoid":
        score = 1 / (1 + np.exp(-logits))
        picks = np.argsort(-(score + np.asarray(layer.expert_bias._value)),
                           axis=-1, kind="stable")[:, :k]
        chosen = np.take_along_axis(score, picks, -1)
        gates = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) \
            * layer.route_scale
    else:
        picks = np.argsort(-logits, axis=-1, kind="stable")[:, :k]
        chosen = np.take_along_axis(logits, picks, -1)
        e = np.exp(chosen - chosen.max(-1, keepdims=True))
        gates = e / e.sum(-1, keepdims=True)
    w_in = np.asarray(layer.w_in._value, np.float64)
    w_out = np.asarray(layer.w_out._value, np.float64)
    f = layer.d_expert
    out = np.zeros_like(x)
    counts = np.zeros(held, np.int64)
    for t in range(x.shape[0]):
        if live is not None and not live[t]:
            continue
        for j in range(k):
            e = picks[t, j] - first
            if 0 <= e < held:
                ab = x[t] @ w_in[e]
                hidden = ab[:f] / (1 + np.exp(-ab[:f])) * ab[f:]
                out[t] += gates[t, j] * (hidden @ w_out[e])
                counts[e] += 1
    return out, counts


@pytest.mark.parametrize("live", [None, "sparse", "prefix"])
@pytest.mark.parametrize("gate", ["softmax", "sigmoid"])
@pytest.mark.parametrize("held", [(0, 8), (2, 4)])
def test_the_held_layer_is_the_dense_per_expert_sum(gate, held, live):
    """Both gates, the whole layer and a share, with and without `live`:
    the rows end to end through the ragged kernel against every expert over
    every token, and the stats against the picks counted by hand."""
    paddle.seed(11)
    layer = HeldExpertsMoE(32, 16, 8, 4, held=held, gate=gate,
                           route_scale=2.826)
    if gate == "sigmoid":
        layer.expert_bias._value = jnp.asarray(
            np.random.default_rng(2).normal(0, 0.05, 8), jnp.float32)
    tokens = 100
    x = np.random.default_rng(8).normal(size=(tokens, 32))
    mask = _MASKS[live](np.arange(tokens)) if live else None
    got, stats = layer(paddle.to_tensor(x.astype(np.float32)),
                       live=None if mask is None else paddle.to_tensor(mask),
                       with_stats=True)
    want, counts = _dense_reference(layer, x.astype(np.float32).astype(
        np.float64), mask)
    assert np.abs(np.asarray(got._value) - want).max() < 1e-5
    if mask is not None:
        assert not np.asarray(got._value)[~mask].any()
    stats = dict(zip(held_moe.STAT_NAMES, np.asarray(stats._value).tolist()))
    assert stats["routed_pairs_held"] == stats["expert_rows_sum"] \
        == counts.sum()
    assert stats["expert_rows_max"] == counts.max()
    assert stats["dropped_pairs"] == 0
    # the visited row tiles by hand: the groups lie end to end in 16-row
    # tiles (400 pairs: the largest of 256, 128, ... that divides them)
    bm = held_moe._row_tile(tokens * 4, 8)
    assert bm == 16
    ends = np.cumsum(counts)
    visits = sum(-(-e // bm) - (e - n) // bm
                 for e, n in zip(ends, counts) if n)
    assert stats["tile_rows"] == visits * bm


def test_most_rows_of_a_prefill_pass_are_real():
    """A call of a prefill's shape (the prefill program returns no stats):
    with the rows end to end the row tiles the kernel visits hold mostly
    real rows, where a worst-case stride made them one in sixteen."""
    layer = _moe((0, 4))
    x = np.random.default_rng(12).normal(size=(2048, 32)).astype(np.float32)
    _, stats = layer(paddle.to_tensor(x), with_stats=True)
    stats = dict(zip(held_moe.STAT_NAMES, np.asarray(stats._value).tolist()))
    assert stats["expert_rows_sum"] > 3000       # about half of 8192 pairs
    assert stats["expert_rows_sum"] / stats["tile_rows"] > 0.5
    assert stats["dropped_pairs"] == 0


@pytest.mark.parametrize("bucket,passes", [(512, 1), (4096, 1), (8192, 2),
                                           (16384, 4)])
def test_a_prefills_span_says_how_many_passes_its_bucket_takes(model, bucket,
                                                               passes):
    """A bucket up to `CHUNK_TOKENS` = 4096 is ONE pass of each expert layer
    (no scan); 8192 and 16384 are scans of 2 and 4."""
    assert held_moe.CHUNK_TOKENS == 4096
    assert model.prefill_span_attrs(bucket) == {"chunks": passes}


def test_a_ticks_routing_is_observed_once_on_both_row_histograms(model):
    eng = _engine(model)
    reg = default_registry()
    eng.add_request(_prompt(20, 3), max_new_tokens=4)
    eng.run()
    live, tiled = reg.get("moe_rows_live"), reg.get("moe_rows_tiled")
    mean = reg.get("moe_expert_rows_mean")
    before = (live.count(), live.sum(), tiled.count(), tiled.sum(),
              mean.count(), mean.sum())
    eng._note_routing(np.array([70, 9, 70, 0, 192, 8, 8], np.int32))
    assert live.count() == before[0] + 1 and live.sum() == before[1] + 70
    assert tiled.count() == before[2] + 1 and tiled.sum() == before[3] + 192
    # one observation a decode tick, as the other routing series
    assert before[0] == before[2] == before[4] > 0
    assert before[1] == pytest.approx(before[5] * eng._moe_groups)
    assert before[3] >= before[1]


# -- 9. the two window kernels against plain masked attention --------------- #

def _masked_attention(q, k, v, window):
    S, H = q.shape[1], q.shape[2]
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    v = jnp.repeat(v, H // v.shape[2], axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = (j <= i) & (i - j < window)
    return jnp.einsum("bhst,bthd->bshd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


@pytest.mark.parametrize("seq,window,block", [
    (40, 8, 8), (64, 16, 16), (50, 32, 16), (24, 100, 8), (96, 32, 32)])
def test_window_prefill_kernel_matches_masked_attention(monkeypatch, seq,
                                                        window, block):
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK", str(block))
    rng = np.random.default_rng(seq)
    q = jnp.asarray(rng.normal(size=(1, seq, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, seq, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, seq, 2, 16)), jnp.float32)
    got = flash_window_fwd(q, k, v, window)
    assert jnp.abs(got - _masked_attention(q, k, v, window)).max() < 2e-6


@pytest.mark.parametrize("lengths", [(5, 23, 12), (10, 11, 40), (1, 9, 33)])
def test_windowed_decode_kernel_matches_masked_attention(lengths):
    """Rows inside, at and past the window; each row's table starts at the
    page of its first cached position and `lengths` counts from there."""
    ps, window, hkv, d, h = 4, 10, 2, 16, 4
    rng = np.random.default_rng(sum(lengths))
    kc = jnp.asarray(rng.normal(size=(40, hkv, ps, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(40, hkv, ps, d)), jnp.float32)
    width = WindowKV(hkv, d, window).table_width(ps)
    tables = np.full((len(lengths), width), -1, np.int32)
    free = iter(rng.permutation(np.arange(1, 40)))
    rel = []
    for b, L in enumerate(lengths):          # L: tokens cached, query at L-1
        first = WindowKV(hkv, d, window).first_page(L - 1, ps)
        for slot in range((L - 1) // ps - first + 1):
            tables[b, slot] = next(free)
        rel.append(L - first * ps)
    q = jnp.asarray(rng.normal(size=(len(lengths), h, d)), jnp.float32)
    got = paged_decode_attention(q, kc, vc, jnp.asarray(tables),
                                 jnp.asarray(rel, jnp.int32), window=window)
    for b, n in enumerate(rel):
        pages = [p for p in tables[b] if p >= 0]
        keys = jnp.concatenate([kc[p] for p in pages], 1)[:, max(0, n - window):n]
        vals = jnp.concatenate([vc[p] for p in pages], 1)[:, max(0, n - window):n]
        s = jnp.einsum("hgd,htd->hgt", q[b].reshape(hkv, h // hkv, d),
                       keys) / np.sqrt(d)
        want = jnp.einsum("hgt,htd->hgd", jax.nn.softmax(s, -1), vals)
        assert jnp.abs(got[b] - want.reshape(h, d)).max() < 2e-6


# -- 10. the other models' programs are the parent's ------------------------ #

@pytest.mark.parametrize("build,moe", [
    (lambda: GPTForCausalLM(gpt3_tiny()), False),
    (lambda: GraniteHybridForCausalLM(granite_hybrid_tiny()), True)],
    ids=["gpt", "granite"])
def test_the_other_models_decode_programs_are_the_parents(build, moe):
    """A cache manager generalised for window layers leaves the GPT and the
    Granite decode program as they were: the jaxpr of the engine's program
    equals that of the parent commit's closure, written out here (one table,
    no window starts, an entry a layer)."""
    paddle.seed(0)
    eng = PagedServingEngine(build(), max_batch_size=4, max_seq_len=64,
                             page_size=8)
    assert len(eng.groups) == 1 and not eng._windowed
    assert eng.tables is eng.group_tables[0]
    stats_kw = {"with_stats": True} if moe else {}

    def decode(p, b, tok, offs, tables, temps, keys, caches):
        pos = offs[:, None]
        logits, new_c, *stats = eng._functional_forward(
            p, b, tok[:, None], pos, caches, offs, tables=tables, **stats_kw)
        last = logits[:, -1]
        return *eng._choose_tokens(last, temps, keys), last, new_c, stats

    args = (eng.params, eng.buffers, jnp.zeros(4, jnp.int32),
            jnp.ones(4, jnp.int32), jnp.zeros((4, eng.P), jnp.int32),
            jnp.zeros(4, jnp.float32), jnp.zeros((4, 2), jnp.uint32),
            eng.pool.kv)
    mine = jax.make_jaxpr(eng._decode_program())(*args)
    parents = jax.make_jaxpr(jax.jit(decode, donate_argnums=(7,)))(*args)
    assert str(mine) == str(parents)


def test_engine_reports_both_kinds_of_pages(model):
    eng = _engine(model)
    eng.add_request(_prompt(70, 8), max_new_tokens=12)
    eng.step()
    eng.step()
    gauge = default_registry().get("serving_pages_live")
    assert gauge.value(kind="full") == (eng.group_tables[0] >= 0).sum()
    assert gauge.value(kind="window") == sum(
        (t >= 0).sum() for t in eng.group_tables[1:])
    assert set(eng._decode_grid) == {
        "pages_per_step", "grid_steps", "window_pages_per_step",
        "window_grid_steps"}
    eng.run()


@pytest.mark.parametrize("prompts,full_steps,window_steps", [
    # contexts 22 and 11 on the second tick, both inside the window (32): a
    # step of each table a row
    ((20, 9), 2, 2),
    # 142 tokens: 18 of the full table's 20 pages at 16 a step are two
    # steps; the window's table starts at page 13 (token 104) and the query
    # sees tokens 110-141, in all five of its slots: two steps at 4 a step.
    # 70 + 2: one step of the full table; the window's starts at token 40
    # and holds tokens 40-71 in slots 0-3: one step
    ((140, 70), 3, 3)],
    ids=["inside-the-window", "past-the-window"])
def test_decode_dispatch_counts_both_kernels_live_steps(model, prompts,
                                                        full_steps,
                                                        window_steps):
    """`live_grid_steps` and `window_live_grid_steps` of a seated batch on
    its second tick: a hand count, and the count of the kernels' own work
    lists over the engine's tables; each kind's histogram takes one
    observation a tick."""
    from paddle_tpu.observability import spans
    from paddle_tpu.ops.pallas import decode_attention as da

    eng = _engine(model)
    for i, n in enumerate(prompts):
        eng.add_request(_prompt(n, i), max_new_tokens=6)
    eng.step()
    share = default_registry().get("serving_decode_live_step_share")
    before = {k: (share.count(kind=k), share.sum(kind=k))
              for k in ("full", "window")}
    tl = spans.enable_step_timeline()
    try:
        eng.step()
    finally:
        tl.uninstall()
    (attrs,) = [r["attrs"] for r in spans.recorded()
                if r["path"] == "engine.step/decode_dispatch"][-1:]
    spans.clear_recorded()
    assert (attrs["pages_per_step"], attrs["grid_steps"]) == (16, 4 * 2)
    assert (attrs["window_pages_per_step"],
            attrs["window_grid_steps"]) == (4, 4 * 2)
    assert attrs["live_grid_steps"] == full_steps
    assert attrs["window_live_grid_steps"] == window_steps
    # what the kernels see: the lengths of the tick in flight, its own token
    # in, a window group's counted from its table's first page
    seen = jnp.asarray(eng.lengths) + 1
    assert int(da.work_list(jnp.asarray(eng.group_tables[0]), seen, PS,
                            16).count) == full_steps
    for table in eng.group_tables[1:]:
        work = da.work_list(
            jnp.asarray(table), seen - jnp.asarray(eng.window_start * PS),
            PS, 4, window=32)
        assert int(work.count) == window_steps
    for kind, steps in (("full", full_steps), ("window", window_steps)):
        assert share.count(kind=kind) == before[kind][0] + 1
        assert share.sum(kind=kind) - before[kind][1] == pytest.approx(
            steps / 8)
    eng.run()


# -- the yardstick's own counts, by hand ------------------------------------ #

def _cell_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini.json")) as f:
        return json.load(f)


def test_costs_by_hand_for_the_published_widths():
    from benchmark import costs_afmoe as costs

    config = _cell_config()
    # q, gate, o: 2048 x 4096 each; k, v: 2048 x 512 each
    assert costs.attention_params(config) == 27_262_976
    assert costs.expert_params(config) == 3 * 2048 * 1024 == 6_291_456
    assert costs.dense_mlp_params(config) == 37_748_736
    # 64 held experts, bf16: [64, 2048, 2048] in and [64, 1024, 2048] out
    assert costs.grouped_gemm_weight_bytes(config) == (536_870_912,
                                                       268_435_456)
    # K and V, 4 KV heads x 128, bf16: 2 KB a token and layer
    assert costs.kv_bytes_per_token_layer(config) == 2048
    assert costs.layers_of(config, "sliding_attention") == 6
    assert costs.layers_of(config, "full_attention") == 2
    assert costs.decode_window_bytes(config, 1000) == 1000 * 6 * 2048
    assert costs.decode_full_bytes(config, 1000) == 1000 * 2 * 2048
    # a 3000-token prompt under a window of 2048: 2048 x 2049 / 2 pairs, then
    # 952 queries x 2048 keys
    assert costs.band_pairs(3000, 2048) == 2_098_176 + 952 * 2048
    assert costs.band_pairs(100, 2048) == costs.causal_pairs(100) == 5050
    assert costs.pair_flops(config) == 4 * 32 * 128
    assert costs.window_prefill_flops(config, [3000, 100]) == (
        (2_098_176 + 952 * 2048 + 5050) * 16384 * 6)
    # 8 layers of attention, 2 dense MLPs, 6 expert layers each with the
    # router 2048 x 128, the shared expert and 4 of 8 picks held; the head
    per_expert_layer = 262_144 + 6_291_456 + 4 * 6_291_456
    assert costs.matmul_params_per_token(config, head=False) == (
        8 * 27_262_976 + 2 * 37_748_736 + 6 * per_expert_layer
        ) == 483_917_824
    assert costs.matmul_params_per_token(config) == (
        483_917_824 + 200_192 * 2048) == 893_911_040
    assert costs.decode_flops_per_token(config, 5000, 2048) == (
        2 * 893_911_040 + 16384 * (2 * 5000 + 6 * 2048))
    assert costs.prompt_flops(config, 100) == (
        2 * 100 * 483_917_824 + 2 * 409_993_216 + 16384 * 8 * 5050)
    # one prompt level of 2040 and one answer level of 10: tokens 1 .. 9 of
    # the answer at contexts 2041 .. 2049, the last two cut to the window
    assert costs.mean_window_context(config, [2040], [10], 16384) == (
        sum(range(2041, 2049)) + 2048) / 9


def _load_reader(name):
    path = os.path.join(ROOT, "benchmark", "readers", name + ".py")
    spec = importlib.util.spec_from_file_location("_reader_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _span(path, root, **attrs):
    from benchmark.program_spans import Span

    return Span(id=0, parent=None, path=path, start=0.0, end=1.0,
                attrs=attrs, root=root)


def test_roofline_reader_on_a_trace_written_by_hand():
    reader = _load_reader("kernel_roofline_afmoe")
    tail = ', custom_call_target="tpu_custom_call", operand_layout...'
    ops = [(f"%decode_window.{i} = bf16[160,4,8,128] custom-call(...){tail}",
            1.0 + i, 0.001) for i in range(6)]
    ops += [(f"%decode_paged.{i} = bf16[160,4,8,128] custom-call(...){tail}",
             10.0 + i, 0.002) for i in range(2)]
    ops += [(f"%flash_fwd_window.3 = (bf16[1,32,4096,128]) custom-call(...)"
             f"{tail}", 20.0, 0.004),
            ("%grouped_gemm.1 = bf16[16384,2048] custom-call(s32[64], "
             f"bf16[16384,2048], bf16[64,2048,2048]){tail}", 30.0, 0.001),
            ("%grouped_gemm.2 = bf16[16384,2048] custom-call(s32[64], "
             f"bf16[16384,1024], bf16[64,1024,2048]){tail}", 31.0, 0.0005),
            (f"%decode_window.99 = ...{tail}", 99.0, 1.0)]   # outside
    trace = types.SimpleNamespace(window=(0.0, 50.0),
                                  devices={"/device:TPU:0": {"XLA Ops": ops}})
    spans = [
        _span("engine.step/decode_dispatch", 0, context_tokens=500_000,
              window_tokens=200_000),
        _span("engine.step/decode_dispatch", 1, context_tokens=100_000,
              window_tokens=50_000),
        _span("engine.step/decode_dispatch", None, context_tokens=7,
              window_tokens=7),                    # outside the trace
        _span("engine.step/admit/prefill", 1, prompt_len=3000, bucket=4096)]
    run = types.SimpleNamespace(
        trace=trace, config=_cell_config(),
        _program_spans={("bm.engine_step", "engine.step"): spans},
        peaks=lambda: {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    # 250,000 window tokens x 6 layers x 2 KB over 819 GB/s against 6 ms
    assert reader.read(run, {}, kernel="decode_window") == pytest.approx(
        100 * (250_000 * 6 * 2048 / 819e9) / 0.006)
    # 600,000 context tokens x 2 layers x 2 KB against 4 ms
    assert reader.read(run, {}, kernel="decode_paged") == pytest.approx(
        100 * (600_000 * 2 * 2048 / 819e9) / 0.004)
    # the band of a 3000-token prompt, 16384 FLOP a pair, 6 layers, 4 ms
    assert reader.read(run, {}, kernel="flash_fwd_window") == pytest.approx(
        100 * ((2_098_176 + 952 * 2048) * 16384 * 6 / 197e12) / 0.004)
    assert reader.read(run, {}, kernel="grouped_gemm") == pytest.approx(
        100 * ((536_870_912 + 268_435_456) / 819e9) / 0.0015)
    run.trace = None
    assert reader.read(run, {}, kernel="decode_window") is None


def test_span_attr_reader_on_spans_written_by_hand():
    reader = _load_reader("span_attr_stat")
    anchor = ["bm.engine_step", "engine.step"]
    spans = [_span("engine.step", 0), _span("engine.step", 1),
             _span("engine.step", 2),
             _span("engine.step/write_targets", 0, window_pages_released=6),
             _span("engine.step/write_targets", 1, window_pages_released=0),
             _span("engine.step/write_targets", 2, pages_allocated=3)]
    run = types.SimpleNamespace(trace=object(),
                                _program_spans={tuple(anchor): spans})
    args = dict(anchor=anchor, path="engine.step/write_targets",
                attr="window_pages_released")
    assert reader.read(run, {}, stat="mean", **args) == 2.0
    assert reader.read(run, {}, stat="p50", **args) == 0.0
    # a program from before the attribute: nothing to read, nothing raised
    assert reader.read(run, {}, stat="mean", **{**args, "attr": "nope"}) is None
    run._program_spans[tuple(anchor)] = None
    assert reader.read(run, {}, stat="mean", **args) is None


def test_mfu_reader_counts_the_window_by_hand():
    from benchmark import costs_afmoe as costs

    reader = _load_reader("mfu_required_afmoe")
    config = _cell_config()
    mix = {"prompt_len": {"lo": 2040, "hi": 2040, "levels": 1},
           "answer_len": {"lo": 10, "hi": 10, "levels": 1},
           "max_total": 16384}
    ticks = [{"decoded_rows": 100, "context_tokens": 300_000,
              "first_tokens": 2},
             {"decoded_rows": 100, "context_tokens": 500_000,
              "first_tokens": 0}]
    run = types.SimpleNamespace(
        window=(10.0, 12.0), config=config, mix=mix,
        peaks=lambda: {"bf16_flops_per_s": 197e12})
    window_context = (sum(range(2041, 2049)) + 2048) / 9
    flops = (200 * costs.decode_flops_per_token(config, 4000, window_context)
             + 2 * costs.prompt_flops(config, 2040))
    assert reader.read(run, {"series": {"ticks": ticks}}) == pytest.approx(
        100 * flops / 2.0 / 197e12)
    assert reader.read(run, {"series": {"ticks": []}}) is None


# -- the cell and the manifest ---------------------------------------------- #

def test_the_cells_rehearsal_runs_end_to_end_and_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PADDLE_TPU_PALLAS_INTERPRET="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PADDLE_TPU_HW", None)
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "serve-trinity-mixed-sat", "--seed", "2147483659",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["rehearsal"]["would_report"] == ["serve_tok_s", "setup_s"]


def test_one_wrong_token_is_not_correct_by_the_worst_position():
    """The comparison's second limit: a served answer passes; the same
    answer with ONE token the reference has no reason to prefer (three
    spreads under the row's largest, at the last position, which is no
    later position's input) keeps the mean limit and breaks the worst."""
    from benchmark import harness
    from benchmark.families import afmoe as family

    config = harness.rehearsal_sizes(_cell_config())
    paddle.seed(5)
    m = AfmoeForCausalLM(family._model_config(config))
    m.eval()
    eng = _engine(m, max_batch_size=2, max_seq_len=128)
    eng.add_request(_prompt(20), max_new_tokens=100)
    (done,) = eng.run()
    prompt = np.asarray(done.prompt)
    served = np.asarray(done.generated, np.int32)
    ok, detail = family.check_served(config, m, [(prompt, served)])
    assert ok, detail
    ids = np.concatenate([prompt, served[:-1]])
    row = np.asarray(reference.logits(
        _params(m), ids, config, family.held(config),
        rows=np.array([len(ids) - 1])))[0]
    share = (row.max() - row) / row.std()
    wrong = served.copy()
    wrong[-1] = int(np.abs(share - 3.0).argmin())
    ok, detail = family.check_served(config, m, [(prompt, wrong)])
    (sample,) = detail["samples"]
    assert not ok
    assert sample["mean_share"] <= detail["tolerance"]
    assert sample["worst_share"] > detail["worst_tolerance"] == 2.0


def test_the_cell_and_its_metrics_are_in_the_manifest():
    """What this family brings to the manifest, whose own checks (names,
    files, readings, room) run under `tests/test_benchmark_suite.py`: the
    cell with its configuration, mix and chips, `serve_tok_s` over it, the
    mix's lengths, the cut against the catalog, and each of the family's
    kernels read against its roofline by the family's reader, under
    whatever entry lists the cell."""
    from benchmark.run import layer_metrics

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = "serve-trinity-mixed-sat"
    (entry,) = [w for w in manifest["workloads"] if w["name"] == cell]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "trinity-mini", "saturated-mixed-16k", 1)
    (serve,) = [m for m in manifest["end_to_end"]
                if m["name"] == "serve_tok_s"]
    assert cell in serve["workloads"]
    readings = {(m["reader"], json.dumps(m.get("args", {}), sort_keys=True)):
                m for m in layer_metrics(cell).values()}
    for kernel in ("decode_window", "flash_fwd_window", "decode_paged",
                   "grouped_gemm"):
        metric = readings["kernel_roofline_afmoe",
                          json.dumps({"kernel": kernel})]
        assert (metric["unit"], metric["moves"]) == ("%", "serve_tok_s")
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "saturated-mixed-16k.json")) as f:
        mix = json.load(f)
    assert (mix["prompt_len"], mix["answer_len"], mix["max_total"]) == (
        {"lo": 512, "hi": 12288, "levels": 12},
        {"lo": 128, "hi": 768, "levels": 8}, 16384)
    config = _cell_config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        (row,) = [json.loads(line) for line in f
                  if '"name": "Trinity-Mini"' in line]
    changed = {k for k, v in row["config"].items() if config.get(k) != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers",
                                                 "num_experts"}
