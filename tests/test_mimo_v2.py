"""The decoder whose sliding layers keep more KV heads than its full ones
(`models/mimo_v2.py`: keys wider than values, rotary positions on part of a
head, a learned sink in the sliding layers' softmax, sigmoid-routed experts
of which a chip holds a share and no shared one) through the paged engine,
over a pool of pages of TWO shapes, against the plain float32 reference
(`benchmark/reference/mimo_v2.py`) at a tiny size on the CPU (window 16,
pages of 8, 1 KV head beside 2). Logits are compared, not tokens: with
random weights the largest logit changes on rounding.

Tolerances. Model and reference are both float32 here (conftest sets
`highest` matmuls), so they differ by the order of summation alone. A row of
logits has a standard deviation of about 0.15 at this size: 2e-5 absolute is
a ten-thousandth of a spread, where a unit of the wrong half of a block, a
window off by one, a missing sink, a rotation over the wrong values or a
wrong expert moves a row by a good part of one.
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
from benchmark.reference import mimo_v2 as reference
from paddle_tpu.incubate.distributed.models.moe import HeldExpertsMoE
from paddle_tpu.inference.paged import (BlockPool, PagedKV,
                                        PagedServingEngine, WindowKV)
from paddle_tpu.inference.paged.block_pool import (LatentKV, RowState,
                                                   page_layout, stored_width)
from paddle_tpu.models.afmoe import AfmoeForCausalLM, afmoe_tiny
from paddle_tpu.models.granite_hybrid import (GraniteHybridForCausalLM,
                                              granite_hybrid_tiny)
from paddle_tpu.models.kimi_k2 import KimiK2ForCausalLM, kimi_k2_tiny
from paddle_tpu.models.mimo_v2 import MimoV2ForCausalLM, mimo_v2_tiny
from paddle_tpu.observability.metrics import default_registry
from paddle_tpu.ops.pallas.decode_attention import (paged_decode_attention,
                                                    pages_per_step)
from paddle_tpu.ops.pallas.flash_attention import flash_window_fwd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "serve-mimo-v2-agent-sat"
ATOL = 2e-5
W, PS = 16, 8       # mimo_v2_tiny's window; the engines' page size here


@pytest.fixture(autouse=True)
def _interpret_mode(pallas_interpret_unless_hw):
    pass


def _as_config_dict(cfg):
    """The model's config under the configuration file's keys, as the
    reference reads them."""
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    out["num_hidden_layers"] = cfg.num_layers
    return out


def _build(seed=7, **kw):
    paddle.seed(seed)
    m = MimoV2ForCausalLM(mimo_v2_tiny(**kw))
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


def _counter(name, **labels):
    m = default_registry().get(name)
    return 0 if m is None else m.value(**labels)


# -- (a) the whole forward --------------------------------------------------- #

def test_full_forward_matches_the_reference(model):
    """The published pattern's first seven layers (full, four sliding, full,
    sliding), layer 0 dense, all experts held, contexts to six windows, a
    batch of two."""
    ids = np.random.default_rng(0).integers(1, 256, (2, 100)).astype(np.int32)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids))._value)
    for b in range(2):
        assert np.abs(got[b] - _reference_logits(model, ids[b])).max() < ATOL


def test_the_config_says_what_the_published_one_does():
    from paddle_tpu.models.mimo_v2 import MimoV2Config

    cfg = MimoV2Config()
    assert cfg.num_layers == 48 and cfg.rotary_dim == 64
    assert cfg.hybrid_layer_pattern[:7] == (0, 1, 1, 1, 1, 0, 1)
    assert sum(cfg.hybrid_layer_pattern) == 39
    assert cfg.moe_layer_freq == (0,) + (1,) * 47
    assert (cfg.kv_heads_of(False), cfg.kv_heads_of(True)) == (4, 8)
    with pytest.raises(ValueError):
        MimoV2Config(swa_head_dim=128)
    tiny = mimo_v2_tiny()
    assert tiny.rotary_dim == 8 and tiny.num_layers == 7


def test_the_sink_the_value_scale_and_the_partial_rotation_all_count(model):
    """Each of the three, taken out of the reference alone, moves the logits
    by far more than the tolerance: the model computes all of them."""
    ids = _prompt(40, 3)
    base = _as_config_dict(model.config)
    want = np.asarray(reference.logits(_params(model), ids, base))
    for change in ({"add_swa_attention_sink_bias": False},
                   {"attention_value_scale": 1.0},
                   {"partial_rotary_factor": 1.0},
                   {"swa_rope_theta": base["rope_theta"]}):
        other = np.asarray(reference.logits(
            _params(model), ids, {**base, **change}))
        assert np.abs(other - want).max() > 20 * ATOL, change


# -- (b) prefill, then decode, through the paged engine ----------------------- #

def _serve_logit_for_logit(m, prompts, new_tokens, **kw):
    """Serve `prompts` together and hold EVERY decode tick's logits of every
    live row against the reference's one forward over what the row has seen.
    Returns the engine."""
    eng = _engine(m, **kw)
    for p in prompts:
        eng.add_request(p, max_new_tokens=new_tokens)
    worst = 0.0
    while eng.has_work():
        seen = {i: (r, r.output_ids.copy()) for i, r in enumerate(eng.active)
                if r is not None}
        eng.step()
        logits = np.asarray(eng.last_logits)
        for i, (req, ids) in seen.items():
            if eng.active[i] is not req and req not in eng.finished:
                continue   # spilled this tick: it decoded nothing
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


def test_prefill_then_decode_past_several_windows(model):
    """Prompts on both sides of the window (12: the row STARTS inside it and
    leaves it while decoding; 28, 50 and 90: past it at admission, the
    window groups take the prompt's last window only), 45 tokens each, so
    contexts reach eight windows and every row releases pages; every tick
    logit for logit."""
    eng = _serve_logit_for_logit(
        model, [_prompt(n, n) for n in (12, 50, 90, 28)], 45)
    assert eng.pool.pages_free == eng.pool.pages_total
    assert eng._window_released > 0
    assert not eng.pool._broken and not eng.pool.ref.any()


def test_a_spill_and_a_resume_in_it_logit_for_logit(model):
    """A pool that cannot hold both rows to their ends: the newer is spilled
    past the window (units of both shapes, its start along) and resumed;
    every tick of both rows still reads the reference's logits."""
    before = _counter("serving_preemptions_total")
    eng = _serve_logit_for_logit(
        model, [_prompt(60, 21), _prompt(44, 22)], 50,
        num_pages=100, watermark_pages=0)
    assert _counter("serving_preemptions_total") > before
    assert sum(r.preemptions for r in eng.finished) >= 1
    assert eng._window_released > 0
    assert eng.pool.pages_free == eng.pool.pages_total


def test_keys_past_a_lane_tile_are_stored_padded_and_served_right():
    """A head 136 wide is stored in 256 (`stored_width`): the model hands
    the pool keys that wide, zeros behind them, and pads its query alike."""
    m = _build(seed=3, head_dim=136, swa_head_dim=136, hidden_size=32,
               num_attention_heads=2, swa_num_attention_heads=2,
               hybrid_layer_pattern=(0, 1), moe_layer_freq=(0, 1))
    eng = _serve_logit_for_logit(m, [_prompt(30, 4)], 20)
    k, v = eng.pool.kv[0]
    assert k.shape[1:] == (1, PS, 256) and v.shape[1:] == (1, PS, 16)
    assert not np.asarray(k[..., 136:]).any()
    spec = m.cache_specs()[0]
    assert spec.page_nbytes(PS, jnp.float32) == 1 * PS * (256 + 16) * 4
    assert (stored_width(192), stored_width(128), stored_width(24)) == (
        256, 128, 24)


def test_first_token_comes_from_the_prompts_last_position(model):
    eng = _engine(model)
    p = _prompt(37, 5)
    eng.add_request(p, max_new_tokens=1)
    (done,) = eng.run()
    want = _reference_logits(model, p, rows=[len(p) - 1])[0]
    assert done.generated == [int(want.argmax())]


# -- (c) the shares add up ---------------------------------------------------- #

def test_four_shares_of_the_experts_add_up_to_the_whole_layer(model):
    """Experts 0-1, 2-3, 4-5 and 6-7 of the tiny model's first expert
    layer, each through `HeldExpertsMoE` (sigmoid gate, scale 1, no shared
    expert), summed, against the uncut reference layer."""
    full = model.layers[1].moe
    params = {"moe.router": full.router._value,
              "moe.expert_bias": full.expert_bias._value,
              "moe.w_in": full.w_in._value, "moe.w_out": full.w_out._value}
    x = np.random.default_rng(2).normal(0, 1, (24, 64)).astype(np.float32)
    sizes = {"low": False, "top_k": 4, "first": 0}
    want = np.asarray(reference._experts(jnp.asarray(x), params, sizes))
    total = np.zeros_like(want)
    for first in range(0, 8, 2):
        share = HeldExpertsMoE(64, 32, 8, 4, held=(first, 2), gate="sigmoid",
                               route_scale=1.0)
        share.router._value = full.router._value
        share.expert_bias._value = full.expert_bias._value
        share.w_in._value = full.w_in._value[first:first + 2]
        share.w_out._value = full.w_out._value[first:first + 2]
        with paddle.no_grad():
            part = np.asarray(share(paddle.to_tensor(x))._value)
        # a share alone against the reference given the same share
        alone = np.asarray(reference._experts(
            jnp.asarray(x), {**params, "moe.w_in": share.w_in._value,
                             "moe.w_out": share.w_out._value},
            {**sizes, "first": first}))
        assert np.abs(part - alone).max() < ATOL
        total += part
    assert np.abs(total - want).max() < ATOL
    assert np.abs(want).max() > 50 * ATOL


def test_a_held_share_of_the_model_matches_the_reference_given_the_share():
    m = _build(seed=9, held_experts=(4, 2))
    ids = _prompt(50, 1)
    with paddle.no_grad():
        got = np.asarray(m(paddle.to_tensor(ids[None]))._value[0])
    assert np.abs(got - _reference_logits(m, ids)).max() < ATOL
    assert m.moe_groups == 6 * 2


# -- (d) the kernels at two widths, with a sink ------------------------------- #

def _paged_case(lengths, Hkv, H, Dk, Dv, P, seed=0, starts=None):
    """q, the two pools, tables over distinct pages and the dense K, V the
    composite sees; `starts`: a window row's first cached position."""
    rng = np.random.default_rng(seed)
    B, n_pages = len(lengths), 1 + len(lengths) * P
    kc = rng.normal(0, 1, (n_pages, Hkv, PS, Dk)).astype(np.float32)
    vc = rng.normal(0, 1, (n_pages, Hkv, PS, Dv)).astype(np.float32)
    q = rng.normal(0, 1, (B, H, Dk)).astype(np.float32)
    tables = np.full((B, P), -1, np.int32)
    free = iter(rng.permutation(np.arange(1, n_pages)))
    for b, n in enumerate(lengths):
        for j in range(-(-n // PS)):
            tables[b, j] = next(free)
    return q, kc, vc, tables


def _composite(q, kc, vc, tables, lengths, scale, window=None, sink=None):
    """Dense softmax over each row's keys, the sink a term of the
    denominator; a row without a live key: zero."""
    B, H, _ = q.shape
    Hkv = kc.shape[1]
    out = np.zeros((B, H, vc.shape[-1]), np.float32)
    for b, n in enumerate(lengths):
        if n == 0:
            continue
        pages = tables[b, :-(-n // PS)]
        k = np.concatenate([kc[p] for p in pages], axis=1)[:, :n]
        v = np.concatenate([vc[p] for p in pages], axis=1)[:, :n]
        lo = 0 if window is None else max(0, n - window)
        for h in range(H):
            s = (k[h // (H // Hkv), lo:] @ q[b, h]) * scale
            m = max(s.max(), -np.inf if sink is None else sink[h])
            e = np.exp(s - m)
            denom = e.sum() + (0 if sink is None else np.exp(sink[h] - m))
            out[b, h] = (e / denom) @ v[h // (H // Hkv), lo:]
    return out


@pytest.mark.parametrize("window,sink", [
    (None, False), (None, True), (16, False), (16, True)],
    ids=["paged", "paged-sink", "window", "window-sink"])
def test_decode_kernels_at_two_widths_match_the_composite(window, sink):
    """`decode_paged` and `decode_window` with keys 24 wide beside values of
    16 (and a sink a head), rows of many lengths, a FREE row among them (no
    live key: zero) and a row of one key."""
    lengths = [37, 0, 1, 24] if window is None else [21, 0, 1, 17]
    H, Hkv, Dk, Dv, P = 4, 2, 24, 16, 6 if window is None else 3
    q, kc, vc, tables = _paged_case(lengths, Hkv, H, Dk, Dv, P, seed=5)
    b = (np.random.default_rng(1).normal(0, 1.5, H).astype(np.float32)
         if sink else None)
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(tables),
        jnp.asarray(lengths, jnp.int32), scale=0.3, window=window,
        sink=None if b is None else jnp.asarray(b)))
    want = _composite(q, kc, vc, tables, lengths, 0.3, window, b)
    assert got.shape == (4, H, Dv)
    assert np.abs(got - want).max() < 1e-5
    assert not got[1].any() and np.abs(want[0]).max() > 0.05
    if sink:   # the sink took its share: the output is smaller than without
        bare = _composite(q, kc, vc, tables, lengths, 0.3, window, None)
        assert np.abs(bare - want).max() > 1e-2


def test_a_step_is_sized_from_both_widths():
    assert pages_per_step(4, 32, 128, 512, 2) == pages_per_step(
        4, 32, 128, 512, 2, 128) == 16
    # a wider key leaves fewer pages a step where VMEM is what limits
    assert pages_per_step(10, 128, 256, 64, 4, 128) == 2
    assert pages_per_step(10, 128, 256, 64, 4) == 1
    spec = WindowKV(8, 192, 128, value_dim=128)
    assert spec.pages_per_step(32, 5, 2) == pages_per_step(
        8, 32, 256, 5, 2, 128) == 4
    assert PagedKV(4, 192, value_dim=128).page_arrays(32) == (
        (4, 32, 256), (4, 32, 128))
    assert BlockPool.page_nbytes(2, 4, 192, 32, jnp.bfloat16,
                                 value_dim=128) == 2 * 4 * 32 * 384 * 2


@pytest.mark.parametrize("seq,sink", [(40, True), (40, False), (150, True)])
def test_window_prefill_kernel_at_two_widths_with_a_sink(seq, sink):
    from paddle_tpu.models.mimo_v2 import _masked_attention

    rng = np.random.default_rng(seq)
    H, Hkv, Dk, Dv = 4, 2, 24, 16
    q = jnp.asarray(rng.normal(0, 1, (1, seq, H, Dk)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, seq, Hkv, Dk)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (1, seq, Hkv, Dv)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 1.5, H), jnp.float32) if sink else None
    got = np.asarray(flash_window_fwd(q, k, v, W, scale=0.3, sink=b))
    want = np.asarray(_masked_attention(q, k, v, W, 0.3, b))
    assert got.shape == (1, seq, H, Dv)
    assert np.abs(got - want).max() < 1e-5
    # the composite itself against the softmax written out for one query
    i, h = seq - 1, 3
    s = np.asarray(k)[0, i - W + 1:i + 1, h // 2] @ np.asarray(q)[0, i, h] * 0.3
    e = np.exp(s)
    denom = e.sum() + (np.exp(np.asarray(b)[h]) if sink else 0.0)
    assert np.abs((e / denom) @ np.asarray(v)[0, i - W + 1:i + 1, h // 2]
                  - want[0, i, h]).max() < 1e-5


def test_a_short_windows_key_block_is_no_wider_than_it_needs(monkeypatch):
    """A window of 128 walks key blocks of 128, not of 1024 (Trinity's
    window of 2048 keeps its 1024)."""
    from paddle_tpu.ops.pallas import autotune

    seen = {}
    real = autotune.pick_block_sizes

    def spy(name, sq, skv, default, *a, **kw):
        seen[name] = default
        return real(name, sq, skv, default, *a, **kw)

    monkeypatch.setattr(autotune, "pick_block_sizes", spy)
    q = jax.ShapeDtypeStruct((1, 4096, 4, 128), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 4096, 2, 128), jnp.float32)
    for window, bk in ((128, 128), (100, 128), (2048, 1024), (300, 512)):
        jax.eval_shape(lambda q, k, v: flash_window_fwd(q, k, v, window),
                       q, kv, kv)
        assert seen["flash_fwd_window"] == (512, bk)


# -- (e) the pool: two shapes out of one budget -------------------------------- #

FULL, WINDOW = PagedKV(1, 24, value_dim=16), WindowKV(2, 24, W, value_dim=16)


def _pool(num_pages=40, **kw):
    return BlockPool(3, page_size=PS, num_pages=num_pages,
                     specs=[FULL, WINDOW, WINDOW], **kw)


def test_page_layout_gives_each_group_its_span(model):
    groups, entry_of, group_of = page_layout(model.cache_specs())
    assert [(g.spec, g.layers, g.span) for g in groups] == [
        (FULL, (0,), 1), (FULL, (5,), 1), (WINDOW, (1,), 2),
        (WINDOW, (2,), 2), (WINDOW, (3,), 2), (WINDOW, (4,), 2),
        (WINDOW, (6,), 2)]
    assert entry_of == [0] * 7 and group_of == [0, 2, 3, 4, 5, 1, 6]
    # what is still refused: another width, a head count that is no whole
    # multiple, three sizes, a latent page beside K and V
    for other in (PagedKV(1, 32, value_dim=16), PagedKV(1, 24),
                  LatentKV(16, 8)):
        with pytest.raises(ValueError):
            page_layout([FULL, other])
    with pytest.raises(ValueError):
        page_layout([PagedKV(2, 16), PagedKV(3, 16)])
    with pytest.raises(ValueError):
        page_layout([PagedKV(1, 16), PagedKV(2, 16), PagedKV(4, 16)])
    with pytest.raises(ValueError):   # an int8 pool of two shapes
        _pool(quantized=True)


def test_units_blocks_and_the_view_are_the_same_memory():
    """A window page is two adjacent units: written through the coarser view
    (as the model's decode step does), read back as units."""
    pool = _pool()
    assert pool.span == 2 and pool.depth == 1 and len(pool.kv) == 1
    assert (pool.pages_total, pool.pages_free, pool.blocks_free) == (38, 38, 19)
    k, v = pool.kv[0]
    assert k.shape == (40, 1, PS, 24) and v.shape == (40, 1, PS, 16)
    page = pool.alloc(2)
    assert page % 2 == 0 and page >= 2 and pool.pages_free == 36
    data = np.arange(2 * PS * 24, dtype=np.float32).reshape(1, 2, PS, 24)
    view = k.reshape(20, 2, PS, 24).at[page // 2].set(data[0])
    pool.kv[0] = (view.reshape(k.shape), v)
    units = pool.units_of([page])
    assert list(units) == [page, page + 1]
    got = pool.read_pages(units)[0][0]
    assert np.array_equal(got, data.reshape(2, 1, PS, 24))
    # the prompt's scatter takes the group's stacked pages and its span
    other = pool.alloc(2)
    vdata = np.ones((1, 2, PS, 16), np.float32)
    pool.write_prompt_pages([other], [True], [data + 1], [vdata], span=2)
    got = pool.read_pages(pool.units_of([other]))[0]
    assert np.array_equal(got[0], (data + 1).reshape(2, 1, PS, 24))
    assert np.array_equal(got[1], vdata.reshape(2, 1, PS, 16))
    assert not pool.read_pages([0, 1])[0][0].any()    # the null block
    assert pool.units_of([]).size == 0


def test_aligned_release_and_reuse_and_the_refused_counter():
    """Singles break a block only when no broken one has a unit left; a
    block is whole again when its last unit comes back; a window page
    refused while as many free units lay unpaired is counted."""
    pool = _pool(num_pages=12)           # blocks 1 .. 5: units 2 .. 11
    refused = lambda: _counter("serving_pool_alloc_refused_total",
                               kind="window")
    before = refused()
    singles = [pool.alloc() for _ in range(4)]
    assert singles == [2, 3, 4, 5]       # block 1, then block 2: the near end
    pairs = [pool.alloc(2), pool.alloc(2)]
    assert pairs == [10, 8]              # the far end, whole blocks
    assert (pool.pages_free, pool.blocks_free) == (2, 1)
    pool.release(3)
    pool.release(4)                      # two free units, no two adjacent
    assert (pool.pages_free, pool.blocks_free) == (4, 1)
    assert pool.alloc(2) == 6 and pool.blocks_free == 0
    assert pool.alloc(2) is None and refused() == before + 1
    assert pool.alloc() in (3, 4) and pool.alloc() in (3, 4)
    assert pool.alloc() is None and pool.alloc(2) is None
    assert refused() == before + 1       # a dry pool refuses nothing it has
    pool.release(2)
    pool.release(3)                      # block 1 whole again
    assert pool.blocks_free == 1 and pool.alloc(2) == 2
    for page in (4, 5, 6, 8, 10, 2):
        pool.release(page)
    assert (pool.pages_free, pool.blocks_free) == (10, 5)
    assert not pool._broken and not pool.ref.any()


def _fill(eng, prompt_len, new_tokens, seed=0):
    """Requests until the scheduler admits no more; the peak share of the
    pool's units in use, and the engine."""
    for i in range(60):
        eng.add_request(_prompt(prompt_len, seed + i),
                        max_new_tokens=new_tokens)
    peak = 0.0
    for _ in range(6):
        eng.step()
        peak = max(peak, 1 - eng.pool.pages_free / eng.pool.pages_total)
    return peak


@pytest.mark.parametrize("prompt_len,window_share", [(150, 0.35), (18, 0.78)],
                         ids=["all-long", "all-short"])
def test_either_mix_fills_ninety_percent_of_the_bytes(model, prompt_len,
                                                      window_share):
    """One budget serves both shapes: rows of 150 tokens hold mostly full
    pages, rows of 18 mostly window pages, and either fill reaches 90 % of
    the pool's bytes (a static split sized for the one would strand the
    other); the gauges say which shape holds the bytes."""
    eng = _engine(model, max_batch_size=48, max_seq_len=200, num_pages=1200,
                  watermark_pages=0)
    assert _fill(eng, prompt_len, 8) >= 0.90
    assert eng.live_count < 48             # pages, not rows, ended admission
    pages = default_registry().get("serving_pages_live")
    live = default_registry().get("serving_pool_bytes_live")
    unit = eng.pool.bytes_per_page
    assert unit == PS * (24 + 16) * 4
    held = {"full": 0, "window": 0}
    for g, t in zip(eng.groups, eng.group_tables):
        held[g.spec.kind] += int((t >= 0).sum())
    assert pages.value(kind="full") == held["full"]
    assert pages.value(kind="window") == held["window"]
    assert live.value(kind="full") == held["full"] * unit
    assert live.value(kind="window") == held["window"] * 2 * unit
    used = (eng.pool.pages_total - eng.pool.pages_free) * unit
    assert live.value(kind="full") + live.value(kind="window") == used
    assert abs(live.value(kind="window") / used - window_share) < 0.08
    eng.run()
    assert eng.pool.pages_free == eng.pool.pages_total and not eng.pool._broken


def test_admission_charges_units_and_whole_blocks(model):
    eng = _engine(model)
    # 90 tokens: 12 pages in each full group, and of each window group the
    # pages holding positions 75 .. 89: 9 .. 11, three
    assert eng._prompt_by_group(90) == [12, 12, 3, 3, 3, 3, 3]
    assert eng._prompt_pages(90) == 24 + 15
    assert list(eng._cost(eng._prompt_by_group(90))) == [24 + 2 * 15, 15]
    assert eng._held_by_group(160) == [20, 20, 3, 3, 3, 3, 3]
    assert list(eng.sched.groups) == [12, 5]
    assert list(eng.sched._watermark(3)) == [36, 15]
    assert list(eng._free()) == [eng.pool.pages_free, eng.pool.blocks_free]
    gauge = default_registry().get("serving_kv_bytes_per_token")
    assert gauge.value() == (2 * 1 + 5 * 2) * (24 + 16) * 4
    # free units that lie unpaired admit no prompt that needs blocks
    small = _engine(model, num_pages=100, watermark_pages=0)
    odd = [small.pool.alloc() for _ in range(98)]
    for page in odd[::2]:
        small.pool.release(page)
    assert (small.pool.pages_free, small.pool.blocks_free) == (49, 0)
    small.add_request(_prompt(20, 1), max_new_tokens=4)   # 6 units, 15 blocks'
    small.step()
    assert small.live_count == 0 and small.sched.waiting_prefill == 1
    for page in odd[1::2]:
        small.pool.release(page)
    small.run()
    assert small.pool.pages_free == small.pool.pages_total


def test_prefix_hit_and_copy_on_write_beside_window_release(model):
    """Two requests with one prompt of 20 tokens (its third page partial):
    the full groups' pages are shared by prefix key (a key a group), the
    window groups' are each row's own; the first decode write copies the
    shared tail page of BOTH full groups, while the rows' lengths pass their
    first window pages, which go back whole."""
    eng = _engine(model, num_pages=200)
    p = _prompt(20, 11)
    hits = _counter("serving_prefix_hits_total")
    cows = _counter("serving_cow_copies_total")
    eng.add_request(p, max_new_tokens=30)
    eng.add_request(p, max_new_tokens=30)
    eng._admit()
    full = eng.group_tables[:2]
    for t in full:
        assert (t[0, :3] == t[1, :3]).all() and (t[0, :3] >= 0).all()
    assert not set(full[0][0, :3]) & set(full[1][0, :3])   # a key a group
    for t in eng.group_tables[2:]:
        assert not set(t[0][t[0] >= 0]) & set(t[1][t[1] >= 0])
        assert (t[0][t[0] >= 0] % 2 == 0).all()            # whole blocks
    assert _counter("serving_prefix_hits_total") == hits + 6
    a, b = eng.run()
    assert a.generated == b.generated
    assert _counter("serving_cow_copies_total") >= cows + 2
    assert eng._window_released > 0
    assert eng.pool.pages_free == eng.pool.pages_total and not eng.pool._broken


def test_window_pages_are_released_as_the_row_passes_them(model):
    eng = _engine(model)
    eng.add_request(_prompt(12, 3), max_new_tokens=70)
    total = eng.pool.pages_total
    while eng.has_work():
        eng.step()
        if eng.active[0] is None:
            break
        L = int(eng.lengths[0])          # tokens cached after this tick
        first = max(0, L - W) // PS
        held = (L - 1) // PS - first + 1
        assert eng.window_start[0] == first
        units = 0
        for group, table in zip(eng.groups, eng.group_tables):
            pages = (table[0] >= 0).sum()
            assert pages == (held if group.window else -(-L // PS))
            assert (table[0, :pages] % group.span == 0).all()
            units += pages * group.span
        assert eng.pool.pages_free == total - units
    assert eng.pool.pages_free == total and not eng.pool.ref.any()


# -- (f) one-shape layouts are what they were --------------------------------- #

def _layout_as_the_parent_gave_it(specs):
    """`page_layout` as the parent commit computed it, written out: kinds in
    order of first appearance, full kinds first, runs of gcd layers."""
    import math

    kinds = {}
    for li, spec in enumerate(specs):
        if not isinstance(spec, RowState):
            kinds.setdefault(spec, []).append(li)
    depth = math.gcd(*(len(v) for v in kinds.values()))
    groups = [(spec, tuple(layers[i:i + depth]))
              for spec, layers in sorted(
                  kinds.items(), key=lambda kv: isinstance(kv[0], WindowKV))
              for i in range(0, len(layers), depth)]
    group_of, array_of = {}, {}
    for gi, (_, layers) in enumerate(groups):
        for j, li in enumerate(layers):
            group_of[li], array_of[li] = gi, j
    entry_of, array_entry = [], {}
    for li in range(len(specs)):
        if li in array_of:
            entry = array_entry.setdefault(array_of[li], len(set(entry_of)))
        else:
            entry = len(set(entry_of))
        entry_of.append(entry)
    return groups, entry_of, [group_of.get(li) for li in range(len(specs))]


@pytest.mark.parametrize("build", [
    lambda: GraniteHybridForCausalLM(granite_hybrid_tiny()),
    lambda: AfmoeForCausalLM(afmoe_tiny()),
    lambda: KimiK2ForCausalLM(kimi_k2_tiny())],
    ids=["granite", "trinity", "kimi"])
def test_one_shape_layouts_and_pools_are_unchanged(build):
    paddle.seed(0)
    specs = build().cache_specs()
    groups, entry_of, group_of = page_layout(specs)
    want_groups, want_entry, want_group = _layout_as_the_parent_gave_it(specs)
    assert [(g.spec, g.layers) for g in groups] == want_groups
    assert (entry_of, group_of) == (want_entry, want_group)
    assert {g.span for g in groups} == {1}
    pool = BlockPool(len(specs), page_size=8, num_pages=11, specs=specs,
                     rows=2)
    assert pool.span == 1 and (pool.pages_total, pool.pages_free) == (10, 10)
    assert list(pool.free) == list(range(1, 11))
    first = [pool.alloc() for _ in range(3)]
    pool.release(first[1])
    assert first == [1, 2, 3] and pool.alloc() == 4 and pool.free[-1] == 2
    paged = next(s for s in specs if not isinstance(s, RowState))
    shapes = [a.shape for a in pool.kv[pool.page_entries[0]]]
    assert shapes == [(11,) + tuple(s) for s in paged.page_arrays(8)]


def test_a_plain_pools_scheduler_still_counts_in_numbers():
    paddle.seed(0)
    eng = PagedServingEngine(AfmoeForCausalLM(afmoe_tiny()), max_batch_size=4,
                             max_seq_len=160, page_size=8)
    assert isinstance(eng._cost(eng._prompt_by_group(90)), int)
    assert eng._cost(eng._prompt_by_group(90)) == 12 + 3 * 5 == (
        eng._prompt_pages(90))
    assert eng.sched.groups == 4 and eng._free() == eng.pool.pages_free


# -- tracing ------------------------------------------------------------------ #

def test_decode_dispatch_says_what_both_kernels_must_read(model):
    from paddle_tpu.observability import spans

    eng = _engine(model)
    eng.add_request(_prompt(40, 1), max_new_tokens=6)
    eng.add_request(_prompt(10, 2), max_new_tokens=6)
    eng.step()
    tl = spans.enable_step_timeline()
    try:
        eng.step()
    finally:
        tl.uninstall()
    (attrs,) = [r["attrs"] for r in spans.recorded()
                if r["path"] == "engine.step/decode_dispatch"][-1:]
    spans.clear_recorded()
    ctx = [int(n) for n in eng.lengths[:2]]   # the tick's keys, its own in
    assert attrs["context_tokens"] == sum(ctx)
    assert attrs["window_tokens"] == sum(min(c, W) for c in ctx)
    for name in ("live_grid_steps", "window_live_grid_steps",
                 "pages_per_step", "window_pages_per_step"):
        assert name in attrs
    eng.run()


# -- the yardstick's counts and readers ---------------------------------------- #

def _cell_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2-flash.json")) as f:
        return json.load(f)


def _load_reader(name):
    spec = importlib.util.spec_from_file_location(
        f"benchmark.readers.{name}",
        os.path.join(ROOT, "benchmark", "readers", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_costs_by_hand_for_the_published_widths():
    from benchmark import costs_mimo_v2 as costs

    config = _cell_config()
    assert costs.layer_kinds(config) == [
        (0, 0), (1, 1), (1, 1), (1, 1), (1, 1), (0, 1), (1, 1)]
    assert (costs.layers_of(config, False), costs.layers_of(config, True)) \
        == (2, 5)
    # ISSUE 39 section 2: a full layer's attention 89.13 M, a sliding one's
    # 94.37 M, an expert 25.17 M
    assert costs.attention_params(config, False) == 4096 * (
        12288 + 768 + 512 + 8192) == 89_128_960
    assert costs.attention_params(config, True) == 94_371_840
    assert costs.expert_params(config) == 25_165_824
    assert costs.kv_bytes_per_token_layer(config, False) == 2560
    assert costs.kv_bytes_per_token_layer(config, True) == 5120
    assert costs.decode_full_bytes(config, 1000) == 1000 * 2 * 2560
    assert costs.decode_window_bytes(config, 1000) == 1000 * 5 * 5120
    assert costs.pair_flops(config) == 64 * (2 * 192 + 2 * 128) == 40_960
    assert costs.band_pairs(100, 128) == 5050
    assert costs.band_pairs(1000, 128) == 128 * 129 // 2 + 872 * 128
    assert costs.window_prefill_flops(config, [1000]) == (
        costs.band_pairs(1000, 128) * 40_960 * 5)
    body = (2 * 89_128_960 + 5 * 94_371_840 + 3 * 4096 * 16384
            + 6 * (4096 * 256 + 0.25 * 25_165_824))
    assert costs.matmul_params_per_token(config, head=False) == body
    assert costs.matmul_params_per_token(config) == body + 152576 * 4096
    assert costs.decode_flops_per_token(config, 5000, 128) == (
        2 * (body + 152576 * 4096) + 40_960 * (2 * 5000 + 5 * 128))
    assert costs.prompt_flops(config, 1000) == (
        2 * 1000 * body + 2 * 152576 * 4096
        + 40_960 * (2 * 500_500 + 5 * costs.band_pairs(1000, 128)))
    # every decoded token of the cell's mix is past the window
    assert costs.mean_window_context(config, [1024, 12288], [256, 4096],
                                     16384) == 128
    assert costs.mean_window_context(config, [100], [60], 1000) == (
        sum(min(100 + t, 128) for t in range(1, 60)) / 59)


def _span(path, tick, **attrs):
    return types.SimpleNamespace(path=path, root=tick, attrs=attrs)


ANCHOR = ("bm.engine_step", "engine.step")


def test_roofline_reader_on_a_trace_written_by_hand():
    from benchmark import costs_mimo_v2 as costs

    reader = _load_reader("kernel_roofline_mimo_v2")
    config = _cell_config()
    call = ('%{}.3 = bf16[256,4,16,128] custom-call(), '
            'custom_call_target="tpu_custom_call"')
    ops = [(call.format("decode_paged"), 1.0, 0.004),
           (call.format("decode_paged"), 2.0, 0.006),
           (call.format("decode_window"), 3.0, 0.002),
           (call.format("flash_fwd_window"), 4.0, 0.001),
           (call.format("decode_paged"), 99.0, 0.5)]     # outside the window
    trace = types.SimpleNamespace(window=(0.0, 10.0),
                                  devices={0: {"XLA Ops": ops}})
    spans = [_span("engine.step/decode_dispatch", 0, context_tokens=900_000,
                   window_tokens=20_000),
             _span("engine.step/decode_dispatch", 1, context_tokens=100_000,
                   window_tokens=5_600),
             _span("engine.step/admit/prefill", 1, prompt_len=4000),
             _span("engine.step/admit/prefill", None, prompt_len=9999)]
    run = types.SimpleNamespace(
        trace=trace, config=config, _program_spans={ANCHOR: spans},
        peaks=lambda: {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    paged = reader.read(run, {}, kernel="decode_paged")
    assert paged == pytest.approx(
        100 * costs.decode_full_bytes(config, 1_000_000) / 819e9 / 0.010)
    assert paged == pytest.approx(100 * 5120e6 / 819e9 / 0.010)
    window = reader.read(run, {}, kernel="decode_window")
    assert window == pytest.approx(100 * 25_600 * 25_600 / 819e9 / 0.002)
    prefill = reader.read(run, {}, kernel="flash_fwd_window")
    assert prefill == pytest.approx(
        100 * costs.window_prefill_flops(config, [4000]) / 197e12 / 0.001)
    # nothing to read: a program without the kernel, the spans, or a trace
    assert reader.read(run, {}, kernel="decode_latent") is None
    run._program_spans = {ANCHOR: None}
    assert reader.read(run, {}, kernel="decode_paged") is None
    run.trace = None
    assert reader.read(run, {}, kernel="decode_paged") is None


def test_mfu_reader_counts_the_window_by_hand():
    from benchmark import costs_mimo_v2 as costs

    reader = _load_reader("mfu_required_mimo_v2")
    config = _cell_config()
    mix = {"prompt_len": {"lo": 2000, "hi": 2000, "levels": 1},
           "answer_len": {"lo": 500, "hi": 500, "levels": 1},
           "max_total": 16384}
    ticks = [{"decoded_rows": 150, "context_tokens": 700_000,
              "first_tokens": 2},
             {"decoded_rows": 150, "context_tokens": 800_000,
              "first_tokens": 0}]
    run = types.SimpleNamespace(
        window=(10.0, 12.0), config=config, mix=mix,
        peaks=lambda: {"bf16_flops_per_s": 197e12})
    flops = (300 * costs.decode_flops_per_token(config, 5000, 128)
             + 2 * costs.prompt_flops(config, 2000))
    assert reader.read(run, {"series": {"ticks": ticks}}) == pytest.approx(
        100 * flops / 2.0 / 197e12)
    assert reader.read(run, {"series": {"ticks": []}}) is None


# -- the cell and the manifest -------------------------------------------------- #

def test_the_cells_rehearsal_runs_end_to_end_and_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PADDLE_TPU_PALLAS_INTERPRET="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PADDLE_TPU_HW", None)
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "2",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["rehearsal"]["would_report"] == ["serve_tok_s", "setup_s"]


def test_a_wrong_token_and_a_lower_precision_are_not_correct():
    """The comparison's two limits at the rehearsal's size: a served answer
    passes; the same answer with ONE token the reference has no reason to
    prefer (three spreads under the row's largest, at the last position,
    which is no later position's input) breaks the limit on the worst
    position and hardly moves the mean."""
    from benchmark import harness
    from benchmark.families import mimo_v2 as family

    config = harness.rehearsal_sizes(_cell_config())
    paddle.seed(5)
    m = MimoV2ForCausalLM(family._model_config(config))
    m.eval()
    assert m.config.held_experts == (0, 4) and m.config.n_routed_experts == 16
    assert m.config.hybrid_layer_pattern == (0, 1, 1, 1, 1, 0, 1)
    eng = _engine(m, max_batch_size=2, max_seq_len=128, num_pages=200)
    eng.add_request(_prompt(20), max_new_tokens=100)
    (done,) = eng.run()
    prompt = np.asarray(done.prompt)
    served = np.asarray(done.generated, np.int32)
    ok, detail = family.check_served(config, m, [(prompt, served)])
    assert ok, detail
    assert detail["samples"][0]["beyond_window"]
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
    assert sample["worst_share"] > detail["worst_tolerance"]
    assert sample["mean_share"] == pytest.approx(3.0 / 100, abs=0.005)


def test_the_cell_and_its_metrics_are_in_the_manifest():
    """The manifest's own checks run under `tests/test_benchmark_suite.py`."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mimo-v2-flash", "saturated-agent-16k", 1)
    (entry,) = [c for c in manifest["configs"] if c["name"] == "mimo-v2-flash"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    mine = sorted(m["name"] for m in manifest["per_layer"]
                  if m.get("workloads") == [CELL])
    assert mine == ["compiles_in_window.mimo", "decode_attn_roofline.mimo",
                    "decode_window_roofline.mimo", "kv_pool_peak_share.mimo",
                    "mfu_required.mimo", "window_prefill_roofline.mimo"]
    assert len(manifest["per_layer"]) == 128
    (tok,) = [m for m in manifest["end_to_end"] if m["name"] == "serve_tok_s"]
    assert tok["workloads"][-1] == CELL
    config = _cell_config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):   # every other key as the catalog has it
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows if r["name"] == "MiMo-V2-Flash"]
        changed = {k for k, v in row["config"].items() if config.get(k) != v}
        assert changed == set(entry["reduced"])
    mix_path = os.path.join(ROOT, "benchmark", "traffic",
                            "saturated-agent-16k.json")
    with open(mix_path) as f:
        mix = json.load(f)
    assert (mix["prompt_len"], mix["answer_len"]) == (
        {"lo": 1024, "hi": 12288, "levels": 8},
        {"lo": 256, "hi": 4096, "levels": 9})
    assert (mix["max_total"], mix["sampled_every"], mix["temperature"],
            mix["min_waiting"]) == (16384, 4, 0.6, 16)


# -- a prefill skips the pieces of its bucket past the prompt ------------------- #

@pytest.mark.parametrize("n", [17, 40, 64])
def test_a_prefill_in_pieces_is_the_whole_one(monkeypatch, model, n):
    """With `seq_lens`, a bucket of 64 tokens is done in eight pieces of 8
    (the chip's are 256 and more) and those past the prompt are skipped:
    the prompt's logits and its K and V are what the whole call gives, the
    skipped pieces zeros."""
    from paddle_tpu.models import mimo_v2

    ids = np.zeros((1, 64), np.int32)
    ids[0, :n] = _prompt(n, n)

    def prefill():
        with paddle.no_grad():
            logits, caches = model(
                paddle.to_tensor(ids), caches=(),
                seq_lens=paddle.to_tensor(np.asarray([n], np.int32)))
        return np.asarray(logits._value), [
            [np.asarray(a._value) for a in layer] for layer in caches]

    assert mimo_v2._pieces(64, 1) == 0          # too short to be cut
    whole, whole_kv = prefill()
    monkeypatch.setattr(mimo_v2, "_MIN_PIECE", 8)
    assert mimo_v2._pieces(64, 1) == 8 and mimo_v2._pieces(60, 1) == 0
    cut, cut_kv = prefill()
    assert np.abs(cut[0, :n] - whole[0, :n]).max() < ATOL
    assert np.abs(cut[0, :n] - _reference_logits(model, ids[0, :n])).max() \
        < ATOL
    live = -(-n // 8) * 8
    for got, want in zip(cut_kv, whole_kv):
        for a, b in zip(got, want):
            assert np.abs(a[0, :n] - b[0, :n]).max() < ATOL
            assert not a[0, live:].any()
