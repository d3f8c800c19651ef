"""The latent-attention decoder (`models/kimi_k2.py`: one normed latent and
one rotated key a token, expanded heads in a prefill, absorbed projections in
a decode step over latent pages, sigmoid-routed experts of which a chip holds
a share) through the paged engine, against the plain float32 reference
(`benchmark/reference/kimi_k2.py`, the EXPANDED form only) at a tiny size on
the CPU (pages of 8). Logits are compared, not tokens: with random weights
the largest logit changes on rounding.

Tolerances. Model and reference are both float32 here (conftest sets
`highest` matmuls), so they differ by the order of summation alone, and by
the absorbed form's other order of products. A row of logits has a standard
deviation of about 0.16 at this size: 2e-5 absolute is a hundred times what
was seen (2e-7) and a ten-thousandth of a spread, where a wrong page, a
missing rotation, a wrong slice of `kv_b_proj` or a wrong expert moves a row
by a good part of one.
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
from benchmark.reference import kimi_k2 as reference
from paddle_tpu.incubate.distributed.models.moe import HeldExpertsMoE
from paddle_tpu.inference.paged import (BlockPool, LatentKV, PagedKV,
                                        PagedServingEngine)
from paddle_tpu.inference.paged.block_pool import page_layout
from paddle_tpu.models import GPTForCausalLM, gpt3_tiny
from paddle_tpu.models import kimi_k2
from paddle_tpu.models.afmoe import AfmoeForCausalLM, afmoe_tiny
from paddle_tpu.models.granite_hybrid import (GraniteHybridForCausalLM,
                                              granite_hybrid_tiny)
from paddle_tpu.models.kimi_k2 import (KimiK2Config, KimiK2ForCausalLM,
                                       kimi_k2_tiny)
from paddle_tpu.observability.metrics import default_registry
from paddle_tpu.ops.pallas import autotune
from paddle_tpu.ops.pallas.decode_attention import (latent_decode_attention,
                                                    latent_kv_write)
from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-5
PS = 8       # the engines' page size here
CELL = "serve-kimi-k2-reason-sat"


@pytest.fixture(autouse=True)
def _interpret_mode(pallas_interpret_unless_hw):
    pass


def _as_config_dict(cfg):
    """The model's config under the configuration file's keys, as the
    reference reads them."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _build(seed=7, **kw):
    paddle.seed(seed)
    m = KimiK2ForCausalLM(kimi_k2_tiny(**kw))
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
    kw = {"max_batch_size": 4, "max_seq_len": 96, "page_size": PS, **kw}
    return PagedServingEngine(m, **kw)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


# -- 1. the whole forward --------------------------------------------------- #

def test_full_forward_matches_the_reference(model):
    """One dense and two expert layers, all experts held, positions on both
    sides of YaRN's original context (16), a batch of two."""
    ids = np.random.default_rng(0).integers(1, 256, (2, 60)).astype(np.int32)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids))._value)
    for b in range(2):
        assert np.abs(got[b] - _reference_logits(model, ids[b])).max() < ATOL


def test_a_held_share_matches_the_reference_given_the_same_share():
    m = _build(seed=9, held_experts=(4, 4))
    ids = _prompt(50, 1)
    with paddle.no_grad():
        got = np.asarray(m(paddle.to_tensor(ids[None]))._value[0])
    assert np.abs(got - _reference_logits(m, ids)).max() < ATOL


# -- 2. the two forms are one function -------------------------------------- #

def test_the_absorbed_form_equals_the_expanded_form():
    """ONE attention layer, float32, the same parameters: the prefill's
    expanded heads over 30 positions, then the decode step's absorbed
    projections for position 29 over latent pages that hold what the prefill
    says positions 0-28 leave behind."""
    paddle.seed(3)
    cfg = kimi_k2_tiny()
    attn = kimi_k2.KimiK2Attention(cfg)
    S = 30
    u = paddle.to_tensor(np.random.default_rng(1).normal(
        size=(1, S, cfg.hidden_size)).astype(np.float32))
    pos = paddle.to_tensor(np.arange(S, dtype=np.int32)[None])
    with paddle.no_grad():
        expanded, (rows,) = attn(u, pos)
    spec = LatentKV(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    assert rows.shape == [1, S, spec.stored_dim]
    latents = np.asarray(rows._value[0])
    assert not latents[:, cfg.kv_lora_rank + cfg.qk_rope_head_dim:].any()
    pages = np.zeros((6, PS, spec.stored_dim), np.float32)
    table = np.array([[3, 1, 5, 2]], np.int32)
    for t in range(S - 1):
        pages[table[0, t // PS], t % PS] = latents[t]
    with paddle.no_grad():
        absorbed, (new_pages,) = attn(
            u[:, S - 1:], paddle.to_tensor(np.array([[S - 1]], np.int32)),
            cache=(paddle.to_tensor(pages),),
            cache_offset=paddle.to_tensor(np.array([S - 1], np.int32)),
            table=paddle.to_tensor(table))
    assert np.abs(np.asarray(absorbed._value[0, 0])
                  - np.asarray(expanded._value[0, S - 1])).max() < 2e-6
    # and the step left position 29's own row where the prefill says
    wrote = np.asarray(new_pages._value)[table[0, (S - 1) // PS], (S - 1) % PS]
    assert np.abs(wrote - latents[S - 1]).max() < 1e-6


# -- 3. prefill, then decode through the latent pages ------------------------ #

def _serve_logit_for_logit(m, prompts, new_tokens, **kw):
    """Serve `prompts` together and hold EVERY decode tick's logits of every
    live row against the reference's one expanded forward over what the row
    has seen. Returns the engine."""
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
            if len(req.generated) == len(ids) - len(req.prompt):
                continue   # admitted this tick: no decode row of its own yet
            padded = np.zeros(96, np.int32)
            padded[:len(ids)] = ids
            want = _reference_logits(m, padded, rows=[len(ids) - 1])[0]
            worst = max(worst, np.abs(logits[i] - want).max())
    assert worst < ATOL, worst
    return eng


def test_prefill_then_decode_across_page_boundaries(model):
    """Prompts inside their buckets (20 and 37 of 32 and 64; 9 of 16; 24
    ends ON a page boundary, so its first decoded token opens a page), 30
    tokens each, so every row crosses three or four page boundaries; every
    tick logit for logit: the prompt cached by the expanded prefill, every
    token read back by the absorbed kernel."""
    eng = _serve_logit_for_logit(
        model, [_prompt(n, n) for n in (20, 37, 9, 24)], 30)
    assert eng.pool.pages_free == eng.pool.pages_total
    assert not eng.pool.ref.any()
    tiles = autotune.chosen_tiles()
    assert tiles["decode_latent"]["consults"] > 0
    assert tiles["flash_fwd"]["consults"] > 0


def test_first_token_comes_from_the_prompts_last_position(model):
    eng = _engine(model)
    p = _prompt(37, 5)
    eng.add_request(p, max_new_tokens=1)
    (done,) = eng.run()
    want = _reference_logits(model, p, rows=[len(p) - 1])[0]
    assert done.generated == [int(want.argmax())]


# -- 4. prefix sharing and copy-on-write ------------------------------------- #

def test_a_prefix_shared_page_and_its_copy_on_write(model):
    """Two requests with one prompt of 37 tokens: all five pages are shared
    by prefix key, the last of them partly full; the first decoded token
    would write into it, so each row but the last to write copies it first.
    Both answers are the lone request's."""
    p = _prompt(37, 11)
    lone = _engine(model)
    lone.add_request(p, max_new_tokens=12)
    (want,) = lone.run()
    eng = _engine(model)
    eng.add_request(p, max_new_tokens=12)
    eng.add_request(p, max_new_tokens=12)
    eng._admit()
    assert (eng.tables[0, :5] == eng.tables[1, :5]).all()
    assert (eng.tables[0, :5] >= 0).all()
    assert eng.pool.pages_total - eng.pool.pages_free == 5
    a, b = eng.run()
    assert eng.pool.cow_copies_total >= 1
    assert a.generated == b.generated == want.generated
    assert eng.pool.pages_free == eng.pool.pages_total


# -- 5. preemption ----------------------------------------------------------- #

def test_a_preempted_row_resumes_token_for_token(model):
    prompts = [_prompt(40, 21), _prompt(28, 22)]
    calm = _engine(model)
    for p in prompts:
        calm.add_request(p, max_new_tokens=40)
    want = {tuple(r.prompt): r.generated for r in calm.run()}
    # a pool that cannot hold both rows to their ends: the newer is spilled,
    # its latent pages go to the host and come back
    eng = _engine(model, num_pages=16, watermark_pages=0)
    for p in prompts:
        eng.add_request(p, max_new_tokens=40)
    done = eng.run()
    assert sum(r.preemptions for r in done) >= 1
    for r in done:
        assert r.generated == want[tuple(r.prompt)]
    assert eng.pool.pages_free == eng.pool.pages_total
    # what a spill carries: per page array ONE array, the latent pages
    host = eng.pool.read_pages([1, 2])
    assert len(host) == eng.pool.depth == 3
    assert [a.shape for a in host[0]] == [(2, PS, 128)]


# -- 6. the spec, the pool and the budget, by hand --------------------------- #

def test_latent_pages_cost_what_the_spec_says():
    spec = LatentKV(512, 64)
    assert spec.stored_dim == 640 and spec.kind == "latent"
    assert spec.page_arrays(64) == ((64, 640),)
    # 64 tokens x 640 values x 2 bytes a layer
    assert spec.page_nbytes(64, jnp.bfloat16) == 81_920
    assert not hasattr(spec, "prefill_cache")
    with pytest.raises(ValueError):
        spec.page_nbytes(64, jnp.bfloat16, quantized=True)
    # the other kinds answer what the pool computed itself before
    assert PagedKV(4, 128).page_nbytes(32, jnp.bfloat16) == 2 * 4 * 32 * 128 * 2
    assert PagedKV(4, 128).page_nbytes(32, jnp.float32, True) == 2 * (
        4 * 32 * 128 + 4 * 4)
    assert BlockPool.page_nbytes(3, 4, 128, 32, jnp.bfloat16) == 3 * 65_536
    groups, entry_of, group_of = page_layout([spec] * 5)
    assert len(groups) == 1 and groups[0].layers == (0, 1, 2, 3, 4)
    assert entry_of == [0, 1, 2, 3, 4] and group_of == [0] * 5
    with pytest.raises(ValueError):
        page_layout([spec, PagedKV(1, 640)])
    pool = BlockPool(5, page_size=64, num_pages=4, dtype=jnp.bfloat16,
                     specs=[spec] * 5)
    assert pool.bytes_per_page == 5 * 81_920
    assert pool.bytes_per_token == 5 * 1280 == 6400
    assert [a.shape for a in pool.kv[0]] == [(4, 64, 640)]
    with pytest.raises(ValueError):
        BlockPool(5, page_size=64, num_pages=4, specs=[spec] * 5,
                  quantized=True)


def test_the_budget_becomes_pages_by_the_specs_bytes(model):
    # f32 here: a page is 8 tokens x 128 values x 4 bytes x 3 layers
    eng = _engine(model, kv_budget_bytes=100_000)
    assert eng.pool.bytes_per_page == 3 * 8 * 128 * 4 == 12_288
    assert eng.pool.num_pages == 100_000 // 12_288 == 8
    assert eng.pool.kv[0][0].shape == (8, PS, 128)
    assert eng._decode_grid == {"pages_per_step": 8, "grid_steps": 4 * 2}
    with pytest.raises(ValueError):
        _engine(model, kv_budget_bytes=100_000, kv_quant=True)


def test_engine_reports_latent_pages_and_what_the_kernel_must_read(model,
                                                                   tmp_path):
    from paddle_tpu.observability import spans

    eng = _engine(model)
    eng.add_request(_prompt(20, 8), max_new_tokens=6)
    eng.add_request(_prompt(11, 9), max_new_tokens=6)
    eng.step()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
    finally:
        jax.profiler.stop_trace()
    gauge = default_registry().get("serving_pages_live")
    assert gauge.value(kind="latent") == (eng.tables >= 0).sum() == 3 + 2
    assert default_registry().get("serving_kv_bytes_per_token").value() == (
        3 * 128 * 4)
    (dispatch,) = [r for r in spans.recorded()
                   if r["path"] == "engine.step/decode_dispatch"][-1:]
    # the second tick: contexts 21 + 1 and 12 + 1 with the token it writes
    assert dispatch["attrs"]["latent_tokens"] == 22 + 13
    assert dispatch["attrs"]["pages_per_step"] == 8
    eng.run()


@pytest.mark.parametrize("prompts,steps", [
    ((20, 11), 2),        # 22 and 13 tokens: 3 and 2 pages, a step a row
    ((70, 11, 64), 5)],   # 72: 9 pages, two steps; 13: one; 66: 9, two
    ids=["a-step-a-row", "two-step-rows"])
def test_decode_dispatch_counts_the_latent_kernels_live_steps(model, prompts,
                                                              steps):
    """`live_grid_steps` of a seated batch on its second tick (a table 12
    pages wide at 8 a step: 2 steps a row, 8 in all): a hand count, and the
    count of the kernel's own work list; the histogram's `latent` series
    takes one observation a tick."""
    from paddle_tpu.observability import spans
    from paddle_tpu.ops.pallas import decode_attention as da

    eng = _engine(model)
    for i, n in enumerate(prompts):
        eng.add_request(_prompt(n, i), max_new_tokens=6)
    eng.step()
    share = default_registry().get("serving_decode_live_step_share")
    before = (share.count(kind="latent"), share.sum(kind="latent"))
    tl = spans.enable_step_timeline()
    try:
        eng.step()
    finally:
        tl.uninstall()
    (attrs,) = [r["attrs"] for r in spans.recorded()
                if r["path"] == "engine.step/decode_dispatch"][-1:]
    spans.clear_recorded()
    assert (attrs["pages_per_step"], attrs["grid_steps"]) == (8, 4 * 2)
    assert attrs["live_grid_steps"] == steps
    # the tick in flight's kernel sees lengths + 1 tokens a row
    work = da.work_list(jnp.asarray(eng.tables),
                        jnp.asarray(eng.lengths) + 1, PS, 8)
    assert int(work.count) == steps
    assert share.count(kind="latent") == before[0] + 1
    assert share.sum(kind="latent") - before[1] == pytest.approx(steps / 8)
    eng.run()


# -- 7. YaRN and the softmax scale, by hand ---------------------------------- #

def test_yarn_frequencies_and_the_softmax_scale_of_the_published_config():
    cfg = KimiK2Config()
    inv = kimi_k2.yarn_inv_freq(cfg)
    plain = 50000.0 ** (-np.arange(32) / 32.0)
    assert inv.shape == (32,)
    # 64 ln(4096 / 2 pi) / (2 ln 50000) = 19.16: pairs 0-19 keep their
    # frequency, pairs 20-31 turn 32 times slower
    assert np.allclose(inv[:20], plain[:20], rtol=1e-6)
    assert np.allclose(inv[20:], plain[20:] / 32, rtol=1e-6)
    assert kimi_k2._rope_amplitude(cfg) == 1.0
    assert kimi_k2.softmax_scale(cfg) == pytest.approx(0.13087, abs=1e-5)
    assert kimi_k2.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(32) + 1) ** 2)
    # the reference computes its own, from the file's keys
    config = _cell_config()
    assert np.allclose(reference.inv_freq(config), inv, rtol=1e-6)
    assert reference.softmax_scale(config) == pytest.approx(0.13087, abs=1e-5)
    # without scaling: the plain frequencies, the plain scale
    bare = KimiK2Config(rope_scaling=None)
    assert np.allclose(kimi_k2.yarn_inv_freq(bare), plain, rtol=1e-6)
    assert kimi_k2.softmax_scale(bare) == pytest.approx(192 ** -0.5)


# -- 8. the expert layer ------------------------------------------------------ #

def test_the_32_shares_and_the_shared_expert_add_up_to_the_whole_layer():
    """One expert of 32 on each of 32 chips: each share through
    `HeldExpertsMoE`, plus the shared expert counted once, against the uncut
    reference layer."""
    m = _build(seed=5, n_routed_experts=32, num_hidden_layers=2)
    layer = m.layers[1]
    x = np.random.default_rng(2).normal(size=(40, 64)).astype(np.float32)
    prefix = "layers.1."
    params = {k[len(prefix):]: v for k, v in _params(m).items()
              if k.startswith(prefix)}
    cfg = m.config
    sizes = {"low": False, "top_k": cfg.num_experts_per_tok,
             "route": cfg.routed_scaling_factor, "first": 0}
    whole = np.asarray(reference._experts(jnp.asarray(x), params, sizes))
    total = np.asarray(layer.shared_experts(paddle.to_tensor(x))._value)
    nonzero = 0
    for first in range(32):
        share = HeldExpertsMoE(64, 32, 32, 4, held=(first, 1), gate="sigmoid",
                               route_scale=cfg.routed_scaling_factor)
        share.router._value = layer.moe.router._value
        share.expert_bias._value = layer.moe.expert_bias._value
        share.w_in._value = layer.moe.w_in._value[first:first + 1]
        share.w_out._value = layer.moe.w_out._value[first:first + 1]
        part = np.asarray(share(paddle.to_tensor(x))._value)
        nonzero += bool(np.abs(part).max() > 0)
        total = total + part
    assert nonzero > 16          # the shares are not empty
    assert np.abs(total - whole).max() < 1e-5


def test_the_bias_decides_a_pick_and_never_a_weight():
    m = _build(seed=6, expert_bias_std=0.05)
    layer = m.layers[1].moe
    x = np.random.default_rng(4).normal(size=(64, 64)).astype(np.float32)
    with_bias = np.asarray(layer(paddle.to_tensor(x))._value)
    score = jax.nn.sigmoid(jnp.asarray(x) @ layer.router._value)
    plain = np.asarray(jax.lax.top_k(score, 4)[1])
    biased = np.asarray(jax.lax.top_k(score + layer.expert_bias._value, 4)[1])
    moved = (np.sort(plain, -1) != np.sort(biased, -1)).any(-1)
    assert moved.any() and not moved.all()
    bias = layer.expert_bias._value
    layer.expert_bias._value = jnp.zeros_like(bias)
    without = np.asarray(layer(paddle.to_tensor(x))._value)
    layer.expert_bias._value = bias
    assert np.abs(with_bias - without)[~moved].max() < 1e-6
    assert np.abs(with_bias - without)[moved].max() > 1e-4


# -- 9. the kernels against plain attention ---------------------------------- #

@pytest.mark.parametrize("lengths", [(5, 23, 0, 12), (9, 64, 1, 33),
                                     (40, 17, 8, 0)])
def test_latent_decode_kernel_matches_attention_over_gathered_latents(
        lengths):
    """Ragged lengths, a dead row (length 0, its table all -1), a row one
    token into a page (9, 17, 33), a row that ends ON a page boundary (64,
    40, 8); the table's unused slots name no page."""
    ps, H, L, R = 8, 4, 32, 8
    W = LatentKV(L, R).stored_dim
    rng = np.random.default_rng(sum(lengths))
    pages = rng.normal(size=(40, ps, W)).astype(np.float32)
    pages[:, :, L + R:] = 0
    tables = np.full((len(lengths), 8), -1, np.int32)
    free = iter(rng.permutation(np.arange(1, 40)))
    for b, n in enumerate(lengths):
        for slot in range(-(-n // ps)):
            tables[b, slot] = next(free)
    q = rng.normal(size=(len(lengths), H, W)).astype(np.float32)
    q[:, :, L + R:] = 0
    got = np.asarray(latent_decode_attention(
        jnp.asarray(q), jnp.asarray(pages), jnp.asarray(tables),
        jnp.asarray(lengths, jnp.int32), L, 0.3))
    assert got.shape == (len(lengths), H, L)
    for b, n in enumerate(lengths):
        if n == 0:
            assert not got[b].any()      # a free row comes out zero
            continue
        kv = np.concatenate([pages[p] for p in tables[b] if p >= 0])[:n]
        p = jax.nn.softmax(jnp.asarray(q[b] @ kv.T * 0.3), -1)
        assert np.abs(got[b] - np.asarray(p) @ kv[:, :L]).max() < 2e-6


def test_latent_write_puts_one_row_where_the_table_says():
    pages = jnp.zeros((6, PS, 128), jnp.float32)
    new = jnp.asarray(np.random.default_rng(0).normal(size=(3, 128)),
                      jnp.float32)
    tables = jnp.asarray([[2, 4, -1], [-1, -1, -1], [5, -1, -1]], jnp.int32)
    got = np.array(latent_kv_write(pages, new, tables,
                                   jnp.asarray([9, 3, 0], jnp.int32)))
    assert np.array_equal(got[4, 1], np.asarray(new[0]))   # slot 9 = (1, 1)
    assert np.array_equal(got[5, 0], np.asarray(new[2]))
    assert np.array_equal(got[0, 3], np.asarray(new[1]))   # parked: page 0
    got[4, 1] = got[5, 0] = got[0, 3] = 0
    assert not got.any()


def _causal_attention(q, k, v, scale):
    S = q.shape[1]
    s = jnp.einsum("bshd,bthd->bhst", q, k) * scale
    seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    return jnp.einsum("bhst,bthd->bshd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


@pytest.mark.parametrize("seq,d,dv,block", [
    (40, 24, 16, 8), (64, 24, 16, 16), (50, 48, 32, 16), (33, 16, 24, 8)])
def test_flash_fwd_with_a_value_width_of_its_own(monkeypatch, seq, d, dv,
                                                 block):
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK", str(block))
    rng = np.random.default_rng(seq)
    q = jnp.asarray(rng.normal(size=(1, seq, 4, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, seq, 4, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, seq, 4, dv)), jnp.float32)
    got = flash_attention_fwd(q, k, v, causal=True, scale=0.2)
    assert got.shape == (1, seq, 4, dv)
    assert jnp.abs(got - _causal_attention(q, k, v, 0.2)).max() < 2e-6
    with pytest.raises(NotImplementedError):
        jax.grad(lambda q: flash_attention_fwd(q, k, v, causal=True).sum())(q)


def test_flash_fwd_at_equal_widths_is_the_parents_program():
    """At `Dv == D` the kernel call is the one it was: the value and output
    blocks, the output and the accumulator all `D` wide, and the tuner's
    signature carries one width (the parent's whole jaxpr was compared once,
    string for string, when this was written: PERF.md section 6, PR 35)."""
    q = jnp.zeros((2, 64, 4, 16), jnp.float32)
    kv = jnp.zeros((2, 64, 2, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True))(q, kv, kv)
    (call,) = [e for e in _all_eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    out, lse = call.outvars
    assert out.aval.shape == (2, 4, 64, 16) and lse.aval.shape == (2, 4, 64, 1)
    mapping = call.params["grid_mapping"]
    widths = [getattr(b.block_shape[-1], "block_size", b.block_shape[-1])
              for b in mapping.block_mappings]
    # q, k transposed (its last axis is the key block), v, the output, lse
    assert [widths[0], widths[2], widths[3], widths[4]] == [16, 16, 16, 1]
    assert [tuple(a.shape) for a in mapping.scratch_avals][-1] == (64, 16)
    tuned = autotune.chosen_tiles()["flash_fwd"]
    assert tuned["consults"] > 0


def _all_eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _all_eqns(sub)


# -- 10. the other models' programs are the parent's ------------------------ #

@pytest.mark.parametrize("build,moe,windowed", [
    (lambda: GPTForCausalLM(gpt3_tiny()), False, False),
    (lambda: GraniteHybridForCausalLM(granite_hybrid_tiny()), True, False),
    (lambda: AfmoeForCausalLM(afmoe_tiny()), True, True)],
    ids=["gpt", "granite", "trinity"])
def test_the_other_models_decode_programs_are_the_parents(build, moe,
                                                          windowed):
    """A pool that asks its specs what a page is leaves the GPT, the Granite
    and the Trinity decode program as they were: the jaxpr of the engine's
    program equals that of the parent commit's closure, written out here,
    over a pool of the parent's shapes (K and V, `[pages, heads, page, D]`
    each), and the pool's bytes are what the parent multiplied out."""
    paddle.seed(0)
    eng = PagedServingEngine(build(), max_batch_size=4, max_seq_len=64,
                             page_size=8)
    cfg = eng.cfg
    assert not eng._latent
    assert eng.pool.bytes_per_page == BlockPool.page_nbytes(
        eng.pool.depth, cfg.kv_heads, cfg.head_dim, 8, eng.kv_dtype)
    for e in eng.pool.page_entries:
        assert [a.shape for a in eng.pool.kv[e]] == [
            (eng.pool.num_pages, cfg.kv_heads, 8, cfg.head_dim)] * 2
    stats_kw = {"with_stats": True} if moe else {}

    def decode(p, b, tok, offs, tables, temps, keys, caches, *starts):
        pos = offs[:, None]
        kw = {"window_starts": starts[0]} if starts else {}
        logits, new_c, *stats = eng._functional_forward(
            p, b, tok[:, None], pos, caches, offs, tables=tables, **stats_kw,
            **kw)
        last = logits[:, -1]
        return *eng._choose_tokens(last, temps, keys), last, new_c, stats

    tables = (tuple(jnp.zeros(t.shape, jnp.int32) for t in eng.group_tables)
              if windowed else jnp.zeros((4, eng.P), jnp.int32))
    args = (eng.params, eng.buffers, jnp.zeros(4, jnp.int32),
            jnp.ones(4, jnp.int32), tables, jnp.zeros(4, jnp.float32),
            jnp.zeros((4, 2), jnp.uint32), eng.pool.kv,
            *((jnp.zeros(4, jnp.int32),) if windowed else ()))
    mine = jax.make_jaxpr(eng._decode_program())(*args)
    parents = jax.make_jaxpr(jax.jit(decode, donate_argnums=(7,)))(*args)
    assert str(mine) == str(parents)


# -- the yardstick's own counts, by hand ------------------------------------ #

def _cell_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-k2-instruct.json")) as f:
        return json.load(f)


def test_costs_by_hand_for_the_published_widths():
    from benchmark import costs_kimi_k2 as costs

    config = _cell_config()
    # q_a 7168 x 1536, q_b 1536 x 64 x 192, kv_a 7168 x 576, kv_b 512 x 64 x
    # 256, o 8192 x 7168
    assert costs.attention_params(config) == (
        11_010_048 + 18_874_368 + 4_128_768 + 8_388_608 + 58_720_256
        ) == 101_122_048
    assert costs.expert_params(config) == 3 * 7168 * 2048 == 44_040_192
    assert costs.dense_mlp_params(config) == 3 * 7168 * 18432 == 396_361_728
    assert costs.expert_layers(config) == 4
    # 12 held experts, bf16: [12, 7168, 4096] in and [12, 2048, 7168] out
    assert costs.grouped_gemm_weight_bytes(config) == (704_643_072,
                                                       352_321_536)
    # a cached token REQUIRES 576 values a layer; the absorbed step costs
    # 2 x 64 heads x (576 + 512) FLOP against each
    assert costs.latent_bytes_per_token_layer(config) == 1152
    assert costs.latent_flops_per_token_layer(config) == 139_264
    assert costs.decode_latent_bytes(config, 1000) == 1000 * 5 * 1152
    assert costs.decode_latent_flops(config, 1000) == 1000 * 5 * 139_264
    assert costs.causal_pairs(100) == 5050
    assert costs.prefill_pair_flops(config) == 2 * 64 * (192 + 128) == 40_960
    assert costs.mla_prefill_flops(config, [100, 10]) == (
        (5050 + 55) * 40_960 * 5)
    # 5 layers of attention, 1 dense MLP, 4 expert layers each with the
    # router 7168 x 384, the shared expert and 0.25 of 8 picks held
    per_expert_layer = 7168 * 384 + 44_040_192 + 0.25 * 44_040_192
    assert costs.matmul_params_per_token(config, head=False) == (
        5 * 101_122_048 + 396_361_728 + 4 * per_expert_layer
        ) == 1_133_182_976
    assert costs.matmul_params_per_token(config) == (
        1_133_182_976 + 163_840 * 7168)
    assert costs.decode_flops_per_token(config, 5000) == (
        2 * (1_133_182_976 + 1_174_405_120) + 5 * 139_264 * 5000)
    assert costs.prompt_flops(config, 100) == (
        2 * 100 * 1_133_182_976 + 2 * 1_174_405_120 + 40_960 * 5 * 5050)
    assert costs.window_flops(config, 10, 5000, 2, [100, 100]) == (
        10 * costs.decode_flops_per_token(config, 5000)
        + 2 * costs.prompt_flops(config, 100))


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
    reader = _load_reader("kernel_roofline_kimi_k2")
    tail = ', custom_call_target="tpu_custom_call", operand_layout...'
    ops = [(f"%decode_latent.{i} = bf16[96,64,512] custom-call(...){tail}",
            1.0 + i, 0.002) for i in range(5)]
    ops += [(f"%flash_fwd.3 = (bf16[1,64,4096,128]) custom-call(...){tail}",
             20.0, 0.004),
            ("%grouped_gemm.1 = bf16[768,4096] custom-call(s32[12], "
             f"bf16[768,7168], bf16[12,7168,4096]){tail}", 30.0, 0.001),
            ("%grouped_gemm.2 = bf16[32768,7168] custom-call(s32[12], "
             f"bf16[32768,2048], bf16[12,2048,7168]){tail}", 31.0, 0.0005),
            (f"%decode_latent.99 = ...{tail}", 99.0, 1.0)]   # outside
    trace = types.SimpleNamespace(window=(0.0, 50.0),
                                  devices={"/device:TPU:0": {"XLA Ops": ops}})
    spans = [
        _span("engine.step/decode_dispatch", 0, latent_tokens=500_000),
        _span("engine.step/decode_dispatch", 1, latent_tokens=100_000),
        _span("engine.step/decode_dispatch", None, latent_tokens=7),
        _span("engine.step/admit/prefill", 1, prompt_len=3000, bucket=4096)]
    run = types.SimpleNamespace(
        trace=trace, config=_cell_config(),
        _program_spans={("bm.engine_step", "engine.step"): spans},
        peaks=lambda: {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})
    # 600,000 tokens x 5 layers: 1,152 B each over 819 GB/s is 4.22 ms, more
    # than 139,264 FLOP each over 197 TFLOP/s (2.12 ms); against 10 ms
    assert reader.read(run, {}, kernel="decode_latent") == pytest.approx(
        100 * (600_000 * 5 * 1152 / 819e9) / 0.010)
    # the causal pairs of a 3000-token prompt, 40,960 FLOP a pair, 5 layers
    assert reader.read(run, {}, kernel="flash_fwd") == pytest.approx(
        100 * (3000 * 3001 // 2 * 40_960 * 5 / 197e12) / 0.004)
    # the first call (24 expected rows: a tick's) is bound by the weights of
    # the experts that got a row, 9 of 12 by the program's histogram of
    # visited tiles (two ticks of 4 layers x 9 experts x 128 rows); the
    # second, 1024 expected rows, by all 352 MB (30 GFLOP is 0.15 ms)
    from paddle_tpu.inference.slo import serving_metrics

    tiled = serving_metrics()["moe_rows_tiled"]
    tiled.observe(4 * 9 * 128)
    tiled.observe(4 * 9 * 128)
    hit = min(1.0, tiled.sum() / tiled.count() / 128 / 48)
    assert reader.read(run, {}, kernel="grouped_gemm") == pytest.approx(
        100 * ((704_643_072 * hit + 352_321_536) / 819e9) / 0.0015)
    assert 0 < hit <= 1.0     # 0.75 in a process that served nothing else
    spans[0].attrs.pop("latent_tokens")
    spans[1].attrs.pop("latent_tokens")
    spans[2].attrs.pop("latent_tokens")
    assert reader.read(run, {}, kernel="decode_latent") is None   # a parent
    # traced seconds in which no prompt was admitted (the driver's seed
    # 641699051 dealt such a window): the spans were read and show none, so
    # the share is 0 and stays in the line; spans that cannot be read: None
    del spans[3], ops[5]
    assert reader.read(run, {}, kernel="flash_fwd") == 0.0
    run._program_spans[("bm.engine_step", "engine.step")] = None
    assert reader.read(run, {}, kernel="flash_fwd") is None
    run.trace = None
    assert reader.read(run, {}, kernel="flash_fwd") is None


def test_mfu_reader_counts_the_window_by_hand():
    from benchmark import costs_kimi_k2 as costs

    reader = _load_reader("mfu_required_kimi_k2")
    config = _cell_config()
    mix = {"prompt_len": {"lo": 2000, "hi": 2000, "levels": 1}}
    ticks = [{"decoded_rows": 60, "context_tokens": 300_000,
              "first_tokens": 2},
             {"decoded_rows": 60, "context_tokens": 420_000,
              "first_tokens": 0}]
    run = types.SimpleNamespace(
        window=(10.0, 12.0), config=config, mix=mix,
        peaks=lambda: {"bf16_flops_per_s": 197e12})
    flops = (120 * costs.decode_flops_per_token(config, 6000)
             + 2 * costs.prompt_flops(config, 2000))
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
    position; what it adds to the mean is its three spreads over the answer's
    100 positions (over the chip's answers of a thousand and more: 0.003,
    which the mean's limit does not see)."""
    from benchmark import harness
    from benchmark.families import kimi_k2 as family

    config = harness.rehearsal_sizes(_cell_config())
    paddle.seed(5)
    m = KimiK2ForCausalLM(family._model_config(config))
    m.eval()
    assert m.config.held_experts == (0, 4) and m.config.n_routed_experts == 16
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
    assert sample["worst_share"] > detail["worst_tolerance"] == 1.8
    assert sample["mean_share"] == pytest.approx(3.0 / 100, abs=0.005)


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
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-k2-instruct", "saturated-reasoning-16k", 1)
    (serve,) = [m for m in manifest["end_to_end"]
                if m["name"] == "serve_tok_s"]
    assert CELL in serve["workloads"]
    readings = {(m["reader"], json.dumps(m.get("args", {}), sort_keys=True)):
                m for m in layer_metrics(CELL).values()}
    for kernel in ("decode_latent", "flash_fwd", "grouped_gemm"):
        metric = readings["kernel_roofline_kimi_k2",
                          json.dumps({"kernel": kernel})]
        assert (metric["unit"], metric["moves"]) == ("%", "serve_tok_s")
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "saturated-reasoning-16k.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "saturated" and "decode_latent" in mix[
        "expected_kernels"]
    assert (mix["prompt_len"], mix["answer_len"], mix["max_total"]) == (
        {"lo": 1024, "hi": 8192, "levels": 8},
        {"lo": 1024, "hi": 8192, "levels": 8}, 16384)
    config = _cell_config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        (row,) = [json.loads(line) for line in f
                  if '"name": "Kimi-K2-Instruct"' in line]
    changed = {k for k, v in row["config"].items() if config.get(k) != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers",
                                                 "n_routed_experts"}
    assert config["published"] == {"num_hidden_layers": 61,
                                   "n_routed_experts": 384}
