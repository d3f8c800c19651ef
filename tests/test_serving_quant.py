"""Quantized serving fast path (kv_quant=True / serve_w8=True):
the int8 BlockPool layout with per-(page, head) scales, the running-abs-max
paged_kv_write_q8 append, the dequant-fused Pallas decode kernel, and the
PagedServingEngine over all three.

Acceptance properties pinned here:
- quantized-vs-dense logit divergence under an explicit tolerance (the
  first decode step after an identical unquantized prefill isolates pure KV
  quantization error);
- BITWISE scheduling invariance of the quantized path itself — preemption/
  spill/resume and prefix sharing produce token-identical output because
  the int8 payload+scale update is a pure function of page history;
- prefix sharing + COW + preemption recovery all pass with kv_quant on;
- strictly more concurrency than the f32 pool at an equal HBM byte budget.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference.paged import BlockPool, PagedServingEngine
from paddle_tpu.models import GPTForCausalLM, gpt3_tiny
from paddle_tpu.observability.metrics import default_registry
from paddle_tpu.ops.pallas.decode_attention import (
    KV_QMAX,
    paged_decode_attention,
    paged_kv_write_q8,
)


@pytest.fixture(autouse=True)
def _interpret_mode(pallas_interpret_unless_hw):
    pass


def _model():
    paddle.seed(0)
    return GPTForCausalLM(gpt3_tiny())


def _counter(name, **labels):
    m = default_registry().get(name)
    return m.value(**labels) if m is not None else 0.0


def _drive(eng, prompts, temps=None, max_new=None, priorities=None):
    ids = [eng.add_request(
        p,
        max_new_tokens=5 if max_new is None else max_new[i],
        temperature=0.0 if temps is None else temps[i],
        priority=0 if priorities is None else priorities[i])
        for i, p in enumerate(prompts)]
    done = eng.run()
    by = {r.req_id: r for r in done}
    return [by[i] for i in ids]


def _quantize_ref(pages):
    """numpy oracle for the pool's per-(page, head) abs-max quantization."""
    absmax = np.abs(pages).max(axis=(2, 3))
    scale = absmax / KV_QMAX
    safe = np.where(scale == 0, 1.0, scale)
    q = np.clip(np.round(pages / safe[:, :, None, None]),
                -KV_QMAX, KV_QMAX).astype(np.int8)
    return q, scale.astype(np.float32)


# --------------------------------------------------------------------------- #
# quantized block pool
# --------------------------------------------------------------------------- #


class TestQuantBlockPool:
    def _pool(self, **kw):
        kw.setdefault("num_layers", 2)
        kw.setdefault("kv_heads", 2)
        kw.setdefault("head_dim", 4)
        kw.setdefault("page_size", 4)
        kw.setdefault("num_pages", 6)
        kw.setdefault("quantized", True)
        return BlockPool(**kw)

    def test_layout_and_byte_accounting(self):
        pool = self._pool()
        k, v = pool.kv[0]
        assert k.dtype == jnp.int8 and v.dtype == jnp.int8
        sk, sv = pool.scales[0]
        assert sk.shape == (6, 2) and sk.dtype == jnp.float32
        # payload 2*2*(2*4*4) + scales 2*2*2*4 per page
        assert pool.bytes_per_page == 2 * 2 * (2 * 4 * 4) + 2 * 2 * 2 * 4
        f32 = BlockPool.page_nbytes(2, 2, 4, 4, jnp.float32, False)
        assert f32 / pool.bytes_per_page > 3.0  # toy dims: scales loom large
        # at a realistic page shape the scale overhead amortizes to ~4x
        q = BlockPool.page_nbytes(12, 12, 64, 16, quantized=True)
        f = BlockPool.page_nbytes(12, 12, 64, 16, jnp.float32, False)
        assert f / q > 3.9

    def test_write_prompt_pages_quantizes_with_error_bound(self):
        pool = self._pool()
        pages = [pool.alloc(), pool.alloc()]
        rng = np.random.default_rng(0)
        stacked = rng.standard_normal((2, 2, 4, 4)).astype(np.float32) * 2.0
        n0 = _counter("serving_kv_quant_pages_total")
        pool.write_prompt_pages(pages, [True, True],
                                [jnp.asarray(stacked)] * 2,
                                [jnp.asarray(-stacked)] * 2)
        assert _counter("serving_kv_quant_pages_total") == n0 + 2
        k, _ = pool.kv[0]
        sk, _ = pool.scales[0]
        deq = (np.asarray(k[np.asarray(pages)], np.float32)
               * np.asarray(sk[np.asarray(pages)])[:, :, None, None])
        err_bound = np.asarray(sk[np.asarray(pages)])[:, :, None, None] / 2
        assert np.all(np.abs(deq - stacked) <= err_bound + 1e-7)
        # matches the numpy oracle bit-for-bit (determinism => sharing works)
        q_ref, s_ref = _quantize_ref(stacked)
        np.testing.assert_array_equal(np.asarray(k[np.asarray(pages)]), q_ref)
        np.testing.assert_allclose(np.asarray(sk[np.asarray(pages)]), s_ref,
                                   rtol=1e-6)

    def test_copy_page_carries_scales(self):
        pool = self._pool()
        src, dst = pool.alloc(), pool.alloc()
        pool.write_prompt_pages(
            [src], [True],
            [jnp.ones((1, 2, 4, 4)) * 3.0] * 2,
            [jnp.ones((1, 2, 4, 4)) * 5.0] * 2)
        pool.copy_page(src, dst)
        for li in range(2):
            k, v = pool.kv[li]
            sk, sv = pool.scales[li]
            np.testing.assert_array_equal(np.asarray(k[dst]),
                                          np.asarray(k[src]))
            np.testing.assert_array_equal(np.asarray(sk[dst]),
                                          np.asarray(sk[src]))
            np.testing.assert_array_equal(np.asarray(sv[dst]),
                                          np.asarray(sv[src]))

    def test_spill_restore_roundtrip_is_bitexact(self):
        pool = self._pool()
        pages = [pool.alloc(), pool.alloc()]
        rng = np.random.default_rng(3)
        stacked = rng.standard_normal((2, 2, 4, 4)).astype(np.float32)
        pool.write_prompt_pages(pages, [True, True],
                                [jnp.asarray(stacked)] * 2,
                                [jnp.asarray(2 * stacked)] * 2)
        before_k = np.asarray(pool.kv[0][0][np.asarray(pages)])
        before_s = np.asarray(pool.scales[0][0][np.asarray(pages)])
        host = pool.read_pages(pages)
        assert len(host[0]) == 4  # (k, v, k_scale, v_scale)
        for p in pages:
            pool.release(p)
        fresh = [pool.alloc(), pool.alloc()]
        pool.restore_pages(fresh, host, [0, 1])
        np.testing.assert_array_equal(
            np.asarray(pool.kv[0][0][np.asarray(fresh)]), before_k)
        np.testing.assert_array_equal(
            np.asarray(pool.scales[0][0][np.asarray(fresh)]), before_s)


# --------------------------------------------------------------------------- #
# quantized append + dequant-fused kernel
# --------------------------------------------------------------------------- #


class TestPagedKvWriteQ8:
    def test_append_dequantizes_to_row_within_bound(self):
        B, Hkv, D, ps = 2, 2, 8, 4
        cache = jnp.zeros((5, Hkv, ps, D), jnp.int8)
        scales = jnp.zeros((5, Hkv), jnp.float32)
        tables = jnp.asarray([[1, 2], [3, -1]], jnp.int32)
        lengths = jnp.asarray([5, 2], jnp.int32)  # -> (page 2, 1), (page 3, 2)
        new = jnp.asarray(
            np.random.default_rng(0).standard_normal((B, Hkv, D)),
            jnp.float32)
        cache, scales = paged_kv_write_q8(cache, scales, new, tables, lengths)
        deq = (np.asarray(cache, np.float32)
               * np.asarray(scales)[:, :, None, None])
        for b, (pg, sl) in enumerate([(2, 1), (3, 2)]):
            bound = np.asarray(scales)[pg][:, None] / 2
            assert np.all(np.abs(deq[pg, :, sl] - np.asarray(new)[b])
                          <= bound + 1e-7)

    def test_scale_grows_and_requantizes_prior_content(self):
        Hkv, D, ps = 1, 4, 4
        cache = jnp.zeros((2, Hkv, ps, D), jnp.int8)
        scales = jnp.zeros((2, Hkv), jnp.float32)
        tables = jnp.asarray([[1]], jnp.int32)
        small = jnp.full((1, Hkv, D), 0.5, jnp.float32)
        big = jnp.full((1, Hkv, D), 4.0, jnp.float32)
        cache, scales = paged_kv_write_q8(
            cache, scales, small, tables, jnp.asarray([0], jnp.int32))
        s0 = float(scales[1, 0])
        cache, scales = paged_kv_write_q8(
            cache, scales, big, tables, jnp.asarray([1], jnp.int32))
        s1 = float(scales[1, 0])
        assert s1 == pytest.approx(4.0 / KV_QMAX) and s1 > s0
        deq = np.asarray(cache, np.float32)[1, 0] * s1
        # slot 0 was requantized under the grown scale; one rounding step
        np.testing.assert_allclose(deq[0], 0.5, atol=s1 / 2 + 1e-7)
        np.testing.assert_allclose(deq[1], 4.0, atol=s1 / 2 + 1e-7)

    def test_unchanged_scale_append_is_bitexact_for_prior_slots(self):
        Hkv, D, ps = 1, 4, 4
        cache = jnp.zeros((2, Hkv, ps, D), jnp.int8)
        scales = jnp.zeros((2, Hkv), jnp.float32)
        tables = jnp.asarray([[1]], jnp.int32)
        big = jnp.full((1, Hkv, D), 4.0, jnp.float32)
        small = jnp.full((1, Hkv, D), 0.5, jnp.float32)
        cache, scales = paged_kv_write_q8(
            cache, scales, big, tables, jnp.asarray([0], jnp.int32))
        slot0 = np.asarray(cache)[1, 0, 0].copy()
        cache, scales = paged_kv_write_q8(
            cache, scales, small, tables, jnp.asarray([1], jnp.int32))
        np.testing.assert_array_equal(np.asarray(cache)[1, 0, 0], slot0)

    def test_slot0_write_ignores_stale_state_from_recycled_page(self):
        """A page popped back off the free list keeps its last tenant's
        payload AND scale (release() never clears device data). Slot 0 is
        always a page's first write, so the append must restart the running
        abs-max there — inheriting a big stale scale would quantize a
        small-magnitude row to a few int8 levels and make page content
        depend on which physical page the free list happened to return,
        breaking the bitwise scheduling invariance."""
        Hkv, D, ps = 1, 4, 4
        tables = jnp.asarray([[1]], jnp.int32)
        small = jnp.full((1, Hkv, D), 0.5, jnp.float32)
        recycled = paged_kv_write_q8(
            jnp.full((2, Hkv, ps, D), 111, jnp.int8),   # stale payload
            jnp.full((2, Hkv), 100.0, jnp.float32),     # stale big scale
            small, tables, jnp.asarray([0], jnp.int32))
        fresh = paged_kv_write_q8(
            jnp.zeros((2, Hkv, ps, D), jnp.int8),
            jnp.zeros((2, Hkv), jnp.float32),
            small, tables, jnp.asarray([0], jnp.int32))
        # written page identical regardless of the previous tenant
        np.testing.assert_array_equal(np.asarray(recycled[0])[1],
                                      np.asarray(fresh[0])[1])
        np.testing.assert_array_equal(np.asarray(recycled[1])[1],
                                      np.asarray(fresh[1])[1])
        assert float(recycled[1][1, 0]) == pytest.approx(0.5 / KV_QMAX)
        assert not np.asarray(recycled[0])[1, :, 1:].any()  # stale slots zeroed

    def test_parked_rows_hit_null_page(self):
        Hkv, D, ps = 1, 4, 4
        cache = jnp.zeros((3, Hkv, ps, D), jnp.int8)
        scales = jnp.zeros((3, Hkv), jnp.float32)
        tables = jnp.asarray([[1], [-1]], jnp.int32)
        new = jnp.ones((2, Hkv, D), jnp.float32)
        cache, scales = paged_kv_write_q8(
            cache, scales, new, tables, jnp.asarray([1, 0], jnp.int32))
        out = np.asarray(cache)
        assert out[1, :, 1].any()      # live row wrote its slot
        assert out[0, :, 0].any()      # parked row landed on null page
        assert not out[2:].any()       # no allocatable page touched


class TestDequantFusedKernel:
    def test_matches_dequantized_reference_kernel(self):
        """The fused kernel on (int8 payload, scales) equals the f32 kernel
        on the pre-dequantized cache — the dequant multiply is the only new
        op, applied to the identical page stream."""
        rng = np.random.default_rng(0)
        B, H, Hkv, D, ps, P = 2, 4, 2, 16, 8, 3
        n_pages = 1 + B * P
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
        pages = rng.standard_normal((n_pages, Hkv, ps, D)).astype(np.float32)
        qk, sk = _quantize_ref(pages)
        qv, sv = _quantize_ref(pages[::-1].copy())
        tables = np.full((B, P), -1, np.int32)
        tables[0, :3] = [1, 2, 3]
        tables[1, :2] = [4, 5]
        lengths = jnp.asarray([21, 13], jnp.int32)  # partial final pages
        fused = paged_decode_attention(
            q, jnp.asarray(qk), jnp.asarray(qv), jnp.asarray(tables),
            lengths, kv_scales=(jnp.asarray(sk), jnp.asarray(sv)))
        deq_k = qk.astype(np.float32) * sk[:, :, None, None]
        deq_v = qv.astype(np.float32) * sv[:, :, None, None]
        ref = paged_decode_attention(
            q, jnp.asarray(deq_k), jnp.asarray(deq_v), jnp.asarray(tables),
            lengths)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

    def test_zero_length_row_is_finite(self):
        B, H, Hkv, D, ps, P = 2, 2, 2, 16, 8, 2
        q = jnp.ones((B, H, D), jnp.float32)
        cache = jnp.ones((3, Hkv, ps, D), jnp.int8)
        scales = jnp.ones((3, Hkv), jnp.float32)
        tables = jnp.asarray([[1, -1], [-1, -1]], jnp.int32)
        out = np.asarray(paged_decode_attention(
            q, cache, cache, tables, jnp.asarray([4, 0], jnp.int32),
            kv_scales=(scales, scales)))
        assert np.all(np.isfinite(out))
        np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))


# --------------------------------------------------------------------------- #
# quantized engine
# --------------------------------------------------------------------------- #


class TestQuantEngine:
    # explicit divergence tolerances the acceptance criteria pin: the first
    # decode tick after the identical (unquantized) prefill isolates pure KV
    # quantization error — observed ~1e-3 on gpt3_tiny, pinned at ~20x
    # margin; later ticks may accumulate one rounding step per scale growth
    FIRST_TICK_LOGIT_TOL = 0.02
    DRAIN_LOGIT_TOL = 0.05

    def test_logit_and_token_divergence_vs_full_precision(self):
        """Lockstep quantized-vs-f32 drive of the same mixed greedy/sampled
        workload: tick-0 logits (pure KV quant error after an identical
        prefill) pinned at 0.02, every tick's at 0.05, and the emitted
        token streams identical — int8 KV error stays under the argmax
        margins, and sampled rows share the (seed, arrival) key stream so
        divergence could only come from logit movement."""
        rng = np.random.default_rng(42)
        prompts = [rng.integers(1, 1000, 4 + i).astype(np.int32)
                   for i in range(4)]
        temps = [0.0, 0.7, 0.0, 0.0]
        engines = {
            quant: PagedServingEngine(_model(), max_batch_size=4,
                                      max_seq_len=64, page_size=16, seed=3,
                                      kv_quant=quant)
            for quant in (False, True)}
        for quant, eng in engines.items():
            for i, p in enumerate(prompts):
                eng.add_request(p, max_new_tokens=5, temperature=temps[i])
        diffs = []
        while engines[False].has_work() or engines[True].has_work():
            engines[False].step()
            engines[True].step()
            if engines[False].last_logits is not None:
                diffs.append(float(np.max(np.abs(
                    np.asarray(engines[False].last_logits)
                    - np.asarray(engines[True].last_logits)))))
        assert 0 < diffs[0] <= self.FIRST_TICK_LOGIT_TOL
        assert max(diffs) <= self.DRAIN_LOGIT_TOL
        toks = {q: [r.generated
                    for r in sorted(e.finished, key=lambda r: r.req_id)]
                for q, e in engines.items()}
        assert toks[True] == toks[False]

    def test_prefix_sharing_and_cow_under_kv_quant(self):
        """Two identical prompts over int8 pages: pages share (hits), the
        first divergent write copies (COW), and both requests emit
        identical tokens — determinism makes shared int8 pages bit-equal."""
        hits0 = _counter("serving_prefix_hits_total")
        cow0 = _counter("serving_cow_copies_total")
        eng = PagedServingEngine(_model(), max_batch_size=4, max_seq_len=64,
                                 page_size=16, seed=3, kv_quant=True)
        assert eng.kv_quant
        prompt = np.random.default_rng(1).integers(1, 1000, 10).astype(
            np.int32)
        eng.add_request(prompt, max_new_tokens=4)
        eng.add_request(prompt, max_new_tokens=4)
        done = sorted(eng.run(), key=lambda r: r.req_id)
        assert done[0].generated == done[1].generated
        assert _counter("serving_prefix_hits_total") > hits0
        assert _counter("serving_cow_copies_total") > cow0

    def test_preemption_recovery_is_bitwise_invariant(self):
        """The quantized path's scheduling invariance: an undersized pool
        that forces spill/resume produces BIT-IDENTICAL tokens to an ample
        pool — the int8 payload+scale update is a pure function of page
        history, and spill buffers round-trip exactly."""
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 1000, 14).astype(np.int32)
                   for _ in range(4)]
        prios = [0, -1, -2, -3]

        def run(num_pages=None, watermark=None):
            eng = PagedServingEngine(
                _model(), max_batch_size=4, max_seq_len=64, page_size=16,
                seed=3, kv_quant=True, prefix_sharing=False,
                num_pages=num_pages, watermark_pages=watermark)
            return [r.generated for r in _drive(
                eng, prompts, max_new=[6] * 4, priorities=prios)]

        ample = run()
        pre0 = _counter("serving_preemptions_total")
        res0 = _counter("serving_resumes_total")
        starved = run(num_pages=6, watermark=0)
        assert _counter("serving_preemptions_total") > pre0
        assert _counter("serving_resumes_total") > res0
        assert starved == ample  # bitwise

    def test_more_concurrency_than_f32_at_equal_byte_budget(self):
        """The headline: at the SAME pool HBM bytes the int8 engine admits
        strictly more concurrent requests (~4x the pages)."""
        cfg = gpt3_tiny()
        budget = 13 * BlockPool.page_nbytes(
            cfg.num_layers, cfg.kv_heads, cfg.head_dim, 16)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 1000, 30).astype(np.int32)
                   for _ in range(8)]
        peak = {}
        for quant in (False, True):
            eng = PagedServingEngine(_model(), max_batch_size=8,
                                     max_seq_len=64, page_size=16, seed=0,
                                     kv_quant=quant,
                                     kv_budget_bytes=budget)
            for p in prompts:
                eng.add_request(p, max_new_tokens=3)
            peak[quant] = 0
            while eng.has_work():
                eng.step()
                peak[quant] = max(peak[quant], eng.live_count)
        assert peak[True] == 8          # all rows live at once
        assert peak[True] > peak[False]  # strictly more than f32
        assert _counter("serving_kv_bytes_per_token") < 512

    def test_sub_two_page_byte_budget_raises(self):
        """A budget that cannot fit the null page plus one allocatable page
        must raise, not silently enlarge the pool past the requested bytes
        (which would falsify the equal-budget A/B)."""
        with pytest.raises(ValueError, match="kv_budget_bytes"):
            PagedServingEngine(_model(), max_batch_size=2, max_seq_len=32,
                               page_size=16, kv_budget_bytes=64)

    def test_num_pages_and_byte_budget_are_mutually_exclusive(self):
        """Passing both would let the page count silently override the byte
        budget — the other way an equal-budget A/B can quietly lie."""
        with pytest.raises(ValueError, match="not both"):
            PagedServingEngine(_model(), max_batch_size=2, max_seq_len=32,
                               page_size=16, num_pages=100,
                               kv_budget_bytes=200_000)

    def test_serve_w8_weight_bytes_drop_and_tokens_flow(self):
        model = _model()
        dense_bytes = sum(
            int(np.prod(p._value.shape)) * p._value.dtype.itemsize
            for _, p in model.named_parameters())
        eng = PagedServingEngine(model, max_batch_size=2, max_seq_len=64,
                                 page_size=16, seed=3, kv_quant=True,
                                 serve_w8=True)
        assert eng.serve_w8
        served = (sum(int(np.prod(v.shape)) * v.dtype.itemsize
                      for v in eng.params.values())
                  + sum(int(np.prod(v.shape)) * v.dtype.itemsize
                        for v in eng.buffers.values()))
        assert served < dense_bytes  # projection HBM dropped
        prompt = np.random.default_rng(2).integers(1, 1000, 8).astype(
            np.int32)
        eng.add_request(prompt, max_new_tokens=4)
        done = eng.run()
        assert len(done[0].generated) == 4


class TestKvDtypeFlowsFromModel:
    def test_bf16_model_gets_bf16_pages(self):
        """Satellite: the pool/prefill dtype follows the model instead of a
        hardcoded f32 — a bf16 model no longer silently pays 2x KV bytes."""
        model = _model()
        for _, p in model.named_parameters():
            p._value = p._value.astype(jnp.bfloat16)
        eng = PagedServingEngine(model, max_batch_size=2, max_seq_len=32,
                                 page_size=16)
        assert eng.kv_dtype == jnp.bfloat16
        assert eng.pool.kv[0][0].dtype == jnp.bfloat16
        assert eng.pool.bytes_per_token == eng.cfg.num_layers * 2 * \
            eng.cfg.kv_heads * eng.cfg.head_dim * 2

    def test_f32_model_unchanged(self):
        eng = PagedServingEngine(_model(), max_batch_size=2, max_seq_len=32,
                                 page_size=16)
        assert eng.kv_dtype == jnp.float32
        assert eng.pool.kv[0][0].dtype == jnp.float32


@pytest.mark.slow
class TestQuantDrainStress:
    def test_large_mixed_drain_under_pressure_quantized(self):
        """16 mixed greedy/sampled requests with shared prefixes through an
        undersized QUANTIZED pool: everything drains, output matches the
        ample-pool quantized run bitwise, and the quant series populate."""
        rng = np.random.default_rng(11)
        shared = rng.integers(1, 1000, 16).astype(np.int32)
        prompts, temps, max_new, prios = [], [], [], []
        for i in range(16):
            tail = rng.integers(1, 1000, 2 + i % 7).astype(np.int32)
            prompts.append(np.concatenate([shared, tail]) if i % 3 == 0
                           else rng.integers(1, 1000,
                                             3 + i % 9).astype(np.int32))
            temps.append(0.6 if i % 4 == 0 else 0.0)
            max_new.append(4 + i % 6)
            prios.append(-(i % 5))

        def run(**kw):
            eng = PagedServingEngine(_model(), max_batch_size=4,
                                     max_seq_len=64, page_size=16, seed=9,
                                     kv_quant=True, **kw)
            return [r.generated
                    for r in _drive(eng, prompts, temps, max_new, prios)]

        ample = run()
        starved = run(num_pages=8, watermark_pages=1)
        assert starved == ample
        assert _counter("serving_kv_quant_pages_total") > 0
