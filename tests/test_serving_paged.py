"""Paged-KV serving subsystem (paddle_tpu/inference/paged/): block pool,
two-queue scheduler, and the PagedServingEngine — including the acceptance
properties: per-token parity with the dense ContinuousBatchingEngine on
mixed greedy/sampled workloads (prefix sharing on and off), strictly more
concurrency than dense at equal HBM page budget, and preemption under an
undersized pool that recovers every request with no lost tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.inference.paged import (
    BlockPool,
    PagedServingEngine,
    SpilledRequest,
    TwoQueueScheduler,
    prefix_page_key,
)
from paddle_tpu.models import GPTForCausalLM, gpt3_tiny
from paddle_tpu.observability import spans
from paddle_tpu.observability.metrics import default_registry


@pytest.fixture(autouse=True)
def _interpret_mode(pallas_interpret_unless_hw):
    pass


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return GPTForCausalLM(gpt3_tiny())


def _counter(name, **labels):
    m = default_registry().get(name)
    return m.value(**labels) if m is not None else 0.0


def _drive(eng, prompts, temps=None, max_new=None, priorities=None):
    ids = [eng.add_request(
        p,
        max_new_tokens=6 if max_new is None else max_new[i],
        temperature=0.0 if temps is None else temps[i],
        priority=0 if priorities is None else priorities[i])
        for i, p in enumerate(prompts)]
    done = eng.run()
    by = {r.req_id: r for r in done}
    return [by[i] for i in ids]


# --------------------------------------------------------------------------- #
# block pool
# --------------------------------------------------------------------------- #


class TestBlockPool:
    def _pool(self, **kw):
        kw.setdefault("num_layers", 1)
        kw.setdefault("kv_heads", 1)
        kw.setdefault("head_dim", 4)
        kw.setdefault("page_size", 4)
        kw.setdefault("num_pages", 5)
        return BlockPool(**kw)

    def test_alloc_free_cycle_never_hands_out_null_page(self):
        pool = self._pool()
        assert pool.pages_total == 4
        got = [pool.alloc() for _ in range(4)]
        assert 0 not in got and pool.alloc() is None
        for p in got:
            pool.release(p)
        assert pool.pages_free == 4

    def test_refcounted_prefix_sharing_and_unregister(self):
        pool = self._pool()
        key = prefix_page_key(np.arange(4, dtype=np.int32), 0, 4)
        p = pool.alloc()
        pool.register_prefix(key, p)
        assert pool.lookup_prefix(key) == p and pool.is_shared(p)
        pool.release(p)            # one holder left
        assert not pool.is_shared(p) and pool.is_registered(p)
        pool.unregister_page(p)    # first divergent write would do this
        assert pool.lookup_prefix(key) is None
        pool.release(p)
        assert pool.pages_free == 4  # freed page left the prefix map too

    def test_release_to_zero_unregisters(self):
        pool = self._pool()
        key = b"k" * 16
        p = pool.alloc()
        pool.register_prefix(key, p)
        pool.release(p)
        assert pool.lookup_prefix(key) is None  # no dangling shared page

    def test_copy_page_copies_content(self):
        pool = self._pool()
        src, dst = pool.alloc(), pool.alloc()
        k, v = pool.kv[0]
        pool.kv[0] = (k.at[src].set(1.5), v.at[src].set(2.5))
        pool.copy_page(src, dst)
        k, v = pool.kv[0]
        np.testing.assert_array_equal(np.asarray(k[dst]), np.asarray(k[src]))
        np.testing.assert_array_equal(np.asarray(v[dst]), np.asarray(v[src]))

    def test_spill_roundtrip(self):
        pool = self._pool()
        pages = [pool.alloc(), pool.alloc()]
        k, v = pool.kv[0]
        pool.kv[0] = (k.at[pages[0]].set(3.0), v.at[pages[1]].set(4.0))
        host = pool.read_pages(pages)
        for p in pages:
            pool.release(p)
        fresh = [pool.alloc(), pool.alloc()]
        pool.restore_pages(fresh, host, [0, 1])
        k, v = pool.kv[0]
        assert float(k[fresh[0]].sum()) == pytest.approx(3.0 * 4 * 4)
        assert float(v[fresh[1]].sum()) == pytest.approx(4.0 * 4 * 4)


# --------------------------------------------------------------------------- #
# scheduler
# --------------------------------------------------------------------------- #


class TestTwoQueueScheduler:
    def _req(self, n):
        from paddle_tpu.inference.serving import GenerationRequest

        return GenerationRequest(np.arange(n, dtype=np.int32))

    def test_watermark_blocks_head_of_line(self):
        sched = TwoQueueScheduler(page_size=16, watermark_pages=2)
        a, b = self._req(20), self._req(20)  # 2 pages each
        sched.enqueue_prefill(a)
        sched.enqueue_prefill(b)
        picked = sched.pick(free_rows=4, pages_free=5, live=0)
        # a fits (5-2 >= 2); b would leave 1 < watermark 2 -> blocked
        assert picked == [a] and sched.waiting_prefill == 1

    def test_fifo_across_buckets(self):
        """Arrival order wins over bucket grouping — the property that keeps
        the sampling-key stream identical to the dense engine's."""
        sched = TwoQueueScheduler(page_size=16, watermark_pages=0)
        big, small, big2 = self._req(30), self._req(4), self._req(30)
        for r in (big, small, big2):
            sched.enqueue_prefill(r)
        assert sched.pick(3, 100, 0) == [big, small, big2]

    def test_resume_queue_preempts_fresh_prefills(self):
        sched = TwoQueueScheduler(page_size=16, watermark_pages=0)
        fresh = self._req(4)
        sched.enqueue_prefill(fresh)
        spilled = SpilledRequest(self._req(4), 5, 1, [], [None])
        sched.enqueue_resume(spilled)
        assert sched.pick(2, 100, 0) == [spilled, fresh]

    def test_idle_engine_admits_whole_pool_request(self):
        """A request whose prompt needs every pool page must not deadlock
        behind the watermark when nothing is live: the head request admits
        whenever it fits at all on an idle engine."""
        sched = TwoQueueScheduler(page_size=16, watermark_pages=1)
        big = self._req(32)  # 2 pages
        sched.enqueue_prefill(big)
        assert sched.pick(free_rows=1, pages_free=2, live=0) == [big]
        # ...but not when other requests are live (reserve holds)
        sched.enqueue_prefill(self._req(32))
        assert sched.pick(free_rows=1, pages_free=2, live=1) == []

    def test_dynamic_watermark_reserves_per_live_row(self):
        sched = TwoQueueScheduler(page_size=16)  # watermark = max(1, live)
        a = self._req(16)  # 1 page
        sched.enqueue_prefill(a)
        assert sched.pick(1, 2, live=3) == []      # 2 - 1 < 3
        assert sched.pick(1, 5, live=3) == [a]     # 5 - 1 >= 3


# --------------------------------------------------------------------------- #
# engine
# --------------------------------------------------------------------------- #


class TestPagedServingEngine:
    def test_mixed_workload_parity_with_dense(self, model):
        """Mixed greedy/sampled, staggered lengths, shared prefixes:
        per-token output identical to the dense engine, prefix sharing on
        AND off; sharing shows hits and allocates fewer pages. (Parity vs
        plain generate() is transitive: test_serving.py pins dense ==
        generate.)"""
        rng = np.random.default_rng(42)
        shared = rng.integers(1, 1000, 20).astype(np.int32)
        prompts, temps = [], []
        for i in range(5):
            tail = rng.integers(1, 1000, 3 + i).astype(np.int32)
            prompts.append(np.concatenate([shared, tail]) if i % 2 == 0
                           else rng.integers(1, 1000, 4 + i).astype(np.int32))
            temps.append(0.0 if i % 3 else 0.7)
        max_new = [4 + i % 3 for i in range(5)]

        dense = _drive(ContinuousBatchingEngine(
            model, max_batch_size=4, max_seq_len=64, seed=3),
            prompts, temps, max_new)
        d_tokens = [r.generated for r in dense]

        hits0 = _counter("serving_prefix_hits_total")
        share_on = PagedServingEngine(model, max_batch_size=4, max_seq_len=64,
                                      page_size=16, seed=3)
        p_tokens = [r.generated
                    for r in _drive(share_on, prompts, temps, max_new)]
        assert p_tokens == d_tokens
        assert _counter("serving_prefix_hits_total") > hits0

        share_off = PagedServingEngine(model, max_batch_size=4,
                                       max_seq_len=64, page_size=16, seed=3,
                                       prefix_sharing=False)
        p2_tokens = [r.generated
                     for r in _drive(share_off, prompts, temps, max_new)]
        assert p2_tokens == d_tokens
        assert share_on.pool.allocs_total < share_off.pool.allocs_total

    def test_admits_more_concurrency_than_dense_hbm(self, model):
        """At the dense engine's exact HBM budget (max_batch_size=4 x
        max_seq_len=64 token slots), the paged engine runs 8 concurrent
        requests — pages are allocated per token actually cached, not per
        slot capacity."""
        dense_budget_pages = (4 * 64) // 16
        eng = PagedServingEngine(model, max_batch_size=8, max_seq_len=64,
                                 page_size=16,
                                 num_pages=dense_budget_pages + 1)
        rng = np.random.default_rng(0)
        for _ in range(8):
            eng.add_request(rng.integers(1, 1000, 6).astype(np.int32),
                            max_new_tokens=4)
        eng.step()
        assert eng.live_count == 8  # strictly more than dense's 4 slots
        assert all(len(r.generated) == 4 for r in eng.run())

    def test_cow_on_first_divergent_write(self, model):
        """Two identical prompts share every page including the partial
        tail; the first decode write must copy-on-write, and both requests
        still produce identical (correct) greedy tokens."""
        cow0 = _counter("serving_cow_copies_total")
        eng = PagedServingEngine(model, max_batch_size=4, max_seq_len=64,
                                 page_size=16, seed=3)
        prompt = np.random.default_rng(1).integers(1, 1000, 10).astype(np.int32)
        eng.add_request(prompt, max_new_tokens=4)
        eng.add_request(prompt, max_new_tokens=4)
        out = eng.run()
        assert out[0].generated == out[1].generated
        assert _counter("serving_cow_copies_total") > cow0

    def test_preemption_recovers_all_requests(self, model):
        """Deliberately undersized pool: decode growth across page
        boundaries must preempt (spill to host) and later resume, with
        per-token output still identical to the dense engine — no lost or
        recomputed tokens."""
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 1000, 14).astype(np.int32)
                   for _ in range(4)]
        prios = [0, -1, -2, -3]
        dense = _drive(ContinuousBatchingEngine(
            model, max_batch_size=4, max_seq_len=64, seed=3),
            prompts, max_new=[6] * 4, priorities=prios)
        pre0 = _counter("serving_preemptions_total")
        res0 = _counter("serving_resumes_total")
        # 4 x 14-token prompts = 4 pages; growth wants 4 more; pool holds 5
        eng = PagedServingEngine(model, max_batch_size=4, max_seq_len=64,
                                 page_size=16, seed=3, num_pages=6,
                                 watermark_pages=0, prefix_sharing=False)
        paged = _drive(eng, prompts, max_new=[6] * 4, priorities=prios)
        assert [r.generated for r in paged] == [r.generated for r in dense]
        assert _counter("serving_preemptions_total") > pre0
        assert _counter("serving_resumes_total") > res0

    def test_sampled_streams_survive_spill_and_resume(self, model):
        """Sampled requests through the undersized pool: the key stream
        lives in the row, leaves with the spilled request and comes back
        with the resume, so every token equals the dense engine's, which
        never preempts."""
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 1000, 14).astype(np.int32)
                   for _ in range(6)]
        temps = [0.7, 0.0, 1.3, 0.7, 0.0, 0.2]
        prios = [0, -1, -2, -3, 0, -1]
        dense = _drive(ContinuousBatchingEngine(
            model, max_batch_size=4, max_seq_len=64, seed=3),
            prompts, temps, [8] * 6, prios)
        eng = PagedServingEngine(model, max_batch_size=4, max_seq_len=64,
                                 page_size=16, seed=3, num_pages=6,
                                 watermark_pages=0, prefix_sharing=False)
        paged = _drive(eng, prompts, temps, [8] * 6, prios)
        assert [r.generated for r in paged] == [r.generated for r in dense]
        assert not any(r.preemptions for r in dense)
        # sampled rows were among the spilled, more than once
        assert sum(r.preemptions for r, t in zip(paged, temps) if t) >= 2
        assert not eng.temps.any()  # an empty row is a greedy row

    @pytest.mark.parametrize("engine", ["paged", "dense"])
    @pytest.mark.parametrize("temps", [(0.7, 0.0, 0.9), (0.0, 0.0, 0.0)],
                             ids=["two-sampled", "all-greedy"])
    def test_one_sample_span_a_tick_and_the_counter(self, model, engine,
                                                    temps):
        """A tick with sampled rows has exactly one `emit/sample` span
        (`rows` of them) and `serving_sampled_tokens_total` rises by that
        many; an all-greedy tick has no such span and leaves the counter."""
        if engine == "paged":
            eng = PagedServingEngine(model, max_batch_size=4, max_seq_len=64,
                                     page_size=16)
        else:
            eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                           max_seq_len=64)
        for i, t in enumerate(temps):
            eng.add_request(np.arange(3, 9 + i, dtype=np.int32),
                            max_new_tokens=4, temperature=t)
        want = sum(t > 0 for t in temps)
        n0 = _counter("serving_sampled_tokens_total", engine=engine)
        spans.clear_recorded()
        tl = spans.enable_step_timeline()
        try:
            out = eng.step()   # admits the three (first tokens) and decodes
        finally:
            tl.uninstall()
        ring = spans.recorded()
        spans.clear_recorded()
        assert len(out) == 3
        by = {}
        for r in ring:
            by.setdefault(r["path"], []).append(r["attrs"])
        assert by["engine.step/emit"] == [{"rows": 3, "sampled_rows": want}]
        assert by["engine.step/decode_dispatch"][0]["sampled_rows"] == want
        assert by.get("engine.step/emit/sample", []) == (
            [{"rows": want}] if want else [])
        assert _counter("serving_sampled_tokens_total",
                        engine=engine) == n0 + want
        eng.run()
        assert _counter("serving_sampled_tokens_total",
                        engine=engine) == n0 + 3 * want

    def test_truncation_is_flagged_and_counted(self, model):
        """A request whose prompt + budget exceeds max_seq_len retires at
        capacity with truncated=True and a counter bump (the dense engine's
        variant lives in test_serving.py)."""
        prompt = np.arange(1, 11, dtype=np.int32)  # 10 tokens, S=16
        eng = PagedServingEngine(model, max_batch_size=2, max_seq_len=16,
                                 page_size=8)
        t0 = _counter("serving_truncations_total", engine="paged")
        eng.add_request(prompt, max_new_tokens=100)
        done = eng.run()
        assert done[0].truncated
        assert len(done[0].generated) == 6  # 16 - 10
        assert _counter("serving_truncations_total", engine="paged") == t0 + 1

    def test_add_request_validation(self, model):
        eng = PagedServingEngine(model, max_batch_size=2, max_seq_len=16,
                                 page_size=8, num_pages=2)  # 1 usable page
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.add_request(np.zeros(16, np.int32))
        with pytest.raises(ValueError, match="pages"):
            eng.add_request(np.zeros(10, np.int32), max_new_tokens=4)

    # the bounded prefill compile cache is the shared BoundedCompileCache;
    # its cap/eviction/counter behavior is pinned on the dense engine in
    # test_serving.py::TestServingSatellites::test_prefill_compile_cache_capped


# --------------------------------------------------------------------------- #
# a prefill starts from the prompt alone, in every served family
# --------------------------------------------------------------------------- #


def _gpt():
    return GPTForCausalLM(gpt3_tiny())


def _llama():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    return LlamaForCausalLM(llama_tiny())   # RoPE, 4 heads over 2 KV heads


def _granite():
    from paddle_tpu.models.granite_hybrid import (GraniteHybridForCausalLM,
                                                  granite_hybrid_tiny)

    return GraniteHybridForCausalLM(granite_hybrid_tiny())


def _afmoe():
    from paddle_tpu.models.afmoe import AfmoeForCausalLM, afmoe_tiny

    return AfmoeForCausalLM(afmoe_tiny())


def _kimi():
    from paddle_tpu.models.kimi_k2 import KimiK2ForCausalLM, kimi_k2_tiny

    return KimiK2ForCausalLM(kimi_k2_tiny())


def _zero_cache(eng, sp):
    """The zeroed cache the engine built on the host for every prefill, an
    eager array a side, before a prefill started from the prompt alone: K
    and V a full-attention layer, the zero state a recurrent one, nothing
    for a window or a latent layer."""
    from paddle_tpu.inference.paged import PagedKV, RowState

    cfg, specs = eng.cfg, eng.cache_specs
    if specs is None:
        specs = [PagedKV(cfg.kv_heads, cfg.head_dim)] * cfg.num_layers
    entries = []
    for spec in specs:
        if isinstance(spec, RowState):
            entries.append(tuple(jnp.zeros((1,) + tuple(shape), eng.kv_dtype)
                                 for shape in spec.shapes))
        elif type(spec) is PagedKV:
            entries.append((jnp.zeros(
                (1, sp, spec.kv_heads, spec.head_dim), eng.kv_dtype),) * 2)
        else:
            entries.append(())
    return entries


@pytest.mark.parametrize("build", [_gpt, _llama, _granite, _afmoe, _kimi])
def test_a_prefill_starts_from_the_prompt_alone(build):
    """In each served family the engine's prefill program takes, beside the
    parameters and the buffers, two arrays: the bucket's tokens and the
    prompt's length. No spec builds a cache for it. What the admission
    leaves behind (the pages, the state rows, the first token) is what the
    program that started from a zeroed cache left: for GPT and LLaMA that
    is the dense-cache branch under its explicit mask."""
    paddle.seed(5)
    model = build()
    eng = PagedServingEngine(model, max_batch_size=2, max_seq_len=96,
                             page_size=8, prefix_sharing=False)
    for spec in eng.cache_specs or ():
        assert not hasattr(spec, "prefill_cache")
    n, sp, ps = 43, 64, 8           # past afmoe_tiny's window of 32
    prompt = np.random.default_rng(3).integers(1, 100, n).astype(np.int32)

    calls = []
    compile_prefill = eng._prefill_programs.get_or_compile

    def recorded(bucket, compile_fn):
        program = compile_prefill(bucket, compile_fn)

        def call(*args):
            calls.append((program, args))
            return program(*args)

        return call

    eng._prefill_programs.get_or_compile = recorded
    rid = eng.add_request(prompt, max_new_tokens=4)
    assert eng._admit() == 1    # and no decode step behind it yet
    (req,) = [r for r in eng.active if r is not None]
    row = eng.active.index(req)
    assert req.req_id == rid and eng.lengths[row] == n

    # the lowered program's inputs
    (program, args), = calls
    shared = len(jax.tree.leaves((eng.params, eng.buffers)))
    info = jax.tree.leaves(program.lower(*args).args_info)
    assert [(tuple(a.shape), str(a.dtype)) for a in info[shared:]] == [
        ((1, sp), "int32"), ((), "int32")]

    # the program that started from the zeroed cache, same weights
    tok = np.zeros((1, sp), np.int32)
    tok[0, :n] = prompt
    ref_logits, ref_c = eng._functional_forward(
        eng.params, eng.buffers, jnp.asarray(tok), jnp.arange(sp)[None],
        _zero_cache(eng, sp), jnp.int32(0),
        logits_at=jnp.asarray([n - 1]),
        **({} if eng.cache_specs is None
           else {"seq_lens": jnp.asarray([n], jnp.int32)}))
    assert req.generated[0] == int(np.argmax(np.asarray(ref_logits)[0, 0]))

    pool, m = eng.pool, -(-n // ps)
    state = dict(zip(pool.state_layers, pool.read_state(row)))
    for li, ref in enumerate(ref_c):
        if li in state:
            for got, want in zip(state[li], ref):
                np.testing.assert_allclose(got, np.asarray(want)[0],
                                           rtol=2e-5, atol=2e-6)
            continue
        gi = pool.group_of_layer[li]
        first = eng.window_start[row] if eng.groups[gi].window else 0
        pages = eng.group_tables[gi][row, :m - first]
        assert (pages > 0).all()
        for got, want in zip(pool.kv[pool.entry_of_layer[li]], ref):
            want = np.array(want)[0]
            want[n:] = 0                        # the padded tail is zero
            want = np.moveaxis(
                want.reshape((-1, ps) + want.shape[1:]), 1, -2)[first:m]
            np.testing.assert_allclose(np.asarray(got)[pages], want,
                                       rtol=2e-5, atol=2e-6)
    eng.run()
    assert pool.pages_free == pool.pages_total


@pytest.mark.slow
class TestPagedDrainEndToEnd:
    def test_large_mixed_drain_under_pressure(self, model):
        """End-to-end: 16 mixed greedy/sampled requests with shared
        prefixes through an undersized pool — everything drains, outputs
        match the dense engine, and the SLO series are populated."""
        rng = np.random.default_rng(11)
        shared = rng.integers(1, 1000, 16).astype(np.int32)
        prompts, temps, max_new, prios = [], [], [], []
        for i in range(16):
            tail = rng.integers(1, 1000, 2 + i % 7).astype(np.int32)
            prompts.append(np.concatenate([shared, tail]) if i % 3 == 0
                           else rng.integers(1, 1000, 3 + i % 9).astype(np.int32))
            temps.append(0.6 if i % 4 == 0 else 0.0)
            max_new.append(4 + i % 6)
            prios.append(-(i % 5))
        dense = _drive(ContinuousBatchingEngine(
            model, max_batch_size=4, max_seq_len=64, seed=9),
            prompts, temps, max_new, prios)
        eng = PagedServingEngine(model, max_batch_size=4, max_seq_len=64,
                                 page_size=16, seed=9, num_pages=8,
                                 watermark_pages=1)
        paged = _drive(eng, prompts, temps, max_new, prios)
        assert [r.generated for r in paged] == [r.generated for r in dense]
        reg = default_registry()
        ttft = reg.get("serving_ttft_seconds")
        assert ttft is not None and ttft.count(engine="paged") >= 16
        assert reg.get("serving_tokens_total").value(engine="paged") >= \
            sum(len(r.generated) for r in paged)
