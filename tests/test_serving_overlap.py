"""One decode tick in flight (inference/paged/engine.py `_step`): the paged
engine dispatches tick N+1 before it reads tick N, and reads N first only
where N's tokens decide what comes next (an admission, a spill).

What holds it: every family's tokens equal those of the same engine made to
read each tick before the next (today's order, `_Drained`), and the dense
engine's where it can serve the model; admissions and spills at the same
steps; `serving_decode_ticks_total{path}` and the `overlapped` attribute
against a hand count; at most one dispatch and one read a step."""

import collections
import copy

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.paged import BlockPool, PagedServingEngine
from paddle_tpu.inference.paged.block_pool import PagedKV
from paddle_tpu.inference.paged.scheduler import TwoQueueScheduler
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models import GPTForCausalLM, gpt3_tiny
from paddle_tpu.models.afmoe import AfmoeForCausalLM, afmoe_tiny
from paddle_tpu.models.granite_hybrid import (GraniteHybridForCausalLM,
                                              granite_hybrid_tiny)
from paddle_tpu.models.kimi_k2 import KimiK2ForCausalLM, kimi_k2_tiny
from paddle_tpu.models.mimo_v2 import MimoV2ForCausalLM, mimo_v2_tiny
from paddle_tpu.observability import spans
from paddle_tpu.observability.metrics import default_registry


@pytest.fixture(autouse=True)
def _interpret_mode(pallas_interpret_unless_hw):
    pass


class _Drained(PagedServingEngine):
    """The engine made to read every tick before it dispatches the next: a
    step in today's order, the reference of the overlapped one."""

    def _must_drain(self):
        return True


def _counter(name, **labels):
    m = default_registry().get(name)
    return m.value(**labels) if m is not None else 0.0


# family -> (model builder, what its engines are given: a pool that spills
# the workload below)
FAMILIES = {
    "gpt": (lambda: GPTForCausalLM(gpt3_tiny()),
            dict(max_seq_len=64, num_pages=9)),
    "granite": (lambda: GraniteHybridForCausalLM(granite_hybrid_tiny()),
                dict(max_seq_len=64, num_pages=9)),
    "trinity": (lambda: AfmoeForCausalLM(afmoe_tiny()),
                dict(max_seq_len=96, num_pages=34)),
    "kimi": (lambda: KimiK2ForCausalLM(kimi_k2_tiny()),
             dict(max_seq_len=64, num_pages=9)),
    # units of the full layers' pages: no reserve, or nothing spills here
    "mimo": (lambda: MimoV2ForCausalLM(mimo_v2_tiny()),
             dict(max_seq_len=96, num_pages=44, watermark_pages=0)),
}

# (prompt length, new tokens, temperature, the step it arrives before)
WORKLOAD = [(9, 14, 0.0, 0), (20, 20, 0.8, 0), (14, 10, 0.0, 0),
            (5, 24, 0.6, 3), (28, 12, 0.0, 3), (12, 16, 0.0, 7),
            (7, 6, 0.9, 12)]


def _serve(eng, eos=None, timeline=False):
    """The workload through `eng`, a step at a time, requests sent before
    the step they arrive at. Returns (per request in arrival order: its
    tokens, the step it was first admitted in, its preemptions; the
    `engine.step` records with their children's paths, when `timeline`)."""
    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, 120, n).astype(np.int32)
               for n, *_ in WORKLOAD]
    spans.clear_recorded()
    tl = spans.enable_step_timeline() if timeline else None
    rids, step, t0 = [], 0, eng._tick
    try:
        while len(rids) < len(WORKLOAD) or eng.has_work():
            for (n, new, temp, at), p in zip(WORKLOAD, prompts):
                if at == step:
                    rids.append(eng.add_request(
                        p, max_new_tokens=new, temperature=temp,
                        eos_token_id=eos))
            eng.step()
            step += 1
    finally:
        if tl is not None:
            tl.uninstall()
    ring = spans.recorded()
    spans.clear_recorded()
    admitted = {}
    for r in ring:
        if r["path"] == "admission" and r["attrs"]["kind"] == "prefill":
            admitted[r["attrs"]["rid"]] = r["attrs"]["tick"] - t0
    by = {r.req_id: r for r in eng.finished}
    eng.finished = []
    out = [(by[i].generated, admitted[i], by[i].preemptions) for i in rids]
    steps = {r["id"]: (r, []) for r in ring if r["path"] == "engine.step"}
    for r in ring:
        if r["parent"] in steps:
            steps[r["parent"]][1].append(r["path"].split("/")[-1])
    return out, list(steps.values())


@pytest.mark.parametrize("family", list(FAMILIES))
def test_every_family_serves_the_tokens_of_a_tick_read_before_the_next(
        family):
    """Greedy and sampled rows, arrivals while decoding and a pool small
    enough to spill and resume: the overlapped engine's tokens, admission
    steps and spills are the drained reference's (and the dense engine's
    tokens, for GPT). Then the same with an EOS that ends requests early:
    the tokens still agree; an EOS'd row's token of the tick already
    dispatched is dropped, and its pages come back."""
    build, kw = FAMILIES[family]
    paddle.seed(3)
    model = build()
    kw = dict(max_batch_size=4, page_size=8, seed=5, **kw)
    eng, ref = PagedServingEngine(model, **kw), _Drained(model, **kw)
    dense = (ContinuousBatchingEngine(model, max_batch_size=4,
                                      max_seq_len=kw["max_seq_len"], seed=5)
             if family == "gpt" else None)
    over0 = _counter("serving_decode_ticks_total", path="overlapped")
    got, steps = _serve(eng, timeline=True)
    want, _ = _serve(ref)
    assert got == want
    assert sum(p for *_, p in got) >= 1          # the pool spilled
    assert _counter("serving_decode_ticks_total",
                    path="overlapped") > over0
    # a step: at most one dispatch and one read, the tick's attribute
    for rec, kids in steps:
        assert kids.count("decode_dispatch") <= 1
        assert kids.count("host_read") <= 1
        assert rec["attrs"]["overlapped"] == (
            "decode_dispatch" in kids and "host_read" in kids
            and kids.index("decode_dispatch") < kids.index("host_read"))
    if dense is not None:
        assert [g for g, *_ in _serve(dense)[0]] == [g for g, *_ in got]

    # an EOS: a token the first request makes mid-answer
    eos = got[0][0][len(got[0][0]) // 2]
    got, _ = _serve(eng, eos=eos)
    want, _ = _serve(ref, eos=eos)
    assert [g for g, *_ in got] == [g for g, *_ in want]
    early = [g for (g, *_), (_, new, *_) in zip(got, WORKLOAD)
             if len(g) < new]
    assert early and all(g[-1] == eos for g in early)
    if dense is not None:
        assert [g for g, *_ in _serve(dense, eos=eos)[0]] == [
            g for g, *_ in got]
    for e in (eng, ref):
        assert e.pool.pages_free == e.pool.pages_total
        assert not e.pool.ref.any() and e._inflight is None


def test_the_tick_counter_and_the_step_attribute_match_a_hand_count():
    """A: 3 tokens, B: 5, C: 2 sent before step 4. Step 1 admits A and B
    and dispatches tick 1 (drained: nothing was in flight); 2 and 3
    dispatch ticks 2 and 3 over the one in flight (A ends at tick 2, so
    tick 3 is B's alone); 4 reads tick 3 first, since C is waiting, admits
    C and dispatches tick 4 (drained); 5 reads tick 4, which ends B and C,
    and dispatches nothing. The dispatch span of each tick counts its rows
    and its live grid steps."""
    paddle.seed(0)
    eng = PagedServingEngine(GPTForCausalLM(gpt3_tiny()), max_batch_size=4,
                             max_seq_len=64, page_size=16)
    before = {p: _counter("serving_decode_ticks_total", path=p)
              for p in ("overlapped", "drained")}
    a = eng.add_request(np.arange(1, 10, dtype=np.int32), max_new_tokens=3)
    b = eng.add_request(np.arange(2, 22, dtype=np.int32), max_new_tokens=5)
    tl = spans.enable_step_timeline()
    outs = []
    try:
        for step in range(1, 6):
            if step == 4:
                c = eng.add_request(np.arange(3, 8, dtype=np.int32),
                                    max_new_tokens=2)
            outs.append(eng.step())
    finally:
        tl.uninstall()
    ring = spans.recorded()
    spans.clear_recorded()
    assert not eng.has_work()
    ticks = [r["attrs"] for r in ring if r["path"] == "engine.step"]
    assert [t["overlapped"] for t in ticks] == [False, True, True, False,
                                                False]
    assert {p: _counter("serving_decode_ticks_total", path=p) - before[p]
            for p in before} == {"overlapped": 2, "drained": 2}
    # what each step returned: the tick it read
    assert [sorted(o) for o in outs] == [[], [a, b], [a, b], [b],
                                         sorted([b, c])]
    dispatches = [r["attrs"] for r in ring
                  if r["path"] == "engine.step/decode_dispatch"]
    # a table of 4 pages at 4 a step: one grid step a live row
    assert [(d["rows"], d["live_grid_steps"]) for d in dispatches] == [
        (2, 2), (2, 2), (1, 1), (2, 2)]
    done = {r.req_id: r.generated for r in eng.finished}
    assert [len(done[r]) for r in (a, b, c)] == [3, 5, 2]


def _taken(s, *args):
    """What `pick` takes from a copy of `s`'s queues (the same requests),
    and whether the copy's queues lost exactly those."""
    c = copy.copy(s)
    c.resume = collections.deque(s.resume)
    c.prefill = {b: collections.deque(d) for b, d in s.prefill.items()}
    got = c.pick(*args)
    left = list(c.resume) + [r for d in c.prefill.values() for _, r in d]
    waiting = list(s.resume) + [r for d in s.prefill.values() for _, r in d]
    return got, left == [r for r in waiting if r not in got]


def test_the_drain_sees_an_admission_the_scheduler_would_make():
    """The drain asks `plan`, and `pick` takes exactly its admissions: no
    row, no waiting work or a head over the watermark plans nothing; the
    idle-engine fallback and a resume ahead of the prefills are planned as
    `pick` takes them, and planning leaves the queues as they were."""
    s = TwoQueueScheduler(8)
    assert s.plan(4, 100, 0) == []
    req = type("R", (), {"prompt": np.zeros(40)})()
    s.enqueue_prefill(req)
    assert s.plan(4, 100, 1) == [req] and s.plan(0, 100, 1) == []
    # 5 pages and a watermark of 2 (live 1, plus the candidate)
    assert s.plan(4, 6, 1) == [] and s.plan(4, 7, 1) == [req]
    assert s.plan(4, 5, 0) == [req]      # nothing live: fits at all
    s.enqueue_resume(type("S", (), {"n_pages": 9})())
    for args in ((4, 100, 1), (4, 6, 1), (4, 7, 1), (4, 9, 0), (4, 8, 0),
                 (4, 11, 1), (4, 12, 1)):
        assert _taken(s, *args) == (s.plan(*args), True)
    assert s.plan(4, 7, 1) == []         # the resume comes first
    assert s.waiting_prefill == s.waiting_resume == 1


@pytest.mark.parametrize("spans_asked,free,walk", [
    ([1, 1, 1, 1], 2, ["break", "unit", "break", "unit"]),
    ([1] * 5, 2, ["break", "unit", "break", "unit", None]),
    ([2, 2], 2, ["block", "block"]), ([1, 2], 2, ["break", "block"]),
    ([1, 2, 2], 2, ["break", "block", None])],
    ids=["units-of-broken-blocks", "one-too-many", "blocks",
         "a-block-after-a-unit", "a-unit-broke-a-block"])
def test_the_pool_says_whether_a_tick_of_allocations_fits(spans_asked, free,
                                                          walk):
    """`fits` is the allocator's own walk on its counts: on a pool of two
    page shapes (blocks of 2 units) a unit breaks a whole block only when no
    broken one has a unit left, and a block needs a whole one; `alloc` takes
    a page where the walk says, and none where it says None."""
    specs = [PagedKV(1, 4), PagedKV(2, 4)]   # units of 1 and blocks of 2
    pool = BlockPool(2, page_size=4, num_pages=2 * (free + 1), specs=specs)
    assert pool.span == 2 and pool.blocks_free == free
    assert list(pool._walk(spans_asked)) == walk
    assert pool.fits(spans_asked) == (None not in walk)
    for span, step in zip(spans_asked, walk):
        blocks = pool.blocks_free
        assert (pool.alloc(span) is None) == (step is None)
        assert pool.blocks_free == blocks - (step in ("block", "break"))


def test_a_tail_page_two_rows_share_is_copied_once():
    """Two rows admitted from one prompt share its tail page, and a pool
    with ONE page free: the first row's copy-on-write leaves the page the
    second's own, so the write targets need one page, fit, and the tick in
    flight is not drained; `_write_targets` carries out that plan."""
    paddle.seed(0)
    eng = PagedServingEngine(GPTForCausalLM(gpt3_tiny()), max_batch_size=4,
                             max_seq_len=64, page_size=8, num_pages=12,
                             watermark_pages=0)
    prompt = np.arange(1, 13, dtype=np.int32)   # a page and a half
    for _ in range(2):
        eng.add_request(prompt, max_new_tokens=4)
    assert eng._admit() == 2
    (a, b) = live = eng._live_rows()
    tail = int(eng.tables[a, 1])
    assert int(eng.tables[b, 1]) == tail and eng.pool.ref[tail] == 2
    while eng.pool.pages_free > 1:
        eng.pool.alloc()
    assert not eng._must_drain()
    assert [act for *_, act in eng._targets] == ["copy", "unregister"]
    eng._write_targets(live, eng._targets)
    assert eng.pool.pages_free == 0 and eng.pool.cow_copies_total == 1
    assert int(eng.tables[b, 1]) == tail != int(eng.tables[a, 1])
    assert eng.pool.ref[tail] == 1 and not eng.pool.is_registered(tail)
    assert not eng.sched.waiting_resume   # nothing spilled
