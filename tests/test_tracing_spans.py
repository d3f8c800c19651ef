"""The one span system (paddle_tpu/observability/spans.py): live exactly when
somebody listens, written into the profiler's own trace, with spans inside
the serving tick and the train step, a compile listener, and a stable name
on every Pallas kernel (docs/OBSERVABILITY.md has the catalog)."""

import ast
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.inference.paged import PagedServingEngine
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models import GPTForCausalLM, gpt3_tiny
from paddle_tpu.observability import spans
from paddle_tpu.observability.metrics import default_registry
from paddle_tpu.observability.spans import span
from paddle_tpu.ops.pallas.autotune import KERNEL_NAMES
from paddle_tpu.profiler import Profiler, RecordEvent

PKG = os.path.dirname(os.path.abspath(paddle.__file__))

# every path of a tick (docs/OBSERVABILITY.md); a tick holds each of the
# first six once, `emit/sample` once if it has sampled rows, the others once
# per admitted or preempted request
TICK_PHASES = ["engine.step/admit", "engine.step/write_targets",
               "engine.step/decode_dispatch", "engine.step/host_read",
               "engine.step/emit"]
TICK_PATHS = ["engine.step"] + TICK_PHASES + [
    "engine.step/admit/prefill", "engine.step/admit/prefill/inputs",
    "engine.step/admit/prefill/call", "engine.step/admit/pages",
    "engine.step/admit/write_pages", "engine.step/admit/first_token",
    "engine.step/admit/resume", "engine.step/write_targets/spill",
    "engine.step/emit/sample"]
PER_REQUEST = [p for p in TICK_PATHS if p.count("/") == 2
               and p != "engine.step/emit/sample"]


@pytest.fixture(autouse=True)
def _clean(pallas_interpret_unless_hw):
    spans.clear_recorded()
    yield
    spans.clear_recorded()
    assert not spans._span_stack(), "a span was left open"


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return GPTForCausalLM(gpt3_tiny())


@pytest.fixture(scope="module")
def traced_ticks(model, tmp_path_factory):
    """(ring records, {host span name: stats of its first event}) of an
    undersized paged engine driven to the end under a REAL profiler trace:
    four requests, every second one sampled, a pool that forces a spill and
    a resume (the recipe of test_preemption_recovers_all_requests)."""
    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    try:
        rng = np.random.default_rng(7)
        eng = PagedServingEngine(model, max_batch_size=4, max_seq_len=64,
                                 page_size=16, seed=3, num_pages=6,
                                 watermark_pages=0, prefix_sharing=False)
        eng.add_request(rng.integers(1, 1000, 14).astype(np.int32),
                        max_new_tokens=2)
        eng.run()  # compiles; nobody listens yet: the one record is always on
        assert [r["path"] for r in spans.recorded()] == ["admission"]
        spans.clear_recorded()
        trace_dir = str(tmp_path_factory.mktemp("trace"))
        jax.profiler.start_trace(trace_dir)
        try:
            for i in range(4):
                eng.add_request(rng.integers(1, 1000, 14).astype(np.int32),
                                max_new_tokens=6, priority=-i,
                                temperature=0.7 if i % 2 else 0.0)
            done = eng.run()
        finally:
            jax.profiler.stop_trace()
    finally:
        del os.environ["PADDLE_TPU_PALLAS_INTERPRET"]
    assert len(done) == 4
    from jax.profiler import ProfileData

    (pb,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(pb).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("engine.step"):
                        host.setdefault(ev.name, dict(ev.stats))
    ring = spans.recorded()
    spans.clear_recorded()
    return ring, host


# -- the tick -------------------------------------------------------------- #

@pytest.mark.parametrize("path", TICK_PATHS)
def test_a_traced_tick_yields_the_path_in_ring_and_trace(traced_ticks, path):
    ring, host = traced_ticks
    assert any(r["path"] == path for r in ring), path
    assert path in host, f"{path} is not on /host:CPU of the .xplane.pb"


def test_children_nest_inside_the_tick_and_cover_it(traced_ticks):
    ring, _ = traced_ticks
    by_id = {r["id"]: r for r in ring}
    ticks = [r for r in ring if r["path"] == "engine.step"]
    assert [r["attrs"]["tick"] for r in ticks] == sorted(
        r["attrs"]["tick"] for r in ticks)
    for r in ring:
        if r["parent"] in by_id and r["path"] not in ("request", "compile",
                                                      "admission"):
            p = by_id[r["parent"]]
            assert r["path"].startswith(p["path"] + "/")
            assert p["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] <= p["t1_ns"]
    whole = [t for t in ticks if "live" in t["attrs"] and t["attrs"]["live"]]
    assert whole
    for t in whole:
        kids = sorted((r for r in ring if r["parent"] == t["id"]
                       and r["path"] in TICK_PHASES),
                      key=lambda r: r["t0_ns"])
        # every phase of the tick is a span, in order, none overlapping
        assert [k["path"] for k in kids] == TICK_PHASES
        assert all(a["t1_ns"] <= b["t0_ns"] for a, b in zip(kids, kids[1:]))
        assert {"tick", "live", "waiting"} <= set(t["attrs"])


@pytest.mark.parametrize("path", PER_REQUEST)
def test_spans_of_one_request_carry_its_rid(traced_ticks, path):
    ring, host = traced_ticks
    mine = [r for r in ring if r["path"] == path]
    assert mine and all(isinstance(r["attrs"].get("rid"), int) for r in mine)
    assert "rid" in host[path]  # the attribute reached the profiler's trace


def test_sampling_is_one_span_a_tick_not_one_a_row(traced_ticks):
    """The decode program picks the sampled rows' tokens: the host's part is
    one `emit/sample` span in a tick that has such rows (`rows` of them, the
    `sampled_rows` of the tick's `emit` and `decode_dispatch`), none in a
    tick that has none."""
    ring, host = traced_ticks
    ticks = {r["id"]: {} for r in ring if r["path"] == "engine.step"}
    emits = {r["id"]: r for r in ring if r["path"] == "engine.step/emit"}
    for r in ring:
        if r["path"] in ("engine.step/emit", "engine.step/decode_dispatch"):
            ticks[r["parent"]][r["path"]] = r["attrs"]["sampled_rows"]
        elif r["path"] == "engine.step/emit/sample":
            ticks[emits[r["parent"]]["parent"]].setdefault(
                "samples", []).append(r["attrs"])
    decoded = [t for t in ticks.values() if "engine.step/emit" in t]
    assert {t["engine.step/emit"] for t in decoded} >= {0, 1}
    for t in decoded:
        n = t["engine.step/emit"]
        assert t["engine.step/decode_dispatch"] == n
        assert t.get("samples", []) == ([{"rows": n}] if n else [])
    assert "rows" in host["engine.step/emit/sample"]
    assert "sampled_rows" in host["engine.step/decode_dispatch"]


def test_decode_dispatch_counts_the_live_grid_steps(traced_ticks):
    """`live_grid_steps`: the steps one call of the paged decode kernel
    walks this tick. Here a table is 4 pages wide and a step takes 4, so a
    live row is one step and the grid of 4 steps shrinks to the live rows;
    the attribute reaches the profiler's trace."""
    ring, host = traced_ticks
    dispatches = [r["attrs"] for r in ring
                  if r["path"] == "engine.step/decode_dispatch"]
    assert dispatches
    for a in dispatches:
        assert (a["pages_per_step"], a["grid_steps"]) == (4, 4)
        assert a["live_grid_steps"] == a["rows"]
    assert len({a["live_grid_steps"] for a in dispatches}) > 1
    assert "live_grid_steps" in host["engine.step/decode_dispatch"]


@pytest.mark.parametrize("prompts,page_size,steps", [
    # a table 8 pages wide at 8 pages a step: a step a live row
    ((14, 30), 8, (1, 1)),
    # 32 pages wide at 16 a step: 70 + 2 tokens are 18 pages, two steps
    ((70, 9, 40), 4, (2, 1, 1))],
    ids=["one-step-rows", "a-two-step-row"])
def test_live_grid_steps_equal_a_hand_count_and_the_work_list(
        model, prompts, page_size, steps):
    """A seated batch on its second tick: `live_grid_steps` is the hand
    count and the kernel's own work list's count; the histogram
    `serving_decode_live_step_share{kind="full"}` takes one observation a
    tick, the live steps over the steps the table holds."""
    from paddle_tpu.ops.pallas import decode_attention as da

    eng = PagedServingEngine(model, max_batch_size=4, max_seq_len=128,
                             page_size=page_size, prefix_sharing=False)
    rng = np.random.default_rng(5)
    for n in prompts:
        eng.add_request(rng.integers(1, 1000, n).astype(np.int32),
                        max_new_tokens=4)
    eng.step()
    share = default_registry().get("serving_decode_live_step_share")
    before = (share.count(kind="full"), share.sum(kind="full"))
    tl = spans.enable_step_timeline()
    try:
        eng.step()
    finally:
        tl.uninstall()
    (attrs,) = [r["attrs"] for r in spans.recorded()
                if r["path"] == "engine.step/decode_dispatch"]
    n = attrs["pages_per_step"]
    assert attrs["live_grid_steps"] == sum(steps)
    assert attrs["grid_steps"] == 4 * -(-eng.P // n)
    # the second tick's kernel saw lengths - 1 + 1 tokens a row
    work = da.work_list(jnp.asarray(eng.tables),
                        jnp.asarray(eng.lengths), page_size, n)
    assert int(work.count) == sum(steps)
    assert share.count(kind="full") == before[0] + 1
    assert share.sum(kind="full") - before[1] == pytest.approx(
        sum(steps) / attrs["grid_steps"])
    eng.run()


def test_a_request_record_appears_on_retirement(traced_ticks):
    ring, _ = traced_ticks
    reqs = [r for r in ring if r["path"] == "request"]
    assert len(reqs) == 4
    for r in reqs:
        a = r["attrs"]
        assert {"rid", "prompt_len", "generated", "t_arrival", "t_admit",
                "t_first", "t_done", "preemptions"} <= set(a)
        assert a["prompt_len"] == 14 and a["generated"] == 6
        assert a["t_arrival"] <= a["t_admit"] <= a["t_first"] <= a["t_done"]
    assert sum(r["attrs"]["preemptions"] for r in reqs) >= 1
    spilled = [r for r in ring if r["path"].endswith("/spill")]
    assert sum(r["attrs"]["pages"] for r in spilled) >= 1


def test_the_dense_engine_spans_the_same_phases(model):
    eng = ContinuousBatchingEngine(model, max_batch_size=2, max_seq_len=32)
    eng.add_request(np.arange(1, 9, dtype=np.int32), max_new_tokens=3,
                    temperature=0.5)
    tl = spans.enable_step_timeline()
    try:
        eng.run()
    finally:
        tl.uninstall()
    paths = {r["path"] for r in spans.recorded()}
    assert {"engine.step", "engine.step/admit", "engine.step/admit/prefill",
            "engine.step/admit/first_token", "engine.step/decode_dispatch",
            "engine.step/host_read", "engine.step/emit",
            "engine.step/emit/sample", "request", "admission"} <= paths


# -- the admission record: always on --------------------------------------- #

PHASES = ("prefill_s", "pages_s", "write_pages_s", "write_state_s",
          "first_token_s")
# the two halves of `prefill_s`: the host builds and places the program's two
# inputs, then the jitted call returns
PREFILL_HALVES = ("inputs_s", "call_s")
PREFILL_KEYS = {"rid", "kind", "tick", "row", "prompt_len", "bucket",
                "compiled", "pages_written", "prefix_hits", "queue_wait_s",
                *PHASES, *PREFILL_HALVES}


def _admissions(ring=None, kind=None):
    return [r for r in (spans.recorded() if ring is None else ring)
            if r["path"] == "admission"
            and kind in (None, r["attrs"]["kind"])]


@pytest.mark.parametrize("engine", ["paged", "dense"])
def test_with_no_listener_an_admitting_tick_leaves_one_record_a_request(
        model, engine):
    """No listener, so no span reaches the ring; the `admission` records do:
    one a request that took a row, with the request's own numbers, phases
    that are each >= 0 and fit inside the record. Both engines write the
    same keys."""
    if engine == "paged":
        eng = PagedServingEngine(model, max_batch_size=4, max_seq_len=64,
                                 page_size=8)
    else:
        eng = ContinuousBatchingEngine(model, max_batch_size=4,
                                       max_seq_len=64)
    rng = np.random.default_rng(11)
    lens = {eng.add_request(rng.integers(1, 1000, n).astype(np.int32),
                            max_new_tokens=3): n for n in (9, 17, 30)}
    assert not spans.live()
    eng.step()
    ring = spans.recorded()
    assert [r["path"] for r in ring] == ["admission"] * 3
    assert sorted(r["attrs"]["rid"] for r in ring) == sorted(lens)
    assert len({r["attrs"]["row"] for r in ring}) == 3
    for r in ring:
        a = r["attrs"]
        assert set(a) == PREFILL_KEYS and r["parent"] is None
        assert (a["kind"], a["tick"]) == ("prefill", 1)
        assert a["prompt_len"] == lens[a["rid"]]
        assert a["bucket"] == (16 if a["prompt_len"] <= 16 else 32)
        assert a["queue_wait_s"] >= 0
        assert all(a[k] >= 0 for k in PHASES)
        assert a["prefill_s"] > 0 and a["first_token_s"] > 0
        assert all(a[k] >= 0 for k in PREFILL_HALVES)
        assert a["inputs_s"] + a["call_s"] <= a["prefill_s"]
        assert sum(a[k] for k in PHASES) <= (r["t1_ns"] - r["t0_ns"]) / 1e9
        if engine == "paged":
            pages = -(-a["prompt_len"] // 8)
            assert a["pages_written"] + a["prefix_hits"] == pages
            assert a["pages_s"] > 0 and a["write_pages_s"] > 0
        else:
            assert a["pages_written"] == a["pages_s"] == 0
    assert all(r["t1_ns"] <= s["t0_ns"] for r, s in zip(ring, ring[1:]))
    eng.run()
    assert len(_admissions()) == 3   # a decode tick writes none


def test_a_prefill_record_a_first_token_and_a_resume_record_a_resume(model):
    """An undersized pool, nobody listening: every request that got a first
    token left one record of kind "prefill", every spilled one that came
    back a second record of kind "resume" in a later tick."""
    eng = PagedServingEngine(model, max_batch_size=4, max_seq_len=64,
                             page_size=16, seed=3, num_pages=6,
                             watermark_pages=0, prefix_sharing=False)
    rng = np.random.default_rng(7)
    for i in range(4):
        eng.add_request(rng.integers(1, 1000, 14).astype(np.int32),
                        max_new_tokens=6, priority=-i)
    resumes0 = default_registry().get("serving_resumes_total").value()
    done = eng.run()
    assert {r["path"] for r in spans.recorded()} == {"admission"}
    first = _admissions(kind="prefill")
    assert len(first) == sum(bool(r.generated) for r in done) == 4
    back = _admissions(kind="resume")
    assert len(back) == (default_registry().get(
        "serving_resumes_total").value() - resumes0) >= 1
    assert sum(r.preemptions for r in done) == len(back)
    by_rid = {r["attrs"]["rid"]: r for r in first}
    for r in back:
        a = r["attrs"]
        assert set(a) == {"rid", "kind", "tick", "row", "prompt_len",
                          "resume_s", "pages_restored", "state_bytes"}
        assert a["tick"] > by_rid[a["rid"]]["attrs"]["tick"]
        assert a["prompt_len"] == 14 and a["pages_restored"] >= 1
        assert 0 < a["resume_s"] <= (r["t1_ns"] - r["t0_ns"]) / 1e9


def test_with_a_listener_the_records_lie_inside_admit_and_cover_it(
        traced_ticks):
    ring, _ = traced_ticks
    admits = {r["id"]: r for r in ring if r["path"] == "engine.step/admit"}
    records = _admissions(ring)
    assert {r["attrs"]["kind"] for r in records} == {"prefill", "resume"}
    covered, prefilled = {}, set()
    for r in records:
        admit = admits[r["parent"]]   # written under the tick's admit span
        assert admit["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] <= admit["t1_ns"]
        covered[admit["id"]] = covered.get(admit["id"], 0) + (
            r["t1_ns"] - r["t0_ns"])
        if r["attrs"]["kind"] == "prefill":
            prefilled.add(admit["id"])
    assert sum(a["attrs"]["picked"] for a in admits.values()) == len(records)
    # what `_admit` does outside the records (free rows, the scheduler's
    # pick) is under 5 % of a tick's span that prefills, and under a
    # millisecond beside a resume of a page or two, itself 0.3 ms here
    assert prefilled
    for key, ns in covered.items():
        whole = admits[key]["t1_ns"] - admits[key]["t0_ns"]
        assert whole - ns <= (0.05 * whole if key in prefilled else 1_000_000)
    # the phases are the child spans' own clock reads
    spans_of = {}
    for r in ring:
        if r["path"].startswith("engine.step/admit/"):
            spans_of[(r["attrs"]["rid"], r["path"].rsplit("/", 1)[1])] = r
    for r in records:
        a = r["attrs"]
        names = (["resume"] if a["kind"] == "resume"
                 else ["prefill", "inputs", "call", "pages", "write_pages",
                       "first_token"])
        for name in names:
            child = spans_of[(a["rid"], name)]
            assert a[name + "_s"] == pytest.approx(
                (child["t1_ns"] - child["t0_ns"]) / 1e9)


def test_a_prefill_spans_its_inputs_and_its_call_once_an_admission(
        traced_ticks):
    """`engine.step/admit/prefill/inputs` and `/call`: one of each a
    prefilled request, in that order, inside the request's `prefill` span;
    both reach the profiler's trace."""
    ring, host = traced_ticks
    prefills = {r["id"]: r for r in ring
                if r["path"] == "engine.step/admit/prefill"}
    assert len(prefills) == len(_admissions(ring, "prefill")) >= 1
    halves = {}
    for r in ring:
        if r["path"].startswith("engine.step/admit/prefill/"):
            halves.setdefault(r["parent"], []).append(r)
    assert set(halves) == set(prefills)
    for key, (inputs, call) in halves.items():
        whole = prefills[key]
        assert (inputs["path"], call["path"]) == (
            "engine.step/admit/prefill/inputs",
            "engine.step/admit/prefill/call")
        assert inputs["attrs"]["rid"] == call["attrs"]["rid"] == (
            whole["attrs"]["rid"])
        assert (whole["t0_ns"] <= inputs["t0_ns"] <= inputs["t1_ns"]
                <= call["t0_ns"] <= call["t1_ns"] <= whole["t1_ns"])
    assert {"engine.step/admit/prefill/inputs",
            "engine.step/admit/prefill/call"} <= set(host)


def test_the_step_histogram_takes_the_spans_own_clock_reads(model):
    eng = PagedServingEngine(model, max_batch_size=2, max_seq_len=32,
                             page_size=8)
    eng.add_request(np.arange(1, 9, dtype=np.int32), max_new_tokens=2)
    h = default_registry().get("serving_step_seconds")
    n0 = h.count(engine="paged") if h is not None else 0
    eng.run()
    assert default_registry().get("serving_step_seconds").count(
        engine="paged") > n0
    src = [open(os.path.join(PKG, "inference", *f)).read()
           for f in (("serving.py",), ("paged", "engine.py"))]
    assert "tick.seconds" in src[0] and "t_tick" not in "".join(src)


# -- live exactly when somebody listens ------------------------------------ #

def test_with_no_listener_a_span_pushes_and_writes_nothing():
    assert not spans.live()
    with span("outer", rid=1) as outer:
        with span("inner") as inner:
            inner.set(pages=3)
            assert spans._span_stack() == []
            assert outer._path is None and inner._path is None
    assert spans.recorded() == []
    assert inner.attrs == {}  # set() kept nothing
    assert outer.seconds >= inner.seconds >= 0  # the clock is read anyway
    spans.record_span("request", 0, 1, rid=1)
    assert spans.recorded() == []


def test_each_listener_makes_spans_live_and_the_ring_is_bounded(tmp_path):
    tl = spans.enable_step_timeline()
    try:
        assert spans.live()
        with span("a", k=1) as a:
            with span("b"):
                pass
            a.set(late=2)
    finally:
        tl.uninstall()
    assert not spans.live()
    b, a = spans.recorded()
    assert (a["path"], b["path"], b["parent"]) == ("a", "a/b", a["id"])
    assert a["attrs"] == {"k": 1, "late": 2} and a["parent"] is None
    with Profiler(timer_only=True) as prof:
        assert spans.live()
        with span("in_window"):
            pass
    assert not spans.live()
    assert [e.name for e in prof.events()] == ["in_window"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert spans.live()
    finally:
        jax.profiler.stop_trace()
    assert not spans.live()
    assert spans._ring.maxlen == spans.RING_KEEP


def test_record_event_and_span_emit_through_one_function(monkeypatch):
    seen = []
    real = spans._emit
    monkeypatch.setattr(spans, "_emit", lambda *a: (seen.append(a[0]),
                                                    real(*a))[1])

    @span("decorated")
    def work():
        return 1

    with Profiler(timer_only=True) as prof:
        with RecordEvent("user"):
            with span("inner"):
                pass
        assert work() == 1
    assert seen == ["user/inner", "user", "decorated"]
    cats = {e.name: e.cat for e in prof.events()}
    assert cats == {"user/inner": "observability", "user": "user_defined",
                    "decorated": "observability"}
    # and exactly one place in the program annotates the profiler's trace
    users = [p for p in glob.glob(os.path.join(PKG, "**", "*.py"),
                                  recursive=True)
             if re.search(r"TraceAnnotation\(", open(p).read())]
    assert [os.path.relpath(p, PKG) for p in users] == [
        os.path.join("observability", "spans.py")]


# -- the train step --------------------------------------------------------- #

class _MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(8, 8)

    def forward(self, x):
        return self.fc(x)


@pytest.mark.parametrize("offload", [False, True])
def test_the_train_step_yields_its_children_with_step_num(offload):
    paddle.seed(0)
    model, crit = _MLP(), nn.MSELoss()
    step = dist.DistributedTrainStep(
        model, lambda o, t: crit(o, t),
        opt.AdamW(learning_rate=1e-3, parameters=model.parameters()),
        mesh=dist.build_mesh(sharding=2), sharding_stage=2,
        offload=offload, comm_overlap=not offload)
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    tl = spans.enable_step_timeline()
    try:
        step(x, x)
        step(x, x)
    finally:
        tl.uninstall()
        dist.env.set_global_mesh(None)
    ring = spans.recorded()
    roots = [r for r in ring if r["path"] == "train_step"]
    assert [r["attrs"]["step_num"] for r in roots] == [1, 2]
    second = [r["path"] for r in ring
              if r["parent"] == roots[1]["id"]]
    want = ["train_step/place_inputs", "train_step/dispatch"]
    if offload:
        want = ["train_step/offload"] + want + ["train_step/offload"]
    assert second == want
    assert any(r["path"] == "train_step/dispatch/compiled" for r in ring)


# -- the compile listener --------------------------------------------------- #

def test_the_compile_listener_counts_a_recompile_and_names_it():
    def value(stage):
        m = default_registry().get("compiles_total")
        return m.value(stage=stage) if m is not None else 0

    @jax.jit
    def a_program_of_this_test(x):
        return x * 2 + 1

    lower0, backend0 = value("lower"), value("backend_compile")
    a_program_of_this_test(jnp.ones(3))
    assert value("lower") > lower0 and value("backend_compile") > backend0
    assert spans.recorded() == []  # counted always, recorded only when live
    lower1 = value("lower")
    tl = spans.enable_step_timeline()
    try:
        a_program_of_this_test(jnp.ones(3))   # cached: nothing compiles
        assert value("lower") == lower1
        a_program_of_this_test(jnp.ones(5))   # a new shape: a recompile
    finally:
        tl.uninstall()
    assert value("lower") > lower1
    named = [r["attrs"] for r in spans.recorded() if r["path"] == "compile"
             and "a_program_of_this_test" in str(r["attrs"].get("fun"))]
    assert {a["stage"] for a in named} == {"lower", "backend_compile"}


# -- names on the device side ---------------------------------------------- #

def _kernel_jaxprs():
    """{kernel name: a function whose jaxpr launches that kernel}."""
    from paddle_tpu.ops.pallas import (decode_attention as da,
                                       flash_attention as fa,
                                       fused_norm as fn, fused_rope as fr,
                                       grouped_gemm as gg, masked_flash as mf,
                                       ssm_decode as sd)

    f32 = jnp.float32
    q = jnp.ones((1, 128, 2, 64), f32)
    idx = jnp.zeros((1, 1, 128, 1), jnp.int32)
    qv = jnp.ones((128, 2, 64), f32)
    cu = jnp.asarray([0, 64, 128], jnp.int32)
    x, w = jnp.ones((16, 128), f32), jnp.ones((128,), f32)
    cos = jnp.ones((1, 128, 32), f32)
    lhs, rhs = jnp.ones((256, 128), f32), jnp.ones((2, 128, 128), f32)
    sizes = jnp.asarray([128, 100], jnp.int32)
    qd = jnp.ones((2, 2, 64), f32)
    pool = jnp.ones((4, 2, 16, 64), f32)
    tables = jnp.asarray([[0, 1], [2, -1]], jnp.int32)
    lens = jnp.asarray([20, 5], jnp.int32)
    scales = jnp.ones((8, 2), f32)
    dense = jnp.ones((2, 2, 32, 64), f32)

    def fwd(f, *a):
        return lambda: jax.make_jaxpr(f)(*a)

    def bwd(f, *a):
        return lambda: jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(f(*a)[0] if isinstance(f(*a), tuple)
                               else f(*a))))(*a)

    flash = lambda q, k, v: fa.flash_attention_fwd(q, k, v, causal=True)
    mask = lambda q, k, v: mf.flashmask_attention_fwd(q, k, v, idx)
    varlen = lambda q, k, v: mf.varlen_flash_attention_fwd(
        q, k, v, cu, cu, 0.125, causal=True)
    ln = lambda x, w: fn.layer_norm_fwd(x, w, w)
    rms = lambda x, w: fn.rms_norm_fwd(x, w)
    rope = lambda t: fr.apply_fused_rope((t,), cos, cos)[0]
    gmm = lambda l, r: gg.grouped_matmul(l, r, sizes)
    return {
        "flash_fwd": fwd(flash, q, q, q),
        "flash_bwd_dq": bwd(flash, q, q, q),
        "flash_bwd_dkv": bwd(flash, q, q, q),
        "flash_fwd_window": fwd(
            lambda q, k, v: fa.flash_window_fwd(q, k, v, 48), q, q, q),
        "flashmask_fwd": fwd(mask, q, q, q),
        "flashmask_bwd_dq": bwd(mask, q, q, q),
        "flashmask_bwd_dkv": bwd(mask, q, q, q),
        "varlen_fwd": fwd(varlen, qv, qv, qv),
        "varlen_bwd_dq": bwd(varlen, qv, qv, qv),
        "varlen_bwd_dkv": bwd(varlen, qv, qv, qv),
        "fused_layer_norm_fwd": fwd(ln, x, w),
        "fused_layer_norm_bwd": bwd(ln, x, w),
        "fused_rms_norm_fwd": fwd(rms, x, w),
        "fused_rms_norm_bwd": bwd(rms, x, w),
        "fused_rope": fwd(rope, q),
        "grouped_gemm": fwd(gmm, lhs, rhs),
        "decode_paged": fwd(lambda q: da.paged_decode_attention(
            q, pool, pool, tables, lens), qd),
        "decode_paged_q8": fwd(lambda q: da.paged_decode_attention(
            q, pool.astype(jnp.int8), pool.astype(jnp.int8), tables, lens,
            kv_scales=(scales, scales)), qd),
        "decode_window": fwd(lambda q: da.paged_decode_attention(
            q, pool, pool, tables, lens, window=24), qd),
        "decode_latent": fwd(lambda q: da.latent_decode_attention(
            q, jnp.ones((4, 16, 128), f32), tables, lens, 64, 0.1),
            jnp.ones((2, 2, 128), f32)),
        "decode_dense": fwd(lambda q: da.dense_decode_attention(
            q, dense, dense, lens), qd),
        "ssm_decode": fwd(lambda s: sd.ssm_decode(
            s, x[:2], x[:2], jnp.ones((2, 8), f32), jnp.ones((2, 8), f32),
            lens > 5), jnp.ones((2, 8, 128), f32)),
    }


def test_the_name_table_has_a_recipe_for_every_kernel():
    assert set(_kernel_jaxprs()) == set(KERNEL_NAMES)
    assert len(set(KERNEL_NAMES)) == len(KERNEL_NAMES)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"]
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner)


@pytest.mark.parametrize("name", KERNEL_NAMES)
def test_every_kernel_carries_its_name_from_the_table(name):
    named = set(_pallas_calls(_kernel_jaxprs()[name]().jaxpr))
    assert name in named, f"no pallas_call named {name} in the jaxpr"
    assert named <= set(KERNEL_NAMES), named - set(KERNEL_NAMES)


def test_every_pallas_call_site_goes_through_the_named_call():
    """No bare `pl.pallas_call` outside the helper, and every literal name
    at a call site is in the table (22 names over 17 call sites)."""
    sites, helper = [], os.path.join(PKG, "ops", "pallas", "__init__.py")
    for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "pallas_call":
                assert path == helper, f"bare pallas_call in {path}"
            if isinstance(f, ast.Name) and f.id == "named_pallas_call":
                sites.append((path, node.args[0]))
    assert len(sites) == 17
    for path, arg in sites:
        if isinstance(arg, ast.Constant):
            assert arg.value in KERNEL_NAMES, (path, arg.value)


def test_a_name_outside_the_table_is_refused():
    from paddle_tpu.ops.pallas import named_pallas_call

    with pytest.raises(ValueError, match="KERNEL_NAMES"):
        named_pallas_call("my_kernel", lambda *refs: None, out_shape=None)
