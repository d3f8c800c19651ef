"""The readers of the program's own spans (program_spans.py, span_stat,
idle_by_span) on a hand-made ring over the small recorded trace, and on the
cut of a chip trace of PR 27 (`data/pr27_*.json`). CPU only.

    python -m pytest benchmark/tests -q
"""

import types

import pytest

from benchmark import harness, program_spans, trace_reduce

SAT = ["bm.engine_step", "engine.step"]
CLOCKS_APART = 50.0   # trace clock - perf_counter, seconds
ENTER = 10e-6         # the program's clock reads 10 us after its twin's start:
                      # the mapping puts a root ON its twin, so it cancels


def _ring(ticks):
    """Ring records of `ticks`: [(start, end, {child path: [(start, end)]})]
    in seconds on the TRACE's clock, written on perf_counter_ns."""
    out, ids = [], iter(range(1, 1000))

    def rec(path, start, end, parent, **attrs):
        out.append({"id": next(ids), "parent": parent, "path": path,
                    "t0_ns": round((start - CLOCKS_APART) * 1e9),
                    "t1_ns": round((end - CLOCKS_APART) * 1e9),
                    "attrs": attrs})
        return out[-1]["id"]

    for k, (start, end, children) in enumerate(ticks):
        root = rec("engine.step", start + ENTER, end + ENTER, None, tick=k)
        parents = {"engine.step": root}
        for path in sorted(children, key=lambda p: p.count("/")):
            for s, e in children[path]:
                parents[path] = rec(path, s + ENTER, e + ENTER,
                                    parents[path.rsplit("/", 1)[0]])
    # the ring is written at each span's END: children before parents
    return sorted(out, key=lambda r: r["t1_ns"])


# two ticks inside the two bm.engine_step spans of small_trace.json
# (100.5-104.0 and 104.5-108.5); the device idles 100.5-101, 103.5-106 and
# 107.5-109.5
TICKS = [
    (100.5, 103.99, {
        "engine.step/admit": [(100.51, 100.9)],
        "engine.step/admit/prefill": [(100.52, 100.8)],
        "engine.step/write_targets": [(100.9, 100.95)],
        "engine.step/decode_dispatch": [(100.95, 101.0)],
        "engine.step/host_read": [(101.0, 103.5)],
        "engine.step/emit": [(103.5, 103.9)],
        "engine.step/emit/sample": [(103.55, 103.6), (103.7, 103.8)]}),
    (104.5, 108.4, {
        "engine.step/admit": [(104.51, 104.52)],
        "engine.step/write_targets": [(104.6, 104.7)],
        "engine.step/decode_dispatch": [(104.7, 106.0)],
        "engine.step/host_read": [(106.0, 107.5)],
        "engine.step/emit": [(107.5, 108.3)]}),
]


@pytest.fixture
def small():
    return trace_reduce.Trace.from_json(
        harness.load_json("tests", "data", "small_trace.json"))


@pytest.fixture
def reading(monkeypatch, small):
    """read(reader, ring, **args) on the small trace with `ring` as what the
    program recorded."""
    from paddle_tpu.observability import spans

    def read(reader, ring, **args):
        monkeypatch.setattr(spans, "recorded", lambda: ring, raising=False)
        run = types.SimpleNamespace(trace=small)
        return harness.load_plugin("readers", reader).read(
            run, {}, anchor=SAT, **args)

    return read


def test_the_ring_maps_onto_the_traces_clock_and_nests(small):
    twins = [(s, d) for n, s, d in small.spans if n == "bm.engine_step"]
    spans, why = program_spans.map_ring(_ring(TICKS), twins, "engine.step")
    assert why is None and len(spans) == 15
    roots = [s for s in spans if s.path == "engine.step"]
    assert [s.root for s in roots] == [0, 1]
    # the median offset puts each root ON its twin's start
    assert roots[0].start == pytest.approx(100.5, abs=1e-7)
    assert roots[1].end == pytest.approx(108.4, abs=1e-7)
    by = {}
    for s in spans:
        by.setdefault(s.root, []).append(s.path)
    assert len(by[0]) == 9 and len(by[1]) == 6   # children carry their root
    assert by[0].count("engine.step/emit/sample") == 2


def test_span_stat_duration_self_time_and_sums(reading):
    ring = _ring(TICKS)

    def stat(path, stat, **kw):
        return reading("span_stat", ring, path=path, stat=stat, **kw)

    assert stat("engine.step", "p50") == pytest.approx((3490 + 3900) / 2)
    assert stat("engine.step/admit", "mean") == pytest.approx(200.0)
    assert stat("engine.step/host_read", "p50") == pytest.approx(2000.0)
    # two samples in the first tick (50 + 100 ms), none in the second: 0
    assert stat("engine.step/emit/sample", "p50") == pytest.approx(75.0)
    assert stat("engine.step/emit/sample", "p100") == pytest.approx(150.0)
    # self time: the tick less what its DIRECT children cover (3.39, 3.71 s)
    assert stat("engine.step", "p50", of="self") == pytest.approx(
        ((3490 - 3390) + (3900 - 3710)) / 2)
    # admit's self time leaves out its prefill (390 - 280, and 10)
    assert stat("engine.step/admit", "mean", of="self") == pytest.approx(60.0)
    assert stat("engine.step/absent", "p50") == 0.0
    with pytest.raises(ValueError):
        stat("engine.step", "max")


def test_idle_by_span_splits_the_idle_share(reading, small):
    ring = _ring(TICKS)

    def idle(*paths):
        return reading("idle_by_span", ring, paths=list(paths))

    # of a 10 s window: admit 0.39 + 0.01 s idle; write_targets and
    # decode_dispatch 0.1 + 1.4; emit 0.4 + 0.8; host_read 0.5 (106-107.5 is
    # busy to 107.5, 103.5 is where the first read ends)
    assert idle("engine.step/admit") == pytest.approx(4.0)
    assert idle("engine.step/write_targets",
                "engine.step/decode_dispatch") == pytest.approx(15.0)
    assert idle("engine.step/emit") == pytest.approx(12.0)
    assert idle("engine.step/host_read") == pytest.approx(0.0)
    # everything inside engine.step: what bm.engine_step saw (3.5 s), less
    # the 0.01 and 0.1 s by which the ticks end before their twins
    assert idle("engine.step") == pytest.approx(33.9)
    assert trace_reduce.idle_gaps_by_span(small)["bm.engine_step"] == \
        pytest.approx(3.5)
    assert idle("engine.step/absent") == 0.0


@pytest.mark.parametrize("fault, says", [
    ("late_start", "residual"),
    ("one_tick_less", "1 'engine.step' spans"),
    ("outlives_twin", "does not lie inside"),
    ("no_spans", "0 'engine.step' spans"),
])
def test_a_mapping_that_does_not_hold_is_refused(reading, capsys, fault, says):
    ticks = [list(t) for t in TICKS]
    if fault == "late_start":
        ticks[1][0] += 300e-6      # residuals of 150 us on each side
    elif fault == "one_tick_less":
        ticks = ticks[:1]
    elif fault == "outlives_twin":
        ticks[1][1] = 108.5 + 300e-6
    ring = [] if fault == "no_spans" else _ring(ticks)
    assert reading("span_stat", ring, path="engine.step", stat="p50") is None
    assert reading("idle_by_span", ring, paths=["engine.step"]) is None
    assert says in capsys.readouterr().err


def test_a_program_without_the_ring_reads_as_nothing(monkeypatch, small):
    """The parent commit of PR 27 has no `spans.recorded`: every new reader
    returns None there and raises nothing."""
    from paddle_tpu.observability import spans

    monkeypatch.delattr(spans, "recorded")
    run = types.SimpleNamespace(trace=small)
    for reader, args in (("span_stat", {"path": "engine.step", "stat": "p50"}),
                         ("idle_by_span", {"paths": ["engine.step"]})):
        assert harness.load_plugin("readers", reader).read(
            run, {}, anchor=SAT, **args) is None
    run.trace = None
    monkeypatch.undo()
    assert harness.load_plugin("readers", "span_stat").read(
        run, {}, anchor=SAT, path="engine.step", stat="p50") is None


def test_the_mapping_is_made_once_a_run(monkeypatch, small, capsys):
    from paddle_tpu.observability import spans

    calls = []
    monkeypatch.setattr(spans, "recorded",
                        lambda: calls.append(1) or _ring(TICKS[:1]))
    run = types.SimpleNamespace(trace=small)
    for _ in range(3):
        assert program_spans.on_trace_clock(run, SAT) is None
    assert len(calls) == 1
    assert capsys.readouterr().err.count("not read") == 1


# -- on the cut of a chip trace -------------------------------------------- #

def _cut(monkeypatch, name):
    """(run, obj): a run whose trace and program ring are the cut's."""
    from paddle_tpu.observability import spans

    obj = harness.load_json("tests", "data", name)
    monkeypatch.setattr(spans, "recorded", lambda: obj["ring"], raising=False)
    return types.SimpleNamespace(trace=trace_reduce.Trace.from_json(obj)), obj


def _metric(run, name, obs=None):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.load_plugin("readers", spec["reader"]).read(
        run, obs or {}, **spec.get("args", {}))


SAT_METRICS = [
    "engine_step_ms_p50.sat", "admit_ms_mean.sat", "write_targets_ms_p50.sat",
    "decode_dispatch_ms_p50.sat", "host_read_ms_p50.sat", "sample_ms_p50.sat",
    "step_unspanned_ms_p50.sat", "idle_admit_share.sat",
    "idle_dispatch_share.sat", "idle_host_read_share.sat",
    "idle_emit_share.sat", "decode_paged_share.sat"]
TRAIN_METRICS = ["flash_share.train", "fused_norm_share.train",
                 "place_inputs_ms_p50.train", "train_dispatch_ms_p50.train"]


def test_the_serving_cut_reads_and_hangs_together(monkeypatch, capsys):
    run, obj = _cut(monkeypatch, "pr27_sat_cut.json")
    got = {m: _metric(run, m) for m in SAT_METRICS}
    assert all(v is not None for v in got.values()), got
    assert capsys.readouterr().err == ""   # the mapping held, nothing refused
    spans = program_spans.on_trace_clock(run, SAT)
    ticks = program_spans.per_root(spans, "engine.step")
    assert len(ticks) == len(obj["ticks"]) == 3
    # inside its twin: at most 1 ms shorter than the driver's own reading
    for k, tick in enumerate(obj["ticks"]):
        assert 0 <= tick["ms"] - ticks[k] * 1e3 <= 1.0
    assert got["step_unspanned_ms_p50.sat"] <= 0.02 * got["engine_step_ms_p50.sat"]
    assert 400 < got["host_read_ms_p50.sat"] < got["engine_step_ms_p50.sat"]
    # the four idle shares are the idle inside engine.step, which is all of
    # the device's idle time but what falls between two ticks
    idle = harness.load_plugin("readers", "idle_by_span")
    inside = idle.read(run, {}, anchor=SAT, paths=["engine.step"])
    parts = sum(got[m] for m in SAT_METRICS if m.startswith("idle_"))
    assert parts == pytest.approx(inside, abs=0.05)
    whole = harness.load_plugin("readers", "trace_idle_share").read(run, {})
    assert 0 <= whole - inside < 0.1
    # the kernel found by its NAME is the one found by the pool operand
    by_operand = harness.load_plugin("readers", "trace_op_share").read(
        run, {}, over="busy", pattern=r"custom-call\(.*\[1746,16,32,128\].*"
        'custom_call_target="tpu_custom_call"')
    assert got["decode_paged_share.sat"] == pytest.approx(by_operand)
    assert 80 < by_operand < 90


def test_the_training_cut_reads_and_hangs_together(monkeypatch, capsys):
    run, obj = _cut(monkeypatch, "pr27_train_cut.json")
    got = {m: _metric(run, m) for m in TRAIN_METRICS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert capsys.readouterr().err == ""
    pallas = _metric(run, "pallas_share.train")
    assert got["flash_share.train"] + got["fused_norm_share.train"] == \
        pytest.approx(pallas, abs=0.5)
    outside = min(s["dispatch_ms"] for s in obj["steps"])
    assert got["place_inputs_ms_p50.train"] < \
        got["train_dispatch_ms_p50.train"] < outside + 1.0
    spans = program_spans.on_trace_clock(run, ["bm.train_step", "train_step"])
    assert {s.path for s in spans} == {
        "train_step", "train_step/place_inputs", "train_step/dispatch",
        "train_step/dispatch/compiled", "train_step/prev_step_inflight"}
    assert [s.attrs["step_num"] for s in spans if s.path == "train_step"] \
        == sorted(s.attrs["step_num"] for s in spans if s.path == "train_step")
