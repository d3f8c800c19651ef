"""The readers of PR 37 on a hand-made ring and the small recorded trace:
`record_stat` (the always-on `admission` records over the whole window),
`busy_in_spans` (the device's side of a host phase), `histogram_mean`, and
the new metric files read through them. CPU only.

    python -m pytest benchmark/tests -q
"""

import time
import types

import pytest

from benchmark import harness, trace_reduce, traffic_gen
from benchmark.tests.test_program_spans import SAT, TICKS, _ring

WINDOW = (100.0, 145.0)   # on run.clock()
MIXES = {"saturated-long": 69.4, "saturated-chat-greedy": 76.3,
         "saturated-mixed-16k": 69.7, "saturated-reasoning-16k": 76.3}


def _spec(name):
    return harness.load_json("layer_metrics", name + ".json")


def _run(monkeypatch, ring, window=WINDOW, recorded=True):
    """A run whose clock reads 0 when the ring's clock does, with `ring` as
    what the program recorded (records given in seconds on that clock)."""
    from paddle_tpu.observability import spans

    if recorded:
        monkeypatch.setattr(spans, "recorded", lambda: ring, raising=False)
    else:
        monkeypatch.delattr(spans, "recorded")
    logged = []
    return types.SimpleNamespace(
        window=window, clock=time.perf_counter, log=logged.append,
        logged=logged)


def _admission(start, seconds, kind="prefill", **attrs):
    return {"id": 1, "parent": None, "path": "admission",
            "t0_ns": round(start * 1e9), "t1_ns": round((start + seconds) * 1e9),
            "attrs": {"kind": kind, **attrs}}


RING = [
    _admission(99.0, 0.5, prompt_len=10, bucket=16, prefill_s=0.4),   # before
    _admission(101.0, 0.10, prompt_len=100, bucket=128, prefill_s=0.02,
               first_token_s=0.07, pages_s=0.001, write_pages_s=0.004,
               write_state_s=0.0),
    _admission(110.0, 0.20, prompt_len=300, bucket=512, prefill_s=0.04,
               first_token_s=0.15, pages_s=0.002, write_pages_s=0.005,
               write_state_s=0.001),
    _admission(120.0, 0.30, prompt_len=500, bucket=512, prefill_s=0.06,
               first_token_s=0.21, pages_s=0.003, write_pages_s=0.006,
               write_state_s=0.002),
    _admission(130.0, 0.05, kind="resume", prompt_len=64, resume_s=0.04),
    {"id": 9, "parent": None, "path": "request", "t0_ns": round(125e9),
     "t1_ns": round(126e9), "attrs": {}},
    _admission(144.9, 0.40, prompt_len=900, bucket=1024, prefill_s=0.1,
               first_token_s=0.2),   # starts inside, ends after: counts
    _admission(146.0, 0.5, prompt_len=10, bucket=16, prefill_s=0.4),  # after
]


# the window's ticks: 30 s of them (a traced window's other 15 s go to
# stopping and reading the trace)
OBS = {"series": {"ticks": [{"ms": 20_000.0}, {"ms": 10_000.0}]}}


def _read(run, obs=OBS, **args):
    return harness.load_plugin("readers", "record_stat").read(run, obs, **args)


@pytest.mark.parametrize("args, want", [
    # both kinds, by start inside the window: 0.1 + 0.2 + 0.3 + 0.05 + 0.4
    (dict(stat="tick_share"), 100 * 1.05 / 30.0),
    (dict(stat="tick_share", where={"kind": "resume"}), 100 * 0.05 / 30.0),
    (dict(stat="p50", where={"kind": "prefill"}, scale=1e3), 250.0),
    (dict(stat="mean", where={"kind": "prefill"}, scale=1e3), 250.0),
    (dict(stat="p100", scale=1e3), 400.0),
    (dict(stat="p50", fields=["prefill_s"], where={"kind": "prefill"},
          scale=1e3), 50.0),
    # a sum of several fields; a record without one of them counts it as 0
    (dict(stat="p50", fields=["pages_s", "write_pages_s", "write_state_s"],
          where={"kind": "prefill"}, scale=1e3), (5.0 + 8.0) / 2),
    (dict(stat="mean", fields=["first_token_s"], where={"kind": "prefill"}),
     (0.07 + 0.15 + 0.21 + 0.2) / 4),
    (dict(stat="ratio", fields=["prompt_len"], over="bucket",
          where={"kind": "prefill"}), 100 * 1800 / 2176),
    (dict(stat="ratio", fields=["prompt_len"], over="absent"), None),
    (dict(stat="p50", where={"kind": "other"}), None),
], ids=["share", "share-where", "p50", "mean", "p100", "field", "field-sum",
        "mean-field", "ratio", "ratio-of-nothing", "where-nothing"])
def test_record_stat_over_the_window(monkeypatch, args, want):
    run = _run(monkeypatch, RING)
    got = _read(run, path="admission", **args)
    assert got == (None if want is None else pytest.approx(want, rel=1e-4))
    assert run.logged == ["'admission' records that start inside the window: "
                          "4 prefill, 1 resume"]


def test_record_stat_reads_the_ring_once_and_refuses_an_unknown_stat(
        monkeypatch):
    run = _run(monkeypatch, RING)
    for _ in range(3):
        _read(run, path="admission", stat="tick_share")
    assert len(run.logged) == 1
    assert _read(run, {"series": {"ticks": []}}, path="admission",
                 stat="tick_share") is None
    with pytest.raises(ValueError):
        _read(run, path="admission", stat="max")


@pytest.mark.parametrize("case", ["empty-ring", "no-such-path",
                                  "none-in-window", "no-ring", "no-window"])
def test_record_stat_is_none_where_there_is_nothing_to_read(monkeypatch, case):
    """A parent commit writes no `admission` record (its ring is empty with
    no listener, and holds spans alone under the trace); one from before the
    ring has no `recorded`: None, nothing raised, nothing logged."""
    ring = {"empty-ring": [], "no-such-path": _ring(TICKS),
            "none-in-window": [RING[0], RING[-1]]}.get(case, RING)
    run = _run(monkeypatch, ring, recorded=case != "no-ring",
               window=None if case == "no-window" else WINDOW)
    for spec in ("admit_window_share.serve", "admission_ms_p50.serve",
                 "prefill_bucket_fill.serve"):
        assert _read(run, **_spec(spec)["args"]) is None
    assert run.logged == []


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_prefill_bucket_fill_of_a_mix_is_its_decks_own(monkeypatch, mix):
    """One admission a level of the mix's prompt deck, in the bucket the
    engine pads it to: the metric reads the deck's sum over the buckets'."""
    from paddle_tpu.inference.serving import _bucket

    levels = traffic_gen.levels(harness.load_json("traffic", mix + ".json")
                                ["prompt_len"])
    ring = [_admission(101.0 + k, 0.01, prompt_len=n, bucket=_bucket(n))
            for k, n in enumerate(levels)]
    got = _read(_run(monkeypatch, ring),
                **_spec("prefill_bucket_fill.serve")["args"])
    assert got == pytest.approx(
        100 * sum(levels) / sum(_bucket(n) for n in levels))
    assert got == pytest.approx(MIXES[mix], abs=0.05)


@pytest.mark.parametrize("name, want", [
    ("admit_window_share.serve", 100 * 1.05 / 30.0),
    ("admission_ms_p50.serve", 250.0),
    ("prefill_dispatch_ms_p50.serve", 50.0),
    ("first_token_wait_ms_p50.serve", (150.0 + 200.0) / 2),
    ("admit_pool_write_ms_p50.serve", 6.5),
    ("prefill_bucket_fill.serve", 100 * 1800 / 2176),
])
def test_the_whole_window_metric_files_read_the_record(monkeypatch, name, want):
    spec = _spec(name)
    assert (spec["reader"], spec["source"]) == ("record_stat", "program_span")
    assert len(spec["workloads"]) == 4
    assert _read(_run(monkeypatch, RING), **spec["args"]) == pytest.approx(
        want, rel=1e-4)


# -- the device's side of a host phase -------------------------------------- #

@pytest.fixture
def busy_in(monkeypatch):
    from paddle_tpu.observability import spans

    small = trace_reduce.Trace.from_json(
        harness.load_json("tests", "data", "small_trace.json"))

    def read(ring, *paths, reader="busy_in_spans"):
        monkeypatch.setattr(spans, "recorded", lambda: ring, raising=False)
        run = types.SimpleNamespace(trace=small)
        return harness.load_plugin("readers", reader).read(
            run, {}, anchor=SAT, paths=list(paths))

    return read


@pytest.mark.parametrize("paths, want", [
    # the device runs 100-100.5, 101-103.5, 106-107.5 and 109.5-110: both
    # admits (100.51-100.9, 104.51-104.52) lie in its gaps, both host reads
    # (101-103.5, 106-107.5) under its operations
    (["engine.step/admit"], 0.0),
    (["engine.step/host_read"], 100.0),
    (["engine.step/host_read", "engine.step/emit"], 100 * 4.0 / 5.2),
    (["engine.step"], 100 * 4.0 / 7.39),
    (["engine.step/absent"], None),
], ids=["admit", "host_read", "two-paths", "tick", "absent"])
def test_busy_in_spans_is_the_devices_share_of_the_hosts_time(
        busy_in, paths, want):
    got = busy_in(_ring(TICKS), *paths)
    assert got == (None if want is None else pytest.approx(want, abs=1e-3))
    if want is not None:
        # with the idle share of the same spans it is all of their time
        idle = busy_in(_ring(TICKS), *paths, reader="idle_by_span")
        host_s = sum(e - s for _, _, c in TICKS for p in paths
                     for s, e in c.get(p, ())) or 7.39
        assert got / 100 * host_s + idle / 100 * 10.0 == pytest.approx(
            host_s, abs=1e-3)


def test_busy_in_spans_is_none_where_the_spans_cannot_be_read(busy_in, capsys):
    assert busy_in([], "engine.step/admit") is None
    assert "not read" in capsys.readouterr().err


def test_the_idle_shares_by_phase_split_the_idle_share_of_admit(busy_in):
    """Disjoint children of `admit`: their idle shares add up to `admit`'s
    less what is idle in `admit` outside them (here 0.39 + 0.01 s of a 10 s
    window, of which the prefill covers 0.28)."""
    ring = _ring(TICKS)
    parts = {}
    for name in ("idle_prefill_dispatch_share.serve",
                 "idle_first_token_share.serve", "idle_pool_write_share.serve"):
        spec = _spec(name)
        assert spec["reader"] == "idle_by_span"
        assert all(p.startswith("engine.step/admit/")
                   for p in spec["args"]["paths"])
        parts[name] = busy_in(ring, *spec["args"]["paths"],
                              reader="idle_by_span")
    assert parts["idle_prefill_dispatch_share.serve"] == pytest.approx(2.8)
    assert sum(parts.values()) == pytest.approx(2.8)
    whole = busy_in(ring, "engine.step/admit", reader="idle_by_span")
    assert whole - sum(parts.values()) == pytest.approx(1.2)
    spec = _spec("admit_device_busy.serve")
    assert spec["args"]["paths"] == ["engine.step/admit"]
    assert "serve-kimi-k2-reason-sat" not in spec["workloads"]


# -- the counters that had no reader ---------------------------------------- #

def test_histogram_mean_and_the_counter_files(monkeypatch):
    from paddle_tpu.observability import metrics

    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "default_registry", lambda: reg)
    read = harness.load_plugin("readers", "histogram_mean").read
    full = _spec("decode_live_step_share.serve")["args"]
    assert read(None, {}, **full) is None          # no such histogram
    shares = reg.histogram("serving_decode_live_step_share", "", ("kind",))
    assert read(None, {}, **full) is None          # never observed
    for v in (0.25, 0.75):
        shares.observe(v, kind="full")
    shares.observe(0.1, kind="latent")
    assert read(None, {}, **full) == pytest.approx(50.0)
    assert read(None, {}, **_spec("decode_live_step_share.k2r")["args"]) \
        == pytest.approx(10.0)
    assert read(None, {}, **_spec(
        "decode_window_live_step_share.tmix")["args"]) is None
    assert read(None, {}, name="serving_decode_live_step_share") is None
    live, tiled = reg.histogram("moe_rows_live"), reg.histogram("moe_rows_tiled")
    spec = _spec("moe_rows_live_share.serve")
    ratio = harness.load_plugin("readers", spec["reader"]).read
    assert ratio(None, {}, **spec["args"]) is None
    live.observe(300), tiled.observe(512), live.observe(100), tiled.observe(288)
    assert ratio(None, {}, **spec["args"]) == pytest.approx(400 / 800)
