"""The benchmark's own tests: CPU only, no TPU topology described anywhere.

    python -m pytest benchmark/tests -q
"""

import json
import os
import re
import types

import numpy as np
import pytest

from benchmark import costs, harness, serving, trace_reduce, traffic_gen

ROOT = harness.ROOT
HERE = harness.HERE
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def xl():
    return harness.load_json("configs", "gpt3-xl.json")


def _cells_of(metric, manifest):
    return metric.get("workloads") or [w["name"] for w in manifest["workloads"]]


# -- the manifest: the faults that refused PR 23 cannot recur unseen ------- #

def test_manifest_has_exactly_the_contract_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark"]
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_every_name_and_layer_is_an_identifier(manifest):
    names = []
    for c in manifest["configs"]:
        names += [c["name"], *c["reduced"]]
    for w in manifest["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        names += [m["name"], *m.get("workloads", [])]
    names += [m["layer"] for m in manifest["per_layer"]]
    names += [m["moves"] for m in manifest["per_layer"]]
    bad = [n for n in names if not NAME.match(n)]
    assert not bad, bad
    for group in ("configs", "workloads"):
        got = [x["name"] for x in manifest[group]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_units_directions_sources_and_one_line_texts(manifest):
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace"), m
        assert 0.01 <= m["bound"] <= 0.1, m
    texts = [w["why"] for w in manifest["workloads"]]
    texts += [c["why"] for c in manifest["configs"]]
    texts += [c["source"] for c in manifest["configs"]] + manifest["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    for c in manifest["configs"]:
        assert len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"]
                    if k.endswith(("_dim", "_rank", "_size"))], "a width is cut"


def test_moves_is_an_end_to_end_metric_of_every_cell_that_reports_it(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        mine = set(_cells_of(m, manifest))
        assert mine <= cells, m
        assert mine <= set(_cells_of(e2e[m["moves"]], manifest)), m


def test_every_cell_reports_setup_another_metric_and_a_layer_metric(manifest):
    assert "workloads" not in next(m for m in manifest["end_to_end"]
                                   if m["name"] == "setup_s")
    for w in manifest["workloads"]:
        e2e = [m["name"] for m in manifest["end_to_end"]
               if w["name"] in _cells_of(m, manifest)]
        layer = [m for m in manifest["per_layer"]
                 if w["name"] in _cells_of(m, manifest)]
        assert "setup_s" in e2e and len(e2e) >= 2 and layer, w["name"]


def test_every_file_a_cell_names_exists(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    used = set()
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        entry = configs[w["config"]]
        used.add(w["config"])
        assert entry["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        assert config["reduced"] == entry["reduced"]
        assert os.path.isfile(os.path.join(HERE, "families",
                                           config["family"] + ".py"))
        assert os.path.isfile(os.path.join(
            HERE, "reference", config["family"] + ".py"))
        mix = harness.load_json("traffic", w["traffic"] + ".json")
        assert os.path.isfile(os.path.join(HERE, "traffic_kinds",
                                           mix["kind"] + ".py"))
    assert used == set(configs), "a configuration no cell uses"


def test_four_chip_cells_stay_within_a_quarter(manifest):
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_layer_metric_files_agree_with_the_manifest(manifest):
    listed = {m["name"]: m for m in manifest["per_layer"]}
    cells = {w["name"] for w in manifest["workloads"]}
    seen = set()
    folder = os.path.join(HERE, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        spec = harness.load_json("layer_metrics", fname)
        assert fname == spec["name"] + ".json"
        assert os.path.isfile(os.path.join(HERE, "readers",
                                           spec["reader"] + ".py"))
        if spec["name"] not in listed:
            # a metric of a cell that is not (yet) in the manifest
            assert not set(spec["workloads"]) & cells, spec["name"]
            continue
        seen.add(spec["name"])
        for key in ("unit", "better", "source", "layer", "moves", "workloads"):
            assert spec[key] == listed[spec["name"]][key], (spec["name"], key)
    assert seen == set(listed)


# -- traffic --------------------------------------------------------------- #

MIX = {"prompt_len": {"lo": 128, "hi": 1024, "levels": 16},
       "answer_len": {"lo": 16, "hi": 64, "levels": 8},
       "max_total": 2048, "sampled_every": 4, "temperature": 0.7}


def _take(seed, n=64):
    stream = traffic_gen.RequestStream(MIX, 50304, seed)
    return [stream.next() for _ in range(n)]


def test_same_seed_same_requests_other_seed_other_requests():
    a, b, c = _take(5), _take(5), _take(6)
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens
               and x.temperature == y.temperature for x, y in zip(a, b))
    assert any(len(x.prompt) != len(y.prompt) for x, y in zip(a, c))
    assert not np.array_equal(a[0].prompt[:8], c[0].prompt[:8]) or \
        len(a[0].prompt) != len(c[0].prompt)


def test_every_seed_deals_the_same_sizes_in_another_order():
    a, c = _take(5), _take(6)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in c)
    assert {len(r.prompt) for r in a} == set(
        traffic_gen.levels(MIX["prompt_len"]))
    assert [r.temperature for r in a[:8]] == [0, 0, 0, 0.7, 0, 0, 0, 0.7]
    assert all(1 <= t < 50304 for r in a for t in r.prompt)


def test_first_wave_has_evenly_spread_residual_answers():
    stream = traffic_gen.RequestStream(MIX, 50304, 3)
    wave = stream.first_wave(32)
    full = max(traffic_gen.levels(MIX["answer_len"]))
    assert all(1 <= r.max_new_tokens <= full for r in wave)
    assert len({r.max_new_tokens for r in wave}) > 8  # spread, not one value


def test_train_batches_and_seeds_take_a_seed_beyond_32_bits():
    mix = {"distinct_batches": 2, "batch": 4, "seq": 16}
    big = 2**31 + 12345
    s = traffic_gen.seeds(big, 2)
    assert s == traffic_gen.seeds(big, 2) != traffic_gen.seeds(big + 1, 2)
    ids, labels = traffic_gen.train_batches(mix, 512, s[1])
    ids2, _ = traffic_gen.train_batches(mix, 512, s[1])
    assert ids.shape == labels.shape == (2, 4, 16) and ids.dtype == np.int32
    assert np.array_equal(ids, ids2) and not np.array_equal(ids, labels)


# -- operations and bytes, by hand for gpt3-xl ----------------------------- #

def test_train_flops_per_token_by_hand(xl):
    # per layer 4*2048^2 + 2*2048*8192 = 50,331,648; x24 = 1,207,959,552;
    # head 50304*2048 = 103,022,592
    assert costs.matmul_params(xl) == 1_310_982_144
    # forward: 2 x params + causal attention 24 * 2 * 2048 * 2049; x3 with
    # the backward
    assert costs.train_flops_per_token(xl, 2048) == 3 * (
        2 * 1_310_982_144 + 201_424_896) == 8_470_167_552


def test_kv_bytes_by_hand(xl):
    # K and V, 24 layers, 16 heads x 128, bf16: the 196,608 B/token PR 22 saw
    assert costs.kv_bytes_per_token(xl) == 196_608
    assert costs.decode_attention_bytes(xl, 1000) == 196_608_000


# -- the trace reduction on the recorded trace ----------------------------- #

@pytest.fixture(scope="module")
def trace():
    return trace_reduce.Trace.from_json(
        harness.load_json("tests", "data", "small_trace.json"))


def test_busy_union_clips_to_the_window(trace):
    # 100-100.5 (clipped), 101-103, 102.5-103.5 (overlaps), 106-107.5, 109.5-110
    assert trace_reduce.busy_s(trace) == pytest.approx(0.5 + 2.5 + 1.5 + 0.5)
    assert trace.window_s == pytest.approx(10.0)


def test_idle_gaps_go_to_the_span_the_host_was_in(trace):
    gaps = trace_reduce.idle_gaps_by_span(trace)
    # idle: 100.5-101, 103.5-106, 107.5-109.5 = 5.0 s
    assert sum(gaps.values()) == pytest.approx(5.0)
    assert gaps["bm.engine_step"] == pytest.approx(0.5 + 0.5 + 1.5 + 1.0)
    assert gaps["bm.client_poll"] == pytest.approx(0.5)
    assert gaps["none"] == pytest.approx(1.0)
    assert list(gaps)[0] == "bm.engine_step"


def test_kernel_and_collective_sums(trace):
    pallas = 'custom_call_target="tpu_custom_call"'
    # decode 101-103 and 106-107, norm 102.5-103.5: the union is 3.5, and
    # ConcatBitcast is no kernel
    assert trace_reduce.op_union_s(trace, pallas) == pytest.approx(3.5)
    coll = r"^%(all-reduce|all-gather)"
    assert trace_reduce.op_union_s(trace, coll) == 0.0
    assert trace_reduce.op_union_s(
        trace, coll, ("XLA Ops", "Async XLA Ops")) == pytest.approx(1.5)
    top = trace_reduce.top_ops(trace, 2)
    assert top[0][0].startswith("%decode.122") and top[0][1] == pytest.approx(3.0)
    assert len(top[0][0]) <= trace_reduce.NAME_CHARS
    assert set(trace_reduce.breakdown(trace)) == {"device_ops", "idle_gaps"}


def _fake_run(trace, xl, ticks=(3, 5)):
    peaks = harness.load_json("peaks.json")["TPU v5 lite"]
    return types.SimpleNamespace(trace=trace, trace_ticks=ticks, config=xl,
                                 peaks=lambda: peaks, compiles_in_window=0,
                                 memory_peak_bytes=15_000_000_000)


def test_readers_on_the_recorded_trace(trace, xl):
    run = _fake_run(trace, xl)
    obs = {"series": {"ticks": [
        {"index": 2, "context_tokens": 10**9, "ms": 50.0, "first_tokens": 1},
        {"index": 3, "context_tokens": 50_000, "ms": 500.0, "first_tokens": 0},
        {"index": 4, "context_tokens": 50_000, "ms": 700.0, "first_tokens": 2}],
        "steps": [{"step_ms": None}, {"step_ms": 640.0}, {"step_ms": 660.0}]},
        "values": {"pool_dims": "1746,16,32,128", "batch": 4, "seq": 2048,
                   "chips": 1}}

    def read(reader, **args):
        return harness.load_plugin("readers", reader).read(run, obs, **args)

    # ticks 3 and 4 lie in the trace: 100,000 tokens x 196,608 B over
    # 819 GB/s = 24.0059 ms, against 3.0 s of decode calls (the norm kernel
    # and the pool copy do not match)
    assert read("decode_attn_roofline") == pytest.approx(
        100 * (1e5 * 196_608 / 819e9) / 3.0)
    assert read("trace_idle_share") == pytest.approx(50.0)
    assert read("trace_op_share", over="busy",
                pattern='custom_call_target="tpu_custom_call"') == pytest.approx(70.0)
    assert read("observed", field="memory_peak_bytes", scale=1e-9) == pytest.approx(15.0)
    assert read("observed", field="compiles_in_window") == 0
    # 8192 tokens in a median step of 650 ms
    assert read("mfu_required") == pytest.approx(
        100 * 8_470_167_552 * (8192 / 0.65) / 197e12)
    assert read("series_stat", series="ticks", field="ms", stat="p50") == 500.0
    assert read("series_stat", series="ticks", field="ms", stat="p50",
                where={"field": "first_tokens", "min": 1}) == 375.0
    assert read("series_stat", series="requests", field="ms", stat="mean") is None
    run.trace = None
    assert read("trace_idle_share") is None and read("decode_attn_roofline") is None


def test_percentile_and_rehearsal_sizes():
    assert harness.percentile([1, 2, 3, 4], 50) == 2.5
    assert harness.percentile([5], 95) == 5 and harness.percentile([], 50) is None
    assert harness.percentile(list(range(101)), 95) == 95
    data = {"a": 1, "serve": {"x": 1, "y": 2}, "rehearsal": {"a": 2, "serve": {"y": 3}}}
    small = harness.rehearsal_sizes(data)
    assert small["a"] == 2 and small["serve"] == {"x": 1, "y": 3}


# -- the client-side view of the engine ------------------------------------ #

class _FakeEngine:
    """Admits one waiting request a tick (first token at admission, as the
    paged engine does), then decodes every live row by one token."""

    def __init__(self):
        self.active, self.finished, self.queue, self.ids = [None] * 4, [], [], 0
        self.pool = types.SimpleNamespace(pages_free=5, pages_total=10)
        self.sched = types.SimpleNamespace(waiting_prefill=0, waiting_resume=0)

    @property
    def live_count(self):
        return sum(r is not None for r in self.active)

    def add_request(self, prompt, max_new_tokens, temperature):
        if len(prompt) > 100:
            raise ValueError("too long")
        req = types.SimpleNamespace(req_id=self.ids, generated=[], truncated=False,
                                    want=max_new_tokens)
        self.ids += 1
        self.queue.append(req)
        return req.req_id

    def step(self):
        if self.queue and None in self.active:
            req = self.queue.pop(0)
            self.active[self.active.index(None)] = req
            self._emit(req)
        out = {}
        for req in [r for r in self.active if r is not None]:
            out[req.req_id] = 7
            self._emit(req)
        return out

    def _emit(self, req):
        if req not in self.active:
            return
        req.generated.append(7)
        if len(req.generated) >= req.want:
            self.active[self.active.index(req)] = None
            self.finished.append(req)


def test_driver_stamps_tokens_with_the_tick_that_returned_them():
    clock = iter(float(i) for i in range(1000))
    run = types.SimpleNamespace(clock=lambda: next(clock), log=lambda m: None,
                                span=lambda name: __import__("contextlib").nullcontext())
    eng = _FakeEngine()
    driver = serving.Driver(run, eng, vocab_size=100)
    req = traffic_gen.Request(np.arange(1, 11, dtype=np.int32), 4, 0.0)
    rec = driver.send(req)                       # t_send = 0
    assert driver.send(traffic_gen.Request(np.ones(200, np.int32), 4, 0.0)) is None
    for _ in range(3):                           # ticks return at 3, 5, 7
        driver.tick()
    assert driver.finished_last == [rec] and rec.done and not rec.bad
    # admission tick: first token and the first decoded one arrive together
    assert rec.token_at == [3.0, 3.0, 5.0, 7.0] and rec.ttft_ms == 3000.0
    t = driver.ticks
    assert [x["tokens"] for x in t] == [2, 1, 1]
    assert [x["first_tokens"] for x in t] == [1, 0, 0]
    # the kernel read prompt + tokens before the new one: 10, 11, 12
    assert [x["context_tokens"] for x in t] == [10 + 1, 10 + 2, 10 + 3]
    s = driver.summary(0.0, 7.0)
    assert len(s["ticks"]) == 3 and s["held"] == [rec]
    assert s["attempted"] == 2 and s["failed"] == 1   # the refusal
    assert s["requests"][0]["ttft_ms"] == 3000.0 and s["requests"][0]["done"]


# -- the plain reference against the program's model ----------------------- #

def test_reference_matches_models_gpt_at_a_tiny_size():
    import jax

    import paddle_tpu as paddle
    from benchmark.families import gpt as family
    from benchmark.reference import gpt as reference
    from paddle_tpu.models import GPTForCausalLM

    config = harness.rehearsal_sizes(harness.load_json("configs", "gpt3-xl.json"))
    paddle.seed(3)
    model = GPTForCausalLM(family._gpt_config(config))
    model.eval()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, config["vocab_size"], (2, 24)).astype(np.int32)
    labels = rng.integers(0, config["vocab_size"], (2, 24)).astype(np.int32)
    params = {k: p._value for k, p in model.named_parameters()}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model(paddle.to_tensor(ids))._value)
    got = np.asarray(reference.logits(params, ids, config["num_layers"],
                                      config["num_heads"]))
    # float32 on both sides: the orders of summation differ, nothing else
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    loss = float(reference.loss(params, ids, labels, config["num_layers"],
                                config["num_heads"]))
    logp = want - np.log(np.exp(want).sum(-1, keepdims=True))
    by_hand = -np.take_along_axis(logp, labels[..., None], -1).mean()
    assert loss == pytest.approx(by_hand, abs=1e-4)
