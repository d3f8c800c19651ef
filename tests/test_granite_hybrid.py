"""The hybrid decoder (`models/granite_hybrid.py`: Mamba-2 layers beside
attention layers, routed experts of which a chip holds a share) through the
paged engine, against the plain float32 reference
(`benchmark/reference/granite_hybrid.py`) at a tiny size on the CPU. Logits
are compared, not tokens: with random weights the largest logit changes on
rounding.

Tolerances. Model and reference are both float32 here (conftest sets
`highest` matmuls), so they differ by the order of summation alone. A row of
logits has a standard deviation of about 9e-4 at this size (the embedding is
drawn small, `models/granite_hybrid.py`): 2e-7 absolute is fifty times what
was seen (3e-9) and a four-thousandth of a spread, where a wrong state, mask
or expert moves a row by a good part of one.
"""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import granite_hybrid as reference
from paddle_tpu.incubate.distributed.models.moe import HeldExpertsMoE
from paddle_tpu.incubate.distributed.models.moe import held_moe
from paddle_tpu.inference.paged import (BlockPool, PagedKV,
                                        PagedServingEngine, RowState)
from paddle_tpu.models import GPTForCausalLM, gpt3_tiny
from paddle_tpu.models.granite_hybrid import (GraniteHybridForCausalLM,
                                              granite_hybrid_tiny,
                                              ssd_chunked)
from paddle_tpu.observability.metrics import default_registry
from paddle_tpu.ops.pallas.ssm_decode import ssm_decode, ssm_decode_reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-7


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


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    m = GraniteHybridForCausalLM(granite_hybrid_tiny())
    m.eval()
    return m


def _params(m):
    return {k: p._value for k, p in m.named_parameters()}


def _reference_logits(m, ids):
    return np.asarray(reference.logits(
        _params(m), ids, _as_config_dict(m.config), m.config.held_experts))


# -- 1. the whole forward --------------------------------------------------- #

def test_full_forward_matches_the_reference(model):
    """Two periods of `m m a m`, all experts held, a batch of two."""
    ids = np.random.default_rng(0).integers(1, 128, (2, 21)).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids))._value)
    for b in range(2):
        np.testing.assert_allclose(got[b], _reference_logits(model, ids[b]),
                                   rtol=0, atol=ATOL)


# -- 2. bucketed prefill, then decode through the engine's cache ------------ #

def test_prefill_inside_its_bucket_then_decode_matches_the_reference(model):
    """A prompt of 21 tokens is padded to the bucket of 32 with zeros the
    recurrence must not see; then 20 decode steps. At every position the
    engine's logits equal the reference's one full forward over prompt +
    answer."""
    prompt = np.random.default_rng(1).integers(1, 128, 21).astype(np.int32)
    eng = PagedServingEngine(model, max_batch_size=4, max_seq_len=64,
                             page_size=8)
    rid = eng.add_request(prompt, max_new_tokens=21)
    rows = []
    while eng.has_work():
        if rid in eng.step():      # the only request: decode row 0
            rows.append(np.asarray(eng.last_logits[0]))
    (req,) = eng.finished
    assert len(req.generated) == 21 and len(rows) == 20
    want = _reference_logits(
        model, np.concatenate([prompt, req.generated[:-1]]).astype(np.int32))
    # the first token comes from the prefill's last real position
    assert req.generated[0] == int(np.argmax(want[20]))
    for step, got in enumerate(rows):
        np.testing.assert_allclose(got, want[21 + step], rtol=0, atol=ATOL)


# -- 3. the mixer's two forms ----------------------------------------------- #

@pytest.mark.parametrize("length", [8, 16, 21, 5])
def test_chunked_scan_agrees_with_the_one_token_recurrence(length):
    """Chunk 8: lengths that are, and are not, multiples of it, and one
    shorter than a chunk."""
    rng = np.random.default_rng(length)
    H, P, N = 8, 16, 16
    x = jnp.asarray(rng.normal(size=(2, length, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (2, length, H)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 8, H), jnp.float32)
    b = jnp.asarray(rng.normal(size=(2, length, N)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(2, length, N)), jnp.float32)
    y, final = ssd_chunked(x, dt, a, b, c, chunk=8)
    state = jnp.zeros((2, N, H * P), jnp.float32)
    live = jnp.ones(2, bool)
    for t in range(length):
        state, y_t = ssm_decode_reference(
            state, jnp.repeat(jnp.exp(dt[:, t] * a), P, axis=1),
            jnp.repeat(dt[:, t], P, axis=1) * x[:, t].reshape(2, H * P),
            b[:, t], c[:, t], live)
        np.testing.assert_allclose(y[:, t].reshape(2, H * P), y_t,
                                   rtol=0, atol=2e-5)
    np.testing.assert_allclose(
        final.transpose(0, 3, 1, 2).reshape(2, N, H * P), state,
        rtol=0, atol=2e-5)


# -- 4, 5. the chip's share of the experts ---------------------------------- #

def _moe_pair(held_a, held_b, seed=3, experts=8, top_k=4):
    """Two shares of one layer: the same router, each its own experts."""
    paddle.seed(seed)
    whole = HeldExpertsMoE(64, 32, experts, top_k)
    parts = []
    for first, count in (held_a, held_b):
        part = HeldExpertsMoE(64, 32, experts, top_k, held=(first, count))
        part.router._value = whole.router._value
        part.w_in._value = whole.w_in._value[first:first + count]
        part.w_out._value = whole.w_out._value[first:first + count]
        parts.append(part)
    return whole, parts


def test_the_two_halves_add_up_to_the_uncut_reference_layer():
    """`model-configs` guide, section 4: experts (0, E/2) on one chip and
    (E/2, E/2) on the other, the shared expert counted once, give what the
    uncut reference gives for the whole layer."""
    whole, (low, high) = _moe_pair((0, 4), (4, 4))
    rng = np.random.default_rng(4)
    u = rng.normal(size=(24, 64)).astype(np.float32)
    shared = {"shared_mlp.input_linear.weight":
              rng.normal(size=(64, 96)).astype(np.float32) * 0.05,
              "shared_mlp.output_linear.weight":
              rng.normal(size=(48, 64)).astype(np.float32) * 0.05}

    def ref(layer, first):
        p = {"moe.router": layer.router._value, "moe.w_in": layer.w_in._value,
             "moe.w_out": layer.w_out._value, **shared}
        return np.asarray(reference._experts(jnp.asarray(u), p, 4, first,
                                             False))

    shared_once = np.asarray(reference._gated(
        jnp.asarray(u), shared["shared_mlp.input_linear.weight"],
        shared["shared_mlp.output_linear.weight"]))
    got = (np.asarray(low(paddle.to_tensor(u))._value)
           + np.asarray(high(paddle.to_tensor(u))._value) + shared_once)
    np.testing.assert_allclose(got, ref(whole, 0), rtol=0, atol=ATOL)
    # and each share alone is what the reference gives for that share
    np.testing.assert_allclose(
        np.asarray(high(paddle.to_tensor(u))._value) + shared_once,
        ref(high, 4), rtol=0, atol=ATOL)
    assert np.abs(np.asarray(low(paddle.to_tensor(u))._value)).max() > 1e-3


def test_no_token_is_dropped_when_every_row_goes_to_one_expert():
    paddle.seed(5)
    layer = HeldExpertsMoE(64, 32, 8, 2, held=(0, 4))
    router = np.zeros((64, 8), np.float32)
    router[0, 2], router[0, 6] = 5.0, 4.0     # every row picks 2, then 6
    layer.router._value = jnp.asarray(router)
    u = np.abs(np.random.default_rng(6).normal(size=(70, 64))).astype(
        np.float32) + 0.1
    out, stats = layer(paddle.to_tensor(u), with_stats=True)
    pairs, rows_max, rows_sum, dropped, tile_rows = (
        int(v) for v in stats._value)
    assert (pairs, rows_max, rows_sum, dropped) == (70, 70, 70, 0)
    # the one group's 70 rows lie from row 0 of the 140 pairs (the 70 picks
    # of expert 6, held elsewhere, sort behind them): the row tiles that
    # hold them and no others are visited
    bm = held_moe._row_tile(140, 8)
    assert tile_rows == -(-70 // bm) * bm
    p = {"moe.router": layer.router._value, "moe.w_in": layer.w_in._value,
         "moe.w_out": layer.w_out._value,
         "shared_mlp.input_linear.weight": jnp.zeros((64, 2)),
         "shared_mlp.output_linear.weight": jnp.zeros((1, 64))}
    want = np.asarray(reference._experts(jnp.asarray(u), p, 2, 0, False))
    np.testing.assert_allclose(np.asarray(out._value), want, rtol=0,
                               atol=ATOL)
    # rows the caller marks dead are routed nowhere
    live = np.arange(70) % 2 == 0
    out, stats = layer(paddle.to_tensor(u), live=paddle.to_tensor(live),
                       with_stats=True)
    assert int(stats._value[0]) == 35
    assert np.abs(np.asarray(out._value)[~live]).max() == 0.0
    np.testing.assert_allclose(np.asarray(out._value)[live], want[live],
                               rtol=0, atol=ATOL)
    assert int(stats._value[4]) == -(-35 // bm) * bm
    # every row to two experts that are NOT held: nothing to compute, no tile
    router[0, 2], router[0, 7] = 0.0, 3.0
    layer.router._value = jnp.asarray(router)
    out, stats = layer(paddle.to_tensor(u), with_stats=True)
    assert [int(v) for v in stats._value] == [0, 0, 0, 0, 0]
    assert not np.asarray(out._value).any()


# -- 6. preemption carries the recurrent state ------------------------------ #

def _row_of(eng, rid):
    return next((i for i, r in enumerate(eng.active)
                 if r is not None and r.req_id == rid), None)


def test_a_preempted_row_resumes_token_for_token(model):
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, 128, n).astype(np.int32) for n in (13, 9, 17)]

    def run(preempt):
        eng = PagedServingEngine(model, max_batch_size=4, max_seq_len=64,
                                 page_size=8)
        ids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
        logits = {i: [] for i in ids}
        tick = 0
        while eng.has_work():
            if preempt and tick == 4:
                eng._spill_row(_row_of(eng, ids[0]))
                assert eng.sched.resume[0].state_host
                # keep it out for two ticks while the others decode
                parked = eng.sched.resume.popleft()
            if preempt and tick == 6:
                eng.sched.enqueue_resume(parked)
            for rid in eng.step():
                row = _row_of(eng, rid)
                if row is not None:   # the step that retires a row: skipped
                    logits[rid].append(np.asarray(eng.last_logits[row]))
            tick += 1
        by = {r.req_id: r for r in eng.finished}
        return [by[i].generated for i in ids], [logits[i] for i in ids]

    calm_tokens, calm_logits = run(False)
    tokens, got_logits = run(True)
    assert tokens == calm_tokens
    # the spilled request's logits after the resume equal the undisturbed
    # run's at the same step (the last step retires the row: not recorded)
    assert len(got_logits[0]) == len(calm_logits[0]) >= 10
    for got, want in zip(got_logits[0], calm_logits[0]):
        np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
    assert default_registry().get("serving_resumes_total").value() >= 1


# -- 7. the decode kernel --------------------------------------------------- #

@pytest.mark.parametrize("live", [[0, 1, 1, 0, 1, 0], [1] * 6, [0] * 6,
                                  [0, 0, 0, 0, 0, 1]],
                         ids=["mixed", "all", "none", "last"])
def test_ssm_decode_kernel_matches_the_recurrence_and_skips_dead_rows(live):
    rng = np.random.default_rng(9)
    rows, n, lanes = 6, 16, 256
    state = jnp.asarray(rng.normal(size=(rows, n, lanes)), jnp.bfloat16)
    a = jnp.asarray(rng.uniform(0.5, 1, (rows, lanes)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(rows, lanes)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(rows, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(rows, n)), jnp.float32)
    live = jnp.asarray(live, bool)
    got_state, got_y = jax.jit(ssm_decode)(state, a, u, b, c, live)
    want_state, want_y = ssm_decode_reference(state, a, u, b, c, live)
    # the same f32 arithmetic, rounded to bf16 once: bit for bit
    assert jnp.array_equal(got_state, want_state)
    np.testing.assert_allclose(got_y, want_y, rtol=0, atol=1e-5)
    dead = ~np.asarray(live)
    assert jnp.array_equal(got_state[dead], state[dead])
    assert not np.asarray(got_y)[dead].any()


# -- the cache manager ------------------------------------------------------ #

def test_pool_keeps_pages_and_row_state_in_one_list():
    specs = [RowState(((3, 10), (4, 8))), PagedKV(2, 4), RowState(((3, 10),
                                                                   (4, 8)))]
    pool = BlockPool(3, 2, 4, page_size=4, num_pages=6, specs=specs, rows=5)
    assert pool.page_layers == [1] and pool.state_layers == [0, 2]
    assert [a.shape for a in pool.kv[0]] == [(5, 3, 10), (5, 4, 8)]
    assert pool.kv[1][0].shape == (6, 2, 4, 4)
    assert pool.state_row_nbytes == 2 * (30 + 32) * 4
    assert pool.bytes_per_page == BlockPool.page_nbytes(1, 2, 4, 4)
    values = [(np.full((1, 3, 10), 2.0, np.float32),
               np.full((1, 4, 8), 3.0, np.float32))] * 2
    pool.write_state(3, values)
    got = pool.read_state(3)
    assert all(np.array_equal(g, v.reshape(g.shape))
               for layer, vals in zip(got, values)
               for g, v in zip(layer, vals))
    assert not np.asarray(pool.kv[0][0])[[0, 1, 2, 4]].any()
    # pages spill and restore over the paged layers only
    pages = [pool.alloc(), pool.alloc()]
    host = pool.read_pages(pages)
    assert len(host) == 1 and host[0][0].shape == (2, 2, 4, 4)
    pool.restore_pages(pages, host, [0, 1])
    pool.kv = []            # what benchmark/serving.py does: all is released
    with pytest.raises(ValueError):
        BlockPool(1, 2, 4, 4, 6, specs=[RowState(((1,),))], rows=2,
                  quantized=True)


def test_gpt_decode_program_is_the_parents():
    """A cache manager generalised for recurrent state leaves the GPT decode
    program as it was: the jaxpr of the engine's program equals that of the
    parent commit's closure, written out here."""
    paddle.seed(0)
    eng = PagedServingEngine(GPTForCausalLM(gpt3_tiny()), max_batch_size=4,
                             max_seq_len=64, page_size=8)
    assert eng.cache_specs is None and not eng.pool.state_layers

    def decode(p, b, tok, offs, tables, temps, keys, caches):
        # the program before cache specs (PR 28's, verbatim) with the
        # sampler that every engine's program calls since PR 32
        pos = offs[:, None]
        logits, new_c = eng._functional_forward(
            p, b, tok[:, None], pos, caches, offs, tables=tables)
        last = logits[:, -1]
        return *eng._choose_tokens(last, temps, keys), last, new_c

    args = (eng.params, eng.buffers, jnp.zeros(4, jnp.int32),
            jnp.ones(4, jnp.int32), jnp.zeros((4, eng.P), jnp.int32),
            jnp.zeros(4, jnp.float32), jnp.zeros((4, 2), jnp.uint32),
            eng.pool.kv)
    mine = jax.make_jaxpr(eng._decode_program())(*args)
    parents = jax.make_jaxpr(jax.jit(decode, donate_argnums=(7,)))(
        *args)
    assert str(mine) == str(parents)


# -- the yardstick's own counts, by hand ------------------------------------ #

def _cell_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-small.json")) as f:
        return json.load(f)


def test_costs_by_hand_for_the_published_widths():
    from benchmark import costs_hybrid as costs

    config = _cell_config()
    # one expert: 4096 x (2 x 768) + 768 x 4096 = 9,437,184; 36 held, bf16
    assert costs.expert_params(config) == 9_437_184
    assert costs.held_expert_weight_bytes_per_layer(config) == 679_477_248
    assert costs.grouped_gemm_weight_bytes(config) == (452_984_832,
                                                       226_492_416)
    # 128 heads x 64 x 128 state values, bf16
    assert costs.ssm_state_bytes_per_row(config) == 2_097_152
    # 128 row steps: 9 Mamba layers read and write each row's state
    assert costs.ssm_decode_bytes(config, 128) == 2 * 2_097_152 * 9 * 128
    # K and V, ONE attention layer of the ten, 8 KV heads x 128, bf16
    assert costs.kv_bytes_per_token(config) == 4096
    # Mamba mixer 4096 x 16768 + 8192 x 4096 = 102,236,160; attention
    # 4096 x 6144 + 4096^2 = 41,943,040; a layer's router 294,912, shared
    # expert 18,874,368 and 5 of 10 picks x 9,437,184; head 100352 x 4096
    per_layer = 294_912 + 18_874_368 + 5 * 9_437_184
    assert costs.matmul_params_per_token(config) == (
        9 * (102_236_160 + per_layer) + 41_943_040 + per_layer
        + 411_041_792) == 2_036_662_272
    # the scan's own: 5 per state value and 2 x 4 per conv channel, 9
    # layers; attention's own: 4 x 4096 per context token, one layer
    assert costs.flops_per_token(config, 1000) == (
        2 * 2_036_662_272 + 9 * (5 * 1_048_576 + 8 * 8448) + 16_384_000
    ) == 4_137_502_720


def test_roofline_reader_on_a_trace_written_by_hand():
    import types

    path = os.path.join(ROOT, "benchmark", "readers",
                        "kernel_roofline_hybrid.py")
    spec = importlib.util.spec_from_file_location("_roofline_reader", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    tail = ', custom_call_target="tpu_custom_call", operand_layout...'
    ops = [(f"%ssm_decode.{i} = (bf16[128,128,8192], f32[128,1,8192]) "
            f"custom-call(...){tail}", 1.0 + i, 0.001) for i in range(9)]
    ops += [("%grouped_gemm.1 = bf16[4608,1536] custom-call(s32[36], "
             f"bf16[4608,4096], bf16[36,4096,1536]){tail}", 20.0, 0.001),
            ("%grouped_gemm.2 = bf16[4608,4096] custom-call(s32[36], "
             f"bf16[4608,768], bf16[36,768,4096]){tail}", 21.0, 0.0005),
            ("%fusion.7 = bf16[36,4096,1536] fusion(...)", 22.0, 0.5),
            (f"%ssm_decode.99 = ...{tail}", 99.0, 1.0)]   # outside
    trace = types.SimpleNamespace(window=(0.0, 50.0),
                                  devices={"/device:TPU:0": {"XLA Ops": ops}})
    run = types.SimpleNamespace(
        trace=trace, trace_ticks=(3, 5), config=_cell_config(),
        peaks=lambda: {"hbm_bytes_per_s": 819e9})
    obs = {"series": {"ticks": [{"index": 2, "decoded_rows": 128},
                                {"index": 3, "decoded_rows": 100},
                                {"index": 4, "decoded_rows": 28}]}}
    # 128 row steps in the traced ticks: 4,831,838,208 B over 819 GB/s is
    # 5.8997 ms, against 9 ms of calls
    assert reader.read(run, obs, kernel="ssm_decode") == pytest.approx(
        100 * (4_831_838_208 / 819e9) / 0.009)
    # (452,984,832 + 226,492,416) B over 819 GB/s against 1.5 ms
    assert reader.read(run, obs, kernel="grouped_gemm") == pytest.approx(
        100 * (679_477_248 / 819e9) / 0.0015)
    run.trace = None
    assert reader.read(run, obs, kernel="ssm_decode") is None


# -- 8, 9. the cell and the manifest ---------------------------------------- #

def test_the_cells_rehearsal_runs_end_to_end_and_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PADDLE_TPU_PALLAS_INTERPRET="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PADDLE_TPU_HW", None)
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "serve-granite-h-sat", "--seed", "2147483659",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["rehearsal"]["would_report"] == ["serve_tok_s", "setup_s"]


def test_the_cell_is_a_workload_of_the_manifest():
    """The manifest's own checks run under `tests/test_benchmark_suite.py`."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert "serve-granite-h-sat" in [w["name"] for w in manifest["workloads"]]
