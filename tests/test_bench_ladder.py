"""bench.py: nothing hides the device, + the 1.3B low-memory recipe.

Five driver rounds produced one chip number: the others died, or silently
fell back to the CPU (or to a smaller model) and still printed a metric.
These tests pin that (a) the named rung runs or fails — no ladder, (b) a run
that finds no TPU fails unless the harness check is asked for by name, (c) a
failed matrix rung leaves a non-zero exit after the remaining rungs ran,
(d) an unknown device kind has no peak, (e) failed rungs free their device
buffers, (f) the bf16-moment AdamW recipe the 1.3B rung uses trains
correctly.
"""

import json

import numpy as np
import pytest

import bench
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.optimizer as opt


@pytest.fixture(autouse=True)
def _clear_mesh():
    yield
    dist.env.set_global_mesh(None)


def _tiny_cfg():
    from paddle_tpu.models import GPTConfig

    return GPTConfig(hidden_size=64, num_layers=2, num_heads=2,
                     vocab_size=512, max_position_embeddings=64)


def test_named_rung_fails_loudly(monkeypatch):
    """A failure anywhere in the named rung — construction included (round
    4's 1.3B run OOMed there) — propagates. No smaller model stands in."""
    calls = []

    def fake(cfg, batch, seq, bf16_amp, low_mem=False, **kw):
        calls.append(cfg.hidden_size)
        raise RuntimeError("RESOURCE_EXHAUSTED: fake construction OOM")

    monkeypatch.setattr(bench, "_decoder_step", fake)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        bench.run_gpt_rung("gpt3_1p3b")
    assert calls == [2048]  # the 1.3B config, once; nothing else was tried


def test_no_tpu_fails_unless_cpu_smoke_by_name(monkeypatch):
    monkeypatch.delenv("BENCH_CONFIG", raising=False)
    monkeypatch.delenv("BENCH_MATRIX", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent-unused")
    with pytest.raises(RuntimeError, match="platform='cpu'"):
        bench.main([])
    monkeypatch.setenv("BENCH_CONFIG", "gpt3_125m")
    with pytest.raises(RuntimeError, match="platform='cpu'"):
        bench.main([])
    monkeypatch.setenv("BENCH_CONFIG", "cpu_smoke")
    monkeypatch.setenv("BENCH_MATRIX", "1")  # the matrix always measures
    with pytest.raises(RuntimeError, match="platform='cpu'"):
        bench.main([])


def test_failed_matrix_rung_exits_nonzero(monkeypatch, capsys):
    ran = []

    def ok(name):
        return lambda: ran.append(name)

    def boom():
        ran.append("boom")
        raise ValueError("rung blew up")

    monkeypatch.setenv("BENCH_MATRIX", "1")
    monkeypatch.delenv("BENCH_CONFIG", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent-unused")
    monkeypatch.setattr(bench, "_require_tpu", lambda: None)
    monkeypatch.setattr(bench, "_matrix_rungs", lambda mp: [
        ("a", ok("a")), ("b", boom), ("c", ok("c"))])
    monkeypatch.setattr(bench, "run_gpt_rung",
                        lambda name, trace_dir=None: ran.append(name))
    assert bench.main([]) == 1
    assert ran == ["a", "boom", "c", "gpt3_1p3b"]  # the rest still ran
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    failed = [ln for ln in lines if ln["metric"] == "b_failed"]
    assert failed and failed[0]["platform"] == "cpu"
    assert "device_kind" in failed[0] and "device_count" in failed[0]

    monkeypatch.setattr(bench, "_matrix_rungs", lambda mp: [("a", ok("a"))])
    assert bench.main([]) == 0


def test_unknown_device_kind_has_no_peak():
    """No utilisation for a chip the table does not know — the old answer
    was v4's peak."""
    import jax

    from paddle_tpu.distributed.planner import chip_specs

    with pytest.raises(ValueError, match="cpu"):
        bench._peak_flops(jax.devices()[0])
    with pytest.raises(ValueError, match="TPU v9"):
        chip_specs("TPU v9")
    assert chip_specs("TPU v5 lite")[0] == 197e12


def test_free_rung_drops_trainstep_state():
    import gc
    import weakref

    step, ids, labels = bench._decoder_step(_tiny_cfg(), 2, 16, False)
    assert step.params
    # a param's device buffer must become unreachable after _free_rung even
    # while the caller still holds `step` (round-4 failure mode: params were
    # pinned through step.model/_state/optimizer during the fallback rung)
    ref = weakref.ref(next(iter(step._state.params.values())))
    bench._free_rung(step, ids, labels)
    assert step.params == {} and step.opt_states == {}
    assert step.model is None and step._state is None
    gc.collect()
    assert ref() is None, "Parameter still reachable after _free_rung"


def test_low_mem_recipe_trains():
    """bf16 params (amp.decorate O2) + bf16 AdamW moments + recompute —
    the 1.3B-fits-one-v5e recipe, on a tiny config."""
    import jax.numpy as jnp

    cfg = _tiny_cfg()
    step, ids, labels = bench._decoder_step(cfg, 2, 16, False, low_mem=True)
    # params stored bf16, moments stored bf16
    dts = {str(v.dtype) for v in step.params.values()}
    assert "bfloat16" in dts, dts
    mdts = {str(st["m"].dtype) for st in step.opt_states.values()
            if "m" in st}
    assert mdts == {"bfloat16"}, mdts
    assert cfg.use_recompute
    losses = [float(step(ids, labels)) for _ in range(4)]
    assert all(np.isfinite(v) for v in losses), losses
    assert losses[-1] < losses[0], losses


def test_adamw_moment_dtype_matches_f32_compute():
    """bf16-stored moments with f32 update compute should track the all-f32
    AdamW closely on an f32 param."""
    rng = np.random.default_rng(0)
    w0 = rng.normal(size=(32, 32)).astype(np.float32)

    def run(moment_dtype):
        w = paddle.to_tensor(w0.copy())
        w.stop_gradient = False
        o = opt.AdamW(learning_rate=1e-2, parameters=[w],
                      moment_dtype=moment_dtype)
        for i in range(5):
            ((w * w).sum()).backward()
            o.step()
            o.clear_grad()
        return w.numpy()

    ref = run(None)
    low = run("bfloat16")
    assert np.max(np.abs(ref - low)) < 1e-2, np.max(np.abs(ref - low))


def test_timed_steps_emits_overlap_metrics(tmp_path):
    """--emit-metrics acceptance: every step-timeline JSONL record carries
    overlap_fraction, the perf line aggregates it, and
    tools/overlap_report.py reads the file back."""
    from paddle_tpu.observability import disable_step_timeline, \
        enable_step_timeline

    path = str(tmp_path / "bench_metrics.jsonl")
    step, ids, labels = bench._decoder_step(_tiny_cfg(), 2, 16, False)
    enable_step_timeline(jsonl_path=path)
    try:
        dt, info = bench._timed_steps(lambda: step(ids, labels), steps=3,
                                      warmup=1, rung="cpu_smoke")
    finally:
        disable_step_timeline()
    assert dt > 0
    assert "overlap_fraction" in info
    assert 0.0 <= info["overlap_fraction"] <= 1.0
    assert "comm_exposed_s_per_step" in info

    recs = [json.loads(ln) for ln in open(path)]
    assert len(recs) == 3
    assert all("overlap_fraction" in r for r in recs)
    assert all(r["rung"] == "cpu_smoke" for r in recs)
    # the distributed step instruments its input placement as comm
    assert all(any(t["desc"] == "h2d/inputs" for t in r["comm_tasks"])
               for r in recs)

    from tools import overlap_report
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = overlap_report.main([path, "--json"])
    assert rc == 0
    summary = json.loads(buf.getvalue().strip())
    assert summary["steps"] == 3
    assert summary["overlap_fraction"] == pytest.approx(
        info["overlap_fraction"], abs=1e-3)
    assert "h2d/inputs" in summary["exposed_by_desc"] or \
        summary["exposed_s"] == 0.0
