"""Pallas block-size autotune cache (reference:
paddle/phi/kernels/autotune/cache.h AutoTuneCache + auto_tune_base.h
candidate measurement)."""

import os
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import autotune


@pytest.fixture(autouse=True)
def _clean(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_DIR", str(tmp_path))
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def test_disabled_returns_default(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_AUTOTUNE", raising=False)
    calls = []
    out = autotune.pick_block_sizes("k", 512, 512, (128, 128),
                                    lambda bq, bk: calls.append((bq, bk)))
    assert out == (128, 128) and not calls


def test_measures_once_and_caches(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
    timings = {(128, 128): 0.004, (128, 256): 0.001, (256, 128): 0.003,
               (256, 256): 0.002, (128, 512): 0.005, (256, 512): 0.006}
    calls = []

    def run_with(bq, bk):
        import time

        calls.append((bq, bk))
        time.sleep(timings.get((bq, bk), 0.01))

    best = autotune.pick_block_sizes("flash_fwd", 512, 512, (128, 128),
                                     run_with, reps=1)
    assert best == (128, 256), best
    assert calls, "no candidates measured"

    # second call: cache hit, no measuring
    calls.clear()
    again = autotune.pick_block_sizes("flash_fwd", 512, 512, (128, 128),
                                      run_with, reps=1)
    assert again == (128, 256) and not calls

    # survives across process state (disk cache)
    autotune._memory.clear()
    autotune._disk_loaded[0] = False
    third = autotune.pick_block_sizes("flash_fwd", 512, 512, (128, 128),
                                      run_with, reps=1)
    assert third == (128, 256) and not calls


def test_tracer_inputs_use_cache_only(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
    calls = []
    out = autotune.pick_block_sizes("k2", 256, 256, (128, 128),
                                    lambda bq, bk: calls.append(1),
                                    allow_measure=False)
    assert out == (128, 128) and not calls  # no cache -> default, no measure


def test_failing_candidates_skipped(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")

    def run_with(bq, bk):
        if (bq, bk) != (128, 128):
            raise RuntimeError("mosaic rejects this tiling")

    best = autotune.pick_block_sizes("k3", 1024, 1024, (128, 128),
                                     run_with, reps=1)
    assert best == (128, 128)


def test_flash_entry_consults_tuner(monkeypatch, pallas_interpret_unless_hw):
    """flash_attention_fwd routes through the tuner: a pre-seeded cache
    winner changes the block shape _fwd actually receives."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    # force tuning on despite interpret mode so the cache lookup runs
    monkeypatch.setattr(autotune, "autotune_enabled", lambda: True)

    B, S, H, D = 1, 512, 2, 32
    # seed the winner for this exact signature (device + jaxlib keyed)
    key = (f"flash_fwd|{autotune._device_kind()}|{autotune._jaxlib_version()}"
           f"|{S}|{S}|{B}|{H}|{H}|{D}|float32|True")
    autotune._memory[key] = [256, 256]
    autotune._disk_loaded[0] = True

    seen = []
    orig_fwd = fa._fwd

    def spy(q, k, v, scale, causal, sq, skv, bq=None, bk=None, safe=None):
        seen.append((bq, bk))
        return orig_fwd(q, k, v, scale, causal, sq, skv, bq=bq, bk=bk,
                        safe=safe)

    monkeypatch.setattr(fa, "_fwd", spy)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    out = fa.flash_attention_fwd(q, q, q, causal=True)
    assert out.shape == q.shape and bool(jnp.isfinite(out).all())
    assert (256, 256) in seen, f"tuned blocks not used: {seen}"


def test_flash_entry_default_under_interpret(monkeypatch,
                                             pallas_interpret_unless_hw):
    """Interpret mode (tuning off) still runs correctly on defaults (on the
    chip the same call sweeps the candidates for real)."""
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 256, 2, 32)), jnp.float32)
    out = flash_attention_fwd(q, q, q, causal=True)
    assert out.shape == q.shape and bool(jnp.isfinite(out).all())


def test_jaxlib_version_in_disk_key(monkeypatch):
    """A jaxlib upgrade must invalidate tuned winners: the cache key embeds
    the jaxlib version, so a winner stored under the old version misses."""
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
    autotune.pick_block_sizes("kver", 256, 256, (128, 128),
                              lambda bq, bk: None, reps=1)
    (key,) = [k for k in autotune._memory if k.startswith("kver|")]
    assert f"|{autotune._jaxlib_version()}|" in key

    # same signature under a different jaxlib version: cache miss
    monkeypatch.setattr(autotune, "_jaxlib_version", lambda: "9.9.9")
    calls = []
    autotune.pick_block_sizes("kver", 256, 256, (128, 128),
                              lambda bq, bk: calls.append(1), reps=1)
    assert calls, "stale winner survived a jaxlib upgrade"


def test_trace_miss_counts_fallback_and_warns_once(monkeypatch):
    """PADDLE_TPU_AUTOTUNE=1 + jit trace + cache miss used to silently run
    defaults; now it counts pallas_autotune_fallbacks_total{kernel=} and
    warns ONCE naming the key."""
    import warnings

    from paddle_tpu.observability.metrics import reset_default_registry

    reg = reset_default_registry()
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = autotune.pick_block_sizes("kfb", 256, 256, (128, 128),
                                        lambda bq, bk: None,
                                        allow_measure=False)
        again = autotune.pick_block_sizes("kfb", 256, 256, (128, 128),
                                          lambda bq, bk: None,
                                          allow_measure=False)
    assert out == (128, 128) and again == (128, 128)
    hits = [x for x in w if "kfb" in str(x.message)]
    assert len(hits) == 1, "fallback warning must fire once per key"
    assert "PADDLE_TPU_AUTOTUNE" in str(hits[0].message)
    ctr = reg.get("pallas_autotune_fallbacks_total")
    assert ctr is not None and ctr.value(kernel="kfb") == 2
    tiles = autotune.chosen_tiles()
    assert tiles["kfb"]["source"] == "default"
    assert tiles["kfb"]["fallbacks"] == 2


def test_hit_and_miss_counters(monkeypatch):
    from paddle_tpu.observability.metrics import reset_default_registry

    reg = reset_default_registry()
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
    autotune.pick_block_sizes("khm", 256, 256, (128, 128),
                              lambda bq, bk: None, reps=1)
    autotune.pick_block_sizes("khm", 256, 256, (128, 128),
                              lambda bq, bk: None, reps=1)
    assert reg.get("pallas_autotune_misses_total").value(kernel="khm") == 1
    assert reg.get("pallas_autotune_hits_total").value(kernel="khm") == 1
    assert autotune.chosen_tiles()["khm"]["source"] == "tuned"


def test_custom_candidates_override_grid(monkeypatch):
    """Kernels with a non-attention tunable (fused norm row block, dense
    decode page tile) pass their own candidate list."""
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
    seen = []

    def run_with(bq, bk):
        seen.append((bq, bk))

    best = autotune.pick_block_sizes(
        "kcand", 512, 384, (64, 384), run_with, reps=1,
        candidates=[(64, 384), (128, 384)])
    assert set(seen) == {(64, 384), (128, 384)}
    assert best in {(64, 384), (128, 384)}


def test_disabled_still_records_default_tile(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_AUTOTUNE", raising=False)
    out = autotune.pick_block_sizes("kdef", 512, 512, (256, 512),
                                    lambda bq, bk: None)
    assert out == (256, 512)
    rec = autotune.chosen_tiles()["kdef"]
    assert rec == {"bq": 256, "bk": 512, "source": "default", "consults": 1}


def test_all_pallas_kernels_consult_tuner(pallas_interpret_unless_hw):
    """Acceptance: every Pallas kernel entry lands a tile in the registry —
    flash, flashmask, varlen, dense+paged decode, fused norm, fused rope."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.decode_attention import (
        dense_decode_attention, paged_decode_attention)
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd
    from paddle_tpu.ops.pallas.fused_norm import layer_norm_fwd, rms_norm_fwd
    from paddle_tpu.ops.pallas.fused_rope import apply_fused_rope
    from paddle_tpu.ops.pallas.masked_flash import (
        flashmask_attention_fwd, varlen_flash_attention_fwd)

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 32)), jnp.float32)
    flash_attention_fwd(q, q, q, causal=True)
    idx = jnp.full((1, 1, 64, 1), 64, jnp.int32)
    flashmask_attention_fwd(q, q, q, idx, causal=True)
    qp = jnp.asarray(rng.standard_normal((48, 2, 32)), jnp.float32)
    cu = jnp.asarray([0, 20, 48], jnp.int32)
    varlen_flash_attention_fwd(qp, qp, qp, cu, cu, 0.17, causal=True)
    qd = jnp.asarray(rng.standard_normal((2, 4, 32)), jnp.float32)
    dense = jnp.asarray(rng.standard_normal((2, 2, 64, 32)), jnp.float32)
    dense_decode_attention(qd, dense, dense, jnp.asarray([5, 9], jnp.int32))
    paged = jnp.asarray(rng.standard_normal((4, 2, 8, 32)), jnp.float32)
    tables = jnp.asarray([[1, 2], [3, -1]], jnp.int32)
    paged_decode_attention(qd, paged, paged, tables,
                           jnp.asarray([10, 5], jnp.int32))
    paged_q = (paged * 16).astype(jnp.int8)
    scales = jnp.ones((4, 2), jnp.float32) / 16
    paged_decode_attention(qd, paged_q, paged_q, tables,
                           jnp.asarray([10, 5], jnp.int32),
                           kv_scales=(scales, scales))
    x = jnp.asarray(rng.standard_normal((2, 40, 96)), jnp.float32)
    rms_norm_fwd(x, None)
    layer_norm_fwd(x, None, None)
    c = jnp.cos(jnp.ones((1, 64, 16), jnp.float32))
    s = jnp.sin(jnp.ones((1, 64, 16), jnp.float32))
    apply_fused_rope((q,), c, s)
    from paddle_tpu.ops.pallas.grouped_gemm import grouped_matmul

    grouped_matmul(jnp.asarray(rng.standard_normal((16, 8)), jnp.float32),
                   jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32),
                   jnp.asarray([8, 4], jnp.int32))

    tiles = autotune.chosen_tiles()
    for kernel in ("flash_fwd", "flashmask_fwd", "varlen_fwd",
                   "decode_dense", "decode_paged", "decode_paged_q8",
                   "fused_rms_norm", "fused_layer_norm", "fused_rope",
                   "grouped_gemm"):
        assert kernel in tiles, (kernel, sorted(tiles))
        assert tiles[kernel]["bq"] > 0 and tiles[kernel]["bk"] > 0


class TestSetConfig:
    """incubate.autotune.set_config error semantics (reference:
    python/paddle/incubate/autotune.py — warn + fall back, never raise)."""

    def test_bad_path_warns_and_defaults(self, monkeypatch):
        import warnings
        import paddle_tpu.incubate as incubate

        monkeypatch.delenv("PADDLE_TPU_AUTOTUNE", raising=False)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            incubate.autotune.set_config("/nonexistent/autotune.json")
        assert any("cannot load" in str(x.message) for x in w)
        assert os.environ["PADDLE_TPU_AUTOTUNE"] == "1"

    def test_non_dict_json_warns_and_defaults(self, tmp_path, monkeypatch):
        import warnings
        import paddle_tpu.incubate as incubate

        p = tmp_path / "cfg.json"
        p.write_text("[1, 2, 3]")
        monkeypatch.delenv("PADDLE_TPU_AUTOTUNE", raising=False)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            incubate.autotune.set_config(str(p))
        assert any("expects" in str(x.message) for x in w)
        assert os.environ["PADDLE_TPU_AUTOTUNE"] == "1"

    def test_dict_without_kernel_leaves_autotune_untouched(self, monkeypatch):
        import paddle_tpu.incubate as incubate

        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "0")
        incubate.autotune.set_config({"layout": {"enable": True}})
        assert os.environ["PADDLE_TPU_AUTOTUNE"] == "0"

    def test_kernel_enable_false(self, monkeypatch):
        import paddle_tpu.incubate as incubate

        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
        incubate.autotune.set_config({"kernel": {"enable": False}})
        assert os.environ["PADDLE_TPU_AUTOTUNE"] == "0"
