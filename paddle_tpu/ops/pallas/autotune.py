"""Kernel block-size autotuning (reference: paddle/phi/kernels/autotune/ —
cache.h AutoTuneCache keyed per kernel+shape, auto_tune_base.h measuring
candidate configs at first use).

TPU formulation: the tunable is the Pallas block shape (bq, bk). Enabled via
PADDLE_TPU_AUTOTUNE=1, the first call of a kernel signature measures each
legal candidate with a compiled micro-run and caches the winner — in-process
and on disk (~/.cache/paddle_tpu_autotune.json, keyed by device kind AND
jaxlib version, so a Mosaic upgrade invalidates stale winners). Disabled
(default) or under the interpreter it returns the caller's default
immediately; measurement failures fall back the same way, so tuning can
never break a run.

Every decision — tuned or default — is recorded for telemetry:

- `chosen_tiles()` returns the last tile picked per kernel plus per-kernel
  hit/miss/fallback counts; the StepTimeline folds it into each step
  record.
- a `pallas_autotune_{hits,misses,fallbacks}_total{kernel=}` counter family
  lands in the observability registry. A *fallback* is the silent failure
  mode this PR makes visible: tuning enabled, lookup under trace
  (allow_measure=False, measurement impossible inside jit), cache miss —
  the kernel runs defaults even though the user asked for tuning. The first
  fallback per key also emits a RuntimeWarning naming the key so "tuning
  never ran" shows up in logs, not just dashboards.
"""

from __future__ import annotations

import json
import os
import threading
import time
import warnings

__all__ = ["autotune_enabled", "pick_block_sizes", "cache_path",
           "clear_cache", "chosen_tiles"]

# Every Pallas kernel's name, one per `pallas_call` site: its `name=`, its
# `jax.named_scope`, and so the HLO instruction a profiler trace shows
# (`%flash_fwd.3`). A forward kernel's name is also its word in this
# registry (`pick_block_sizes(kernel_name=...)`; the fused norms tune as
# "fused_layer_norm"/"fused_rms_norm" and run as `_fwd`/`_bwd`). Readers of
# traces hold on to these: renaming one silences a metric.
KERNEL_NAMES = (
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_fwd_window",
    "flashmask_fwd", "flashmask_bwd_dq", "flashmask_bwd_dkv",
    "varlen_fwd", "varlen_bwd_dq", "varlen_bwd_dkv",
    "fused_layer_norm_fwd", "fused_layer_norm_bwd",
    "fused_rms_norm_fwd", "fused_rms_norm_bwd",
    "fused_rope", "grouped_gemm",
    "decode_paged", "decode_paged_q8", "decode_dense", "decode_window",
    "decode_latent", "ssm_decode",
)

_lock = threading.Lock()
_memory: dict = {}
_disk_loaded = [False]
# telemetry: last tile picked per kernel + decision counts (plain dicts —
# mutated under the GIL only, read by chosen_tiles() snapshots)
_chosen: dict = {}
_stats: dict = {}
_warned: set = set()
_metric_handles = None


def autotune_enabled() -> bool:
    from . import interpret_mode

    return (os.environ.get("PADDLE_TPU_AUTOTUNE", "0") == "1"
            and not interpret_mode())


def cache_path():
    d = os.environ.get("PADDLE_TPU_AUTOTUNE_DIR",
                       os.path.join(os.path.expanduser("~"), ".cache"))
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, "paddle_tpu_autotune.json")


def _device_kind():
    try:
        import jax

        return getattr(jax.devices()[0], "device_kind", "unknown")
    except Exception:
        return "unknown"


def _jaxlib_version():
    """Part of the cache key: a tuned winner reflects one Mosaic compiler's
    code generation — letting it survive a jaxlib upgrade silently pins the
    new compiler to the old compiler's tile choice."""
    try:
        import jaxlib
    except ImportError:
        return "unknown"
    return getattr(jaxlib, "__version__", "unknown")


def _load_disk():
    if _disk_loaded[0]:
        return
    _disk_loaded[0] = True
    try:
        with open(cache_path()) as f:
            _memory.update(json.load(f))
    except Exception:
        pass


def _store_disk():
    """Merge-then-atomic-rename: concurrent tuners must not clobber each
    other's winners, and an interrupt must not truncate the shared file."""
    try:
        path = cache_path()
        merged = {}
        try:
            with open(path) as f:
                merged.update(json.load(f))
        except Exception:
            pass
        merged.update(_memory)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(merged, f)
        os.replace(tmp, path)
        _memory.update(merged)
    except Exception:
        pass


def clear_cache():
    with _lock:
        _memory.clear()
        _disk_loaded[0] = False
        _chosen.clear()
        _stats.clear()
        _warned.clear()
        try:
            os.remove(cache_path())
        except OSError:
            pass


def _candidates(sq, skv, default):
    """Legal (bq, bk) choices: block divides (or covers) the padded seq,
    bounded so the f32 logits tile [bq, bk] stays well under VMEM."""
    cands = {default}
    for bq in (128, 256, 512, 1024):
        for bk in (128, 256, 512, 1024):
            if bq * bk > 1024 * 1024:
                # f32 logits tile caps at 4MB — round-5 on-chip sweeps show
                # the large tiles (512x1024, 1024x1024) winning at long seq
                continue
            if sq >= bq and skv >= bk:
                cands.add((bq, bk))
    return sorted(cands)


def _counters():
    """(hits, misses, fallbacks) registry handles, registry-swap safe."""
    global _metric_handles
    if _metric_handles is None:
        from ...observability.metrics import HandleCache

        _metric_handles = HandleCache(lambda reg: (
            reg.counter("pallas_autotune_hits_total",
                        "autotune cache hits (tuned tile used)",
                        labelnames=("kernel",)),
            reg.counter("pallas_autotune_misses_total",
                        "autotune cache misses that ran a measurement sweep",
                        labelnames=("kernel",)),
            reg.counter("pallas_autotune_fallbacks_total",
                        "autotune enabled but lookup missed under trace — "
                        "kernel ran DEFAULT tiles, tuning never happened",
                        labelnames=("kernel",)),
        ))
    return _metric_handles.get()


def _stat(kernel):
    s = _stats.get(kernel)
    if s is None:
        s = _stats[kernel] = {"hits": 0, "misses": 0, "fallbacks": 0}
    return s


_KINDS = ("hits", "misses", "fallbacks")


def _bump(kind, kernel):
    """Count a tuner decision: module-local (chosen_tiles) + registry
    counter. A kernel launch must never die on telemetry — registry failure
    (e.g. a conflicting foreign declaration of the metric name) degrades to
    the module-local count."""
    _stat(kernel)[kind] += 1
    try:
        _counters()[_KINDS.index(kind)].inc(kernel=kernel)
    except Exception:  # graftlint: disable=GL003 telemetry must not break kernel dispatch; module-local count above still records the event
        pass


def _record(kernel, tile, source):
    # consults counts every trace that reached this kernel, so a caller can
    # tell which kernels a given stretch of work (re)traced — chip_smoke.py
    # uses the delta per phase to prove no phase fell back to a composite
    consults = _chosen.get(kernel, {}).get("consults", 0) + 1
    _chosen[kernel] = {"bq": int(tile[0]), "bk": int(tile[1]),
                       "source": source, "consults": consults}


def chosen_tiles() -> dict:
    """{kernel: {bq, bk, source, consults, hits, misses, fallbacks}} for
    every Pallas kernel that consulted the tuner this process (the hit/miss
    counts only exist once tuning is enabled). `source`: "tuned" (cache
    winner), "measured" (swept this call), "fixed" (single legal candidate,
    nothing tunable at launch), "default" (tuning disabled or trace-time
    miss). The StepTimeline attaches this snapshot to each step record."""
    out = {}
    for kernel, tile in list(_chosen.items()):
        rec = dict(tile)
        rec.update(_stats.get(kernel, {}))
        out[kernel] = rec
    return out


def pick_block_sizes(kernel_name, sq, skv, default, run_with, reps=3,
                     allow_measure=True, signature=(), candidates=None):
    """Return the best (bq, bk) for this signature.

    `run_with(bq, bk)` must execute one full kernel invocation (compiling on
    first use) and block on the result; it is measured `reps` times per
    candidate. Key: (kernel, device kind, jaxlib version, sq, skv,
    *signature) — pass every workload dimension the timing depends on
    (batch, heads, head_dim, dtype, causal) in `signature` so a winner tuned
    for one model is never reused for a different-shaped workload. With
    allow_measure=False (inputs are tracers — measurement impossible inside
    a jit trace) only the cache is consulted; the miss is counted as a
    fallback and warned once per key. `candidates` overrides the built-in
    attention-shaped (bq, bk) grid for kernels with a different tunable
    (e.g. the fused-norm row block, where bk is pinned to the feature
    width)."""
    if not autotune_enabled():
        _record(kernel_name, default, "default")
        return default
    if candidates is not None and len(candidates) == 1:
        # nothing tunable at launch (e.g. the paged-decode tile IS the
        # pool's physical page size): record for telemetry, but never run a
        # foregone one-candidate sweep or count a fallback
        tile = tuple(candidates[0])
        _record(kernel_name, tile, "fixed")
        return tile
    sig = "|".join(str(s) for s in signature)
    key = (f"{kernel_name}|{_device_kind()}|{_jaxlib_version()}|{sq}|{skv}|"
           f"{sig}")
    with _lock:
        _load_disk()
        hit = _memory.get(key)
    if hit is not None:
        _bump("hits", kernel_name)
        _record(kernel_name, tuple(hit), "tuned")
        return tuple(hit)
    if not allow_measure:
        _bump("fallbacks", kernel_name)
        if key not in _warned:
            _warned.add(key)
            warnings.warn(
                f"PADDLE_TPU_AUTOTUNE=1 but no tuned tiles for {key!r} and "
                f"measurement is impossible under trace; running default "
                f"{default}. Prime the cache by calling the kernel's "
                f"ops.pallas entry point (flash_attention_fwd, rms_norm_fwd, "
                f"apply_fused_rope, ...) with CONCRETE arrays of this shape "
                f"first — the model-level functional dispatch always traces, "
                f"so it can only ever read the cache, never fill it "
                f"(ops/pallas/README.md, 'Autotuning').",
                RuntimeWarning, stacklevel=3)
        _record(kernel_name, default, "default")
        return default

    _bump("misses", kernel_name)
    cands = candidates if candidates is not None else _candidates(
        sq, skv, default)
    best, best_t = default, float("inf")
    for bq, bk in cands:
        try:
            run_with(bq, bk)  # compile + warm up
            t0 = time.perf_counter()
            for _ in range(reps):
                run_with(bq, bk)
            dt = (time.perf_counter() - t0) / reps
        except Exception:
            continue  # illegal tiling / OOM candidate: skip
        if dt < best_t:
            best, best_t = (bq, bk), dt
    with _lock:
        _memory[key] = list(best)
        _store_disk()
    _record(kernel_name, best, "measured")
    return best
