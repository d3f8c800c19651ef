"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

One process drives the system's main path once, through the entry points a
user calls, at the full width of gpt3_1p3b (h=2048, 16 heads, d_head 128),
with random weights made from a seed:

- train: GPTForCausalLM + GPTPretrainingCriterion + amp.decorate(O2, bf16) +
  AdamW(bf16 moments) + per-layer recompute + DistributedTrainStep on a
  one-device mesh, batch 4x2048, 24 layers — the recipe of the benchmark's
  `train-xl-2k` cell. A few steps on one fixed batch: every loss finite, the
  last below the first.
- serve: the same model class in bf16 behind PagedServingEngine (page size
  32, a pool sized from free HBM), prompts in two prefill buckets, one pair
  sharing a prefix, greedy and temperature=0.7, drained with eng.step().
  Every request returns exactly its max_new_tokens ids in [0, vocab), and a
  greedy request's tokens are checked against a teacher-forced forward of
  the same model through the training path (flash kernel, no cache).
- serve_q8: once more with kv_quant=True (int8 pages, dequant-fused decode).
- four_chip (runs when there are >= 4 devices, says so when skipped): the
  train recipe at the same width (depth cut to 8) on
  build_mesh(sharding=2, mp=2), sharding_stage=2 — every device must hold a
  shard — then __graft_entry__.dryrun_multichip(4)'s parity factorizations
  (mp2 x pp2 1F1B, dp2 x sep2 ring attention, ZeRO 2 and 3) on the chips.

Each phase must (re)trace the Pallas kernels it is expected to run
(ops/pallas/autotune.chosen_tiles), so a quiet composite fallback cannot
pass. It fails if any phase failed, prints no result and exits non-zero when
the platform is not a TPU, never sets jax_platforms or
PADDLE_TPU_PALLAS_INTERPRET, and refuses to start where that variable is 1.
Standard output is two lines, each one JSON object. First the report:
{"report": "chip_smoke", versions, per-phase set-up (compile) and steady
seconds, losses, tokens, live kernels, compile-cache state} — the seconds
are set-up observations, not benchmark results. Then, last, the verdict the
driver reads, with exactly these keys and the device as JAX reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

("ok" is false, and the exit code 1, when a phase failed on the chip.)

`--rehearse-cpu` is the tiny-size rehearsal of the same code on the CPU
(on-chip-measurement guide, section 1) and prints "platform": "cpu". It is never
chosen automatically, and the caller provides its environment:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    PADDLE_TPU_PALLAS_INTERPRET=1 python chip_smoke.py --rehearse-cpu
"""

import argparse
import gc
import json
import os
import sys
import time
import traceback

# what each phase must have traced (ops/pallas/autotune kernel names)
EXPECTED_KERNELS = {
    "train": {"flash_fwd", "fused_layer_norm"},
    "serve": {"decode_paged", "fused_layer_norm", "flash_fwd"},
    "serve_q8": {"decode_paged_q8", "fused_layer_norm"},
    "four_chip": {"flash_fwd", "fused_layer_norm"},
}

SIZES = {
    # gpt3_1p3b width; the shape of the benchmark's train-xl-2k cell
    "chip": dict(batch=4, seq=2048, steps=4, four_chip_layers=8,
                 max_batch=8, max_seq_len=2048, page_size=32, max_new=16,
                 warm_new=2, short=(300, 300, 450), shared=256,
                 long=(1500, 1200), hbm_reserve=3 << 30),
    "rehearsal": dict(batch=4, seq=64, steps=3, four_chip_layers=2,
                      max_batch=4, max_seq_len=128, page_size=8, max_new=4,
                      warm_new=2, short=(20, 20, 30), shared=16,
                      long=(100, 80), hbm_reserve=0),
}


def _log(msg):
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _model_cfg(rehearse, num_layers=None):
    from paddle_tpu.models import GPTConfig, gpt3_1p3b

    if rehearse:
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, max_position_embeddings=128)
    else:
        cfg = gpt3_1p3b(max_position_embeddings=2048)
    if num_layers is not None:
        cfg.num_layers = num_layers
    return cfg


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #


def _train_steps(cfg, sz, mesh, **step_kw):
    """The gpt3_1p3b rung's recipe (bench._decoder_step(low_mem=True)) for a
    few steps on one fixed batch. Returns (result dict, step)."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion

    t0 = time.perf_counter()
    paddle.seed(0)
    cfg.use_recompute = True
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    amp.decorate(model, level="O2", dtype="bfloat16")
    optimizer = opt.AdamW(learning_rate=1e-4, moment_dtype="bfloat16",
                          parameters=model.parameters())
    step = dist.DistributedTrainStep(
        model, lambda lg, lb: crit(lg, lb), optimizer, mesh=mesh,
        amp_level="O2", amp_dtype="bfloat16", **step_kw)
    rng = np.random.default_rng(0)
    shape = (sz["batch"], sz["seq"])
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, shape))
    labels = paddle.to_tensor(rng.integers(0, cfg.vocab_size, shape))
    losses = [float(step(ids, labels))]  # float() waits for the device
    setup_s = time.perf_counter() - t0
    _log(f"  first step (build + compile) {setup_s:.1f}s loss={losses[0]:.4f}")
    t1 = time.perf_counter()
    for _ in range(sz["steps"] - 1):
        losses.append(float(step(ids, labels)))
    steady_s = time.perf_counter() - t1
    if not all(np.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall on a fixed batch: {losses}")
    return {"setup_s": round(setup_s, 2), "steady_s": round(steady_s, 2),
            "steady_steps": sz["steps"] - 1,
            "losses": [round(v, 4) for v in losses]}, step


def phase_train(rehearse, sz):
    import jax

    import paddle_tpu.distributed as dist

    mesh = dist.build_mesh(devices=jax.devices()[:1])
    res, _step = _train_steps(_model_cfg(rehearse), sz, mesh)
    return res


def _prompts(sz, vocab):
    """[(prompt, temperature)]: short-bucket and long-bucket prompts, the
    first two sharing a prefix, greedy and sampled in both buckets."""
    import numpy as np

    rng = np.random.default_rng(1)

    def fresh(n):
        return rng.integers(1, vocab, n).astype(np.int32)

    shared = fresh(sz["shared"])
    s0, s1, s2 = sz["short"]
    l0, l1 = sz["long"]
    return [
        (np.concatenate([shared, fresh(s0 - len(shared))]), 0.0),
        (np.concatenate([shared, fresh(s1 - len(shared))]), 0.7),
        (fresh(s2), 0.0),
        (fresh(l0), 0.0),
        (fresh(l1), 0.7),
    ]


def _teacher_forced_gap(model, prompt, generated):
    """Largest (max logit - logit of the token the engine chose) over the
    generated positions, from ONE full forward of prompt+generated through
    the no-cache path (flash kernel). Near zero when the serving path —
    bucketed prefill, paged KV writes, the paged decode kernel — computed
    the same function; a wrong cache or kernel picks tokens whose logit
    sits several sigma below the row maximum."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.jit import functional_call

    params = {k: p._value for k, p in model.named_parameters()}
    buffers = {k: b._value for k, b in model.named_buffers()}
    ids = np.concatenate([prompt, np.asarray(generated[:-1], np.int32)])
    n0, chosen = len(prompt), jnp.asarray(generated, jnp.int32)

    @jax.jit
    def gap(p, b, tok):
        logits, _ = functional_call(model, p, b, [Tensor(tok)], train=False)
        rows = logits[0, n0 - 1:].astype(jnp.float32)
        picked = jnp.take_along_axis(rows, chosen[:, None], axis=-1)[:, 0]
        return jnp.max(rows.max(axis=-1) - picked), rows.std()

    worst, spread = gap(params, buffers, jnp.asarray(ids[None]))
    return float(worst), float(spread)


def phase_serve(rehearse, sz, kv_quant=False):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    import paddle_tpu.distributed as dist
    from paddle_tpu.inference.paged import PagedServingEngine
    from paddle_tpu.models import GPTForCausalLM

    dist.env.set_global_mesh(None)
    t0 = time.perf_counter()
    cfg = _model_cfg(rehearse)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    amp.decorate(model, level="O2", dtype="bfloat16")
    if rehearse:
        budget = 4 << 20
    else:
        stats = paddle.device.memory_stats()
        budget = (stats["bytes_limit"] - stats["bytes_in_use"]
                  - sz["hbm_reserve"])
    eng = PagedServingEngine(
        model, max_batch_size=sz["max_batch"], max_seq_len=sz["max_seq_len"],
        page_size=sz["page_size"], kv_budget_bytes=budget, kv_quant=kv_quant)
    want = jnp.int8 if kv_quant else jnp.bfloat16
    if eng.pool.kv[0][0].dtype != want:
        raise AssertionError(
            f"kv pages are {eng.pool.kv[0][0].dtype}, expected {want}")
    _log(f"  pool: {eng.pool.pages_total} pages x {sz['page_size']} tokens "
         f"({budget / 2**30:.2f} GiB, {eng.pool.bytes_per_token:.0f} B/token)")

    # set-up: one request per prefill bucket, one of them sampled, compiles
    # both prefill programs, the decode program and the sampling ops
    work = _prompts(sz, cfg.vocab_size)
    for prompt, temp in (work[1], work[3]):
        eng.add_request(prompt, max_new_tokens=sz["warm_new"],
                        temperature=temp)
    while eng.has_work():
        eng.step()
    eng.finished.clear()
    setup_s = time.perf_counter() - t0
    _log(f"  set-up (build + compile) {setup_s:.1f}s")

    t1 = time.perf_counter()
    asked = {}
    for prompt, temp in work:
        rid = eng.add_request(prompt, max_new_tokens=sz["max_new"],
                              temperature=temp)
        asked[rid] = (prompt, temp)
    ticks = 0
    while eng.has_work():
        eng.step()  # ends in a host read of the tick's tokens
        ticks += 1
    steady_s = time.perf_counter() - t1

    done = {r.req_id: r for r in eng.finished}
    if sorted(done) != sorted(asked):
        raise AssertionError(f"asked {sorted(asked)}, finished {sorted(done)}")
    tokens = 0
    for rid, req in done.items():
        ids = req.generated
        if len(ids) != sz["max_new"] or req.truncated:
            raise AssertionError(
                f"request {rid}: {len(ids)} tokens (truncated="
                f"{req.truncated}), asked {sz['max_new']}")
        if not all(0 <= t < cfg.vocab_size for t in ids):
            raise AssertionError(f"request {rid}: token out of range: {ids}")
        tokens += len(ids)

    first = min(asked)  # the first greedy short prompt
    worst, spread = _teacher_forced_gap(model, asked[first][0],
                                        done[first].generated)
    # bf16 (and int8-KV) noise against a logit spread of `spread` per row
    tol = 0.5 * spread
    _log(f"  teacher-forced gap {worst:.4f} (row std {spread:.3f}, "
         f"tol {tol:.3f})")
    if not worst <= tol:
        raise AssertionError(
            f"served tokens disagree with the teacher-forced forward: "
            f"gap {worst} > {tol}")
    return {"setup_s": round(setup_s, 2), "steady_s": round(steady_s, 2),
            "steady_ticks": ticks, "requests": len(done), "tokens": tokens,
            "pages_total": eng.pool.pages_total,
            "kv_dtype": str(eng.pool.kv[0][0].dtype),
            "teacher_forced_gap": round(worst, 4),
            "logit_row_std": round(spread, 4)}


def phase_four_chip(rehearse, sz):
    import jax

    import paddle_tpu.distributed as dist

    devs = jax.devices()
    if len(devs) < 4:
        _log(f"  skipped: {len(devs)} device(s)")
        return {"skipped": f"{len(devs)} device(s), needs 4"}

    mesh = dist.build_mesh(sharding=2, mp=2, devices=devs[:4])
    cfg = _model_cfg(rehearse, num_layers=sz["four_chip_layers"])
    res, step = _train_steps(cfg, sz, mesh, sharding_stage=2)
    # code that has only met one device tends to put everything on the first
    spans = {len(v.sharding.device_set) for v in step.params.values()}
    if spans != {4}:
        raise AssertionError(f"parameters do not span 4 devices: {spans}")
    split = sum(not v.sharding.is_fully_replicated
                for v in step.params.values())
    if not split:
        raise AssertionError("no parameter is partitioned on sharding x mp")
    res["params_partitioned"] = f"{split}/{len(step.params)}"
    if not rehearse:  # the CPU backend has no per-device allocator stats
        in_use = [d.memory_stats()["bytes_in_use"] for d in devs[:4]]
        res["bytes_in_use"] = in_use
        if min(in_use) < 0.25 * max(in_use):
            raise AssertionError(f"a device holds almost nothing: {in_use}")
    del step
    dist.env.set_global_mesh(None)
    gc.collect()

    # the CPU dryrun's parity gate, on the chips: the first time
    # parallel/pipeline.py and parallel/ring.py issue ppermute over ICI
    from __graft_entry__ import dryrun_multichip

    t0 = time.perf_counter()
    dryrun_multichip(4)
    res["parity_s"] = round(time.perf_counter() - t0, 2)
    res["parity"] = "mp2xpp2_1f1b dp2xsep2_ring zero2 zero3 rtol=2e-3"
    return res


PHASES = {
    "train": phase_train,
    "serve": phase_serve,
    "serve_q8": lambda rehearse, sz: phase_serve(rehearse, sz, kv_quant=True),
    "four_chip": phase_four_chip,
}


def verdict_line(ok, devs):
    """The last line of stdout, which the driver parses: exactly the keys
    "ok" and "device" {"platform", "kind", "count"}, the device as JAX
    reports it. Everything else belongs in the report line before it."""
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs)},
    })


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU platform; never automatic")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help=f"comma-separated subset of {list(PHASES)}")
    args = ap.parse_args()
    phases = [p for p in args.phases.split(",") if p]
    if not phases or set(phases) - set(PHASES):
        ap.error(f"--phases takes a subset of {list(PHASES)}")
    rehearse = args.rehearse_cpu
    if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1" and not rehearse:
        sys.exit("chip_smoke: PADDLE_TPU_PALLAS_INTERPRET=1 is set — "
                 "interpreted kernels prove nothing about the chip; unset it")

    import jax

    devs = jax.devices()
    platform = devs[0].platform
    wanted = "cpu" if rehearse else "tpu"
    if platform != wanted:
        sys.exit(f"chip_smoke: platform is {platform!r} "
                 f"({devs[0].device_kind}, {len(devs)} device(s)), not "
                 f"{wanted!r}" + ("" if rehearse else
                                  " — this script proves the chip path and "
                                  "has no fallback (the CPU rehearsal is "
                                  "--rehearse-cpu, by name)"))

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jaxlib

    import paddle_tpu as paddle
    from paddle_tpu.framework.compile_cache import place_compile_cache
    from paddle_tpu.ops.pallas import autotune

    cache_dir = place_compile_cache()
    cache_empty = not (os.path.isdir(cache_dir) and os.listdir(cache_dir))
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = None
    _log(f"{platform} {devs[0].device_kind} x{len(devs)}; jax "
         f"{jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu}; "
         f"compile cache {cache_dir} ({'empty' if cache_empty else 'warm'})")

    sz = SIZES["rehearsal" if rehearse else "chip"]
    results, failed = {}, []
    t_all = time.perf_counter()
    for name in phases:
        _log(f"phase {name}")
        before = {k: v["consults"] for k, v in autotune.chosen_tiles().items()}
        t0 = time.perf_counter()
        try:
            res = PHASES[name](rehearse, sz)
            tiles = autotune.chosen_tiles()
            live = sorted(k for k, v in tiles.items()
                          if v["consults"] > before.get(k, 0))
            res["kernels_live"] = live
            missing = EXPECTED_KERNELS[name] - set(live)
            if missing and "skipped" not in res:
                raise AssertionError(
                    f"expected Pallas kernels never traced: {sorted(missing)} "
                    f"(live: {live}) — a composite fallback ran instead")
            res["wall_s"] = round(time.perf_counter() - t0, 2)
            results[name] = res
            _log(f"phase {name} ok: {json.dumps(res)}")
        except Exception:
            # format now: a kept exception would pin the phase's arrays
            failed.append(name)
            _log(f"phase {name} FAILED\n{traceback.format_exc()}")
        # traces pin their closures (the phase's model and state) in jax's
        # global caches; the next phase needs that HBM back
        jax.clear_caches()
        gc.collect()
        if not rehearse:
            _log(f"  bytes_in_use after {name}: "
                 f"{paddle.device.memory_stats()['bytes_in_use'] / 2**30:.2f}"
                 " GiB")
    tiles = autotune.chosen_tiles()
    print(json.dumps({
        "report": "chip_smoke",
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu,
                     "python": sys.version.split()[0]},
        "rehearsal": rehearse,
        "compile_cache": {"dir": cache_dir, "empty_at_start": cache_empty,
                          "placed_by_env": bool(
                              os.environ.get("JAX_COMPILATION_CACHE_DIR"))},
        "total_s": round(time.perf_counter() - t_all, 2),
        "phases": results,
        "failed": failed,
        "tiles": {k: [v["bq"], v["bk"]] for k, v in sorted(tiles.items())},
    }), flush=True)
    print(verdict_line(not failed, devs), flush=True)
    if failed:
        sys.exit(f"chip_smoke: phases failed: {failed} "
                 f"(passed: {sorted(results)})")


if __name__ == "__main__":
    main()
