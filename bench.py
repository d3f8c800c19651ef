"""Benchmarks against the BASELINE.md matrix.

Default: the headline GPT-3 1.3B decoder train step — prints ONE JSON line
{"metric", "value", "unit", "vs_baseline", "platform", "device_kind",
"device_count", ...} (MFU; north star >=45% so vs_baseline = mfu / 0.45).

BENCH_CONFIG=<rung> runs a single named rung: it runs or the process fails —
no rung stands in for another. BENCH_MATRIX=1 runs the BASELINE.md matrix
(gpt3 headline + llama flashmask + bert-base + resnet50 + SD-scale unet +
moe + serving), one JSON line per rung, headline line LAST; a rung that
fails prints `<rung>_failed`, the remaining rungs still run, and the
process exits non-zero.

Every rung measures the device, so every rung needs a TPU: on any other
platform the run fails before building anything. The one exception is
BENCH_CONFIG=cpu_smoke, asked for by name — a tiny-shape check that the
harness itself runs, which prints no time, rate or utilisation. Every
printed line names the platform, device kind and device count it ran on.
Peaks come from the planner's chip table; a device kind it does not know
is an error, never another chip's peak.

`--emit-metrics[=path]` (default path: $BENCH_METRICS_PATH or
bench_metrics.jsonl) installs an observability StepTimeline over the timed
loops, appending one JSON step record per timed step — host-sync counts,
dispatch-cache hit/miss/bypass deltas, comm_task intervals — so a round can
be read next to the per-step telemetry that produced it, not just the
wall-time headline.

Rungs: gpt3_1p3b gpt3_350m gpt3_125m llama_7bshape bert_base resnet50
unet_sd gpt3_moe serving serving_quant cpu_smoke. `serving` drives the
paged-KV engine (docs/SERVING.md) and reports tokens/sec at the p99 token
latency it measured, plus TTFT percentiles; with --emit-metrics the serving
SLO registry series is appended to the JSONL once per scheduler tick.
`serving_quant` A/Bs the int8-KV + weight-only-int8 fast path against the
full-precision engine at an equal KV HBM byte budget (tokens/s, p99, peak
concurrency, kv bytes/token per leg).

`--plan` prints the mesh planner's analytic top-K shortlist + cost
breakdown for the selected rung config (docs/PLANNER.md) without timing
anything — BENCH_PLAN_DEVICES sizes the grid, BENCH_PLAN_CHIP names the chip
kind to plan for when it is not the live device (e.g. "TPU v5 lite" from a
CPU host), and PADDLE_TPU_PLAN_OVERLAP_JSONL feeds measured overlap history
into the hybrid cost model.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import numpy as np

def _device_info():
    """What every printed line says about where it ran."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _require_tpu():
    """A measurement that finds no TPU fails; it never degrades to the CPU."""
    info = _device_info()
    if info["platform"] != "tpu":
        raise RuntimeError(
            f"bench.py measures the device and found platform="
            f"{info['platform']!r} ({info['device_kind']}, "
            f"{info['device_count']} device(s)), not a TPU. The harness "
            f"check off the chip is BENCH_CONFIG=cpu_smoke, by name.")


def _peak_flops(device):
    """Chip kind -> peak bf16 FLOP/s, resolved through the mesh planner's
    chip spec table (paddle_tpu/distributed/planner/cost_model.py) so the
    bench MFU denominator and the planner's compute term can never disagree
    about what a chip can do. An unknown kind raises there."""
    from paddle_tpu.distributed.planner.cost_model import chip_specs

    peak, _hbm, _ici, kind = chip_specs(device)
    return peak, kind


def _timed_steps(step_fn, steps, trace_dir=None, warmup=3, rung=None):
    """Warmed-up timed loop; returns (seconds/step, timeline_info).
    step_fn() must return a device value whose float() forces completion.
    timeline_info carries the overlap aggregate over the timed steps when
    --emit-metrics installed a StepTimeline ({} otherwise).

    warmup: executions AFTER compile before the clock starts — the first few
    runs of a fresh executable pay settling costs (measured round 5: ~2x on
    the first timed batch), which inflated the 125M rung from 192 to 272
    ms/step when only one warmup call ran."""
    # warmup BEFORE the profiler starts so the trace holds only timed steps
    last = None
    for _ in range(warmup):
        last = step_fn()
    if last is not None:
        _ = float(last)
    prof = None
    if trace_dir:
        import paddle_tpu.profiler as profiler

        prof = profiler.Profiler(
            device_trace_dir=trace_dir,
            on_trace_ready=profiler.export_chrome_tracing(trace_dir))
        prof.start()
    from paddle_tpu.observability import spans as _obs_spans

    tl = _obs_spans.active_timeline()  # installed by --emit-metrics
    timed_records = []
    t0 = time.perf_counter()
    last = None
    for i in range(steps):
        if tl is not None:
            tl.step_begin(i)
        last = step_fn()
        if tl is not None:
            # rung tag: a BENCH_MATRIX run interleaves several rungs'
            # step sequences in one JSONL — untagged records with repeating
            # step indices would be unattributable
            timed_records.append(
                tl.step_end(extra={"rung": rung} if rung else None))
        if prof is not None:
            prof.step()
    _ = float(last)
    dt = (time.perf_counter() - t0) / steps
    if prof is not None:
        prof.stop()
    info = {}
    if timed_records:
        agg = _obs_spans.aggregate_overlap(
            r.get("overlap") or {} for r in timed_records if r)
        n = max(len(timed_records), 1)
        info = {
            "overlap_fraction": round(agg["fraction"], 4),
            "comm_exposed_s_per_step": round(agg["exposed_s"] / n, 6),
        }
        info.update(_kernel_ladder_info())
    return dt, info


def _kernel_ladder_info():
    """Pallas-kernel attribution for the perf line (under --emit-metrics):
    which fused kernels were live (toggle x backend) and the autotuned tile
    + hit/miss/fallback counts per kernel — so a BENCH round's MFU movement
    can be attributed to tile choices, not guessed at."""
    from paddle_tpu.nn.functional.flash_attention import _use_pallas_kernel
    from paddle_tpu.ops.pallas import autotune as _autotune
    from paddle_tpu.ops.pallas.fused_norm import fused_norm_on
    from paddle_tpu.ops.pallas.fused_rope import fused_rope_on

    pallas = _use_pallas_kernel()
    return {
        "fused_norm": bool(pallas and fused_norm_on()),
        "fused_rope": bool(pallas and fused_rope_on()),
        "autotuned_tiles": _autotune.chosen_tiles(),
    }


def _emit(name, dt, flops, tokens=None, extra=None):
    peak, kind = _peak_flops(jax.devices()[0])
    mfu = flops / dt / peak
    line = {
        "metric": f"mfu_{name}_{kind.replace(' ', '_')}",
        "value": round(mfu, 4),
        "unit": "mfu_fraction",
        "vs_baseline": round(mfu / 0.45, 4),
        "step_time_s": round(dt, 4),
    }
    if tokens is not None:
        line["tokens_per_sec_per_chip"] = round(tokens / dt, 1)
    line.update(_device_info())
    if extra:
        line.update(extra)
    print(json.dumps(line), flush=True)
    return line


# --------------------------------------------------------------------------- #
# rungs
# --------------------------------------------------------------------------- #


def _cpu_smoke_cfg():
    """The harness-check model shape, shared by the cpu_smoke rung and
    `--plan` so the planned config is always the config that rung runs."""
    from paddle_tpu.models import GPTConfig

    return GPTConfig(hidden_size=256, num_layers=4, num_heads=4,
                     vocab_size=8192, max_position_embeddings=512)


def _decoder_flops(cfg, batch, seq):
    """6ND fwd+bwd + attention quadratic term (12*L*h*T^2 per token batch)."""
    n_params = (cfg.num_params(include_embeddings=False)
                + cfg.vocab_size * cfg.hidden_size)
    tokens = batch * seq
    return (6.0 * n_params * tokens
            + 12.0 * cfg.num_layers * cfg.hidden_size * seq * tokens)


def _free_rung(*objs):
    """Release a failed/finished rung's device buffers before the next one
    allocates (round-4 lesson: the 1.3B OOM left 15GB of params+states live
    while the 350M fallback tried to allocate)."""
    import gc

    for o in objs:
        try:
            if hasattr(o, "params"):  # TrainStep: drop device state dicts
                o.params = {}
                o.opt_states = {}
                o.buffers = {}
                # the same buffers stay live through model Parameters
                # (_ModuleState) — null those refs too or nothing is freed
                o.model = None
                o._state = None
                o._compiled = None
                # optimizer._parameter_list also pins the Parameters
                if getattr(o, "optimizer", None) is not None:
                    o.optimizer._parameter_list = None
                    o.optimizer = None
        except Exception:
            pass
    del objs
    gc.collect()
    try:
        jax.clear_caches()
    except Exception:
        pass


def _decoder_step(cfg, batch, seq, bf16_amp, low_mem=False, **step_kw):
    """Shared scaffold: seeded model + criterion + AdamW + single-device mesh
    + DistributedTrainStep + random token batch. Returns (step, ids, labels).

    low_mem (the 1.3B-on-one-16GB-chip recipe): bf16 params via amp.decorate
    + bf16 AdamW moments (f32 update compute) + per-layer recompute. Steady
    HBM for 1.3B drops 15.6GB -> ~7.8GB; the f32-master recipe needs >1 chip
    (that path is exercised by the sharded dryrun/tests instead)."""
    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion

    paddle.seed(0)
    if low_mem:
        cfg.use_recompute = True
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion(cfg)
    if low_mem:
        amp.decorate(model, level="O2", dtype="bfloat16")
        optimizer = opt.AdamW(learning_rate=1e-4, moment_dtype="bfloat16",
                              parameters=model.parameters())
    else:
        optimizer = opt.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
    mesh = dist.build_mesh(devices=jax.devices()[:1])
    # bf16 compute with f32 master weights — the production TPU recipe
    step = dist.DistributedTrainStep(
        model, lambda lg, lb: crit(lg, lb), optimizer, mesh=mesh,
        amp_level="O2" if bf16_amp else None, amp_dtype="bfloat16",
        **step_kw)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)))
    labels = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)))
    return step, ids, labels


_GPT_RUNGS = {
    # models/gpt.py preset (or the harness check): (batch, seq, timed steps)
    "gpt3_1p3b": (4, 2048, 10),
    "gpt3_350m": (8, 2048, 10),
    "gpt3_125m": (8, 2048, 10),
    "cpu_smoke": (2, 256, 3),
}


def run_gpt_rung(cfg_name, trace_dir=None):
    """The named GPT rung — it runs, or the exception leaves the process
    non-zero. (An earlier ladder fell from 1.3B to 350M to 125M on any
    exception and only added a `note`; a number for a model nobody asked
    for is worse than a failure.)"""
    import paddle_tpu.models as models

    batch, seq, steps = _GPT_RUNGS[cfg_name]
    smoke = cfg_name == "cpu_smoke"
    cfg = (_cpu_smoke_cfg() if smoke else
           getattr(models, cfg_name)(max_position_embeddings=seq))
    low_mem = cfg_name == "gpt3_1p3b"
    step, ids, labels = _decoder_step(cfg, batch, seq, not smoke,
                                      low_mem=low_mem)
    first = float(step(ids, labels))  # compile + warmup
    dt, tl_info = _timed_steps(lambda: step(ids, labels), steps, trace_dir,
                               rung=cfg_name)
    if smoke:
        # the harness ran end to end; a CPU step time is not a device metric
        # and is not printed under one's name
        line = {"metric": f"harness_smoke_bs{batch}x{seq}", "value": steps,
                "unit": "timed_steps_completed",
                "first_loss_finite": bool(np.isfinite(first)),
                **_device_info(), **tl_info}
        print(json.dumps(line), flush=True)
        return line
    extra = dict(tl_info)
    if low_mem:
        extra["recipe"] = "bf16_params+bf16_moments+recompute"
    return _emit(f"{cfg_name}_bs{batch}x{seq}", dt,
                 _decoder_flops(cfg, batch, seq), batch * seq, extra)


def run_llama_rung():
    """LLaMA-7B-shape (h=4096, GQA, SwiGLU, RoPE) scaled in depth to fit one
    chip's optimizer states; flashmask Pallas attention; sharding stage-2 code
    path (degenerate on 1 chip); BASELINE.md row 'LLaMA-7B/13B sharding +
    flash_attn'."""
    from paddle_tpu.models.llama import LlamaConfig

    # 7B's matmul shapes (h=4096, f=11008, heads 32/kv 8) at depth 3:
    # ~0.9B params => ~12.5GB AdamW f32 states on one v5e
    cfg = LlamaConfig(hidden_size=4096, num_layers=3, num_heads=32,
                      num_kv_heads=8, intermediate_size=11008,
                      max_position_embeddings=2048,
                      attn_variant="flashmask")
    batch, seq, steps = 4, 2048, 10
    step, ids, labels = _decoder_step(cfg, batch, seq, True,
                                      sharding_stage=2)
    _ = float(step(ids, labels))
    dt, tl_info = _timed_steps(lambda: step(ids, labels), steps,
                               rung="llama_7bshape")
    return _emit(f"llama_7bshape_flashmask_bs{batch}x{seq}", dt,
                 _decoder_flops(cfg, batch, seq), batch * seq,
                 extra=tl_info or None)


def run_bert_rung():
    """BERT-base MLM+NSP pretraining step (BASELINE.md 'BERT-base / ERNIE-1.0
    pretraining, fleet data-parallel' — DP collectives are a no-op on one
    chip; the dp axis is exercised in tests/dryrun)."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models.bert import (BertForPretraining,
                                        BertPretrainingCriterion, bert_base)

    cfg = bert_base()
    batch, seq, n_mask, steps = 32, 512, 80, 10
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_dropout_prob = 0.0
    paddle.seed(0)
    model = BertForPretraining(cfg)
    crit = BertPretrainingCriterion(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())
    mesh = dist.build_mesh(devices=jax.devices()[:1])
    step = dist.DistributedTrainStep(
        model, lambda mlm, nsp, ml, nl: crit(mlm, nsp, ml, nl), optimizer,
        mesh=mesh, amp_level="O2", amp_dtype="bfloat16")
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)))
    tt = paddle.to_tensor(np.zeros((batch, seq), np.int32))
    am = paddle.to_tensor(np.ones((batch, seq), np.float32))
    mpos = paddle.to_tensor(rng.integers(0, seq, (batch, n_mask)))
    mlab = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, n_mask)))
    nlab = paddle.to_tensor(rng.integers(0, 2, (batch,)))
    _ = float(step([ids, tt, am, mpos], [mlab, nlab]))
    dt, tl_info = _timed_steps(lambda: step([ids, tt, am, mpos], [mlab, nlab]),
                               steps, rung="bert_base")
    h, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    # encoder 12h^2/layer params, attention quadratic, + MLM head on n_mask
    n_enc = 12 * L * h * h
    flops = (6.0 * n_enc * batch * seq
             + 12.0 * L * h * seq * batch * seq
             + 6.0 * batch * n_mask * h * V)
    return _emit(f"bert_base_bs{batch}x{seq}", dt, flops, batch * seq,
                 extra=tl_info or None)


def run_unet_rung():
    """Stable-Diffusion-style UNet denoising step (BASELINE.md 'Stable
    Diffusion UNet: conv + cross-attn' row). SD-scale channel stack
    (320/640/1280, cross-attn context 768) at the 64x64x4 latent shape;
    throughput metric is latents/sec (MFU for a conv+attn hybrid is not
    comparable to the decoder rungs)."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu.models import UNetConfig, UNetModel

    cfg = UNetConfig(in_channels=4, out_channels=4, base_channels=320,
                     channel_mult=(1, 2, 4), num_res_blocks=2,
                     attention_levels=(1, 2), num_heads=8,
                     context_dim=768)
    batch, hw, ctx_len, steps = 8, 64, 77, 10
    paddle.seed(0)
    model = UNetModel(cfg)
    mse = nn.MSELoss()
    optimizer = opt.AdamW(learning_rate=1e-4, moment_dtype="bfloat16",
                          parameters=model.parameters())
    mesh = dist.build_mesh(devices=jax.devices()[:1])
    step = dist.DistributedTrainStep(
        model, lambda pred, target: mse(pred, target), optimizer, mesh=mesh,
        amp_level="O2", amp_dtype="bfloat16")
    rng = np.random.default_rng(0)
    noisy = paddle.to_tensor(
        rng.normal(size=(batch, cfg.in_channels, hw, hw)).astype(np.float32))
    t = paddle.to_tensor(rng.integers(0, 1000, (batch,)))
    ctx = paddle.to_tensor(
        rng.normal(size=(batch, ctx_len, cfg.context_dim)).astype(np.float32))
    noise = paddle.to_tensor(
        rng.normal(size=(batch, cfg.out_channels, hw, hw)).astype(np.float32))
    _ = float(step([noisy, t, ctx], noise))
    dt, tl_info = _timed_steps(lambda: step([noisy, t, ctx], noise), steps,
                               rung="unet_sd")
    info = _device_info()
    line = {
        "metric": f"unet_sd_bs{batch}x{hw}_"
                  f"{info['device_kind'].replace(' ', '_')}",
        "value": round(batch / dt, 2),
        "unit": "latents_per_sec",
        "vs_baseline": 0.0,  # reference publishes no UNet number
        "step_time_s": round(dt, 4),
        **info, **tl_info,
    }
    print(json.dumps(line), flush=True)
    return line


def run_resnet_rung():
    """ResNet-50 ImageNet train step (BASELINE.md first-slice row)."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as opt
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    model, batch, hw, steps, fwd_flops = resnet50(), 128, 224, 10, 4.1e9
    paddle.seed(0)
    optimizer = opt.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters())
    mesh = dist.build_mesh(devices=jax.devices()[:1])
    step = dist.DistributedTrainStep(
        model, lambda lg, lb: F.cross_entropy(lg, lb), optimizer, mesh=mesh,
        amp_level="O2", amp_dtype="bfloat16")
    rng = np.random.default_rng(0)
    img = paddle.to_tensor(rng.normal(size=(batch, 3, hw, hw)).astype(np.float32))
    lab = paddle.to_tensor(rng.integers(0, 1000, (batch, 1)))
    _ = float(step(img, lab))
    dt, tl_info = _timed_steps(lambda: step(img, lab), steps, rung="resnet50")
    flops = 3.0 * fwd_flops * batch  # fwd + ~2x bwd
    return _emit(f"resnet50_bs{batch}", dt, flops,
                 extra={"images_per_sec": round(batch / dt, 1), **tl_info})


def run_moe_rung(metrics_path=None):
    """Expert-parallel MoE train step (BASELINE.md 'gpt3_moe' row;
    ISSUE-14): decoder embedding + L pre-norm MoE-FFN residual blocks
    (8 experts, GShard top-2) + tied-size LM head — attention-free, so the
    measured fast-vs-einsum delta is the MoE dispatch/GEMM path itself,
    not attention noise. Experts shard over the `ep` mesh axis (as many
    devices as divide the expert count); the batch shards over ep too, so
    the dispatch/combine reshards are REAL all-to-all traffic.

    A/B knobs (the recorded bench delta, not a claim): PADDLE_TPU_MOE_FAST
    =0 runs the dense einsum oracle, PADDLE_TPU_MOE_A2A_CHUNKS sets the
    a2a chunk schedule. The perf line carries fast=/a2a_chunks=/ep= and,
    over the timed loop, the collective_bytes_total{op="all_to_all"} delta
    (all_to_all_bytes=) next to overlap_fraction under --emit-metrics."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu.incubate.distributed.models.moe import (ExpertFFN,
                                                            MoELayer,
                                                            moe_a2a_chunks,
                                                            moe_fast_on)
    from paddle_tpu.observability.metrics import default_registry

    E, topk = 8, 2
    M, H, L, V = 1024, 4096, 4, 32000
    batch, seq, steps = 8, 1024, 10
    ndev = len(jax.devices())
    ep = next((c for c in (8, 4, 2) if E % c == 0 and ndev >= c
               and batch % c == 0), 1)
    ep_axis = "ep" if ep > 1 else None
    paddle.seed(0)
    mesh = dist.build_mesh(ep=ep, devices=jax.devices()[:ep])

    class MoEDecoder(nn.Layer):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(V, M)
            self.norms = nn.LayerList([nn.LayerNorm(M) for _ in range(L)])
            self.moes = nn.LayerList([
                MoELayer(M, ExpertFFN(E, M, H, ep_axis=ep_axis),
                         gate={"type": "gshard", "top_k": topk},
                         ep_axis=ep_axis)
                for _ in range(L)])
            self.head = nn.Linear(M, V)

        def forward(self, ids):
            x = self.embed(ids)
            for norm, moe in zip(self.norms, self.moes):
                x = x + moe(norm(x))
            return self.head(x)

    model = MoEDecoder()
    optimizer = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = dist.DistributedTrainStep(
        model, lambda lg, lb: F.cross_entropy(
            lg.reshape([-1, V]), lb.reshape([-1, 1])), optimizer, mesh=mesh,
        batch_axes=("dp", "ep"), amp_level="O2", amp_dtype="bfloat16")
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, V, (batch, seq)))
    labels = paddle.to_tensor(rng.integers(0, V, (batch, seq)))
    for _ in range(4):  # compile + the settle warmups _timed_steps would run
        last = step(ids, labels)
    _ = float(last)
    # snapshot AFTER warmup so the a2a byte delta covers exactly the timed
    # steps the dt covers (every executed step re-emits its volume)
    reg = default_registry()
    base = reg.snapshot()
    dt, tl_info = _timed_steps(lambda: step(ids, labels), steps,
                               rung="gpt3_moe", warmup=0)
    a2a_bytes = reg.delta(base).get("collective_bytes_total{op=all_to_all}", 0)
    if metrics_path:
        # the counter registry next to the step-timeline records, like the
        # serving rung — a standalone gpt3_moe run leaves the a2a series on
        # disk, not only in the perf line
        reg.export_jsonl(metrics_path)
    tokens = batch * seq
    cap = int(np.ceil(1.2 * tokens / E))
    routed = min(topk * tokens, E * cap)
    # fwd FLOPs: expert GEMMs over ROUTED rows (the fast-path work model;
    # the einsum oracle burns strictly more) + router + LM head; *3 fwd+bwd
    fwd = (L * routed * 4.0 * M * H + L * tokens * 2.0 * M * E
           + tokens * 2.0 * M * V)
    return _emit(
        f"gpt3_moe_e{E}top{topk}_bs{batch}x{seq}", dt, 3.0 * fwd, tokens,
        extra={"fast": moe_fast_on(), "a2a_chunks": moe_a2a_chunks(),
               "ep": ep, "experts": E, "top_k": topk,
               "all_to_all_bytes": int(a2a_bytes), **tl_info})


def _serving_workload(cfg, S, n_req):
    """The serving rungs' shared request mix: every third prompt extends one
    long common prefix (exercises prefix sharing), lengths staggered, every
    fourth request sampled at T=0.7 and the rest greedy. One definition so
    `serving` and `serving_quant` numbers stay comparable — returns
    [(prompt, temperature), ...]."""
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, S // 4).astype(np.int32)
    out = []
    for i in range(n_req):
        tail = rng.integers(1, cfg.vocab_size,
                            2 + i % (S // 8)).astype(np.int32)
        prompt = (np.concatenate([shared, tail]) if i % 3 == 0
                  else rng.integers(1, cfg.vocab_size,
                                    4 + i % (S // 4)).astype(np.int32))
        out.append((prompt, 0.7 if i % 4 == 0 else 0.0))
    return out


def _drain_serving_engine(eng, reg, metrics_path=None, timeline=None,
                          rung=None):
    """Drain a serving engine, timing every scheduler tick. Ticks that paid
    a one-time XLA compile (a prefill bucket or the decode program) are
    warmup, not steady-state token latency — excluding them keeps p99/slo
    honest on cold runs; throughput still counts every token and all wall
    time. One definition shared by the `serving` and `serving_quant` rungs
    so their latency-exclusion semantics cannot drift apart. With
    `metrics_path` the registry is appended to the JSONL once per tick."""
    step_lat, tokens, tick, compile_ticks, peak_live = [], 0, 0, 0, 0
    t_start = time.perf_counter()
    while eng.has_work():
        if timeline is not None:
            timeline.step_begin(tick)
        compiles0 = eng._prefill_cache.compiles_total
        decode_cold = eng._decode_jit is None
        t0 = time.perf_counter()
        out = eng.step()
        dt = time.perf_counter() - t0
        if timeline is not None:
            timeline.step_end(extra={"rung": rung})
        peak_live = max(peak_live, eng.live_count)
        if out:
            if (eng._prefill_cache.compiles_total > compiles0
                    or decode_cold):
                compile_ticks += 1
            else:
                step_lat.append(dt)
            tokens += len(out)
        if metrics_path:
            reg.export_jsonl(metrics_path)
        tick += 1
    return {"step_lat": step_lat, "tokens": tokens,
            "compile_ticks": compile_ticks, "peak_live": peak_live,
            "total_s": time.perf_counter() - t_start}


def run_serving_rung(metrics_path=None):
    """Paged-KV serving throughput at a fixed p99 token-latency SLO
    (docs/SERVING.md; BASELINE.md 'inference' row). Drives the
    PagedServingEngine over a mixed greedy/sampled workload with shared
    prefixes, reporting tokens/sec alongside the p99 per-step token latency
    it was measured at (SLO target: SERVING_SLO_MS env, default 200) and the
    TTFT distribution. With --emit-metrics the full serving registry
    (TTFT/tokens-per-second histograms, queue-depth/pages-free gauges,
    preemption/prefix counters) is appended to the JSONL once per scheduler
    tick — a time series, not just the final line."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.paged import PagedServingEngine
    from paddle_tpu.models import GPTForCausalLM, gpt3_125m
    from paddle_tpu.observability import spans as _obs_spans
    from paddle_tpu.observability.metrics import default_registry

    paddle.seed(0)
    cfg, B, S, ps, n_req, max_new = gpt3_125m(), 16, 512, 32, 64, 32
    model = GPTForCausalLM(cfg)
    eng = PagedServingEngine(model, max_batch_size=B, max_seq_len=S,
                             page_size=ps)
    for prompt, temp in _serving_workload(cfg, S, n_req):
        eng.add_request(prompt, max_new_tokens=max_new, temperature=temp)
    reg = default_registry()
    base = reg.snapshot()
    st = _drain_serving_engine(eng, reg, metrics_path,
                               timeline=_obs_spans.active_timeline(),
                               rung="serving")
    total_s, step_lat = st["total_s"], st["step_lat"]
    compile_ticks = st["compile_ticks"]
    done = eng.finished
    delta = reg.delta(base)
    # step() returns only decode-advance tokens; each request's FIRST
    # token is emitted at admission and never appears in `out`. The
    # registry counter saw every token, so it is the honest numerator.
    tokens = delta.get("serving_tokens_total{engine=paged}",
                       st["tokens"])
    ttfts = sorted(r._t_first - r._t_arrival for r in done
                   if r._t_first is not None)
    slo_s = float(os.environ.get("SERVING_SLO_MS", "200")) / 1e3
    p99 = float(np.percentile(step_lat, 99)) if step_lat else 0.0
    info = _device_info()
    line = {
        "metric": f"serving_paged_gpt3_125m_bs{B}x{S}_"
                  f"{info['device_kind'].replace(' ', '_')}",
        "value": round(tokens / total_s, 2),
        "unit": "tokens_per_sec",
        "vs_baseline": 0.0,  # reference publishes no serving number
        "requests": len(done),
        "p99_token_latency_s": round(p99, 4),
        "slo_p99_s": slo_s,
        "slo_met": p99 <= slo_s,
        "compile_ticks_excluded": compile_ticks,
        "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 4),
        "ttft_p99_s": round(float(np.percentile(ttfts, 99)), 4),
        "preemptions": delta.get("serving_preemptions_total", 0),
        "prefix_hits": delta.get("serving_prefix_hits_total", 0),
        "truncations": delta.get("serving_truncations_total"
                                 "{engine=paged}", 0),
        "pages_total": eng.pool.pages_total,
        **info,
    }
    print(json.dumps(line), flush=True)
    return line


def run_serving_quant_rung(metrics_path=None):
    """Quantized serving A/B at EQUAL KV HBM budget (docs/SERVING.md
    "Quantized KV cache"; BASELINE.md row). Leg A: the full-precision paged
    engine. Leg B: `PADDLE_TPU_KV_QUANT=1` + `PADDLE_TPU_SERVE_W8=1` — int8
    pages with per-(page, head) scales through the dequant-fused Pallas
    decode kernel, plus weight-only int8 projections. Both legs get the
    same pool bytes; the int8 pool fits ~4x the pages, so at a page-starved
    budget the quantized leg sustains strictly more concurrent requests
    (and the line records tokens/s + p99 for both so the throughput side of
    the trade is visible too)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.paged import BlockPool, PagedServingEngine
    from paddle_tpu.models import GPTForCausalLM, gpt3_125m
    from paddle_tpu.observability.metrics import default_registry

    cfg_f, B, S, ps, n_req, max_new = gpt3_125m, 16, 512, 32, 64, 32
    pages_budget = (B * S) // (2 * ps)  # page-starved on purpose
    cfg = cfg_f()
    budget = pages_budget * BlockPool.page_nbytes(
        cfg.num_layers, cfg.kv_heads, cfg.head_dim, ps)
    workload = _serving_workload(cfg, S, n_req)

    def drive(kv_quant, w8):
        # fresh model per leg: the serve_w8 convert pass mutates in
        # place, and the A/B must compare equal starting weights
        paddle.seed(0)
        model = GPTForCausalLM(cfg_f())
        eng = PagedServingEngine(
            model, max_batch_size=B, max_seq_len=S, page_size=ps,
            kv_budget_bytes=budget, kv_quant=kv_quant, serve_w8=w8)
        for prompt, temp in workload:
            eng.add_request(prompt, max_new_tokens=max_new,
                            temperature=temp)
        reg = default_registry()
        base = reg.snapshot()
        st = _drain_serving_engine(eng, reg, metrics_path)
        delta = reg.delta(base)
        tokens = delta.get("serving_tokens_total{engine=paged}", 0)
        step_lat = st["step_lat"]
        return {
            "tokens_per_sec": round(tokens / st["total_s"], 2),
            "p99_token_latency_s": round(
                float(np.percentile(step_lat, 99)) if step_lat else 0.0,
                4),
            "peak_concurrent": st["peak_live"],
            "pages_total": eng.pool.pages_total,
            "kv_bytes_per_token": round(eng.pool.bytes_per_token, 1),
            "preemptions": delta.get("serving_preemptions_total", 0),
            "quant_pages": delta.get("serving_kv_quant_pages_total", 0),
            "compile_ticks_excluded": st["compile_ticks"],
        }

    a = drive(kv_quant=False, w8=False)
    b = drive(kv_quant=True, w8=True)
    info = _device_info()
    line = {
        "metric": f"serving_quant_ab_gpt3_125m_bs{B}x{S}_"
                  f"{info['device_kind'].replace(' ', '_')}",
        "value": b["tokens_per_sec"],
        "unit": "tokens_per_sec",
        "vs_baseline": 0.0,  # reference publishes no serving number
        "equal_kv_budget_bytes": budget,
        "requests": n_req,
        "dense": a,
        "int8_kv_w8": b,
        "concurrency_gain": (round(b["peak_concurrent"]
                                   / a["peak_concurrent"], 2)
                             if a["peak_concurrent"] else 0.0),
        **info,
    }
    print(json.dumps(line), flush=True)
    return line


def run_plan(top_k=None):
    """`--plan`: the mesh planner's analytic shortlist + cost breakdown for
    the current rung config — one JSON line per shortlisted candidate and a
    final mesh_plan_shortlist line. Pure analytic: nothing is measured. The
    predicted times are for a named chip: the live device, or
    BENCH_PLAN_CHIP (a `device_kind` such as "TPU v5 lite") when planning
    from a host that is not the target; a kind the spec table does not know
    is an error.

    Env: BENCH_PLAN_DEVICES (default: live device count), BENCH_PLAN_CHIP,
    BENCH_PLAN_TOP_K, BENCH_PLAN_GBS, BENCH_CONFIG picks the model shape
    (default gpt3_1p3b), PADDLE_TPU_PLAN_OVERLAP_JSONL feeds the measured
    overlap_fraction half of the hybrid cost model."""
    from paddle_tpu.distributed.planner import CostModel, rank_candidates
    from paddle_tpu.models import gpt3_1p3b, gpt3_125m, gpt3_350m

    cfg_name = os.environ.get("BENCH_CONFIG") or "gpt3_1p3b"
    builders = {"gpt3_1p3b": gpt3_1p3b, "gpt3_350m": gpt3_350m,
                "gpt3_125m": gpt3_125m}
    if cfg_name in builders:
        c = builders[cfg_name](max_position_embeddings=2048)
        seq = 2048
    else:
        c = _cpu_smoke_cfg()
        seq = 256
    ndev = int(os.environ.get("BENCH_PLAN_DEVICES", "0")) or len(jax.devices())
    top_k = top_k or int(os.environ.get("BENCH_PLAN_TOP_K", "5"))
    tuner_cfg = {
        "num_devices": ndev,
        "global_batch_size": int(os.environ.get("BENCH_PLAN_GBS", "0"))
        or max(8, ndev),
        "model_cfg": {"hidden_size": c.hidden_size,
                      "num_layers": c.num_layers,
                      "num_heads": c.num_heads,
                      "vocab_size": c.vocab_size,
                      "seq_length": seq},
    }
    cm = CostModel(chip=os.environ.get("BENCH_PLAN_CHIP") or jax.devices()[0])
    ranked, pruned = rank_candidates(tuner_cfg, cm)
    for rank, (cfg, bd) in enumerate(ranked[:top_k], 1):
        print(json.dumps({
            "metric": "plan_candidate", "rank": rank,
            "dp": cfg["dp_degree"], "mp": cfg["mp_degree"],
            "pp": cfg["pp_degree"], "sharding": cfg["sharding_degree"],
            "sharding_stage": cfg.get("sharding_stage", 1)
            if cfg["sharding_degree"] > 1 else 0,
            "micro_batch_size": cfg["micro_batch_size"],
            "use_recompute": cfg["use_recompute"],
            "predicted_step_time_s": bd["total_s"],
            "compute_s": bd["compute_s"], "bubble_s": bd["bubble_s"],
            "exposed_comm_s": bd["exposed_comm_s"],
            "comm_s_by_axis": bd["comm_s_by_axis"],
            "mem_estimate_gb": round(bd["mem_estimate_bytes"] / 1e9, 3),
            "n_micro": bd["n_micro"], "chip": cm.chip, **_device_info(),
        }), flush=True)
    top = ranked[0][0] if ranked else None
    line = {
        "metric": f"mesh_plan_shortlist_{cfg_name}",
        "value": len(ranked[:top_k]),
        "unit": "candidates",
        "vs_baseline": 0.0,
        "num_devices": ndev,
        "candidates_ranked": len(ranked),
        "candidates_pruned": len(pruned),
        "overlap_fraction": cm.overlap_fraction,
        "overlap_source": cm.overlap_source,
        "chip": cm.chip,
        "top": (None if top is None else
                f"dp{top['dp_degree']}xpp{top['pp_degree']}"
                f"xsharding{top['sharding_degree']}xmp{top['mp_degree']}"
                f"/mbs{top['micro_batch_size']}"),
        **_device_info(),
    }
    print(json.dumps(line), flush=True)
    return line


# every non-headline BASELINE.md rung: BENCH_CONFIG name -> fn(metrics_path)
_RUNGS = {
    "llama_7bshape": lambda mp: run_llama_rung(),
    "bert_base": lambda mp: run_bert_rung(),
    "resnet50": lambda mp: run_resnet_rung(),
    "unet_sd": lambda mp: run_unet_rung(),
    "gpt3_moe": run_moe_rung,
    "serving": run_serving_rung,
    "serving_quant": run_serving_quant_rung,
}


def _matrix_rungs(metrics_path):
    """(name, thunk) for the BENCH_MATRIX run, in table order."""
    return [(name, lambda fn=fn: fn(metrics_path))
            for name, fn in _RUNGS.items()]


def _run_matrix(rungs):
    """Run every rung; a failure is printed and remembered, the remaining
    rungs still run. Returns the names that failed — the caller exits
    non-zero on any."""
    import traceback

    import paddle_tpu.distributed as dist

    failed = []
    for rung_name, rung in rungs:
        try:
            rung()
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            failed.append(rung_name)
            print(json.dumps({"metric": f"{rung_name}_failed",
                              "error": f"{type(e).__name__}: {e}"[:300],
                              **_device_info()}),
                  flush=True)
        dist.env.set_global_mesh(None)
        _free_rung()  # gc + clear_caches between rungs
    return failed


def main(argv=None):
    """Returns the process exit code."""
    argv = sys.argv[1:] if argv is None else argv
    from paddle_tpu.framework.compile_cache import place_compile_cache

    place_compile_cache()
    # --emit-metrics[=path]: step-timeline JSONL alongside the perf line
    # (env-var style config everywhere else; this one is a flag so BENCH
    # driver scripts can toggle it without touching the environment block)
    metrics_path = None
    for a in argv:
        if a == "--emit-metrics":
            metrics_path = os.environ.get("BENCH_METRICS_PATH",
                                          "bench_metrics.jsonl")
        elif a.startswith("--emit-metrics="):
            metrics_path = a.split("=", 1)[1]
    if metrics_path:
        from paddle_tpu.observability import enable_step_timeline

        enable_step_timeline(jsonl_path=metrics_path)
        print(json.dumps({"metric": "step_timeline_jsonl",
                          "path": metrics_path}), file=sys.stderr)

    if "--plan" in argv:
        run_plan()  # analytic only: needs a named chip, not a live one
        return 0
    cfg_name = os.environ.get("BENCH_CONFIG") or "gpt3_1p3b"
    if cfg_name not in _GPT_RUNGS and cfg_name not in _RUNGS:
        raise ValueError(
            f"unknown BENCH_CONFIG={cfg_name!r}; rungs: "
            f"{sorted([*_GPT_RUNGS, *_RUNGS])}")
    if cfg_name != "cpu_smoke":
        _require_tpu()
    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    if os.environ.get("BENCH_NO_PALLAS"):
        # model-level A/B: force the XLA-composite attention instead of the
        # Pallas kernels (perf attribution on hardware). importlib, because
        # both `from ... import` AND `import ... as` resolve through the
        # package attribute, which the star-import rebound to the
        # same-named FUNCTION.
        import importlib

        _fa_mod = importlib.import_module(
            "paddle_tpu.nn.functional.flash_attention")
        _fa_mod._USE_PALLAS = False

    if os.environ.get("BENCH_MATRIX"):
        _require_tpu()  # the matrix measures, whatever the headline rung
        failed = _run_matrix(_matrix_rungs(metrics_path))
        # headline GPT line LAST (drivers read the final line)
        if cfg_name not in _GPT_RUNGS:
            cfg_name = "gpt3_1p3b"
        run_gpt_rung(cfg_name, trace_dir)
        if failed:
            print(f"bench matrix: rungs failed: {failed}", file=sys.stderr)
            return 1
        return 0

    if cfg_name in _RUNGS:
        _RUNGS[cfg_name](metrics_path)
    else:
        run_gpt_rung(cfg_name, trace_dir)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # the failure as a last JSON line, and rc != 0
        import traceback

        traceback.print_exc(file=sys.stderr)
        print(json.dumps({
            "metric": "bench_failed",
            "error": f"{type(e).__name__}: {e}"[:400],
        }))
        sys.exit(1)
