"""Analytic half of the mesh planner's hybrid cost model.

Predicts per-config step time as

    total = compute + bubble + exposed_comm

* **compute** — a roofline max(FLOPs / (ndev * peak * mfu), hbm_bytes / hbm_bw)
  over the decoder FLOPs formula (6ND + attention quadratic), with a 4/3
  recompute multiplier (recompute re-runs the forward inside the backward:
  8N vs 6N per token).
* **bubble** — the 1F1B pipeline bubble `compute * (pp-1)/n_micro`
  (arxiv 1909.09756 hand-tuned exactly this trade on TPU-v3 pods).
* **exposed_comm** — per-axis collective byte volumes over ICI, plus a
  per-collective launch latency `alpha` (the term that dominates at small
  message sizes), discounted by the MEASURED `overlap_fraction` from the
  step-timeline JSONL when such history is available — the measured half
  of the hybrid (arxiv 2011.03641: pod-scale loss is mostly exposed
  collectives, which is precisely what overlap_fraction tracks).

The table below is the program's one chip table (`chip_specs`); the
benchmark keeps its own published peaks in `benchmark/peaks.json`.

Byte-volume conventions (documented in docs/PLANNER.md):
- ring all-reduce moves `2*(g-1)/g * bytes` per participant, reduce-scatter
  and all-gather `(g-1)/g * bytes`;
- grads are counted at 4 B/elem (f32 reduction), params and activations at
  2 B/elem (bf16 compute);
- sharding stage 1 all-reduces grads over the combined dp*sharding group;
  stages 2/3 reduce-scatter grads + all-gather updated params over
  `sharding` (stage 3 adds the fwd+bwd param all-gathers) with the dp
  all-reduce on top;
- mp all-reduces move the activation block 4x per layer per microbatch
  (attn out + mlp out, forward and backward); pp p2p moves it twice per
  microbatch per stage boundary.
"""

from __future__ import annotations

import json
import os

__all__ = ["CHIP_SPECS", "chip_specs", "CostModel",
           "measured_overlap_fraction"]

# chip kind -> (peak bf16 FLOP/s, HBM bytes/s, ICI bytes/s) per chip
# (public spec sheets; ICI is the per-chip aggregate link bandwidth)
CHIP_SPECS = {
    "TPU v2": (22.5e12, 0.70e12, 0.10e12),
    "TPU v3": (61.0e12, 0.90e12, 0.14e12),  # per chip (2 cores)
    "TPU v4": (137.5e12, 1.20e12, 0.27e12),  # per chip (megacore)
    "TPU v5 lite": (197e12, 0.82e12, 0.20e12),
    "TPU v5e": (197e12, 0.82e12, 0.20e12),
    "TPU v5": (229.5e12, 2.77e12, 0.60e12),
    "TPU v5p": (229.5e12, 2.77e12, 0.60e12),
    "TPU v6 lite": (459e12, 1.64e12, 0.36e12),
    "TPU v6e": (459e12, 1.64e12, 0.36e12),
    "TPU7x": (2307e12, 7.40e12, 1.20e12),
}


def chip_specs(chip=None):
    """(peak_flops, hbm_Bps, ici_Bps, kind) for a chip, named either by a
    jax device or by its `device_kind` string; None reads the first live
    device. A kind missing from CHIP_SPECS is an error, never a default:
    every number derived from these — a utilisation, an estimated time —
    would silently be another chip's. Planning off the chip (CPU tests,
    sizing a pod in advance) names the kind it plans for."""
    if chip is None:
        import jax

        chip = jax.devices()[0]
    kind = chip if isinstance(chip, str) else getattr(chip, "device_kind", "")
    for k, v in CHIP_SPECS.items():
        if kind.startswith(k) or k in kind:
            return v[0], v[1], v[2], kind
    raise ValueError(
        f"no peak FLOP/s / bandwidth entry for device kind {kind!r}; known "
        f"kinds: {sorted(CHIP_SPECS)}. Name the chip to plan for "
        f"(CostModel(chip=...), tuner_cfg['chip']) or add it to CHIP_SPECS "
        f"with its source.")


def measured_overlap_fraction(paths=None):
    """The measured half of the hybrid: aggregate comm/compute
    `overlap_fraction` out of step-timeline JSONL records
    (`observability.enable_step_timeline(jsonl_path=...)`).

    `paths`: a path, a list of paths, or None (read the os.pathsep-separated
    PADDLE_TPU_PLAN_OVERLAP_JSONL env). Returns (fraction, source) or
    (None, None) when no history is available — the caller falls back to
    the conservative all-comm-exposed default.
    """
    if paths is None:
        env = os.environ.get("PADDLE_TPU_PLAN_OVERLAP_JSONL", "")
        paths = [p for p in env.split(os.pathsep) if p]
    elif isinstance(paths, str):
        paths = [paths]
    overlaps = []
    for path in paths:
        if not path or not os.path.exists(path):
            continue
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if not isinstance(rec, dict):
                    continue
                if isinstance(rec.get("overlap"), dict):
                    overlaps.append(rec["overlap"])
    if overlaps:
        from ...observability.spans import aggregate_overlap

        agg = aggregate_overlap(overlaps)
        if agg["comm_s"] > 0:
            return agg["fraction"], f"step_timeline:{len(overlaps)}_records"
    return None, None


class CostModel:
    """Analytic roofline + measured-overlap discount over a tuner candidate
    grid. Stateless per prediction; construct once per (chip, history) pair.

    Parameters
    ----------
    chip : jax Device | str | None
        Chip to read the spec table for: a device, or a `device_kind`
        string such as "TPU v5 lite" (planning before/without the chip).
        None reads the first live device; an unknown kind raises.
    peak_flops, hbm_bandwidth, ici_bandwidth : float | None
        Explicit overrides of the spec-table numbers.
    mfu : float
        Achievable fraction of peak for the compute term (calibration
        knob). Affects absolute predictions, not the ranking.
    alpha : float
        Per-collective launch latency in seconds. This is what separates
        the latency-bound regime (tiny messages: collective COUNT dominates)
        from the bandwidth-bound one (byte volume dominates).
    overlap_fraction : float | None
        Fraction of comm covered by compute. None: resolve from
        `overlap_paths` / PADDLE_TPU_PLAN_OVERLAP_JSONL via
        `measured_overlap_fraction`, defaulting to 0.0 (all comm exposed).
    """

    def __init__(self, chip=None, peak_flops=None, hbm_bandwidth=None,
                 ici_bandwidth=None, mfu=0.4, alpha=5e-6,
                 overlap_fraction=None, overlap_paths=None, a2a_chunks=None):
        peak, hbm, ici, kind = chip_specs(chip)
        self.peak_flops = peak_flops or peak
        self.hbm_bandwidth = hbm_bandwidth or hbm
        self.ici_bandwidth = ici_bandwidth or ici
        self.chip = kind
        self.mfu = mfu
        self.alpha = alpha
        # MoE dispatch/combine chunking: more chunks = more a2a launches
        # (the alpha/latency term) buying overlap; the byte volume is
        # chunk-invariant. None resolves the SAME env knob the runtime
        # schedule honors (PADDLE_TPU_MOE_A2A_CHUNKS, default 2, clamp
        # [1, 8]) so predictions cost the schedule that will actually run.
        if a2a_chunks is None:
            try:
                a2a_chunks = int(
                    os.environ.get("PADDLE_TPU_MOE_A2A_CHUNKS", "2"))
            except ValueError:
                a2a_chunks = 2
        self.a2a_chunks = max(1, min(int(a2a_chunks), 8))
        if overlap_fraction is not None:
            self.overlap_fraction = float(overlap_fraction)
            self.overlap_source = "explicit"
        else:
            frac, src = measured_overlap_fraction(overlap_paths)
            self.overlap_fraction = 0.0 if frac is None else frac
            self.overlap_source = src or "default_all_exposed"

    # ------------------------------------------------------------------ #

    def predict(self, tuner_cfg, cfg):
        """Cost breakdown dict for one candidate config (JSON-native: no
        infinities — infeasible-memory configs are the prunes' job, this
        reports `mem_ok` and lets the planner decide)."""
        from ..auto_tuner.tuner import (estimate_memory_bytes,
                                        params_per_device)

        model = tuner_cfg.get("model_cfg", {})
        h = model.get("hidden_size", 0)
        L = max(model.get("num_layers", 1), 1)
        vocab = model.get("vocab_size", 0)
        seq = model.get("seq_length", 1024)
        dp, mp = cfg["dp_degree"], cfg["mp_degree"]
        pp, sh = cfg["pp_degree"], cfg["sharding_degree"]
        ep = cfg.get("ep_degree", 1)
        stage = cfg.get("sharding_stage", 1) if sh > 1 else 0
        mbs = cfg["micro_batch_size"]
        gbs = cfg.get("global_batch_size",
                      tuner_cfg.get("global_batch_size", 8))
        ndev = dp * mp * pp * sh * ep
        n_micro = max(gbs // max(dp * sh * mbs, 1), 1)

        # -- compute roofline ------------------------------------------- #
        tokens = gbs * seq
        body = 12.0 * L * h * h          # transformer block params
        emb = float(vocab * h)           # vocab embedding params
        flops = 6.0 * (body + emb) * tokens + 12.0 * L * h * seq * tokens
        mult = 4.0 / 3.0 if cfg.get("use_recompute") else 1.0
        flops_s = flops * mult / (ndev * self.peak_flops * self.mfu)
        # per-device params via the ONE encoding of the placement split
        # rules (shared with estimate_memory_bytes — see params_per_device)
        body_dev, emb_dev = params_per_device(model, cfg)
        params_dev = body_dev + emb_dev
        # HBM traffic: read bf16 params + f32 master/moments, write them
        # back (~28 B/param-shard) + one activation block per layer held
        acts_dev = n_micro * mbs * seq * h * (L / pp)
        hbm_bytes = 28.0 * params_dev + 2.0 * acts_dev
        hbm_s = hbm_bytes / self.hbm_bandwidth
        compute_s = max(flops_s, hbm_s)
        bubble_s = compute_s * (pp - 1) / n_micro if pp > 1 else 0.0

        # -- per-axis collective volumes -------------------------------- #
        comm_bytes, comm_count = {}, {}
        act_block = mbs * seq * h * 2.0  # bf16 activation microbatch block
        if stage >= 2:
            comm_bytes["sharding_rs"] = (sh - 1) / sh * params_dev * 4.0
            ag = (sh - 1) / sh * params_dev * 2.0  # updated-param gather
            if stage >= 3:
                ag += 2.0 * (sh - 1) / sh * params_dev * 2.0  # fwd+bwd
            comm_bytes["sharding_ag"] = ag
            comm_count["sharding_rs"] = 1
            comm_count["sharding_ag"] = 1 if stage < 3 else 3
            dp_group = dp
        else:
            # stage 0/1: grads all-reduced over the combined replica group
            dp_group = dp * sh
        if dp_group > 1:
            comm_bytes["dp_allreduce"] = \
                2.0 * (dp_group - 1) / dp_group * params_dev * 4.0
            comm_count["dp_allreduce"] = 2  # bucketed, a handful of launches
        if mp > 1:
            comm_bytes["mp_allreduce"] = (4.0 * (L / pp) * n_micro * act_block
                                          * 2.0 * (mp - 1) / mp)
            comm_count["mp_allreduce"] = int(4 * (L // pp or 1) * n_micro)
        if pp > 1:
            comm_bytes["pp_p2p"] = 2.0 * n_micro * act_block
            comm_count["pp_p2p"] = 2 * n_micro
        if ep > 1:
            # MoE dispatch + combine all-to-alls (ISSUE-14): per MoE layer
            # per microbatch, top-k routed copies of the activation block
            # reshard token->expert and back; a2a moves (ep-1)/ep of the
            # payload off-chip. The launch count scales with the chunk
            # schedule (the latency-bound alpha regime — chunking buys
            # overlap at the price of more launches), the byte volume does
            # not.
            topk = model.get("moe_top_k", 2)
            moe_layers = max(model.get("moe_layers", L), 1)
            comm_bytes["ep_a2a"] = (2.0 * moe_layers * n_micro * topk
                                    * act_block * (ep - 1) / ep)
            comm_count["ep_a2a"] = int(2 * moe_layers * n_micro
                                       * self.a2a_chunks)
        comm_s_by_axis = {
            k: v / self.ici_bandwidth + self.alpha * comm_count.get(k, 1)
            for k, v in comm_bytes.items()
        }
        comm_s = sum(comm_s_by_axis.values())
        exposed_s = comm_s * (1.0 - self.overlap_fraction)

        mem = estimate_memory_bytes(tuner_cfg, cfg)
        cap = tuner_cfg.get("max_mem_usage_bytes")
        return {
            "total_s": round(compute_s + bubble_s + exposed_s, 9),
            "compute_s": round(compute_s, 9),
            "bubble_s": round(bubble_s, 9),
            "comm_s": round(comm_s, 9),
            "exposed_comm_s": round(exposed_s, 9),
            "comm_s_by_axis": {k: round(v, 9)
                               for k, v in comm_s_by_axis.items()},
            "comm_bytes_by_axis": {k: round(v, 1)
                                   for k, v in comm_bytes.items()},
            "mem_estimate_bytes": round(mem, 1),
            "mem_ok": bool(cap is None or mem <= cap),
            "n_micro": n_micro,
            "overlap_fraction": self.overlap_fraction,
            "overlap_source": self.overlap_source,
            "chip": self.chip,
            "mfu_assumed": self.mfu,
        }

    def step_time(self, tuner_cfg, cfg) -> float:
        return self.predict(tuner_cfg, cfg)["total_s"]
