"""Parallel-config auto-tuner (reference:
python/paddle/distributed/auto_tuner/tuner.py AutoTuner :21 + search.py
GridSearch, prune.py prune_by_mp/pp/sharding/mbs (+ *_history variants),
recorder.py Recorder, memory_cost_model.py).

TPU formulation: candidates are hybrid-mesh shapes (dp, mp, pp, sharding,
micro-batch, recompute) over a device count. Static pruning enforces the
mesh/model divisibility laws and an analytic HBM estimate; history pruning
skips configs strictly more memory-hungry than a known OOM. The cost model
is DIRECT MEASUREMENT: each surviving config builds a DistributedTrainStep
on a submesh and times real steps (the reference launches subprocess trials
for the same reason — compile-time cost models lie), which on the CPU test
mesh doubles as a correctness sweep of every parallel mode."""

from __future__ import annotations

import csv
import itertools
import os
import time

__all__ = ["AutoTuner", "Recorder", "default_candidates", "tune"]


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


def default_candidates(tuner_cfg):
    """Grid of mesh shapes for `num_devices` (reference utils.py
    default_candidates): every (dp, mp, pp, sharding[, ep]) factorization
    plus micro-batch and recompute choices. The expert-parallel axis only
    enters the grid when the model declares experts
    (model_cfg["moe_num_experts"], or an explicit tuner_cfg["ep_degree"]
    candidate list) — dense models keep the exact pre-ep grid."""
    ndev = tuner_cfg["num_devices"]
    gbs = tuner_cfg.get("global_batch_size", 8)
    has_moe = tuner_cfg.get("model_cfg", {}).get("moe_num_experts", 0) > 1
    eps = tuner_cfg.get("ep_degree", _divisors(ndev) if has_moe else [1])
    cands = []
    for mp in tuner_cfg.get("mp_degree", _divisors(ndev)):
        for pp in tuner_cfg.get("pp_degree", _divisors(ndev)):
            for sharding in tuner_cfg.get("sharding_degree", _divisors(ndev)):
                for ep in eps:
                    if ndev % (mp * pp * sharding * ep):
                        continue
                    dp = ndev // (mp * pp * sharding * ep)
                    if dp not in tuner_cfg.get("dp_degree", _divisors(ndev)):
                        continue
                    for mbs in tuner_cfg.get("micro_batch_size", [1, 2, 4]):
                        for rc in tuner_cfg.get("use_recompute", [True]):
                            cands.append({
                                "dp_degree": dp, "mp_degree": mp,
                                "pp_degree": pp, "sharding_degree": sharding,
                                "ep_degree": ep,
                                "sharding_stage": tuner_cfg.get("sharding_stage", 1),
                                "micro_batch_size": mbs,
                                "use_recompute": rc,
                                "global_batch_size": gbs,
                            })
    return cands


# --------------------------------------------------------------------------- #
# pruning (reference prune.py)
# --------------------------------------------------------------------------- #


def prune_by_mp(tuner_cfg, cfg, history=None):
    """mp must divide hidden/heads/vocab (reference prune.py:129)."""
    mp = cfg["mp_degree"]
    model = tuner_cfg.get("model_cfg", {})
    for key in ("hidden_size", "num_heads", "vocab_size"):
        v = model.get(key)
        if v is not None and v % mp:
            return f"mp {mp} does not divide {key} {v}"
    return None


def prune_by_pp(tuner_cfg, cfg, history=None):
    """pp must divide the layer count and the microbatch count
    (reference prune.py:173)."""
    pp = cfg["pp_degree"]
    layers = tuner_cfg.get("model_cfg", {}).get("num_layers")
    if layers is not None and layers % pp:
        return f"pp {pp} does not divide num_layers {layers}"
    n_micro = cfg["global_batch_size"] // (
        cfg["dp_degree"] * cfg["sharding_degree"] * cfg["micro_batch_size"])
    if pp > 1 and n_micro < pp:
        return f"{n_micro} microbatches < pp {pp}"
    return None


def prune_by_ep(tuner_cfg, cfg, history=None):
    """ep must divide the expert count (expert-stacked weights shard on
    `ep`, planner/layout.py expert_stacked), and a dense model has no ep
    axis to use at all."""
    ep = cfg.get("ep_degree", 1)
    if ep <= 1:
        return None
    experts = tuner_cfg.get("model_cfg", {}).get("moe_num_experts", 0)
    if experts <= 1:
        return f"ep {ep} on a dense model (no moe_num_experts)"
    if experts % ep:
        return f"ep {ep} does not divide moe_num_experts {experts}"
    return None


def prune_by_mbs(tuner_cfg, cfg, history=None):
    """global batch must shard exactly (reference prune.py:307)."""
    denom = cfg["dp_degree"] * cfg["sharding_degree"] * cfg["micro_batch_size"]
    if cfg["global_batch_size"] % denom:
        return (f"global batch {cfg['global_batch_size']} not divisible by "
                f"dp*sharding*mbs {denom}")
    return None


def params_per_device(model_cfg, cfg):
    """(body_elems, emb_elems) on the worst-case device under the actual
    DistributedTrainStep placement — THE one encoding of the split rules,
    shared by the memory estimate below and the planner's HBM/comm terms
    (planner/cost_model.py) so they can never diverge:

    - the transformer body (12*L*h^2 params) is split by mp (TP column/row
      specs) and pp (layer partition); sharding stage 3 (FSDP) splits it
      by `sharding` as well;
    - the vocab embedding (vocab*h) is vocab-sharded by mp ONLY
      (VocabParallelEmbedding P("mp", None)); it lives on one pipeline
      stage, so pp does NOT divide it — worst case is the stage that owns
      it. Stage 3 adds the `sharding` split on its free h dim (fsdp_spec
      respects the TP-taken vocab dim).
    """
    h = model_cfg.get("hidden_size", 0)
    L = model_cfg.get("num_layers", 0)
    vocab = model_cfg.get("vocab_size", 0)
    mp, pp = cfg["mp_degree"], cfg["pp_degree"]
    sh = max(cfg["sharding_degree"], 1)
    stage = cfg.get("sharding_stage", 1) if sh > 1 else 0
    body_dev = 12 * L * h * h / (mp * pp)
    emb_dev = vocab * h / mp
    if stage >= 3:
        body_dev /= sh
        emb_dev /= sh
    return body_dev, emb_dev


def estimate_memory_bytes(tuner_cfg, cfg):
    """Per-device parameter+optimizer+activation estimate (reference
    memory_cost_model.py) over the `params_per_device` placement: bf16
    params are 2 B/elem; optimizer states (f32 master + two f32 moments)
    are 12 B/elem and are `sharding`-split at every stage >= 1 (ZeRO-1),
    while the params themselves stay unsplit below stage 3 (stage 3's
    split already happened in params_per_device)."""
    model = tuner_cfg.get("model_cfg", {})
    h = model.get("hidden_size", 0)
    L = model.get("num_layers", 0)
    seq = model.get("seq_length", 1024)
    if not h:
        return 0
    mp, pp = cfg["mp_degree"], cfg["pp_degree"]
    sh = max(cfg["sharding_degree"], 1)
    stage = cfg.get("sharding_stage", 1) if sh > 1 else 0
    body_dev, emb_dev = params_per_device(model, cfg)
    param_bytes = 2 * (body_dev + emb_dev)
    if stage >= 3:
        state_bytes = 12 * (body_dev + emb_dev)
    else:
        state_bytes = 12 * (body_dev + emb_dev) / sh
    act_layers = 1 if cfg.get("use_recompute") else L // pp
    act_bytes = (cfg["micro_batch_size"] * seq * h * 16 * act_layers / mp)
    return param_bytes + state_bytes + act_bytes


def prune_by_memory(tuner_cfg, cfg, history=None):
    cap = tuner_cfg.get("max_mem_usage_bytes")
    if cap:
        est = estimate_memory_bytes(tuner_cfg, cfg)
        if est > cap:
            return f"estimated {est / 1e9:.2f} GB > cap {cap / 1e9:.2f} GB"
    return None


def prune_by_history(tuner_cfg, cfg, history):
    """Skip configs at least as memory-hungry as a known OOM
    (reference prune_by_*_history)."""
    est = estimate_memory_bytes(tuner_cfg, cfg)
    for h in history or []:
        if h.get("error") == "oom" and est >= (h.get("mem_estimate") or 0):
            return f"memory {est / 1e9:.2f} GB >= known OOM config"
    return None


_PRUNES = [prune_by_mp, prune_by_pp, prune_by_ep, prune_by_mbs,
           prune_by_memory, prune_by_history]


# --------------------------------------------------------------------------- #
# recorder (reference recorder.py)
# --------------------------------------------------------------------------- #


class Recorder:
    def __init__(self, metric_name="step_time", direction="min"):
        self.metric_name = metric_name
        self.direction = direction
        self.history: list[dict] = []

    def add_cfg(self, **kw):
        self.history.append(dict(kw))

    def get_best(self):
        valid = [h for h in self.history
                 if h.get(self.metric_name) is not None and not h.get("error")]
        if not valid:
            return None, True
        key = lambda h: h[self.metric_name]
        best = (min if self.direction == "min" else max)(valid, key=key)
        return best, False

    def store_history(self, path="./history.csv"):
        if not self.history:
            return
        keys = sorted({k for h in self.history for k in h})
        with open(path, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=keys)
            w.writeheader()
            for h in self.history:
                w.writerow(h)

    @staticmethod
    def _coerce(row):
        """csv.DictReader returns all-string rows; restore the types the
        history was recorded with, or every numeric comparison downstream
        (prune_by_history's `est >= mem_estimate`) raises TypeError.
        "" round-trips to None (store_history writes None as empty),
        True/False back to bool, numerics to int-then-float, everything
        else stays a string (error reasons, pruned reasons)."""
        out = {}
        for k, v in row.items():
            if v is None or v == "":
                out[k] = None
            elif v == "True":
                out[k] = True
            elif v == "False":
                out[k] = False
            else:
                try:
                    out[k] = int(v)
                except ValueError:
                    try:
                        out[k] = float(v)
                    except ValueError:
                        out[k] = v
        return out

    def load_history(self, path="./history.csv"):
        if not os.path.exists(path):
            return [], True
        with open(path) as f:
            return [self._coerce(r) for r in csv.DictReader(f)], False


# --------------------------------------------------------------------------- #
# tuner
# --------------------------------------------------------------------------- #


class AutoTuner:
    """reference tuner.py:21 — search_once/add_cfg over a pruned grid."""

    def __init__(self, tuner_cfg):
        self.tuner_cfg = dict(tuner_cfg)
        self.candidates = (tuner_cfg.get("candidates")
                           or default_candidates(self.tuner_cfg))
        self.task_limit = tuner_cfg.get("task_limit", 100)
        self.cur_task_id = 0
        self.history_cfgs: list[dict] = []
        # (cfg, prune-rule name, reason) — the rule is recorded at the
        # point it fires so reports never have to re-derive it
        self.pruned: list[tuple[dict, str, str]] = []
        self._iter = iter(self.candidates)

    def search_once(self):
        """Next unpruned config, or None when exhausted (reference :62)."""
        while self.cur_task_id < self.task_limit:
            try:
                cfg = next(self._iter)
            except StopIteration:
                return None
            reason = None
            for prune in _PRUNES:
                reason = prune(self.tuner_cfg, cfg, self.history_cfgs)
                if reason:
                    self.pruned.append((cfg, prune.__name__, reason))
                    break
            if reason:
                continue
            self.cur_task_id += 1
            return cfg
        return None

    def add_cfg(self, cfg):
        self.history_cfgs.append(cfg)


def _timed_steps(step, ids, labels, steps):
    """(last loss, seconds a step) over `steps` steps of a warmed-up trial:
    the one place a trial reads the clock."""
    t0 = time.perf_counter()
    for _i in range(steps):
        loss = step(ids, labels)
    return float(loss), (time.perf_counter() - t0) / steps


def tune(model_builder, loss_fn, optimizer_builder, tuner_cfg, devices=None,
         steps=2, recorder=None):
    """Run the measurement loop: for each surviving config build the hybrid
    mesh + DistributedTrainStep, time `steps` real steps, and return
    (best_cfg, recorder). `model_builder()` -> fresh model;
    `optimizer_builder(model)` -> optimizer. The reference launches each
    trial as a subprocess with a timeout; under the single controller a
    trial is a compile+measure in-process, with OOM/compile errors recorded
    and fed back into history pruning."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from .. import env as _env
    from ..train_step import DistributedTrainStep

    devices = devices if devices is not None else jax.devices()
    recorder = recorder or Recorder()
    tuner = AutoTuner(tuner_cfg)
    gbs = tuner_cfg.get("global_batch_size", 8)
    model_cfg = tuner_cfg.get("model_cfg", {})
    seq = model_cfg.get("seq_length", 128)
    vocab = model_cfg.get("vocab_size", 1024)

    # the sweep must not clobber the caller's mesh: every trial sets the
    # global mesh (build_mesh AND DistributedTrainStep both do), so snapshot
    # it here and restore it when the sweep ends, however it ends. The
    # per-trial `finally` below is unconditional-safe: it runs whether the
    # failure came from build_mesh, model_builder, or the timed loop — a
    # model_builder raise must not leave the PREVIOUS trial's mesh visible.
    prev_mesh = _env.get_global_mesh()
    try:
        while True:
            cfg = tuner.search_once()
            if cfg is None:
                break
            entry = dict(cfg)
            entry["mem_estimate"] = estimate_memory_bytes(tuner_cfg, cfg)
            try:
                paddle.seed(0)
                mesh = _env.build_mesh(
                    dp=cfg["dp_degree"], pp=cfg["pp_degree"],
                    sharding=cfg["sharding_degree"], mp=cfg["mp_degree"],
                    ep=cfg.get("ep_degree", 1), devices=devices)
                model = model_builder(cfg)
                optimizer = optimizer_builder(model)
                step = DistributedTrainStep(
                    model, loss_fn, optimizer, mesh=mesh,
                    sharding_stage=cfg.get("sharding_stage", 1)
                    if cfg["sharding_degree"] > 1 else 0)
                rng = np.random.default_rng(0)
                ids = paddle.to_tensor(rng.integers(0, vocab, (gbs, seq)))
                labels = paddle.to_tensor(rng.integers(0, vocab, (gbs, seq)))
                _ = float(step(ids, labels))  # compile + warmup
                entry["loss"], entry["step_time"] = _timed_steps(
                    step, ids, labels, steps)
            except Exception as e:  # OOM / infeasible compile
                msg = str(e).lower()
                entry["error"] = ("oom" if "resource exhausted" in msg
                                  or "out of memory" in msg else
                                  f"{type(e).__name__}")
            finally:
                _env.set_global_mesh(None)
            tuner.add_cfg(entry)
            recorder.add_cfg(**entry)
    finally:
        _env.set_global_mesh(prev_mesh)

    # pruned configs land in the history too, so shortlist reports can show
    # WHY a config was never measured (tools/plan_report.py prints these)
    for cfg, _rule, reason in tuner.pruned:
        recorder.add_cfg(**dict(cfg), pruned=reason)

    best, _err = recorder.get_best()
    return best, recorder
