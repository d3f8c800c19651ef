"""Mesh planner: rank the full candidate grid analytically, measure only a
top-K shortlist.

The existing auto-tuner (`auto_tuner/tuner.py`) times real steps for every
surviving grid point — sound at 8 devices, unaffordable at pod scale. The
planner in front of it:

1. `rank_candidates` — run the static prunes over the full grid, predict
   every survivor's step time with the analytic `CostModel`, sort.
2. `shortlist` — keep the top K (default 5) and hand ONLY those to the
   existing `tune()` measurement loop.
3. `plan_and_tune` — measure the shortlist, record predicted-vs-measured
   error per trial into the Recorder history (the model is falsifiable:
   tools/plan_report.py prints the table), and emit the winning `MeshPlan`.
4. `analytic_plan` — the measurement-free fast path an elastic restart
   uses to adopt a mesh for a changed device count without burning a
   cluster on trials (ResilientTrainer calls this).

Counters/spans flow through the observability registry (catalog rows in
docs/OBSERVABILITY.md).
"""

from __future__ import annotations

from ...observability import metrics as _metrics
from ...observability import spans as _spans
from ..auto_tuner.tuner import AutoTuner, Recorder, tune
from .cost_model import CostModel
from .layout import MeshPlan

__all__ = ["DEFAULT_TOP_K", "rank_candidates", "shortlist", "plan_and_tune",
           "analytic_plan"]

DEFAULT_TOP_K = 5

_pm = _metrics.HandleCache(lambda reg: {
    "candidates": reg.counter(
        "planner_candidates_total",
        "mesh candidates considered by the analytic ranking"),
    "pruned": reg.counter(
        "planner_pruned_total",
        "mesh candidates rejected by static pruning", labelnames=("reason",)),
    "shortlisted": reg.counter(
        "planner_shortlisted_total",
        "mesh candidates kept for measurement"),
    "measured": reg.counter(
        "planner_measured_trials_total",
        "shortlist trials actually timed by tune()"),
    "replans": reg.counter(
        "planner_replans_total",
        "analytic re-plans triggered by a changed device count"),
    "err": reg.gauge(
        "planner_prediction_error_pct",
        "abs(predicted-measured)/measured of the latest measured trial"),
})


def _cfg_key(cfg):
    """Identity of a candidate across planner/tuner bookkeeping."""
    return (cfg["dp_degree"], cfg["mp_degree"], cfg["pp_degree"],
            cfg["sharding_degree"], cfg.get("ep_degree", 1),
            cfg.get("sharding_stage", 1),
            cfg["micro_batch_size"], bool(cfg.get("use_recompute")))


def rank_candidates(tuner_cfg, cost_model=None):
    """(ranked, pruned): ranked = [(cfg, breakdown)] sorted by predicted
    step time over every statically-feasible grid point; pruned =
    [(cfg, prune_rule_name, reason)]. No measurement happens here."""
    cm = cost_model or CostModel(chip=tuner_cfg.get("chip"))
    tuner = AutoTuner(dict(tuner_cfg, task_limit=10 ** 9))
    survivors = []
    with _spans.span("planner/rank"):
        while True:
            cfg = tuner.search_once()
            if cfg is None:
                break
            survivors.append(cfg)
        ranked = sorted(
            ((cfg, cm.predict(tuner_cfg, cfg)) for cfg in survivors),
            key=lambda t: t[1]["total_s"])
    pruned = list(tuner.pruned)
    pm = _pm.get()
    pm["candidates"].inc(len(survivors) + len(pruned))
    for _cfg, rule, _r in pruned:
        pm["pruned"].inc(reason=rule)
    return ranked, pruned


def shortlist(tuner_cfg, top_k=DEFAULT_TOP_K, cost_model=None):
    """Top-K analytically-ranked candidates: [(cfg, breakdown)]."""
    ranked, _pruned = rank_candidates(tuner_cfg, cost_model)
    kept = ranked[:top_k]
    _pm.get()["shortlisted"].inc(len(kept))
    return kept


def analytic_plan(tuner_cfg, cost_model=None, model_cfg=None) -> MeshPlan:
    """Measurement-free fast path: the analytic top-1 as a MeshPlan.
    Raises if the grid has no feasible candidate (a device count the model
    cannot factorize onto is a config error, not a plan)."""
    ranked, pruned = rank_candidates(tuner_cfg, cost_model)
    if not ranked:
        raise ValueError(
            f"no feasible mesh candidate for num_devices="
            f"{tuner_cfg.get('num_devices')}; pruned: "
            + "; ".join(f"{r}" for _c, _n, r in pruned[:5]))
    cfg, breakdown = ranked[0]
    return MeshPlan.from_candidate(
        cfg, breakdown, model_cfg=model_cfg or tuner_cfg.get("model_cfg"),
        source="analytic")


def plan_and_tune(model_builder, loss_fn, optimizer_builder, tuner_cfg,
                  top_k=DEFAULT_TOP_K, cost_model=None, devices=None,
                  steps=2, recorder=None):
    """The hybrid loop: analytic shortlist -> measured trials -> MeshPlan.

    Returns (plan, best_cfg, recorder). The recorder history carries, per
    measured trial, `predicted_step_time` and `prediction_error_pct`
    (signed, (pred-meas)/meas*100) so the analytic model is falsifiable
    against exactly the trials it selected; the planner's pruned configs
    land in the history as `pruned=<reason>` rows (via tune()) for
    shortlist reports. Configs the analytic ranking REJECTED (beyond
    top-K) are recorded with `pruned="analytic rank > K"`.
    """
    cm = cost_model or CostModel(chip=tuner_cfg.get("chip"))
    recorder = recorder or Recorder()
    ranked, pruned = rank_candidates(tuner_cfg, cm)
    if not ranked:
        raise ValueError(
            f"no feasible mesh candidate for num_devices="
            f"{tuner_cfg.get('num_devices')}; pruned: "
            + "; ".join(f"{r}" for _c, _n, r in pruned[:5]))
    kept, rejected = ranked[:top_k], ranked[top_k:]
    pm = _pm.get()
    pm["shortlisted"].inc(len(kept))
    predicted = {_cfg_key(cfg): bd for cfg, bd in ranked}
    measure_cfg = dict(tuner_cfg, candidates=[dict(cfg) for cfg, _ in kept])
    # only THIS call's trials get attributed: a caller-supplied recorder
    # may carry an earlier sweep whose entries must not be re-stamped (or
    # re-counted into planner_measured_trials_total)
    n_prior = len(recorder.history)
    with _spans.span("planner/measure"):
        best, recorder = tune(model_builder, loss_fn, optimizer_builder,
                              measure_cfg, devices=devices, steps=steps,
                              recorder=recorder)
    for entry in recorder.history[n_prior:]:
        if "dp_degree" not in entry:
            continue
        bd = predicted.get(_cfg_key(entry))
        if bd is None:
            continue
        entry["predicted_step_time"] = bd["total_s"]
        meas = entry.get("step_time")
        if meas:
            pm["measured"].inc()
            err = (bd["total_s"] - meas) / meas * 100.0
            entry["prediction_error_pct"] = round(err, 2)
            pm["err"].set(abs(err))
    for cfg, bd in rejected:
        recorder.add_cfg(**cfg, mem_estimate=bd["mem_estimate_bytes"],
                         predicted_step_time=bd["total_s"],
                         pruned=f"analytic rank > {top_k}")
    # a caller-supplied recorder may carry history from an earlier sweep;
    # get_best can then name a config outside this grid — predict it fresh
    best_bd = predicted.get(_cfg_key(best)) if best is not None else None
    if best is not None:
        plan = MeshPlan.from_candidate(
            {k: best[k] for k in ("dp_degree", "mp_degree", "pp_degree",
                                  "sharding_degree", "ep_degree",
                                  "sharding_stage",
                                  "micro_batch_size", "use_recompute",
                                  "global_batch_size") if k in best},
            best_bd if best_bd is not None else cm.predict(tuner_cfg, best),
            model_cfg=tuner_cfg.get("model_cfg"),
            measured_step_time_s=best["step_time"], source="measured")
    else:
        # every shortlist trial errored (OOM storm): fall back to the
        # analytic winner so the caller still gets an adoptable plan
        plan = MeshPlan.from_candidate(
            kept[0][0], kept[0][1],
            model_cfg=tuner_cfg.get("model_cfg"), source="analytic")
    return plan, best, recorder


def note_replan(old_devices, new_devices):
    """Counter hook for ResilientTrainer's elastic adoption path."""
    _pm.get()["replans"].inc()
