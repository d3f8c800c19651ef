"""Mesh planner subsystem: analytic+measured hybrid cost model, canonical
layout plans, elastic plan adoption (ROADMAP item 3; docs/PLANNER.md).

- cost_model.py: the analytic roofline (compute + pipeline bubble +
  per-axis collective volumes discounted by the MEASURED overlap_fraction
  from step-timeline history) and the chip spec table (`chip_specs`).
- planner.py: rank the full candidate grid analytically, hand only a
  top-K shortlist to the auto-tuner's measurement loop, record
  predicted-vs-measured error per trial.
- layout.py: SpecLayout (canonical per-param-group PartitionSpecs) and the
  MeshPlan JSON artifact ResilientTrainer adopts across elastic restarts.
"""

from .cost_model import (
    CHIP_SPECS,
    CostModel,
    chip_specs,
    measured_overlap_fraction,
)
from .layout import PLAN_FILENAME, MeshPlan, SpecLayout
from .planner import (
    DEFAULT_TOP_K,
    analytic_plan,
    note_replan,
    plan_and_tune,
    rank_candidates,
    shortlist,
)

__all__ = [
    "CHIP_SPECS",
    "CostModel",
    "chip_specs",
    "measured_overlap_fraction",
    "PLAN_FILENAME",
    "MeshPlan",
    "SpecLayout",
    "DEFAULT_TOP_K",
    "analytic_plan",
    "note_replan",
    "plan_and_tune",
    "rank_candidates",
    "shortlist",
]
