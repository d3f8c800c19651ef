"""MoE all-to-all accounting: trace-time registration, per-step emission.

The compiled MoE fast path (incubate/.../moe_layer.py) issues its
dispatch/combine all-to-alls INSIDE the jitted train step — XLA gives the
host no per-collective timing, so the eager-collective counters
(`collective_{calls,bytes}_total{op="all_to_all"}`) and the StepTimeline's
comm intervals would miss MoE traffic entirely (exactly the gap ISSUE-14's
first satellite closes for the eager path in collective.py/moe_utils.py).

The split mirrors PR 7's offload instrumentation: the traced layer runs its
host code ONCE per trace, so it registers the per-step a2a volume here
(`note_a2a` — a plain list append, no metric emission inside the traced
region: GL006), and the host-side step wrapper
(`DistributedTrainStep._post_dispatch`) drains the registration at compile
time and re-emits it every executed step:

- `collective_calls_total{op="all_to_all"}` / `collective_bytes_total{...}`
  counters (the same family the eager collectives bump), and
- `comm_task(kind="a2a")` intervals for the overlap accounting. The
  interval duration is the ANALYTIC bytes/ICI-bandwidth estimate (marked
  `[est]` in the desc), anchored inside the step's compute span — the
  chunked fast path overlaps its a2a with expert GEMMs by construction, and
  XLA exposes no host-visible boundary to measure instead. Eager
  global_scatter/global_gather intervals (moe_utils.py) are real measured
  times; only compiled-path intervals are estimates (docs/MOE.md).
"""

from __future__ import annotations

import time

__all__ = ["note_a2a", "trace_marker", "drain_since", "emit_step",
           "estimated_seconds"]

# records appended at trace time by the MoE layer; drained by the train
# step right after its first (tracing) call. Single-threaded trace
# assumption, same as the dispatch cache. Markers are absolute sequence
# numbers so the bounded-growth eviction can never skew a drain slice.
_registered: list = []
_seq = [0]


def trace_marker() -> int:
    return _seq[0]


def note_a2a(desc: str, nbytes: int, calls: int = 1, overlapped: bool = True):
    """Register one per-step all-to-all volume (bytes are the analytic
    per-step estimate for the traffic GSPMD materializes). Called at TRACE
    time from inside the traced MoE forward — appends only; metric
    emission happens host-side in emit_step. `overlapped` states what the
    traced schedule arranges (chunked pipeline = True; a single unchunked
    exchange = False) so the estimated interval lands on the covered or
    exposed side of the overlap accounting accordingly."""
    _registered.append({"desc": str(desc), "bytes": int(nbytes),
                        "calls": int(calls), "overlapped": bool(overlapped),
                        "seq": _seq[0]})
    _seq[0] += 1
    if len(_registered) > 512:
        # eager-only callers (no train step ever drains) must not leak:
        # drop the oldest half — absolute seq markers stay valid
        del _registered[:256]


def drain_since(marker: int) -> tuple:
    """Hand the records registered at/after sequence `marker` to the
    caller (the train step that just traced them) and drop them from the
    shared list."""
    taken = tuple({k: v for k, v in r.items() if k != "seq"}
                  for r in _registered if r["seq"] >= marker)
    _registered[:] = [r for r in _registered if r["seq"] < marker]
    return taken


def estimated_seconds(nbytes: int, chip=None) -> float:
    """bytes / per-chip ICI bandwidth, resolved through the planner's chip
    spec table (the same numbers the cost model's a2a term uses). `chip`: a
    device or `device_kind` string; None reads the live device. A kind the
    table does not know raises there — never another chip's bandwidth."""
    from .planner.cost_model import chip_specs

    _peak, _hbm, ici, _kind = chip_specs(chip)
    return nbytes / ici


def emit_step(records, floor_ns: int = 0, chip=None) -> None:
    """Host-side, once per executed step: bump the collective counters and
    fire comm_task observers with the estimated a2a intervals, anchored to
    reflect what the traced schedule arranges on device:

    - `overlapped` records (the chunked pipeline) anchor BACKWARD from now
      — inside the step's compute span, where _post_dispatch runs — and
      are floored at `floor_ns` (the caller's dispatch start, which the
      span opens just after), so a large estimate can never poke out ahead
      of the span and get miscounted as exposed;
    - unchunked records (PADDLE_TPU_MOE_A2A_CHUNKS=1, the A/B baseline)
      anchor FORWARD from now, past the span's imminent end — counted as
      exposed comm, so the chunking knob's effect is visible in
      overlap_fraction, not just wall clock.

    The byte/call counters are exact on any backend. The intervals are
    bytes over the chip's ICI bandwidth, so they exist only for a chip the
    spec table knows: the live TPU, or the `chip` a caller names. On the
    CPU backend there is no interconnect to estimate and none is emitted."""
    if not records:
        return
    from . import comm_watchdog
    from .collective import record_collective_traffic

    if chip is None:
        import jax

        live = jax.devices()[0]
        chip = live if live.platform == "tpu" else None
    for rec in records:
        record_collective_traffic("all_to_all", rec["bytes"], rec["calls"])
        if chip is None:
            continue
        now = time.perf_counter_ns()
        est = max(int(estimated_seconds(rec["bytes"], chip) * 1e9), 1)
        if rec.get("overlapped", True):
            t0, t1 = now - est, now
            if floor_ns:
                t0 = max(t0, min(floor_ns, t1 - 1))
        else:
            t0, t1 = now, now + est
        comm_watchdog.record_task(f"{rec['desc']}[est]", t0, t1, kind="a2a")
