"""The program's own spans, put on the clock of the profiler's trace.

`trace_reduce.load_xplane` keeps only the benchmark's `bm.*` host spans, so
the readers take the program's spans from its in-memory ring
(`paddle_tpu.observability.spans.recorded()`: live during the traced seconds
because the profiler is on, on `time.perf_counter_ns`) and map them through
anchors both sides have: the k-th `bm.engine_step` (or `bm.train_step`) span
of the trace and the k-th `engine.step` (or `train_step`) span of the ring
are the same call. The offset between the clocks is the median difference of
their starts. Where the two sides count differently, a residual exceeds
`LIMIT_S`, or a program span does not lie inside its `bm.*` twin, the mapping
is refused: `on_trace_clock` returns None and says why on stderr, once.

A program without the ring (a parent commit from before the spans) gives
None too, and nothing is raised.
"""

import dataclasses
import statistics
import sys

LIMIT_S = 100e-6


@dataclasses.dataclass
class Span:
    """One record of the ring; `start` and `end` in seconds on the trace's
    clock, `root` the index of the anchor span (tick or step) it lies under,
    None for a record outside all of them."""

    id: int
    parent: object
    path: str
    start: float
    end: float
    attrs: dict
    root: object = None

    @property
    def seconds(self):
        return self.end - self.start


def map_ring(ring, twins, root_path, limit_s=LIMIT_S):
    """(spans, None) or (None, reason). `ring`: the records of
    `spans.recorded()`; `twins`: [(start, duration)] of the benchmark's span
    around each call, in seconds on the trace's clock, in order."""
    roots = sorted((r for r in ring if r["path"] == root_path),
                   key=lambda r: r["t0_ns"])
    if not roots or len(roots) != len(twins):
        return None, (f"{len(roots)} {root_path!r} spans in the program's "
                      f"ring against {len(twins)} in the trace")
    offsets = [t[0] - r["t0_ns"] * 1e-9 for r, t in zip(roots, twins)]
    offset = statistics.median(offsets)
    worst = max(abs(o - offset) for o in offsets)
    if worst > limit_s:
        return None, (f"the clocks do not map: residual {worst * 1e6:.0f} us "
                      f"over {limit_s * 1e6:.0f} us")
    spans = [Span(r["id"], r["parent"], r["path"], r["t0_ns"] * 1e-9 + offset,
                  r["t1_ns"] * 1e-9 + offset, r.get("attrs") or {})
             for r in ring]
    by_id = {s.id: s for s in spans}
    for k, (root, (t_start, t_dur)) in enumerate(zip(roots, twins)):
        s = by_id[root["id"]]
        s.root = k
        if s.start < t_start - limit_s or s.end > t_start + t_dur + limit_s:
            return None, (f"{root_path!r} span {k} does not lie inside its "
                          "twin in the trace")
    for s in spans:  # a child carries its root's index down
        seen, at = [], s
        while at is not None and at.root is None and at.parent in by_id:
            seen.append(at)
            at = by_id[at.parent]
        if at is not None and at.root is not None:
            for x in seen:
                x.root = at.root
    return spans, None


def on_trace_clock(run, anchor):
    """The ring's spans on the clock of `run.trace`, or None. `anchor` is
    (the benchmark's span name, the program's root path), such as
    ("bm.engine_step", "engine.step"). Computed once a run."""
    cache = run.__dict__.setdefault("_program_spans", {})
    key = tuple(anchor)
    if key not in cache:
        cache[key] = _map(run, *key)
    return cache[key]


def _map(run, bm_name, root_path):
    from paddle_tpu.observability import spans as program

    recorded = getattr(program, "recorded", None)
    if run.trace is None or recorded is None:
        return None
    twins = [(s, d) for n, s, d in run.trace.spans if n == bm_name]
    spans, why = map_ring(recorded(), twins, root_path)
    if spans is None:
        print(f"benchmark: program spans not read: {why}", file=sys.stderr,
              flush=True)
        return None
    for s in spans:
        if s.path == "compile":
            print(f"benchmark: compiled inside the traced seconds: {s.attrs}",
                  file=sys.stderr, flush=True)
    return spans


def per_root(spans, path, of="duration"):
    """{root index: seconds} over every root: the summed length of the
    root's spans at `path` (0 where it has none); with `of="self"` what
    their direct children leave uncovered."""
    out = {s.root: 0.0 for s in spans if s.root is not None}
    children = {}
    if of == "self":
        for s in spans:
            children.setdefault(s.parent, []).append(s)
    for s in spans:
        if s.root is None or s.path != path:
            continue
        sec = s.seconds
        if of == "self":
            sec -= _union(children.get(s.id, ()), s.start, s.end)
        out[s.root] += sec
    return out


def _union(spans, lo, hi):
    sec, at = 0.0, lo
    for s in sorted(spans, key=lambda s: s.start):
        a, b = max(s.start, at), min(s.end, hi)
        if b > a:
            sec += b - a
            at = b
    return sec
