"""A statistic, over the traced ticks or steps, of a NUMBER the program wrote
on one of its own spans: per tick (step) the sum of attribute `attr` over its
spans at `path` (a tick without the span, or a span without the attribute,
counting 0), then p<q> or `mean` over the ticks. `anchor` as in span_stat.
None where the program's spans cannot be read or no span at `path` carries
the attribute (a program from before it)."""

from benchmark import program_spans
from benchmark.harness import percentile


def read(run, obs, anchor, path, attr, stat):
    spans = program_spans.on_trace_clock(run, anchor)
    if spans is None:
        return None
    per_root = {s.root: 0.0 for s in spans if s.root is not None}
    found = False
    for s in spans:
        if s.root is not None and s.path == path and attr in s.attrs:
            per_root[s.root] += s.attrs[attr]
            found = True
    if not found:
        return None
    values = list(per_root.values())
    if stat == "mean":
        return sum(values) / len(values)
    if stat.startswith("p"):
        return percentile(values, float(stat[1:]))
    raise ValueError(f"unknown stat {stat!r}")
