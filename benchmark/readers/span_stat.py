"""A statistic, over the traced ticks or steps, of the time the program spent
in one of its own spans, in ms: per tick (step) the summed duration of its
spans at `path`, or with `of="self"` their self time (duration minus the part
their children cover); then p<q> or `mean` over the ticks, a tick without
the span counting 0. `anchor` names the benchmark's span and the program's
root span that are the same call (benchmark/program_spans.py). Only the
ticks inside the trace are read, not the whole window."""

from benchmark import program_spans
from benchmark.harness import percentile


def read(run, obs, anchor, path, stat, of="duration"):
    spans = program_spans.on_trace_clock(run, anchor)
    if spans is None:
        return None
    values = list(program_spans.per_root(spans, path, of).values())
    if stat == "mean":
        out = sum(values) / len(values)
    elif stat.startswith("p"):
        out = percentile(values, float(stat[1:]))
    else:
        raise ValueError(f"unknown stat {stat!r}")
    return out * 1e3
