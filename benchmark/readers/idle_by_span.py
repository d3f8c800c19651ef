"""Share of the traced window, in %, in which no operation ran on the device
WHILE the host was inside one of the program's spans named in `paths`
(mean over the devices). The shares of disjoint sets of spans add up to
`trace_idle_share` less what was idle outside all of them. `anchor` as in
span_stat."""

from benchmark import program_spans, trace_reduce


def read(run, obs, anchor, paths):
    spans = program_spans.on_trace_clock(run, anchor)
    trace = run.trace
    if spans is None or trace.window_s <= 0:
        return None
    lo, hi = trace.window
    inside = trace_reduce.merge(
        ((s.start, s.end) for s in spans if s.path in paths), lo, hi)
    shares = []
    for device in trace.devices:
        busy = trace_reduce.busy_intervals(trace, device)
        # |inside and not busy| = |inside or busy| - |busy|
        either = trace_reduce.merge(inside + busy, lo, hi)
        shares.append(trace_reduce.total(either) - trace_reduce.total(busy))
    return 100.0 * sum(shares) / len(shares) / trace.window_s
