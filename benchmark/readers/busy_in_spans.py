"""Of the traced time the host spent inside the program's spans named in
`paths`, the share, in %, in which an operation ran on the device (mean over
the devices): the device's side of a host phase, on the shared clock. High
where the phase's cost is the device's own work, low where it is the host's.
None where the program's spans cannot be read or the trace holds no span at
`paths`. `anchor` as in span_stat."""

from benchmark import program_spans, trace_reduce


def read(run, obs, anchor, paths):
    spans = program_spans.on_trace_clock(run, anchor)
    if spans is None:
        return None
    trace = run.trace
    lo, hi = trace.window
    inside = trace_reduce.merge(
        ((s.start, s.end) for s in spans if s.path in paths), lo, hi)
    host_s = trace_reduce.total(inside)
    if host_s <= 0:
        return None
    shares = []
    for device in trace.devices:
        busy = trace_reduce.busy_intervals(trace, device)
        # |inside and busy| = |inside| + |busy| - |inside or busy|
        either = trace_reduce.merge(inside + busy, lo, hi)
        shares.append(host_s + trace_reduce.total(busy)
                      - trace_reduce.total(either))
    return 100.0 * sum(shares) / len(shares) / host_s
