"""Device time of the operations whose HLO text matches `pattern`, in % of
the device's busy time (`over="busy"`) or of the traced window
(`over="window"`), mean over the devices. Overlapping matches on one device
count once."""

from benchmark import trace_reduce


def read(run, obs, pattern, over, lines=("XLA Ops",)):
    trace = run.trace
    if trace is None:
        return None
    base = trace_reduce.busy_s(trace) if over == "busy" else trace.window_s
    if base <= 0:
        return None
    return 100.0 * trace_reduce.op_union_s(trace, pattern, tuple(lines)) / base
