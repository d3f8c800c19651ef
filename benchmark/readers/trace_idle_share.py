"""Share of the traced window, in %, in which no operation ran on the
device: 1 - union of the device's operation intervals over the window, mean
over the devices."""

from benchmark import trace_reduce


def read(run, obs):
    trace = run.trace
    if trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(trace) / trace.window_s)
