"""Roofline share of the paged-decode kernel, in %: the least time the chip
could take to read the K and V that the live rows' contexts hold (bytes over
the HBM peak: the kernel is bound by bandwidth, its FLOP are 2 per byte) over
the summed device time of the kernel's calls in the trace.

Bytes come from costs.decode_attention_bytes and the context lengths the
driver recorded for the ticks inside the trace; the kernel's calls are the
`tpu_custom_call`s that take the pool (an operand of the pool's shape)."""

import re

from benchmark import costs


def read(run, obs):
    trace, span = run.trace, run.trace_ticks
    if trace is None or span is None or len(trace.devices) != 1:
        return None
    ticks = [t for t in obs["series"]["ticks"] if span[0] <= t["index"] < span[1]]
    context = sum(t["context_tokens"] for t in ticks)
    pattern = re.compile(
        r'custom-call\(.*\[' + re.escape(obs["values"]["pool_dims"])
        + r'\].*custom_call_target="tpu_custom_call"')
    lo, hi = trace.window
    (lines,) = trace.devices.values()
    kernel_s = sum(d for name, s, d in lines.get("XLA Ops", ())
                   if lo <= s and s + d <= hi and pattern.search(name))
    if not context or kernel_s <= 0:
        return None
    least_s = (costs.decode_attention_bytes(run.config, context)
               / run.peaks()["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s
