"""Roofline share, in %, of one of the `granite_hybrid` family's two
weight- or state-bound kernels, from the device trace: the least time the
chip could take to move the bytes the kernel's calls require (bytes over the
HBM peak: both are bound by bandwidth) over the summed device time of the
calls the trace shows under the kernel's NAME (`%<kernel>.N`, a
`tpu_custom_call`).

`ssm_decode`: 2 x one row's SSM state x the rows decoded in the traced ticks
x the Mamba layers (costs_hybrid.ssm_decode_bytes): every decoded row reads
and writes its whole state in every such layer.
`grouped_gemm`: per call the bytes of the held experts' stacked matrix it
multiplies, whole (costs_hybrid.grouped_gemm_weight_bytes): told from the
call's own operand, a bf16[held, K, N] array in its HLO text. A call that
finds few rows still reads every held expert some row routed to, and at a
serving batch all of them are hit, so the whole matrix is the requirement; a
prefill's calls read it again per row tile, which the share then shows.

Nothing to read (no trace, a parent without the kernel, no call inside the
traced seconds): None."""

import re

from benchmark import costs_hybrid


def _calls(trace, kernel):
    pattern = re.compile(r"^%" + re.escape(kernel) + r"(\.[\w.]+)? = .*"
                         r'custom_call_target="tpu_custom_call"')
    lo, hi = trace.window
    (lines,) = trace.devices.values()
    return [(name, d) for name, s, d in lines.get("XLA Ops", ())
            if lo <= s and s + d <= hi and pattern.search(name)]


def read(run, obs, kernel):
    trace, span = run.trace, run.trace_ticks
    if trace is None or span is None or len(trace.devices) != 1:
        return None
    calls = _calls(trace, kernel)
    kernel_s = sum(d for _, d in calls)
    if kernel_s <= 0:
        return None
    config = run.config
    if kernel == "ssm_decode":
        rows = sum(t["decoded_rows"] for t in obs["series"]["ticks"]
                   if span[0] <= t["index"] < span[1])
        required = costs_hybrid.ssm_decode_bytes(config, rows)
    elif kernel == "grouped_gemm":
        by_elements = {b // 2: b for b in
                       costs_hybrid.grouped_gemm_weight_bytes(config)}
        stacked = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")
        required = 0
        for name, _ in calls:
            sizes = [int(e) * int(k) * int(n)
                     for e, k, n in stacked.findall(name)]
            known = [by_elements[s] for s in sizes if s in by_elements]
            if not known:
                return None   # a call that multiplies something else
            required += known[0]
    else:
        raise ValueError(f"no byte count for kernel {kernel!r}")
    if not required:
        return None
    least_s = required / run.peaks()["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
