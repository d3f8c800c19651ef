"""Roofline share, in %, of one of the `afmoe` family's kernels, from the
device trace: the least time the chip could take for what the kernel's calls
require over the summed device time of the calls the trace shows under the
kernel's NAME (`%<kernel>.N`, a `tpu_custom_call`). What is required comes
from `costs_afmoe` and from what the program's own spans say of the traced
ticks (`engine.step/decode_dispatch`: `context_tokens`, `window_tokens`;
`engine.step/admit/prefill`: `prompt_len`):

`decode_window` (bandwidth): sum over the decoded rows of min(context,
window) tokens x the sliding layers x K and V of one token and layer.
`decode_paged` (bandwidth): the decoded rows' whole contexts x the full
layers x the same.
`flash_fwd_window` (bf16 peak): the admitted prompts' causal band, 4 FLOP a
head value and (query, key) pair, every sliding layer. The kernel runs over
the prompt's bucket, so a prompt that fills half its bucket reads half.
`grouped_gemm` (bandwidth): per call the bytes of the held experts' stacked
matrix it multiplies, whole, told from the call's own operand
(bf16[held, K, N] in its HLO text); a prefill's calls read it again per row
tile, which the share then shows.

Nothing to read (no trace, a program without the spans or the kernel, no
call inside the traced seconds): None."""

import re

from benchmark import costs_afmoe, program_spans
from benchmark.readers.kernel_roofline_hybrid import _calls

ANCHOR = ("bm.engine_step", "engine.step")


def _attrs(run, path, name):
    """The values of attribute `name` over the traced ticks' spans at
    `path`, or None where the program's spans cannot be read."""
    spans = program_spans.on_trace_clock(run, ANCHOR)
    if spans is None:
        return None
    return [s.attrs[name] for s in spans
            if s.root is not None and s.path == path and name in s.attrs]


def read(run, obs, kernel):
    trace = run.trace
    if trace is None or len(trace.devices) != 1:
        return None
    calls = _calls(trace, kernel)
    kernel_s = sum(d for _, d in calls)
    if kernel_s <= 0:
        return None
    config, peaks = run.config, run.peaks()
    if kernel in ("decode_window", "decode_paged"):
        name = "window_tokens" if kernel == "decode_window" else "context_tokens"
        tokens = _attrs(run, "engine.step/decode_dispatch", name)
        if not tokens:
            return None
        count = (costs_afmoe.decode_window_bytes if kernel == "decode_window"
                 else costs_afmoe.decode_full_bytes)
        least_s = count(config, sum(tokens)) / peaks["hbm_bytes_per_s"]
    elif kernel == "flash_fwd_window":
        prompts = _attrs(run, "engine.step/admit/prefill", "prompt_len")
        if not prompts:
            return None
        least_s = (costs_afmoe.window_prefill_flops(config, prompts)
                   / peaks["bf16_flops_per_s"])
    elif kernel == "grouped_gemm":
        by_elements = {b // 2: b for b in
                       costs_afmoe.grouped_gemm_weight_bytes(config)}
        stacked = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")
        required = 0
        for name, _ in calls:
            sizes = [int(e) * int(k) * int(n)
                     for e, k, n in stacked.findall(name)]
            known = [by_elements[s] for s in sizes if s in by_elements]
            if not known:
                return None   # a call that multiplies something else
            required += known[0]
        least_s = required / peaks["hbm_bytes_per_s"]
    else:
        raise ValueError(f"no count for kernel {kernel!r}")
    return 100.0 * least_s / kernel_s if least_s else None
