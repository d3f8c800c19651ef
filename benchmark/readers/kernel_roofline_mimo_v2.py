"""Roofline share, in %, of one of the `mimo_v2` family's attention kernels,
from the device trace: the least time the chip could take for what the
kernel's calls require over the summed device time of the calls the trace
shows under the kernel's NAME (`%<kernel>.N`, a `tpu_custom_call`). What is
required comes from `costs_mimo_v2` (the published widths: a key of 192
values, whatever the pool stores it in) and from what the program's own
spans say of the traced ticks (`engine.step/decode_dispatch`:
`context_tokens`, `window_tokens`; `engine.step/admit/prefill`:
`prompt_len`):

`decode_paged` (bandwidth): the decoded rows' whole contexts x the full
layers x K and V of one token and full layer (4 heads of 192 + 128).
`decode_window` (bandwidth): sum over the decoded rows of min(context,
window) tokens x the sliding layers x K and V of one token and sliding layer
(8 heads of 192 + 128).
`flash_fwd_window` (bf16 peak): the admitted prompts' causal band, 2 FLOP a
head value of q.k (192) and of p.v (128) a (query, key) pair, every sliding
layer. The kernel runs over the prompt's bucket, so a prompt that fills half
its bucket reads half.

Nothing to read (no trace, a program without the spans or the kernel, no
call inside the traced seconds): None."""

from benchmark import costs_mimo_v2
from benchmark.readers.kernel_roofline_afmoe import _attrs
from benchmark.readers.kernel_roofline_hybrid import _calls


def read(run, obs, kernel):
    trace = run.trace
    if trace is None or len(trace.devices) != 1:
        return None
    kernel_s = sum(d for _, d in _calls(trace, kernel))
    if kernel_s <= 0:
        return None
    config, peaks = run.config, run.peaks()
    if kernel in ("decode_window", "decode_paged"):
        name = "window_tokens" if kernel == "decode_window" else "context_tokens"
        tokens = _attrs(run, "engine.step/decode_dispatch", name)
        if not tokens:
            return None
        count = (costs_mimo_v2.decode_window_bytes if kernel == "decode_window"
                 else costs_mimo_v2.decode_full_bytes)
        least_s = count(config, sum(tokens)) / peaks["hbm_bytes_per_s"]
    elif kernel == "flash_fwd_window":
        prompts = _attrs(run, "engine.step/admit/prefill", "prompt_len")
        if not prompts:
            return None
        least_s = (costs_mimo_v2.window_prefill_flops(config, prompts)
                   / peaks["bf16_flops_per_s"])
    else:
        raise ValueError(f"no count for kernel {kernel!r}")
    return 100.0 * least_s / kernel_s if least_s else None
