"""Utilisation on REQUIRED operations of a `kimi_k2` serving window, in % of
the chip's bf16 peak: the FLOP the window's tokens require
(costs_kimi_k2.window_flops: its decoded tokens at their mean context, 2 FLOP
per matmul parameter a token meets here plus the absorbed attention's own
over the whole context; plus its admissions x the mix's mean prompt in the
expanded form) over the window's seconds over the peak. Everything the window
did is in it, prefill and decode, busy and idle: the whole-window share that
bounds a later claim in the cell. An end-to-end utilisation, not a kernel's
roofline share."""

from benchmark import costs_kimi_k2, traffic_gen


def read(run, obs):
    ticks = obs["series"]["ticks"]
    if not ticks or run.window is None:
        return None
    seconds = run.window[1] - run.window[0]
    decoded = sum(t["decoded_rows"] for t in ticks)
    if seconds <= 0 or not decoded:
        return None
    flops = costs_kimi_k2.window_flops(
        run.config, decoded,
        sum(t["context_tokens"] for t in ticks) / decoded,
        sum(t["first_tokens"] for t in ticks),
        traffic_gen.levels(run.mix["prompt_len"]))
    return 100.0 * flops / seconds / run.peaks()["bf16_flops_per_s"]
