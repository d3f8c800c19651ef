"""Utilisation on REQUIRED operations of a `mimo_v2` serving window, in % of
the chip's bf16 peak: the FLOP the window's tokens require
(costs_mimo_v2.window_flops: its decoded tokens at their mean context, the
sliding layers at the mix's own mean of min(context, window), plus its
admissions x the mix's mean prompt) over the window's seconds over the peak.
Everything the window did is in it, prefill and decode, busy and idle: the
whole-window share that bounds a later claim in the cell. An end-to-end
utilisation, not a kernel's roofline share."""

from benchmark import costs_mimo_v2, traffic_gen


def read(run, obs):
    ticks = obs["series"]["ticks"]
    if not ticks or run.window is None:
        return None
    seconds = run.window[1] - run.window[0]
    decoded = sum(t["decoded_rows"] for t in ticks)
    if seconds <= 0 or not decoded:
        return None
    mix = run.mix
    prompts = traffic_gen.levels(mix["prompt_len"])
    answers = traffic_gen.levels(mix["answer_len"])
    flops = costs_mimo_v2.window_flops(
        run.config, decoded,
        sum(t["context_tokens"] for t in ticks) / decoded,
        costs_mimo_v2.mean_window_context(run.config, prompts, answers,
                                          mix["max_total"]),
        sum(t["first_tokens"] for t in ticks), prompts)
    return 100.0 * flops / seconds / run.peaks()["bf16_flops_per_s"]
