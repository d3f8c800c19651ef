"""Model FLOP/s utilisation on REQUIRED operations, in % of the chips' bf16
peak: costs.train_flops_per_token x tokens per second per chip over the
peak, with tokens per second taken from the median step time (the traced
window also holds the seconds it takes to stop the profiler, so its own rate
reads low). Causal attention counted once, recomputation not counted. An
end-to-end utilisation, not a kernel's roofline share."""

from benchmark import costs
from benchmark.harness import percentile


def read(run, obs):
    step_ms = percentile([s["step_ms"] for s in obs["series"]["steps"]
                          if s["step_ms"] is not None], 50)
    if not step_ms:
        return None
    v = obs["values"]
    rate = v["batch"] * v["seq"] / (step_ms * 1e-3) / v["chips"]
    flops = costs.train_flops_per_token(run.config, v["seq"])
    return 100.0 * flops * rate / run.peaks()["bf16_flops_per_s"]
