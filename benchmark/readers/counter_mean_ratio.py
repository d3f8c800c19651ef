"""The ratio of two of the program's per-tick histograms' means (sum over
count of each: `paddle_tpu.observability.metrics`), over every decode tick
the process made (the set-up's few fill ticks are among them: the registry
keeps no window). None where the program has no such counter or it never
counted."""


def read(run, obs, numerator, denominator):
    from paddle_tpu.observability import metrics

    registry = metrics.default_registry()
    top, bottom = registry.get(numerator), registry.get(denominator)
    if top is None or bottom is None or not top.count() or not bottom.count():
        return None
    mean = bottom.sum() / bottom.count()
    return (top.sum() / top.count()) / mean if mean else None
