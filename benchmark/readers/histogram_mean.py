"""The mean of one of the program's histograms (sum over count:
`paddle_tpu.observability.metrics`) at `labels`, times `scale`, over every
observation the process made (the set-up's few fill ticks among them: the
registry keeps no window). None where the program has no such histogram, not
with these label names, or it never observed at `labels`."""


def read(run, obs, name, labels=None, scale=1.0):
    from paddle_tpu.observability import metrics

    histogram = metrics.default_registry().get(name)
    labels = labels or {}
    if histogram is None or set(labels) != set(histogram.labelnames):
        return None
    count = histogram.count(**labels)
    return scale * histogram.sum(**labels) / count if count else None
