"""A statistic of one field over a series the window recorded (`steps`,
`ticks`, `requests`): p<q> for a percentile, `mean` or `max`. `where` keeps
only the records whose field `where["field"]` is at least `where["min"]`."""

from benchmark.harness import percentile


def read(run, obs, series, field, stat, where=None, scale=1.0):
    records = obs["series"].get(series, [])
    if where:
        records = [r for r in records
                   if (r.get(where["field"]) or 0) >= where["min"]]
    values = [r[field] for r in records if r.get(field) is not None]
    if not values:
        return None
    if stat == "mean":
        out = sum(values) / len(values)
    elif stat == "max":
        out = max(values)
    elif stat.startswith("p"):
        out = percentile(values, float(stat[1:]))
    else:
        raise ValueError(f"unknown stat {stat!r}")
    return out * scale
