"""A statistic over the WHOLE window of the records the program writes at
`path` whether or not anyone listens (`admission`: one a request that takes a
row; `paddle_tpu.observability.spans.record`). The records are taken from the
program's ring, on `time.perf_counter_ns`; the window is on `run.clock()`,
and the offset between the two is read from both clocks here. A record
counts when its START lies inside the window. `where` keeps the records
whose attributes equal its values (`{"kind": "prefill"}`). A record's value
is the sum of its attributes `fields`, `seconds` standing for its own length
(t1 - t0). `stat`:

- `p<q>` or `mean` of the records' values, times `scale`;
- `tick_share`: the sum of the values over the summed seconds of the
  window's ticks (`obs["series"]["ticks"]`), %: the share of the window in
  which the engine ran. Not over the window's own seconds: the per-layer
  metrics are printed by the `--trace 1` run, whose window holds 8-17 s in
  which the harness stops and reads the trace and no tick runs;
- `ratio`: the sum of the values over the sum of attribute `over`, %.

None where the program keeps no ring, the ring holds no record at `path`
(a parent commit from before the record) or none of them is in the window.
"""

import time

from benchmark.harness import percentile


def window_records(run, path):
    """The ring's records at `path` that start inside `run.window`, oldest
    first; the counts by `kind` are logged once a run."""
    cache = run.__dict__.setdefault("_window_records", {})
    if path not in cache:
        from paddle_tpu.observability import spans as program

        recorded = getattr(program, "recorded", None)
        if recorded is None or not run.window:
            return cache.setdefault(path, [])
        # the ring's clock to the run's: both read now
        offset = run.clock() - time.perf_counter_ns() * 1e-9
        lo, hi = run.window
        cache[path] = [r for r in recorded() if r["path"] == path
                       and lo <= r["t0_ns"] * 1e-9 + offset <= hi]
        if cache[path]:
            kinds = [r["attrs"].get("kind") for r in cache[path]]
            run.log(f"{path!r} records that start inside the window: "
                    + ", ".join(f"{kinds.count(k)} {k}"
                                for k in sorted(set(kinds), key=str)))
    return cache[path]


def _value(record, fields):
    return sum((record["t1_ns"] - record["t0_ns"]) * 1e-9 if f == "seconds"
               else record["attrs"].get(f) or 0 for f in fields)


def read(run, obs, path, stat, fields=("seconds",), where=None, over=None,
         scale=1.0):
    records = [r for r in window_records(run, path)
               if all(r["attrs"].get(k) == v
                      for k, v in (where or {}).items())]
    if not records:
        return None
    values = [_value(r, fields) for r in records]
    if stat == "tick_share":
        tick_s = sum(t["ms"] for t in obs["series"]["ticks"]) * 1e-3
        return 100.0 * sum(values) / tick_s if tick_s else None
    if stat == "ratio":
        bottom = sum(_value(r, (over,)) for r in records)
        return 100.0 * sum(values) / bottom if bottom else None
    if stat == "mean":
        return scale * sum(values) / len(values)
    if stat.startswith("p"):
        return scale * percentile(values, float(stat[1:]))
    raise ValueError(f"unknown stat {stat!r}")
