"""From a profiler trace to numbers: device busy time, idle gaps by what the
host was doing, time per device operation.

`load_xplane` reads the `.xplane.pb` that `jax.profiler` writes
(`ProfileData.from_file`, nothing but JAX) into a plain `Trace`; every
reduction below works on that, so the tests run them on a small recorded
trace kept as JSON (`Trace.from_json`).

On a TPU each chip is a plane `/device:TPU:<n>`. Its line `XLA Ops` holds
one event per executed HLO operation, named by the operation's HLO text
(`%fusion.12 = bf16[...] fusion(...)`; a Pallas kernel carries
`custom_call_target="tpu_custom_call"`); `Async XLA Ops` holds the spans of
asynchronous operations (copies, collectives between their -start and
-done). Host threads are lines of `/host:CPU`; a
`jax.profiler.TraceAnnotation` shows there under its name, on the same clock.
"""

import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops", "Async XLA Ops")
SPAN_PREFIX = "bm."
WINDOW_SPAN = "bm.trace_window"
NAME_CHARS = 160  # HLO text runs to kilobytes; the head tells the op


@dataclasses.dataclass
class Trace:
    """All times in seconds on the trace's own clock.

    window: (start, end) of the `bm.trace_window` span.
    devices: {plane name: {line name: [(op name, start, duration)]}}.
    spans: [(name, start, duration)] of the other `bm.*` host spans."""

    window: tuple
    devices: dict
    spans: list

    @classmethod
    def from_json(cls, obj):
        return cls(tuple(obj["window"]),
                   {d: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
                    for d, lines in obj["devices"].items()},
                   [tuple(s) for s in obj["spans"]])

    def to_json(self):
        return {"window": list(self.window), "devices": self.devices,
                "spans": self.spans}

    @property
    def window_s(self):
        return self.window[1] - self.window[0]


def load_xplane(path):
    """The Trace of one `.xplane.pb`, or None when it has no device plane or
    no `bm.trace_window` span (nothing to reduce)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans, window = {}, [], None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in OP_LINES:
                    lines[line.name] = [
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                        for ev in line.events]
            devices[plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(SPAN_PREFIX):
                        continue
                    rec = (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    if ev.name == WINDOW_SPAN:
                        window = (rec[1], rec[1] + rec[2])
                    else:
                        spans.append(rec)
    if not devices or window is None:
        return None
    return Trace(window, devices, sorted(spans, key=lambda s: s[1]))


def merge(intervals, lo, hi):
    """Union of [start, end) intervals clipped to [lo, hi], as a sorted list
    of disjoint (start, end)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def _ops(trace, device, lines, pattern=None):
    rx = re.compile(pattern) if pattern else None
    for line in lines:
        for name, start, dur in trace.devices[device].get(line, ()):
            if rx is None or rx.search(name):
                yield name, start, dur


def busy_intervals(trace, device):
    """Disjoint intervals in which an operation ran on `device`."""
    lo, hi = trace.window
    return merge(((s, s + d) for _, s, d in _ops(trace, device, ("XLA Ops",))),
                 lo, hi)


def busy_s(trace):
    """Seconds in which an operation ran, averaged over the devices."""
    per = [total(busy_intervals(trace, d)) for d in trace.devices]
    return sum(per) / len(per)


def op_union_s(trace, pattern, lines=("XLA Ops",)):
    """Seconds covered by operations whose HLO text matches `pattern`
    (union per device, so overlapping async spans count once), averaged over
    the devices."""
    lo, hi = trace.window
    per = [total(merge(((s, s + d) for _, s, d in _ops(trace, dev, lines, pattern)),
                       lo, hi))
           for dev in trace.devices]
    return sum(per) / len(per)


def idle_gaps_by_span(trace):
    """{span name: seconds} of the first device's idle time, by the `bm.*`
    span the host was in ("none" outside all of them), longest first."""
    lo, hi = trace.window
    device = sorted(trace.devices)[0]
    gaps, at = [], lo
    for s, e in busy_intervals(trace, device):
        if s > at:
            gaps.append((at, s))
        at = e
    if hi > at:
        gaps.append((at, hi))
    by = {}
    covered = 0.0
    names = sorted({n for n, _, _ in trace.spans})
    for name in names:
        own = merge(((s, s + d) for n, s, d in trace.spans if n == name), lo, hi)
        sec = _overlap(gaps, own)
        if sec > 0:
            by[name] = sec
            covered += sec
    rest = total(gaps) - covered
    if rest > 1e-12:
        by["none"] = rest
    return dict(sorted(by.items(), key=lambda kv: -kv[1]))


def _overlap(a, b):
    """Seconds common to two sorted lists of disjoint intervals."""
    i = j = 0
    sec = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            sec += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return sec


def top_ops(trace, n=10):
    """[[op name, seconds]]: the n operations with most device time in the
    window, summed over their executions and averaged over the devices."""
    lo, hi = trace.window
    sums = {}
    for device in trace.devices:
        for name, s, d in _ops(trace, device, ("XLA Ops",)):
            sec = min(s + d, hi) - max(s, lo)
            if sec > 0:
                key = name[:NAME_CHARS]
                sums[key] = sums.get(key, 0.0) + sec
    k = len(trace.devices)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec / k] for name, sec in ranked]


def breakdown(trace, n=10):
    return {"device_ops": top_ops(trace, n),
            "idle_gaps": [[k, v] for k, v in
                          list(idle_gaps_by_span(trace).items())[:n]]}
