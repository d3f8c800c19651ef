"""What every cell's run shares: the clock, the log, host spans, the count of
compilations, the profiler trace of a few seconds of the window, and the
table of peaks. A traffic kind gets one `Run` and drives its window with it.
"""

import contextlib
import glob
import json
import os
import shutil
import sys
import tempfile
import time

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
TRACE_DELAY_S = 2.0    # into the window before the trace starts
TRACE_SECONDS = 5.0    # traces are large and tracing slows the host


def load_json(*parts):
    """The JSON file at `parts` under benchmark/ (an absolute first part,
    such as ROOT, overrides that)."""
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_plugin(folder, name):
    """The module `benchmark/<folder>/<name>.py`, found by file name: a later
    PR adds a family, a traffic kind or a reader as a new file."""
    import importlib.util

    path = os.path.join(HERE, folder, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {folder}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{folder}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def rehearsal_sizes(data):
    """`data` with its `rehearsal` overrides applied (one level deep for
    nested sections), for the tiny CPU rehearsal."""
    out = dict(data)
    for key, value in data.get("rehearsal", {}).items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = {**out[key], **value}
        else:
            out[key] = value
    return out


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation; None if empty."""
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


class Run:
    """One run of one cell. `clock()` is seconds since process start."""

    def __init__(self, args, cell, config, mix, devices, t_start):
        self.args, self.cell, self.config, self.mix = args, cell, config, mix
        self.devices = devices
        self.rehearse = args.rehearse
        self.seconds = float(args.seconds)
        self.trace_on = bool(args.trace)
        self._t_start = t_start
        self._lowerings = 0
        self._mark = None
        self._trace_dir = None
        self._trace_span = None
        self.trace = None          # trace_reduce.Trace once a trace was read
        self.trace_ticks = None    # (first, last) loop indices inside the trace
        self.setup_s = None
        self.window = None         # (open, close) on clock()
        self.compiles_in_window = None
        self.memory_peak_bytes = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    # -- clock, log, spans ------------------------------------------------ #

    def clock(self):
        return time.perf_counter() - self._t_start

    def log(self, msg):
        print(f"[benchmark {self.clock():7.1f}s] {msg}", file=sys.stderr,
              flush=True)

    def span(self, name):
        """A host span `bm.<name>` in the profiler's trace (free when no
        trace is being taken)."""
        return jax.profiler.TraceAnnotation(f"bm.{name}")

    def _on_event(self, event, duration, **_):
        if event == LOWERING_EVENT:
            self._lowerings += 1

    # -- the window ------------------------------------------------------- #

    def open_window(self):
        """Set-up is over: everything before this is `setup_s`."""
        self._mark = self._lowerings
        self.setup_s = self.clock()
        self.log(f"set-up {self.setup_s:.1f}s ({self._lowerings} programs "
                 f"lowered); measuring {self.seconds:g}s")
        return self.setup_s

    def close_window(self, t_close):
        self.stop_trace()
        self.window = (self.setup_s, t_close)
        self.compiles_in_window = self._lowerings - self._mark
        stats = [d.memory_stats() for d in self.devices]
        if all(s and "peak_bytes_in_use" in s for s in stats):
            self.memory_peak_bytes = max(s["peak_bytes_in_use"] for s in stats)
        self.log(f"window {t_close - self.setup_s:.2f}s; programs lowered "
                 f"inside it: {self.compiles_in_window}")

    # -- the trace -------------------------------------------------------- #

    def poll_trace(self, index):
        """Called between two steps or ticks with the index of the next one:
        starts the trace TRACE_DELAY_S into the window, stops it
        TRACE_SECONDS later."""
        if not self.trace_on or self.trace is not None:
            return
        since = self.clock() - self.setup_s
        if self._trace_span is None:
            if since >= TRACE_DELAY_S and self._trace_dir is None:
                self._start_trace(index)
        elif since - self._trace_from >= TRACE_SECONDS:
            self.stop_trace(index)

    def _start_trace(self, index):
        self._trace_dir = tempfile.mkdtemp(prefix="bm_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans yes, every Python call no
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._trace_span = jax.profiler.TraceAnnotation("bm.trace_window")
        self._trace_span.__enter__()
        self._trace_from = self.clock() - self.setup_s
        self._trace_first = index

    def stop_trace(self, index=None):
        if self._trace_span is None:
            return
        from benchmark import trace_reduce

        self._trace_span.__exit__(None, None, None)
        self._trace_span = None
        t0 = self.clock()
        jax.profiler.stop_trace()
        t1 = self.clock()
        try:
            files = glob.glob(os.path.join(
                self._trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
            self.trace = trace_reduce.load_xplane(files[0]) if files else None
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
        self.trace_ticks = (self._trace_first,
                            float("inf") if index is None else index)
        self.log(f"trace: {t1 - t0:.1f}s to stop, {self.clock() - t1:.1f}s to "
                 "read" + ("" if self.trace else
                           "; it holds no device plane or no window span"))

    # -- peaks ------------------------------------------------------------ #

    def peaks(self):
        table = load_json("peaks.json")
        kind = self.devices[0].device_kind
        if self.rehearse:  # control flow only: a rehearsal prints no value
            kind = next(iter(table))
        if kind not in table:
            raise SystemExit(f"benchmark: no peaks for device kind {kind!r} "
                             "in benchmark/peaks.json")
        return table[kind]


@contextlib.contextmanager
def timed(run, what):
    t0 = run.clock()
    yield
    run.log(f"  {what}: {run.clock() - t0:.1f}s")
