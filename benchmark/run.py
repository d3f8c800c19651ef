"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process runs one cell of `BENCHMARK.json` on the machine it is started
on: it refuses any platform but `tpu` and any machine with fewer chips than
the cell asks for (exit code 2, no result), builds the model on the device
from `--seed`, warms this cell's shapes (all of that is `setup_s`), measures
for `--seconds`, checks the outputs against the plain reference after the
window, and prints LAST one JSON object:

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}

With `--trace 0` the metrics are the cell's end-to-end metrics; with
`--trace 1` a few seconds of the window are traced and the metrics are the
cell's per-layer metrics, each read by its own reader
(`layer_metrics/<metric>.json` names it). Everything else goes to stderr.

`--rehearse` is the tiny-size run of the same code on the CPU, chosen by
name and never automatically. It prints "platform": "cpu" and no metric
value, only the names it would report; the caller provides its environment:

    JAX_PLATFORMS=cpu PADDLE_TPU_PALLAS_INTERPRET=1 \\
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    python benchmark/run.py --workload train-xl-2k --seed 1 --seconds 2 --trace 0 --rehearse
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def load_cell(name):
    """(cell, config, mix, manifest) of the cell `name` in BENCHMARK.json."""
    from benchmark.harness import load_json

    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no cell {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    return (cell, load_json(ROOT, entry["file"]),
            load_json("traffic", f"{cell['traffic']}.json"), manifest)


def layer_metrics(cell_name):
    """The per-layer metric files that list this cell, by metric name."""
    from benchmark.harness import load_json

    specs = (load_json("layer_metrics", f)
             for f in sorted(os.listdir(os.path.join(HERE, "layer_metrics"))))
    return {s["name"]: s for s in specs if cell_name in s["workloads"]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU platform; never automatic")
    args = ap.parse_args(argv)
    cell, config, mix, manifest = load_cell(args.workload)

    from benchmark import harness

    if args.rehearse:
        config = harness.rehearsal_sizes(config)
        mix = harness.rehearsal_sizes(mix)
    elif os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") == "1":
        sys.exit("benchmark: PADDLE_TPU_PALLAS_INTERPRET=1 is set: "
                 "interpreted kernels say nothing about the chip")

    import jax

    devices = jax.devices()
    wanted = "cpu" if args.rehearse else "tpu"
    if devices[0].platform != wanted or len(devices) < cell["chips"]:
        print(f"benchmark: {args.workload} needs {cell['chips']} {wanted} "
              f"device(s); jax found {len(devices)} x "
              f"{devices[0].platform} ({devices[0].device_kind}). There is "
              "no fallback.", file=sys.stderr)
        sys.exit(2)

    from paddle_tpu.framework.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    # the engine compiles many small programs; cache those too, so that only
    # a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    run = harness.Run(args, cell, config, mix, devices[:cell["chips"]],
                      _T_START)
    run.log(f"{args.workload} seed={args.seed} seconds={args.seconds:g} "
            f"trace={args.trace}; {devices[0].platform} "
            f"{devices[0].device_kind} x{len(devices)}; compile cache "
            f"{cache_dir}")
    family = harness.load_plugin("families", config["family"])
    kind = harness.load_plugin("traffic_kinds", mix["kind"])
    obs = kind.run_cell(run, family)
    run.log("check: " + json.dumps(obs["check"]))

    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
    if args.trace:
        values = {}
        for name, spec in layer_metrics(args.workload).items():
            reader = harness.load_plugin("readers", spec["reader"])
            values[name] = reader.read(run, obs, **spec.get("args", {}))
            units[name] = spec["unit"]
    else:
        values = dict(obs["end_to_end"], setup_s=run.setup_s)
    # a reader that found nothing to read returns None: the metric is left out
    values = {k: float(v) for k, v in values.items() if v is not None}
    run.log("metrics: " + json.dumps(values))

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": bool(obs["correct"]), "attempted": int(obs["attempted"]),
            "failed": int(obs["failed"])}
    if args.rehearse:
        # a CPU run gives no device number: names only
        line["metrics"] = {}
        line["rehearsal"] = {"would_report": sorted(values)}
    else:
        line["metrics"] = {k: {"value": v, "unit": units[k]}
                           for k, v in values.items()}
    if args.trace and run.trace is not None:
        from benchmark import trace_reduce

        if not args.rehearse:
            device["busy_s"] = trace_reduce.busy_s(run.trace)
            device["window_s"] = run.trace.window_s
        line["breakdown"] = trace_reduce.breakdown(run.trace)
    line["device"] = device
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
