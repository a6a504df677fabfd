#!/usr/bin/env python3
"""One process, one cell, one last line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from `BENCHMARK.json`:

    benchmark/configs/<config>.json            sizes, source, `driver`, `reference`
    benchmark/traffic/<traffic>.json           parameters of the traffic mix
    benchmark/drivers/<driver>.py              set-up, warm-up, window
    benchmark/references/<reference>.py        the plain float32 forward
    benchmark/end_to_end_metrics/<metric>.json reader and its arguments
    benchmark/layer_metrics/<metric>.json      the same, for `--trace 1`
    benchmark/readers/<reader>.py              one reducer per file
    benchmark/peaks.json                       published peaks by device_kind

`--trace 0` prints the cell's end-to-end metrics, `--trace 1` its per-layer
metrics (the profiler runs over the last seconds of the window), with
`device.busy_s`, `device.window_s` and `breakdown`. No TPU, or fewer chips
than the cell asks for: exit 1, no result. `--rehearsal` runs the benchmark's
own CPU checks at the tiny sizes of `benchmark/checks/rehearsal.json`; its
line names the CPU and carries no device metric.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import harness  # noqa: E402


def _rehearse(cell):
    """Tiny sizes for the CPU checks: the rehearsal file's keys replace the
    configuration's and the traffic's."""
    sizes = harness.load_json("checks", "rehearsal.json")
    cell["config_json"].update(sizes["configs"][cell["config"]])
    cell["traffic_json"].update(sizes["traffic"][cell["traffic"]])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--keep-trace", help="copy the .xplane.pb here (debug)")
    args = ap.parse_args()

    cell = harness.load_cell(args.workload)
    if args.rehearsal:
        _rehearse(cell)
        # XLA:CPU cannot read its own cache entries back without an error log
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    harness.place_compile_cache()
    devices = harness.devices_or_exit(cell["chips"], args.rehearsal)
    compiles = harness.CompileCounter()
    profiler = harness.Profiler(bool(args.trace))
    driver = harness.load_module("drivers", cell["config_json"]["driver"])
    try:
        result = driver.run(cell, args.seconds, args.seed, devices, profiler,
                            compiles, T_PROCESS)
        obs = result["obs"]
        obs["device_kind"] = devices[0].device_kind
        trace = None
        if args.trace and devices[0].platform == "tpu":
            import xplane
            if args.keep_trace:
                import shutil
                shutil.copy(profiler.xplane_path(), args.keep_trace)
            trace = xplane.reduce_file(profiler.xplane_path())
    finally:
        profiler.cleanup()

    kind, defs = ("layer_metrics", cell["per_layer"]) if args.trace \
        else ("end_to_end_metrics", cell["end_to_end"])
    on_device = trace is not None or devices[0].platform == "tpu"
    metrics = {}
    for metric in defs:
        if metric["source"] == "device_trace" and not on_device:
            continue                 # a CPU run prints no device metric
        spec = harness.load_json(kind, metric["name"] + ".json")
        reader = harness.load_module("readers", spec["reader"])
        value = reader.read(obs, trace, spec.get("args", {}))
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}

    device = harness.device_record(devices)
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device,
            "notes": result["notes"]}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = xplane.breakdown(trace)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
