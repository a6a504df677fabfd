"""What every driver shares: the files a cell is made of, the device gate,
the compile cache, the compile counter, the quiet host, the profiler window
and the device record of the last line."""

import contextlib
import gc
import importlib.util
import json
import os
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """`benchmark/<kind>/<name>.py`, found by the name a data file gives."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload):
    """BENCHMARK.json's entry for `workload` with its configuration, its
    traffic and the metrics it reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = dict(cells[workload])
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, config["file"])) as f:
        cell["config_json"] = json.load(f)
    cell["traffic_json"] = load_json("traffic", cell["traffic"] + ".json")

    def reported(metric):
        return workload in metric.get("workloads", [workload])

    cell["end_to_end"] = [m for m in bench["end_to_end"] if reported(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if reported(m)]
    return cell


def place_compile_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, before JAX is imported. The program (`ensure_compile_cache`)
    picks the same directory by itself and takes `JAX_COMPILATION_CACHE_DIR`
    where that is set, so the benchmark sets nothing else. Small programs are
    cached too: a warm run then compiles nothing at all."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def devices_or_exit(chips, rehearsal):
    """The devices this cell runs on. No TPU, or fewer chips than the cell
    asks for: exit 1 and print no result. `--rehearsal` (the benchmark's own
    CPU checks) is the one way onto another platform, and its line names
    that platform and carries no device metric."""
    import jax
    devices = jax.devices()
    if rehearsal:
        return devices[:chips]
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(f"benchmark: needs {chips} TPU chip(s); JAX found "
                         f"{len(devices)} x {devices[0].platform!r}")
    return devices[:chips]


def device_record(devices):
    import jax
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts what JAX traces or compiles, by its own monitoring events, so
    that 'nothing compiles inside the window' is measured and not assumed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kwargs):
        if event in self.EVENTS:
            self.count += 1


@contextlib.contextmanager
def quiet_host():
    """The collector frozen and off while the window is measured."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        gc.unfreeze()


class Profiler:
    """The JAX profiler around part of a run; `annotate(name)` writes a host
    span on the trace's clock while it is on and costs nothing while off."""

    def __init__(self, enabled):
        self.enabled = enabled
        self.running = False
        self.dir = None
        self.started_at = self.closed_at = None
        self._window = None

    def start(self):
        import jax
        self._tmp = tempfile.TemporaryDirectory(prefix="bench_trace_")
        self.dir = self._tmp.name
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.running = True
        # the traced window is this span; what runs before `stop()` but after
        # `close_window()` is in the file and outside the numbers
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.started_at = time.perf_counter()

    def close_window(self):
        if self._window is not None:
            self.closed_at = time.perf_counter()
            self._window.__exit__(None, None, None)
            self._window = None

    def stop(self):
        import jax
        self.close_window()
        self.running = False
        jax.profiler.stop_trace()

    def annotate(self, name):
        if self.running:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def xplane_path(self):
        for base, _dirs, files in os.walk(self.dir):
            for name in files:
                if name.endswith(".xplane.pb"):
                    return os.path.join(base, name)
        raise FileNotFoundError(f"no .xplane.pb under {self.dir}")

    def cleanup(self):
        if self.dir is not None:
            self._tmp.cleanup()
