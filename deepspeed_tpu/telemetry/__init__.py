"""Unified telemetry: one registry, many sinks.

`Telemetry` is the object the instrumented subsystems hold: it owns a
`MetricsRegistry` (`registry.py`), the configured exporters
(`exporters.py` — Prometheus textfile, JSONL log, monitor bridge) and an
optional chrome-trace span sink (`spans.py`). Construction from a
`TelemetryConfig` (config/core.py) with `enabled=False` — the default — is a
complete no-op: no directory is created, no file is written, `span()`
returns a shared null context and every record method returns immediately,
so the serving scheduler and the train loop can instrument unconditionally.
The one thing that is on with or without the block is the STEP TIMELINE
(`steptrace.py`): `new_steptrace()` gives each engine a bounded in-memory
ring of per-step and per-request records, fed by its `phase()` spans; it
writes nothing anywhere.

Wiring (all opt-in via the `telemetry` config block):

  * ServingEngine (`inference/scheduler.py`): per-request
    `serving/ttft_ms` / `serving/tpot_ms` / `serving/queue_wait_ms` /
    `serving/e2e_ms` histograms, queue/slot/pool gauges, per-phase spans;
  * training Engine (`runtime/engine.py`): `train/step_time_ms` histogram,
    tokens/s + achieved-MFU gauges, device-memory watermarks;
  * checkpoint saver / recovery paths: their `(tag, value, step)` events
    route through `record_events`, turning save latency into a histogram.

Three per-request diagnostics ride on the same config block and the same
disabled-by-default contract:

  * `tracer` (`tracing.py`, `telemetry.tracing` flag) — request-scoped
    span trees (`<subsystem>.trace.jsonl` + a flow-linked chrome trace);
  * `flightrec` (`flight_recorder.py`, `telemetry.flight_recorder` flag)
    — bounded ring of scheduling events, dumped on failure;
  * `watchdog` (always armed while telemetry is enabled) — recompile
    detection over the persistent jitted serving programs.

`bin/dstpu_metrics` renders the JSONL log (`telemetry/cli.py`);
`bin/dstpu_trace` reconstructs request timelines (`telemetry/tracing.py`).
"""

import contextlib
import pathlib

from deepspeed_tpu.telemetry.registry import (Counter, Gauge, Histogram,
                                              MetricsRegistry,
                                              merge_snapshots)
from deepspeed_tpu.telemetry.exporters import (JsonlExporter, MonitorBridge,
                                               PrometheusFileExporter,
                                               prometheus_text)
from deepspeed_tpu.telemetry import spans
from deepspeed_tpu.telemetry.spans import ChromeTraceSink, Span
from deepspeed_tpu.telemetry.steptrace import StepTrace
from deepspeed_tpu.telemetry.tracing import (NULL_TRACER, TraceContext,
                                             Tracer)
from deepspeed_tpu.telemetry.flight_recorder import (NULL_RECORDER,
                                                     CompileWatchdog,
                                                     FlightRecorder)
from deepspeed_tpu.telemetry.memscope import (MemoryPlan, PredictedOOMError,
                                              ServingMemScope, TrainMemScope,
                                              fmt_bytes, max_kv_blocks,
                                              plan_serving, plan_training,
                                              plan_training_from_infinity,
                                              tree_bytes)

__all__ = ["Telemetry", "MetricsRegistry", "Counter", "Gauge", "Histogram",
           "merge_snapshots",
           "PrometheusFileExporter", "JsonlExporter", "MonitorBridge",
           "prometheus_text", "ChromeTraceSink", "Span", "StepTrace", "Tracer",
           "TraceContext", "FlightRecorder", "CompileWatchdog",
           "MemoryPlan", "PredictedOOMError", "ServingMemScope",
           "TrainMemScope", "plan_training", "plan_serving",
           "plan_training_from_infinity", "max_kv_blocks",
           "fmt_bytes", "tree_bytes"]

_NULL_SPAN = contextlib.nullcontext()


class Telemetry:
    """Registry + exporters behind enable flags. See module docstring."""

    def __init__(self, config=None, subsystem="metrics", monitor=None,
                 registry=None):
        self.config = config
        self.subsystem = subsystem
        self.enabled = bool(config is not None and
                            getattr(config, "enabled", False))
        self.registry = registry if registry is not None else MetricsRegistry()
        self._exporters = []
        self._trace = None
        self._closed = False
        self.tracer = NULL_TRACER
        self.flightrec = NULL_RECORDER
        self.watchdog = CompileWatchdog(self if self.enabled else None)
        if not self.enabled:
            return
        out = pathlib.Path(config.output_path or "telemetry")
        tracing = bool(getattr(config, "tracing", False))
        flight = bool(getattr(config, "flight_recorder", False))
        if config.prometheus or config.jsonl or config.chrome_trace \
                or tracing or flight:
            # registry-only configurations (all file sinks off) must not
            # litter an empty directory
            out.mkdir(parents=True, exist_ok=True)
        if config.prometheus:
            self._exporters.append(
                PrometheusFileExporter(out / f"{subsystem}.prom"))
        if config.jsonl:
            self._exporters.append(JsonlExporter(out / f"{subsystem}.jsonl"))
        if config.monitor_bridge and monitor is not None and \
                getattr(monitor, "enabled", False):
            self._exporters.append(MonitorBridge(monitor))
        if config.chrome_trace or tracing:
            # one shared chrome sink: phase spans (span()) and request
            # traces (tracer) land on one Perfetto timeline
            self._trace = ChromeTraceSink(out / f"{subsystem}.trace.json")
        if tracing:
            self.tracer = Tracer(out / f"{subsystem}.trace.jsonl",
                                 chrome=self._trace)
        if flight:
            self.flightrec = FlightRecorder(
                out, subsystem=subsystem,
                capacity=int(getattr(config, "flight_recorder_events", 256)))
        self.watchdog.recorder = self.flightrec

    # ---- recording ---------------------------------------------------

    def observe(self, name, value):
        if self.enabled:
            self.registry.histogram(name).observe(value)

    def set_gauge(self, name, value):
        if self.enabled:
            self.registry.gauge(name).set(value)

    def inc(self, name, n=1.0):
        if self.enabled:
            self.registry.counter(name).inc(n)

    def record_events(self, event_list):
        """Route monitor-style `(tag, value, step)` events into the registry:
        `*_ms` / `*_seconds` tags become histogram observations (save latency
        as a DISTRIBUTION, not a point value), everything else a gauge."""
        if not self.enabled:
            return
        for tag, value, _step in event_list:
            if tag.endswith(("_ms", "_seconds")):
                self.registry.histogram(tag).observe(value)
            else:
                self.registry.gauge(tag).set(value)

    def span(self, name, tid=0):
        """Timed/annotated region; a shared null context when disabled.
        `tid` selects the chrome-trace track (per-replica tids keep a
        serving pool's phase timelines separated in Perfetto)."""
        if not self.enabled:
            return _NULL_SPAN
        return spans.span(name, sink=self._trace, tid=tid)

    # ---- step timeline (on by default; telemetry/steptrace.py) ---------

    def new_steptrace(self, clock):
        """This subsystem's step recorder on `clock`; its phases reach the
        chrome sink too when that is on."""
        return StepTrace(self.subsystem, clock=clock, sink=self._trace)

    # ---- export ------------------------------------------------------

    def maybe_export(self, step):
        """Export every `export_interval`-th step (cheap modulo when idle)."""
        if not self.enabled:
            return
        interval = max(1, int(getattr(self.config, "export_interval", 1)))
        if step % interval == 0:
            self.export(step)

    def export(self, step=None):
        if not self.enabled:
            return
        snap = self.registry.snapshot()
        for e in self._exporters:
            e.export(self.registry, step=step, snapshot=snap)

    def peak_flops(self):
        """Per-chip peak FLOPs/s: the `peak_tflops` override when set, else
        the published peak of the live `device_kind`
        (`platform/device.py::DEVICE_PEAKS`), else None — a device nobody
        measured on (the CPU harness included) gets NO `train/mfu` gauge
        rather than a utilization against another chip's peak."""
        override = float(getattr(self.config, "peak_tflops", 0.0) or 0.0)
        if override > 0:
            return override * 1e12
        from deepspeed_tpu.platform.device import DEVICE_PEAKS, device_kind
        peaks = DEVICE_PEAKS.get(device_kind())
        return None if peaks is None else peaks.bf16_tflops * 1e12

    def close(self):
        if self._closed:
            return
        self._closed = True
        # final export so runs shorter than export_interval (and the tail of
        # longer ones) still land in the files; guarded — close() also runs
        # from __del__ during interpreter teardown
        try:
            if self.enabled and self.registry.metrics():
                self.export()
        except Exception:
            pass
        for e in self._exporters:
            try:
                e.close()
            except Exception:
                pass
        if self._trace is not None:
            try:
                self._trace.close()
            except Exception:
                pass
        try:
            self.tracer.close()
        except Exception:
            pass

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
