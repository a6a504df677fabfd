"""Host-side spans: named timed regions layered on the nvtx shim.

A span does two things at once:

  * enters a `jax.profiler.TraceAnnotation` via `utils/nvtx.annotate` (a hard
    no-op when `jax.profiler` is unavailable), so the region shows up in a
    real xprof/TensorBoard trace when one is being captured;
  * optionally records `(name, start, duration)` into a `ChromeTraceSink`,
    so a scheduler-step timeline (admit / prefill chunk / decode window) can
    be opened in Perfetto WITHOUT a TPU profiler session — the host-side
    phases are exactly the ones a device trace cannot see.

The sink writes the Chrome trace event format as streamed JSON: an opening
`[` then one complete event object per line, comma-terminated. Perfetto and
chrome://tracing both accept the unterminated-array form, which is what
makes the sink append-only and crash-safe. Beyond the duration ("X") events
the sink also speaks the metadata ("M": `process_name`/`thread_name`, so
every replica of a serving pool gets its own NAMED Perfetto track) and flow
("s"/"f": the arrows that connect a request's spans across tracks when the
router re-routes or hands a slot off) subsets of the format — the request
tracer (`telemetry/tracing.py`) drives those.
"""

import json
import os
import threading
import time

from deepspeed_tpu.utils import nvtx

__all__ = ["Span", "ChromeTraceSink", "span"]


class ChromeTraceSink:
    """Streamed chrome-trace event log (open directly in Perfetto). One sink
    = one run = one file: the file is truncated at first write so a re-run
    into the same output path cannot interleave two runs' timelines (every
    event's `ts` is relative to THIS sink's construction). Within the run
    events append and flush one by one — the timeline is readable mid-run
    and survives a crash."""

    def __init__(self, path):
        self.path = str(path)
        self._f = None
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()

    def write(self, ev):
        """Append one raw chrome-trace event dict (already carrying its own
        `ts`/`dur` in trace microseconds). The structured-span tracer uses
        this directly so its events stay on ONE caller-owned clock domain;
        `add` below converts from this sink's perf_counter baseline."""
        with self._lock:
            if self._f is None:
                self._f = open(self.path, "w")
                self._f.write("[\n")
            self._f.write(json.dumps(ev) + ",\n")
            self._f.flush()     # crash-safe: the timeline is readable mid-run

    def add(self, name, start_s, dur_s, tid=0):
        """Record one complete event; timestamps are seconds on the
        `time.perf_counter` clock (converted to trace microseconds).
        `tid` picks the Perfetto track — per-replica tids keep a serving
        pool's timelines from collapsing onto one row."""
        self.write({"name": name, "ph": "X", "pid": os.getpid(), "tid": tid,
                    "ts": round((start_s - self._t0) * 1e6, 3),
                    "dur": round(dur_s * 1e6, 3)})

    def add_meta(self, kind, value, tid=0):
        """Metadata event: kind is "process_name" or "thread_name"; value
        labels this pid (or `tid`'s track) in the Perfetto UI."""
        self.write({"name": kind, "ph": "M", "pid": os.getpid(), "tid": tid,
                    "ts": 0, "args": {"name": str(value)}})

    def close(self):
        with self._lock:
            if self._f is not None:
                try:
                    self._f.flush()
                    self._f.close()
                finally:
                    self._f = None


class Span:
    """Context manager: nvtx annotation + optional chrome-trace event. `tid`
    selects the chrome-trace track (default 0 — single-engine timelines; the
    serving stack passes its replica tid so pool timelines stay separated).
    The clock is read only for a sink; `telemetry/steptrace.py::Phase` adds
    the step ring as a third destination. `attrs` ride the annotation alone
    (`serving/decode_window#call=7#` in a profiler's trace)."""

    __slots__ = ("name", "sink", "tid", "attrs", "_t0", "_nvtx")

    def __init__(self, name, sink=None, tid=0, **attrs):
        self.name = name
        self.sink = sink
        self.tid = tid
        self.attrs = attrs
        self._t0 = 0.0
        self._nvtx = None

    def __enter__(self):
        self._nvtx = nvtx.annotate(self.name, **self.attrs)
        self._nvtx.__enter__()
        if self.sink is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._nvtx.__exit__(exc_type, exc, tb)
        self._nvtx = None
        if self.sink is not None:
            self.sink.add(self.name, self._t0,
                          time.perf_counter() - self._t0, tid=self.tid)
        return False


def span(name, sink=None, tid=0):
    """Open a named span (see `Span`); usable as `with span("admit"): ...`."""
    return Span(name, sink=sink, tid=tid)
