"""Unified telemetry (deepspeed_tpu/telemetry/): metrics registry units,
exporter golden output, ServingEngine TTFT/TPOT on a mixed trace, train-lane
MFU accounting, monitor bridge + never-die, dstpu_metrics round-trip.

Everything rides the `telemetry` marker (tier-1; run alone with
`pytest -m telemetry`).
"""

import json
import types

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig, TelemetryConfig
from deepspeed_tpu.inference.engine import init_inference
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model, \
    make_gpt_model
from deepspeed_tpu.telemetry import (Histogram, JsonlExporter,
                                     MetricsRegistry, MonitorBridge,
                                     PrometheusFileExporter, Telemetry,
                                     merge_snapshots, prometheus_text)
from deepspeed_tpu.telemetry.cli import load_latest, main as metrics_main
from tests.paged_cases import assert_one_compile_each

pytestmark = pytest.mark.telemetry

TINY = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=256,
                 vocab_size=256, dtype=jnp.float32, remat=False)


def _mk_mesh(**axes):
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    return mesh_mod.init_mesh(MeshConfig(**{**dict(data=1, tensor=1,
                                                   sequence=1, expert=1,
                                                   pipe=1), **axes}))


def _mk_serving_engine(tmp_path, telemetry=True, **tcfg):
    _mk_mesh(data=1)
    spec = make_gpt_decode_model(cfg=TINY, name="tiny")
    cfg = {"dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
           "kv_block_size": 16, "max_out_tokens": 64}
    if telemetry:
        cfg["telemetry"] = {"enabled": True, "output_path": str(tmp_path),
                            "export_interval": 4, **tcfg}
    return init_inference(model=spec, config=cfg)


# ----------------------------------------------------------------------
# registry units
# ----------------------------------------------------------------------


def test_histogram_bucket_and_percentile_math():
    h = Histogram("t")
    vals = [1.0, 2.0, 3.0, 10.0, 100.0, 1000.0]
    for v in vals:
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 6
    assert snap["sum"] == pytest.approx(sum(vals))
    assert snap["mean"] == pytest.approx(sum(vals) / 6)
    assert snap["min"] == 1.0 and snap["max"] == 1000.0
    # log-bucket interpolation: p50 lands between the 3rd and 4th value
    assert 3.0 <= snap["p50"] <= 10.0
    assert snap["p50"] <= snap["p90"] <= snap["p99"] <= snap["max"]
    # quantiles clamp to the observed range
    assert h.quantile(0.0) >= snap["min"]
    assert h.quantile(1.0) <= snap["max"]
    # out-of-range observations land in the edge buckets, never lost
    h.observe(1e-9)
    h.observe(1e12)
    assert h.count == 8 == sum(h.counts)
    assert h.cumulative_buckets()[-1] == (float("inf"), 8)


def test_histogram_empty_and_single():
    h = Histogram("t")
    snap = h.snapshot()
    # bounds/counts ride along so snapshots stay mergeable (PR 20)
    assert snap.pop("bounds") == list(h.bounds)
    assert snap.pop("counts") == [0] * len(h.counts)
    assert snap == {"type": "histogram", "count": 0, "sum": 0.0,
                    "mean": 0.0, "min": 0.0, "max": 0.0, "p50": 0.0,
                    "p90": 0.0, "p99": 0.0}
    h.observe(42.0)
    s = h.snapshot()
    assert s["p50"] == s["p99"] == s["min"] == s["max"] == 42.0


def test_registry_snapshot_deterministic():
    def build():
        r = MetricsRegistry()
        r.gauge("z/gauge").set(3)
        r.counter("a/count").inc(2)
        h = r.histogram("m/lat_ms")
        for v in (5, 50, 500):
            h.observe(v)
        return r

    r1, r2 = build(), build()
    assert r1.snapshot() == r2.snapshot()
    # name-sorted iteration order regardless of creation order
    assert [n for n, _ in r1.metrics()] == ["a/count", "m/lat_ms", "z/gauge"]
    # type conflicts are errors, not silent coercions
    with pytest.raises(TypeError):
        r1.counter("z/gauge")


def test_registry_get_or_create_identity():
    r = MetricsRegistry()
    assert r.histogram("h") is r.histogram("h")
    r.counter("c").inc()
    r.counter("c").inc()
    assert r.snapshot()["c"]["value"] == 2.0


def test_merge_snapshots_exact_bucketwise():
    """Pool merge semantics (PR 20): counters sum, gauges keep a per-source
    map, histograms merge bucket-wise EXACTLY — the merged snapshot is
    identical to one histogram that observed the union of all samples."""
    rng = np.random.default_rng(4)
    union = Histogram("serving/ttft_ms")
    per, per_counts = {}, {}
    for i, src in enumerate(("r0", "r1", "r2")):
        r = MetricsRegistry()
        h = r.histogram("serving/ttft_ms")
        vals = rng.uniform(0.3, 8000.0, size=17 + 11 * i)
        for v in vals:
            h.observe(v)
            union.observe(v)
        per_counts[src] = h.count
        r.counter("router/completed").inc(10 * (i + 1))
        r.gauge("serving/queue_depth").set(i)
        per[src] = r.snapshot()
    merged = merge_snapshots(per)
    m = merged["serving/ttft_ms"]
    # the acceptance equality: merged count == sum of per-source counts,
    # and the whole snapshot (percentiles included) matches the union.
    # sum/mean differ only by float summation order (per-source subtotals
    # vs interleaved observes) — everything bucket-derived is bit-exact
    assert m["count"] == sum(per_counts.values())
    u = union.snapshot()
    for key in ("sum", "mean"):
        assert m[key] == pytest.approx(u[key], rel=1e-12)
    assert {k: v for k, v in m.items() if k not in ("sum", "mean")} == \
        {k: v for k, v in u.items() if k not in ("sum", "mean")}
    assert merged["router/completed"]["value"] == 10 + 20 + 30
    g = merged["serving/queue_depth"]
    assert g["sources"] == {"r0": 0, "r1": 1, "r2": 2}
    assert g["value"] == 3          # across-source sum (pool-additive)
    # merges compose: a merged snapshot is itself a valid source
    again = merge_snapshots({"pool": merged, "r3": per["r0"]})
    assert again["serving/ttft_ms"]["count"] == \
        m["count"] + per_counts["r0"]


def test_merge_snapshots_conflicts_raise():
    c = {"x": {"type": "counter", "value": 1.0}}
    g = {"x": {"type": "gauge", "value": 1.0}}
    with pytest.raises(ValueError, match="type conflict"):
        merge_snapshots({"a": c, "b": g})
    with pytest.raises(ValueError, match="unknown snapshot type"):
        merge_snapshots({"a": {"x": {"type": "nope"}}})
    h1 = Histogram("h", bounds=[1.0, 2.0])
    h2 = Histogram("h", bounds=[1.0, 2.0, 4.0])
    with pytest.raises(ValueError, match="mismatched bucket"):
        merge_snapshots({"a": {"h": h1.snapshot()},
                         "b": {"h": h2.snapshot()}})


def test_dstpu_metrics_pool_mode(tmp_path, capsys):
    """`dstpu_metrics --pool`: the latest record of every *.jsonl in the
    dir merges into one pool table; non-metrics JSONL (trace logs) are
    skipped."""
    for i, name in enumerate(("r0", "r1")):
        h = Histogram("serving/ttft_ms")
        for v in (5.0, 50.0 * (i + 1)):
            h.observe(v)
        rec = {"step": i + 1, "time": 100.0 + i,
               "metrics": {"serving/ttft_ms": h.snapshot(),
                           "router/completed":
                               {"type": "counter", "value": 2.0}}}
        (tmp_path / f"{name}.jsonl").write_text(json.dumps(rec) + "\n")
    (tmp_path / "r0.trace.jsonl").write_text('{"span": 1, "trace": "t"}\n')
    assert metrics_main([str(tmp_path), "--pool", "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["sources"] == ["r0", "r1"]
    assert out["metrics"]["serving/ttft_ms"]["count"] == 4
    assert out["metrics"]["router/completed"]["value"] == 4.0
    # human table renders the merged view too
    assert metrics_main([str(tmp_path), "--pool"]) == 0
    assert "serving/ttft_ms" in capsys.readouterr().out


# ----------------------------------------------------------------------
# exporters: golden output
# ----------------------------------------------------------------------


def _golden_registry():
    r = MetricsRegistry()
    r.counter("serving/requests").inc(3)
    r.gauge("serving/queue_depth").set(2.5)
    h = r.histogram("serving/ttft_ms", bounds=[1.0, 10.0, 100.0])
    for v in (0.5, 5.0, 50.0, 5000.0):
        h.observe(v)
    return r


def test_prometheus_golden():
    expected = "\n".join([
        "# HELP serving_queue_depth deepspeed-tpu serving/queue_depth",
        "# TYPE serving_queue_depth gauge",
        "serving_queue_depth 2.5",
        "# HELP serving_requests_total deepspeed-tpu serving/requests",
        "# TYPE serving_requests_total counter",
        "serving_requests_total 3",
        "# HELP serving_ttft_ms deepspeed-tpu serving/ttft_ms",
        "# TYPE serving_ttft_ms histogram",
        'serving_ttft_ms_bucket{le="1"} 1',
        'serving_ttft_ms_bucket{le="10"} 2',
        'serving_ttft_ms_bucket{le="100"} 3',
        'serving_ttft_ms_bucket{le="+Inf"} 4',
        "serving_ttft_ms_sum 5055.5",
        "serving_ttft_ms_count 4",
    ]) + "\n"
    assert prometheus_text(_golden_registry()) == expected


def _check_prometheus_conformance(text):
    """Validate the text exposition rules an external scraper enforces:
    name grammar, HELP-then-TYPE exactly once per family, counters ending
    in `_total`, the mandatory `+Inf` bucket, and `_count`/`_sum`
    consistency (cumulative +Inf count == _count)."""
    import re
    name_re = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")
    lines = text.strip().splitlines()
    seen_help, seen_type, types = set(), set(), {}
    samples = {}                       # family -> [(suffix_or_name, value)]
    for ln in lines:
        if ln.startswith("# HELP "):
            fam = ln.split()[2]
            assert fam not in seen_help, f"duplicate HELP for {fam}"
            assert fam not in seen_type, f"HELP after TYPE for {fam}"
            assert "\n" not in ln      # newlines must be escaped
            seen_help.add(fam)
        elif ln.startswith("# TYPE "):
            _, _, fam, kind = ln.split()
            assert fam in seen_help, f"TYPE before HELP for {fam}"
            assert fam not in seen_type, f"duplicate TYPE for {fam}"
            assert kind in ("counter", "gauge", "histogram")
            seen_type.add(fam)
            types[fam] = kind
        else:
            name = ln.split("{", 1)[0].split()[0]
            assert name_re.match(name), f"bad sample name {name!r}"
            fam = name
            for suffix in ("_bucket", "_sum", "_count"):
                if name.endswith(suffix) and name[:-len(suffix)] in types:
                    fam = name[:-len(suffix)]
            assert fam in types, f"sample {name!r} outside any TYPE family"
            float(ln.split()[-1])      # value parses
            samples.setdefault(fam, []).append(ln)
    for fam, kind in types.items():
        assert samples.get(fam), f"family {fam} has no samples"
        if kind == "counter":
            assert fam.endswith("_total")
        if kind == "histogram":
            buckets = [s for s in samples[fam] if "_bucket{" in s]
            les = [re.search(r'le="([^"]+)"', s).group(1) for s in buckets]
            assert les[-1] == "+Inf", f"{fam} misses the +Inf bucket"
            counts = [int(s.split()[-1]) for s in buckets]
            assert counts == sorted(counts), f"{fam} buckets not cumulative"
            count_line = next(s for s in samples[fam]
                              if s.startswith(f"{fam}_count "))
            assert int(count_line.split()[-1]) == counts[-1], \
                f"{fam}: +Inf bucket != _count"
            assert any(s.startswith(f"{fam}_sum ") for s in samples[fam])


def test_prometheus_conformance_rules():
    # the golden registry plus every escaping hazard: slashes and dashes in
    # names, a leading digit, backslash + newline in HELP text
    reg = _golden_registry()
    reg.counter("1weird/name-with.dots").inc()
    reg.histogram("spans/dur_ms").observe(3.0)
    text = prometheus_text(reg, help_map={
        "spans/dur_ms": 'line1\nline2 "quoted" \\backslash'})
    _check_prometheus_conformance(text)
    # escaping: the HELP newline/backslash survive as \n and \\
    help_line = next(ln for ln in text.splitlines()
                     if ln.startswith("# HELP spans_dur_ms"))
    assert "\\n" in help_line and "\\\\" in help_line
    assert "_1weird_name_with_dots_total 1" in text
    # and the serving engine's real registry passes the same checker
    _check_prometheus_conformance(prometheus_text(_golden_registry()))


def test_prometheus_file_exporter_atomic(tmp_path):
    path = tmp_path / "m.prom"
    exp = PrometheusFileExporter(path)
    exp.export(_golden_registry())
    assert path.read_text() == prometheus_text(_golden_registry())
    assert not (tmp_path / "m.prom.tmp").exists()


def test_jsonl_exporter_golden_roundtrip(tmp_path):
    path = tmp_path / "m.jsonl"
    exp = JsonlExporter(path)
    reg = _golden_registry()
    exp.export(reg, step=7)
    exp.export(reg, step=8)
    exp.close()
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 2
    rec = json.loads(lines[-1])
    assert rec["step"] == 8
    assert rec["metrics"] == reg.snapshot()


def test_dstpu_metrics_watch_rate_column():
    """--watch threads the previous snapshot through render(): counters
    grow a per-interval rate column (delta/dt), histograms and gauges do
    not, and a counter RESET (monotonic total going backward — process
    restart) suppresses the rate instead of printing a negative one."""
    from deepspeed_tpu.telemetry.cli import counter_rate, render

    def rec(t, tokens, depth):
        return {"step": 1, "time": t, "metrics": {
            "serving/tokens": {"type": "counter", "value": tokens},
            "serving/queue_depth": {"type": "gauge", "value": depth},
            "serving/ttft_ms": {"type": "histogram", "count": 3, "sum": 30.0,
                                "mean": 10.0, "min": 1.0, "max": 20.0,
                                "p50": 10.0, "p90": 19.0, "p99": 20.0}}}

    r0, r1 = rec(100.0, 1000.0, 2.0), rec(104.0, 1600.0, 3.0)
    assert counter_rate("serving/tokens", r1, r0) == pytest.approx(150.0)
    assert counter_rate("serving/tokens", r1, None) is None    # first sample
    assert counter_rate("serving/queue_depth", r1, r0) is None  # not a counter
    assert counter_rate("serving/tokens", r0, r1) is None       # dt <= 0
    reset = rec(108.0, 5.0, 1.0)
    assert counter_rate("serving/tokens", reset, r1) is None    # reset guard
    out = render(r1, prev=r0)
    row = next(ln for ln in out.splitlines() if "serving/tokens" in ln)
    assert "150/s" in row
    hist_row = next(ln for ln in out.splitlines() if "ttft" in ln)
    assert "/s" not in hist_row
    # without prev (plain one-shot mode) the rate column stays empty
    assert "150/s" not in render(r1)


def test_dstpu_metrics_cli_json_roundtrip(tmp_path, capsys):
    reg = _golden_registry()
    JsonlExporter(tmp_path / "serving.jsonl").export(reg, step=11)
    # dir resolution + --json round-trips the exact snapshot
    assert metrics_main([str(tmp_path), "--json"]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["step"] == 11 and rec["metrics"] == reg.snapshot()
    # table mode renders every metric name
    assert metrics_main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    for name in reg.snapshot():
        assert name in out
    # missing log -> nonzero exit
    assert metrics_main([str(tmp_path / "nope")]) == 1


# ----------------------------------------------------------------------
# monitor bridge + never-die
# ----------------------------------------------------------------------


def test_monitor_bridge_flattens_and_never_dies(tmp_path):
    events = []
    good = types.SimpleNamespace(
        enabled=True, write_events=lambda evs: events.extend(evs))
    reg = _golden_registry()
    MonitorBridge(good).export(reg, step=5)
    tags = {t for t, _v, _s in events}
    assert ("serving/ttft_ms/p50" in tags and "serving/ttft_ms/p99" in tags
            and "serving/ttft_ms/count" in tags)
    assert ("serving/queue_depth", 2.5, 5) in events
    # a monitor that throws (dropped wandb network) must not crash the caller
    def boom(_evs):
        raise OSError("network down")
    bad = types.SimpleNamespace(enabled=True, write_events=boom)
    MonitorBridge(bad).export(reg, step=6)     # does not raise


def test_write_events_safe_is_the_one_name():
    from deepspeed_tpu.monitor import monitor as M
    assert not hasattr(M, "write_recovery_events")
    assert not hasattr(M, "write_serving_events")
    M.write_events_safe(None, [("a", 1.0, 0)])          # no monitor: no-op
    def boom(_evs):
        raise RuntimeError("die")
    M.write_events_safe(types.SimpleNamespace(enabled=True,
                                              write_events=boom),
                        [("a", 1.0, 0)])                # guarded


def test_csv_monitor_caches_handles(tmp_path):
    from deepspeed_tpu.monitor.monitor import CsvMonitor
    cfg = types.SimpleNamespace(enabled=True, output_path=str(tmp_path),
                                job_name="job")
    m = CsvMonitor(cfg)
    m.write_events([("Train/loss", 1.0, 0), ("Train/lr", 0.1, 0)])
    m.write_events([("Train/loss", 0.5, 1)])
    assert set(m._files) == {"Train/loss", "Train/lr"}   # one handle per tag
    f_loss = m._files["Train/loss"][0]
    m.write_events([("Train/loss", 0.25, 2)])
    assert m._files["Train/loss"][0] is f_loss           # handle reused
    rows = (tmp_path / "job" / "Train_loss.csv").read_text().strip() \
        .splitlines()
    assert len(rows) == 4 and rows[0].startswith("step")  # header + 3 rows
    m.close()
    assert f_loss.closed and m._files == {}
    m.close()                                            # idempotent


def test_record_events_routes_ms_to_histograms(tmp_path):
    t = Telemetry(TelemetryConfig(enabled=True, output_path=str(tmp_path),
                                  prometheus=False, jsonl=False))
    for ms in (10.0, 20.0, 40.0):
        t.record_events([("Checkpoint/save_ms", ms, 1),
                         ("Checkpoint/bytes", 1024.0, 1)])
    snap = t.registry.snapshot()
    assert snap["Checkpoint/save_ms"]["type"] == "histogram"
    assert snap["Checkpoint/save_ms"]["count"] == 3
    assert snap["Checkpoint/bytes"] == {"type": "gauge", "value": 1024.0}


def test_ckpt_saver_emit_routes_through_telemetry(tmp_path):
    from deepspeed_tpu.checkpoint.saver import _emit_ckpt_events
    telem = Telemetry(TelemetryConfig(enabled=True,
                                      output_path=str(tmp_path),
                                      prometheus=False, jsonl=False))
    fake_engine = types.SimpleNamespace(monitor=None, telemetry=telem)
    _emit_ckpt_events(fake_engine, [("Checkpoint/save_ms", 12.5, 3)])
    assert telem.registry.snapshot()["Checkpoint/save_ms"]["count"] == 1
    # engines without a telemetry attribute (hybrid/inference) stay safe
    _emit_ckpt_events(types.SimpleNamespace(monitor=None),
                      [("Checkpoint/save_ms", 1.0, 0)])


# ----------------------------------------------------------------------
# spans + nvtx guard
# ----------------------------------------------------------------------


def test_span_chrome_trace_sink(tmp_path):
    t = Telemetry(TelemetryConfig(enabled=True, output_path=str(tmp_path),
                                  prometheus=False, jsonl=False,
                                  chrome_trace=True), subsystem="sched")
    with t.span("serving/admit"):
        pass
    with t.span("serving/decode_window"):
        pass
    t.close()
    body = (tmp_path / "sched.trace.json").read_text()
    assert body.startswith("[")
    events = [json.loads(ln.rstrip(",")) for ln in
              body.strip().splitlines()[1:]]
    assert [e["name"] for e in events] == ["serving/admit",
                                           "serving/decode_window"]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in events)


def test_chrome_sink_metadata_and_tid(tmp_path):
    """ChromeTraceSink speaks the metadata ("M") subset and honors a
    caller-supplied tid, so a serving pool's replicas land on separate
    NAMED Perfetto tracks instead of collapsing onto tid 0."""
    from deepspeed_tpu.telemetry.spans import ChromeTraceSink, span
    path = tmp_path / "t.trace.json"
    sink = ChromeTraceSink(path)
    sink.add_meta("process_name", "dstpu serving pool")
    sink.add_meta("thread_name", "router", tid=0)
    sink.add_meta("thread_name", "replica r1", tid=1)
    with span("serving/admit", sink=sink):            # default tid 0
        pass
    with span("serving/decode_window", sink=sink, tid=1):
        pass
    sink.close()
    events = [json.loads(ln.rstrip(",")) for ln in
              path.read_text().strip().splitlines()[1:]]
    meta = [e for e in events if e["ph"] == "M"]
    assert [(e["name"], e["tid"], e["args"]["name"]) for e in meta] == [
        ("process_name", 0, "dstpu serving pool"),
        ("thread_name", 0, "router"),
        ("thread_name", 1, "replica r1")]
    spans_x = {e["name"]: e["tid"] for e in events if e["ph"] == "X"}
    assert spans_x == {"serving/admit": 0, "serving/decode_window": 1}
    # the Telemetry facade plumbs tid through span() too
    t = Telemetry(TelemetryConfig(enabled=True, output_path=str(tmp_path),
                                  prometheus=False, jsonl=False,
                                  chrome_trace=True), subsystem="pool")
    with t.span("serving/verify", tid=3):
        pass
    t.close()
    events = [json.loads(ln.rstrip(",")) for ln in
              (tmp_path / "pool.trace.json").read_text()
              .strip().splitlines()[1:]]
    assert events[0]["name"] == "serving/verify" and events[0]["tid"] == 3


def test_metric_catalog_lint():
    """The docs/profiling.md metric catalog and the source tree must agree:
    every literal metric name recorded through the telemetry facade (or a
    registry handle) appears in the catalog, and every catalog row names a
    metric that still exists (no dead rows). The check itself lives in ONE
    place — `deepspeed_tpu.analysis.rules_catalog` (rule DT005), shared
    with `bin/dstpu_lint` — so the CLI and this test can never drift; the
    dynamic-name escape hatch (router counters, LEDGER_GAUGES, record_events
    routing) is enumerated there."""
    import pathlib

    from deepspeed_tpu.analysis.rules_catalog import catalog_findings

    repo_root = pathlib.Path(deepspeed_tpu.__file__).parent.parent
    findings = catalog_findings(repo_root)
    assert not findings, "metric catalog drift:\n" + "\n".join(
        f.render() for f in findings)


def test_disabled_telemetry_is_total_noop(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    t = Telemetry(TelemetryConfig(output_path="telemetry"))   # enabled=False
    assert not t.enabled
    t.observe("x_ms", 1.0)
    t.inc("c")
    t.set_gauge("g", 1.0)
    t.record_events([("a_ms", 1.0, 0)])
    with t.span("region"):
        pass
    t.maybe_export(1)
    t.close()
    assert t.registry.snapshot() == {}
    assert list(tmp_path.iterdir()) == []                 # nothing written
    assert Telemetry(None).enabled is False               # no config at all


def test_registry_only_config_writes_no_dir(tmp_path):
    # enabled with every file sink off: the
    # registry records but no output directory may appear
    out = tmp_path / "tel"
    t = Telemetry(TelemetryConfig(enabled=True, output_path=str(out),
                                  prometheus=False, jsonl=False,
                                  monitor_bridge=False))
    t.observe("x_ms", 1.0)
    t.export(step=1)
    t.close()
    assert not out.exists()


def test_close_flushes_final_export(tmp_path):
    # a run shorter than export_interval must still land in the files
    t = Telemetry(TelemetryConfig(enabled=True, output_path=str(tmp_path),
                                  export_interval=1000), subsystem="m")
    t.observe("lat_ms", 5.0)
    t.maybe_export(3)                       # 3 % 1000 != 0: nothing yet
    assert not (tmp_path / "m.jsonl").exists()
    t.close()
    rec = load_latest(tmp_path / "m.jsonl")
    assert rec["metrics"]["lat_ms"]["count"] == 1
    t.close()                               # idempotent


def test_chrome_trace_fresh_file_per_run(tmp_path):
    from deepspeed_tpu.telemetry.spans import ChromeTraceSink, span
    path = tmp_path / "t.trace.json"
    for run in range(2):
        sink = ChromeTraceSink(path)
        with span(f"run{run}", sink=sink):
            pass
        sink.close()
    body = path.read_text()
    # the second sink truncated: one run, one timeline, no stale events
    assert '"run1"' in body and '"run0"' not in body


def test_nvtx_hard_noop_without_profiler(monkeypatch):
    from deepspeed_tpu.utils import nvtx
    monkeypatch.setattr(nvtx, "_TraceAnnotation", None)
    assert nvtx.range_push("r") is None
    nvtx.range_pop()                                      # empty stack: no-op
    with nvtx.annotate("region"):
        pass

    @nvtx.instrument_w_nvtx
    def f(x):
        return x + 1

    assert f(1) == 2


# ----------------------------------------------------------------------
# ServingEngine: TTFT/TPOT on a mixed trace
# ----------------------------------------------------------------------


def test_serving_latency_histograms_mixed_trace(tmp_path):
    engine = _mk_serving_engine(tmp_path, export_interval=4)
    serving = engine.serving(max_slots=4, max_context=128)
    rng = np.random.default_rng(0)
    shapes = [(5, 4), (30, 8), (17, 3), (50, 6), (9, 5), (23, 7)]
    reqs = [Request(uid=i, tokens=rng.integers(0, 256, (L,)).astype(np.int32),
                    max_new_tokens=n, stop_on_eos=False)
            for i, (L, n) in enumerate(shapes)]
    done = serving.run(reqs)
    assert len(done) == len(reqs)

    # monotone per-request timestamps: arrival -> admission -> first token
    # (strictly after admission: prefill must run first) -> finish
    for r in done.values():
        t = r.timing
        assert t["arrival"] <= t["admit"] < t["first_token"] <= t["finish"]

    lat = serving.latency_snapshot()
    assert set(lat) == {"ttft_ms", "tpot_ms", "queue_wait_ms", "e2e_ms"}
    assert lat["ttft_ms"]["count"] == len(reqs)
    assert lat["e2e_ms"]["count"] == len(reqs)
    assert lat["queue_wait_ms"]["count"] == len(reqs)
    # TPOT is per-TOKEN (interpolated inside each emission burst, so decode
    # windows and accepted drafts stay honest): one sample per decode-phase
    # token — every generated token except each request's first
    assert lat["tpot_ms"]["count"] == sum(n for _, n in shapes) - len(reqs)
    assert 0 < lat["ttft_ms"]["p50"] <= lat["ttft_ms"]["p99"]
    assert 0 < lat["tpot_ms"]["p50"] <= lat["tpot_ms"]["p99"]
    assert lat["queue_wait_ms"]["min"] >= 0
    # TTFT covers at least the queue wait for every request
    assert lat["e2e_ms"]["max"] >= lat["ttft_ms"]["min"]
    assert "latency" in serving.stats()

    # gauges settle at drained values; the export interval produced files
    snap = serving.telemetry.registry.snapshot()
    assert snap["serving/queue_depth"]["value"] == 0
    assert snap["serving/active_slots"]["value"] == 0
    assert (tmp_path / "serving.jsonl").exists()
    assert (tmp_path / "serving.prom").exists()
    assert load_latest(tmp_path)["metrics"].keys() == snap.keys()


def test_serving_disabled_default_unchanged(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    engine = _mk_serving_engine(tmp_path, telemetry=False)
    serving = engine.serving(max_slots=2, max_context=128)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, tokens=rng.integers(0, 256, (9,)).astype(np.int32),
                    max_new_tokens=3, stop_on_eos=False) for i in range(3)]
    done = serving.run(reqs)
    # contract: compile_stats unchanged, results carry no timing, stats()
    # grows no latency block, and NO files appear anywhere
    assert_one_compile_each(serving)
    assert all(r.timing is None for r in done.values())
    assert "latency" not in serving.stats()
    assert serving.latency_snapshot() == {}
    assert list(tmp_path.iterdir()) == []


# ----------------------------------------------------------------------
# train lane: MFU accounting
# ----------------------------------------------------------------------


def test_train_step_telemetry_mfu(tmp_path):
    _mk_mesh(data=-1)
    model = make_gpt_model(cfg=TINY, name="tiny")
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 10**9,
        # the CPU harness has no published peak: MFU exists only against
        # the stated override
        "telemetry": {"enabled": True, "output_path": str(tmp_path),
                      "export_interval": 1, "peak_tflops": 1.0}})
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, (engine.train_batch_size(), 33)) \
        .astype(np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    steps = 3
    for _ in range(steps):
        engine.train_batch(batch)

    snap = engine.telemetry.registry.snapshot()
    mfu = snap["train/mfu"]["value"]
    assert 0.0 < mfu <= 1.0                   # achieved MFU is a fraction
    assert snap["train/step_time_ms"]["count"] == steps
    assert snap["train/step_time_ms"]["p50"] > 0
    assert snap["train/tokens_per_sec"]["value"] > 0
    assert snap["train/tflops_per_chip"]["value"] > 0
    # program flops measured exactly once, reused across steps
    assert engine._program_flops is not None and engine._program_flops > 0
    rec = load_latest(tmp_path / "train.jsonl")
    assert rec is not None and "train/mfu" in rec["metrics"]


def _train_engine(**config):
    _mk_mesh(data=1)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_gpt_model(cfg=TINY, name="tiny"), config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0}, **config})
    toks = np.random.default_rng(0).integers(
        0, 256, (engine.train_batch_size(), 33)).astype(np.int32)
    return engine, {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_train_rates_and_step_times_read_the_step_ring_only(tmp_path):
    # a scripted clock on the recorder, 100 ms a reading, while a real
    # `train_batch` of this model takes about a millisecond: whatever the
    # gauges and the histogram hold came from the records' stamps, and from
    # no clock of the engine's own
    engine, batch = _train_engine(
        steps_per_print=10**9,
        telemetry={"enabled": True, "output_path": str(tmp_path),
                   "prometheus": False, "jsonl": False, "peak_tflops": 1.0})
    now = [0.0]

    def clock():
        now[0] += 0.1
        return now[0]

    engine.steptrace.clock = clock
    steps = 3
    for _ in range(steps):
        float(engine.train_batch(batch))
    recs = engine.steptrace.records()
    assert len(recs) == steps
    span = recs[-1].t_end - recs[0].t_start
    assert span > steps * 0.5                   # >= 6 readings a step
    reg = engine.telemetry.registry
    tokens = batch["tokens"].size
    assert reg.gauge("train/tokens_per_sec").value == \
        pytest.approx(steps * tokens / span)
    assert reg.gauge("train/tflops_per_chip").value == \
        pytest.approx(engine._program_flops * steps / span / 1e12)
    assert reg.gauge("train/mfu").value == \
        pytest.approx(engine._program_flops * steps / span / 1e12)
    hist = reg.histogram("train/step_time_ms")
    windows = [(r.t_end - r.t_start) * 1e3 for r in recs]
    assert hist.count == steps
    assert hist.sum == pytest.approx(sum(windows))
    assert (hist.min, hist.max) == pytest.approx((min(windows), max(windows)))


def test_wall_clock_breakdown_logs_the_ring_phases_and_no_barrier(monkeypatch):
    import logging

    import jax

    from deepspeed_tpu.utils.logging import logger as ds_logger

    def no_barrier():
        raise AssertionError("train_batch fences with effects_barrier, "
                             "which waits for no ordinary computation")

    monkeypatch.setattr(jax, "effects_barrier", no_barrier)
    engine, batch = _train_engine(steps_per_print=2, wall_clock_breakdown=True)
    messages = []
    handler = logging.Handler()
    handler.emit = lambda r: messages.append(r.getMessage())
    ds_logger.addHandler(handler)       # propagate=False: hook it
    try:
        for _ in range(3):
            engine.train_batch(batch)   # the loss is not fetched: no fence
    finally:
        ds_logger.removeHandler(handler)
    lines = [m for m in messages if "samples/s=" in m]
    assert len(lines) == 1 and "step=2," in lines[0], messages
    for phase in ("train/place", "train/dispatch", "train/fence",
                  "train/after_step"):
        assert f"| {phase}: " in lines[0]
    rate = float(lines[0].split("samples/s=")[1].split()[0])
    first, second = engine.steptrace.records()[:2]
    assert rate == pytest.approx(
        2 * engine.train_batch_size() / (second.t_end - first.t_start),
        rel=1e-4)


def test_documents_and_package_name_no_retired_benchmark():
    """`benchmark/run.py` is the one instrument for speed; the lane runner
    it replaced and its environment knobs are gone, and no sentence of the
    README, `docs/` or the package may send a reader to them."""
    import pathlib
    import re

    root = pathlib.Path(deepspeed_tpu.__file__).parent.parent
    files = [root / "README.md", *sorted((root / "docs").glob("*.md")),
             *sorted((root / "deepspeed_tpu").rglob("*.py"))]
    assert len(files) > 100
    retired = re.compile(r"(?<![A-Za-z0-9_])bench\.py|BENCH_")
    found = [f"{f.relative_to(root)}:{n}: {line.strip()}"
             for f in files
             for n, line in enumerate(f.read_text().splitlines(), 1)
             if retired.search(line)]
    assert not found, "\n".join(found)


def test_train_peak_flops_override(tmp_path):
    t = Telemetry(TelemetryConfig(enabled=True, output_path=str(tmp_path),
                                  prometheus=False, jsonl=False,
                                  peak_tflops=100.0))
    assert t.peak_flops() == pytest.approx(100e12)
    t2 = Telemetry(TelemetryConfig(enabled=True, output_path=str(tmp_path),
                                   prometheus=False, jsonl=False))
    # no override + a device with no published peak on file (the CPU
    # harness): no peak, hence no train/mfu gauge — never another chip's
    assert t2.peak_flops() is None
