"""End-to-end request tracing, failure flight recorder, compile watchdog
(deepspeed_tpu/telemetry/tracing.py + flight_recorder.py): connected span
trees across the serving-router pool, failover trace continuity, black-box
dumps on replica failure, recompile detection over the persistent jitted
programs, and the `dstpu_trace` CLI.

Everything rides the `tracing` marker (tier-1; run alone with
`pytest -m tracing`).
"""

import json
import pathlib

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig, TelemetryConfig
from deepspeed_tpu.inference.engine import init_inference
from deepspeed_tpu.inference.kv_cache import TRASH_BLOCK
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model
from deepspeed_tpu.serving import ServingRouter
from deepspeed_tpu.telemetry import CompileWatchdog, FlightRecorder, Telemetry
from deepspeed_tpu.telemetry.flight_recorder import _WatchedProgram
from deepspeed_tpu.telemetry.tracing import (NULL_TRACER, Tracer, load_spans,
                                             trace_main)

pytestmark = pytest.mark.tracing

TINY = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=256,
                 vocab_size=256, dtype=jnp.float32, remat=False)
BS = 16


@pytest.fixture(scope="module")
def engine():
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    mesh_mod.init_mesh(MeshConfig(data=1, tensor=1, sequence=1, expert=1,
                                  pipe=1))
    spec = make_gpt_decode_model(cfg=TINY, name="tiny")
    return init_inference(model=spec, config={
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": BS, "max_out_tokens": 64})


def _replica(engine, **over):
    kw = dict(max_slots=2, max_context=96, prefill_chunk=BS,
              enable_prefix_caching=True)
    kw.update(over)
    return engine.serving(**kw)


def _traced_router(engine, tmp_path, n=2, **rover):
    tcfg = TelemetryConfig(enabled=True, output_path=str(tmp_path),
                           prometheus=False, jsonl=False,
                           tracing=True, flight_recorder=True)
    reps = [_replica(engine,
                     spec_decode={"drafter": "ngram", "draft_k": 3})
            for _ in range(n)]
    return ServingRouter(replicas=reps, telemetry_config=tcfg, **rover)


def _by_trace(spans):
    traces = {}
    for s in spans:
        traces.setdefault(s["trace"], []).append(s)
    return traces


def _shared_prefix_trace(rng, n, prefix_blocks=2):
    prefix = rng.integers(0, TINY.vocab_size,
                          (prefix_blocks * BS,)).astype(np.int32)
    tails = rng.integers(2, 14, (n,))
    return [np.concatenate([prefix, rng.integers(0, TINY.vocab_size,
                                                 (t,)).astype(np.int32)])
            for t in tails]


def _chrome_events(path):
    body = pathlib.Path(path).read_text()
    assert body.startswith("[")
    return [json.loads(ln.rstrip(",")) for ln in
            body.strip().splitlines()[1:]]


# ----------------------------------------------------------------------
# acceptance: one connected trace through a 2-replica spec-decode router
# ----------------------------------------------------------------------


def test_router_trace_single_connected_spec_decode(engine, tmp_path, capsys):
    # round_robin spreads the shared-prefix trace over BOTH replicas, so
    # the chrome view exercises spans on every named track (affinity would
    # rightly coalesce it onto one)
    router = _traced_router(engine, tmp_path, routing_policy="round_robin")
    rng = np.random.default_rng(3)
    prompts = _shared_prefix_trace(rng, 5)
    res = router.run([Request(uid=i, tokens=p, max_new_tokens=5,
                              stop_on_eos=False)
                      for i, p in enumerate(prompts)])
    assert sorted(res) == list(range(len(prompts)))

    spans = load_spans(tmp_path / "router.trace.jsonl")
    traces = _by_trace(spans)
    # ONE trace id per request, spanning router AND replica hops
    assert len(traces) == len(prompts)
    for s in spans:
        assert len({x["trace"] for x in spans if x["uid"] == s["uid"]}) == 1
    for tid_, tr in traces.items():
        by_id = {s["span"]: s for s in tr}
        roots = [s for s in tr if s["parent"] == 0]
        assert len(roots) == 1 and roots[0]["name"] == "request"
        # every non-root span parents INSIDE its own trace (connected tree)
        for s in tr:
            if s["parent"] != 0:
                assert s["parent"] in by_id
        names = {s["name"] for s in tr}
        # router-side dispatch + replica-side prefill/verify/completion
        assert {"dispatch", "submit", "admit", "prefill_chunk",
                "verify", "retire"} <= names
        # engine spans nest under the router's dispatch span
        disp = next(s for s in tr if s["name"] == "dispatch")
        pf = next(s for s in tr if s["name"] == "prefill_chunk")
        assert pf["parent"] == disp["span"]
        # replica spans live on a nonzero (per-replica) tid; router on 0
        assert disp["tid"] == 0 and pf["tid"] in (1, 2)

    # chrome view: named process + one named track per replica, flow arrows
    evs = _chrome_events(tmp_path / "router.trace.json")
    meta = {(e["name"], e.get("tid")): e["args"]["name"]
            for e in evs if e["ph"] == "M"}
    assert meta[("process_name", 0)] == "dstpu serving pool"
    assert meta[("thread_name", 1)] == "replica r0"
    assert meta[("thread_name", 2)] == "replica r1"
    assert {e["tid"] for e in evs if e["ph"] == "X"} >= {0, 1, 2}
    starts = [e for e in evs if e["ph"] == "s"]
    ends = [e for e in evs if e["ph"] == "f"]
    # every dispatch arrow lands on a replica track at admission
    assert len(starts) == len(ends) == len(prompts)
    assert {e["id"] for e in starts} == {e["id"] for e in ends}

    # dstpu_trace --uid reconstructs the timeline as a table
    assert trace_main([str(tmp_path), "--uid", "2"]) == 0
    out = capsys.readouterr().out
    for name in ("request", "dispatch", "prefill_chunk", "verify", "retire"):
        assert name in out
    # --slowest ranks by e2e with per-phase columns
    assert trace_main([str(tmp_path), "--slowest", "3"]) == 0
    out = capsys.readouterr().out
    assert "e2e_ms" in out and "verify" in out
    router.telemetry.close()


# ----------------------------------------------------------------------
# acceptance: failover keeps ONE trace id; quarantine lands in the dump
# ----------------------------------------------------------------------


def test_trace_continuity_under_failover(engine, tmp_path):
    router = _traced_router(engine, tmp_path)
    rng = np.random.default_rng(7)
    prompts = _shared_prefix_trace(rng, 6)
    for i, p in enumerate(prompts):
        router.submit(Request(uid=i, tokens=p, max_new_tokens=5,
                              stop_on_eos=False))
    res = {}
    for _ in range(2):
        for d in router.step():
            res[d.uid] = d
    victim = next(rec.replica for rec in router._pending.values()
                  if rec.replica is not None)
    router.kill_replica(victim)
    while router.in_flight:
        for d in router.step():
            res[d.uid] = d
    assert sorted(res) == list(range(len(prompts)))

    spans = load_spans(tmp_path / "router.trace.jsonl")
    rerouted = {s["uid"] for s in spans if s["name"] == "reroute"}
    assert rerouted, "the kill must have re-routed at least one request"
    for uid in rerouted:
        mine = [s for s in spans if s["uid"] == uid]
        # ONE trace id across both attempts — the continuity contract
        assert len({s["trace"] for s in mine}) == 1
        names = [s["name"] for s in mine]
        # the re-route is a visible span between two dispatches
        assert "reroute" in names
        assert names.count("dispatch") == 2
        rr = next(s for s in mine if s["name"] == "reroute")
        assert rr["attrs"]["from"] == victim
        # both dispatch attempts hang off the root, not off each other
        root = next(s for s in mine if s["parent"] == 0)
        for d in (s for s in mine if s["name"] == "dispatch"):
            assert d["parent"] == root["span"]

    # the black box: quarantine event + state snapshot hit disk
    dumps = sorted(tmp_path.glob("router.flightrec.*.json"))
    assert len(dumps) == 1
    dump = json.loads(dumps[0].read_text())
    assert f"replica {victim} failed" in dump["reason"]
    kinds = [e["kind"] for e in dump["events"]]
    assert "quarantine" in kinds and "dispatch" in kinds
    q = next(e for e in dump["events"] if e["kind"] == "quarantine")
    assert q["replica"] == victim and q["requeued"] > 0
    # the snapshot is the router's full stats() at failure time
    assert dump["state"]["counters"]["replica_failures"] == 1
    assert victim in dump["state"]["replicas"]
    router.telemetry.close()


# ----------------------------------------------------------------------
# standalone engine: the engine owns (and closes) its own traces
# ----------------------------------------------------------------------


def test_standalone_engine_trace_and_flight_recorder(tmp_path):
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    mesh_mod.init_mesh(MeshConfig(data=1, tensor=1, sequence=1, expert=1,
                                  pipe=1))
    spec = make_gpt_decode_model(cfg=TINY, name="tiny")
    eng = init_inference(model=spec, config={
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": BS, "max_out_tokens": 64,
        "telemetry": {"enabled": True, "output_path": str(tmp_path),
                      "prometheus": False, "jsonl": False,
                      "tracing": True, "flight_recorder": True,
                      "flight_recorder_events": 4}})
    serving = eng.serving(max_slots=2, max_context=128)
    rng = np.random.default_rng(0)
    res = serving.run([Request(uid=i,
                               tokens=rng.integers(0, 256, (9 + i,))
                               .astype(np.int32),
                               max_new_tokens=4, stop_on_eos=False)
                       for i in range(3)])
    assert len(res) == 3
    spans = load_spans(tmp_path / "serving.trace.jsonl")
    traces = _by_trace(spans)
    assert len(traces) == 3
    for tr in traces.values():
        roots = [s for s in tr if s["parent"] == 0]
        assert len(roots) == 1       # the ENGINE closed its own root span
        assert roots[0]["dur"] > 0
        assert {"submit", "queued", "admit", "prefill_chunk",
                "decode_window", "retire"} <= {s["name"] for s in tr}

    # flight ring: bounded to flight_recorder_events, newest kept
    assert len(serving.flightrec.events()) == 4
    seqs = [e["seq"] for e in serving.flightrec.events()]
    assert seqs == sorted(seqs) and seqs[-1] > 4
    path = serving.flightrec.dump("operator dump", state=serving.stats())
    dump = json.loads(pathlib.Path(path).read_text())
    assert dump["reason"] == "operator dump"
    assert len(dump["events"]) == 4
    assert dump["state"]["tokens_generated"] == 12
    # dumps are numbered; the ring keeps rolling
    path2 = serving.flightrec.dump("again")
    assert path2 != path and pathlib.Path(path2).exists()
    # a NEW recorder in the same dir (a restarted process — exactly when
    # the previous crash's black box matters) resumes numbering past the
    # existing dumps instead of overwriting them
    fresh = FlightRecorder(out_dir=str(tmp_path), subsystem="serving")
    fresh.record("post-restart")
    path3 = fresh.dump("after restart")
    assert path3 not in (path, path2)
    assert json.loads(pathlib.Path(path).read_text())["reason"] \
        == "operator dump"
    serving.telemetry.close()


# ----------------------------------------------------------------------
# acceptance: disabled default = no files, no tracing work on the hot path
# ----------------------------------------------------------------------


def test_disabled_default_no_tracing_work(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    mesh_mod.init_mesh(MeshConfig(data=1, tensor=1, sequence=1, expert=1,
                                  pipe=1))
    spec = make_gpt_decode_model(cfg=TINY, name="tiny")
    eng = init_inference(model=spec, config={
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": BS, "max_out_tokens": 64})
    serving = eng.serving(max_slots=2, max_context=128)
    # the hot path carries NO tracing machinery: the step programs are the
    # raw jitted functions (no watchdog wrapper), the tracer/recorder are
    # the shared disabled singletons, and every record site gates on them
    assert serving.tracer is NULL_TRACER and not serving.tracer.enabled
    assert not serving.flightrec.enabled
    assert not isinstance(serving.programs.decode, _WatchedProgram)
    assert not isinstance(serving.programs.prefill, _WatchedProgram)
    rng = np.random.default_rng(0)
    serving.submit(Request(uid=0,
                           tokens=rng.integers(0, 256, (9,)).astype(np.int32),
                           max_new_tokens=3, stop_on_eos=False))
    assert serving.queue[0][-1] is None          # no TraceContext minted
    res = serving.run([])
    assert res[0].finish_reason == "length"
    assert "watchdog" not in serving.stats()
    assert serving.flightrec.events() == []
    assert list(tmp_path.iterdir()) == []        # NOT ONE file
    # a disabled tracer/recorder accepts every call as a no-op
    NULL_TRACER.record(None, "x", 0.0)
    NULL_TRACER.finish(None, 1.0)
    assert NULL_TRACER.start(0) is None
    serving.flightrec.record("x")
    assert serving.flightrec.dump("x") is None


# ----------------------------------------------------------------------
# compile watchdog: recompiles after warmup are counted and named
# ----------------------------------------------------------------------


def test_compile_watchdog_names_recompiled_program(engine, tmp_path):
    eng2 = init_inference(model=engine.model_spec, config={
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": BS, "max_out_tokens": 64,
        "telemetry": {"enabled": True, "output_path": str(tmp_path),
                      "prometheus": False, "jsonl": False,
                      "flight_recorder": True}})
    serving = eng2.serving(max_slots=2, max_context=128)
    rng = np.random.default_rng(0)
    serving.run([Request(uid=0,
                         tokens=rng.integers(0, 256, (9,)).astype(np.int32),
                         max_new_tokens=4, stop_on_eos=False)])
    wd = serving.stats()["watchdog"]
    assert wd["recompiles"] == 0                 # warmup compiles are free
    assert wd["programs"]["decode_step"]["compiles"] == 1

    # force a NEW batch shape through the persistent decode program — the
    # exact regression the watchdog exists to catch
    S1 = serving.max_slots + 1
    tok = np.zeros((S1,), np.int32)
    pos = np.ones((S1,), np.int32)
    tables = np.full((S1, serving.nb), TRASH_BLOCK, np.int32)
    _, serving.pool = serving.programs.decode(eng2.params, tok, pos,
                                               serving.pool, tables,
                                               serving._next_rng())
    wd = serving.stats()["watchdog"]
    assert wd["recompiles"] == 1
    assert wd["programs"]["decode_step"]["recompiles"] == 1
    assert wd["programs"]["prefill_step"]["recompiles"] == 0
    snap = serving.telemetry.registry.snapshot()
    assert snap["telemetry/recompiles"]["value"] == 1.0
    assert snap["telemetry/compile_ms"]["count"] >= 2    # warmups + recompile
    ev = [e for e in serving.flightrec.events() if e["kind"] == "recompile"]
    assert len(ev) == 1 and ev[0]["program"] == "decode_step"
    assert ev[0]["shapes"][0] == [S1] and ev[0]["compile_ms"] > 0
    # compile_stats still reads through the wrapper
    assert serving.compile_stats()["decode_step"] == 2
    serving.telemetry.close()


def test_compile_watchdog_unit_wrap_and_disabled(tmp_path):
    telem = Telemetry(TelemetryConfig(enabled=True, output_path=str(tmp_path),
                                      prometheus=False, jsonl=False))
    rec = FlightRecorder(out_dir=str(tmp_path), capacity=8)
    wd = CompileWatchdog(telem, recorder=rec)
    calls = []

    @jax.jit
    def f(x):
        calls.append(1)
        return x * 2

    g = wd.wrap("f", f)
    g(jnp.zeros((2,)))
    g(jnp.zeros((2,)))                           # cache hit: no recompile
    assert wd.recompiles == 0
    g(jnp.zeros((3,)))                           # new shape after warmup
    assert wd.recompiles == 1
    assert wd.programs["f"] == {"compiles": 2, "recompiles": 1,
                                "last_shapes": [(3,)]}
    assert [e["kind"] for e in rec.events()] == ["recompile"]
    # disabled telemetry: wrap returns the function UNTOUCHED
    off = CompileWatchdog(None)
    assert off.wrap("f", f) is f


# ----------------------------------------------------------------------
# tracer + CLI units
# ----------------------------------------------------------------------


def test_tracer_units_parenting_and_torn_line(tmp_path):
    t = Tracer(tmp_path / "u.trace.jsonl")
    ctx = t.start("req", t0=10.0, owner="router")
    assert ctx.parent_id == ctx.root_id          # children default to root
    sid = t.record(ctx, "dispatch", 10.5, 0.0, parent=ctx.root_id)
    ctx.parent_id = sid
    t.record(ctx, "prefill", 10.6, 0.1, tid=1)
    t.event(ctx, "mark", 10.7, tid=1)
    t.finish(ctx, 11.0)
    t.close()
    with open(tmp_path / "u.trace.jsonl", "a") as f:
        f.write('{"trace": "t9", "span"')        # torn final line (crash)
    spans = load_spans(tmp_path / "u.trace.jsonl")
    assert len(spans) == 4                       # torn line skipped
    root = next(s for s in spans if s["parent"] == 0)
    assert root["name"] == "request" and root["dur"] == pytest.approx(1.0)
    pf = next(s for s in spans if s["name"] == "prefill")
    assert pf["parent"] == sid and pf["tid"] == 1


def test_dstpu_trace_cli_errors(tmp_path, capsys):
    assert trace_main([str(tmp_path / "nope")]) == 1
    (tmp_path / "x.trace.jsonl").write_text("")
    assert trace_main([str(tmp_path)]) == 1
    t = Tracer(tmp_path / "x.trace.jsonl")
    ctx = t.start(42, t0=0.0)
    t.record(ctx, "phase", 0.1, 0.2)
    t.finish(ctx, 0.5)
    t.close()
    capsys.readouterr()
    assert trace_main([str(tmp_path)]) == 0      # trace listing
    assert "42" in capsys.readouterr().out
    assert trace_main([str(tmp_path), "--uid", "nope"]) == 1
