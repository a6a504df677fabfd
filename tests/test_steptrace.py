"""The step timeline (deepspeed_tpu/telemetry/steptrace.py): phase spans that
tile a scheduler or train step, exposed host seconds from the
dispatched()/ready() marks, the request-lifecycle ring, and the stable kernel
names in the compiled programs.

Rides the `telemetry` marker (tier-1; `pytest -m telemetry`).
"""

import collections
import functools
import gc
import importlib
import json
import os
import re
import sys
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig, TelemetryConfig
from deepspeed_tpu.inference.engine import init_inference
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt import (GPTConfig, make_gpt_decode_model,
                                      make_gpt_model)
from deepspeed_tpu.telemetry import Telemetry, steptrace
from deepspeed_tpu.telemetry.steptrace import StepTrace

# the benchmark's readers of the call ring and of the wait phase, on a
# hand-made ring: held beside the benchmark's own checks, run here too
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "checks"))
from test_call_ring_readers import (  # noqa: E402,F401
    test_a_stall_is_judged_within_its_kind_of_call,
    test_call_ring_reader_on_a_hand_made_ring,
    test_the_metrics_files_name_their_readers_and_arguments)

pytestmark = pytest.mark.telemetry

TINY = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=256,
                 vocab_size=256, dtype=jnp.float32, remat=False)
SERVING_PHASES = {"serving/admit", "serving/prefill_chunk",
                  "serving/decode_build", "serving/decode_window",
                  "serving/read_back", "serving/emit",
                  "serving/housekeeping"}


def _mk_mesh():
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    return mesh_mod.init_mesh(MeshConfig(data=1, tensor=1, sequence=1,
                                         expert=1, pipe=1))


def _engine(**telemetry):
    _mk_mesh()
    cfg = {"dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
           "kv_block_size": 16, "max_out_tokens": 64}
    if telemetry:
        cfg["telemetry"] = telemetry
    return init_inference(model=make_gpt_decode_model(cfg=TINY, name="tiny"),
                          config=cfg)


def _requests(n, prompt_len=9, max_new=3, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, max_new_tokens=max_new, stop_on_eos=False,
                    tokens=rng.integers(0, 256, (prompt_len,)).astype(np.int32))
            for i in range(n)]


class Ticker:
    """A clock that advances one second a reading: every stamp is distinct,
    so phases tile a step only if they share their boundaries."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        self.now += 1.0
        return self.now


# ----------------------------------------------------------------------
# the recorder alone
# ----------------------------------------------------------------------


def test_phases_tile_the_step_and_exposed_follows_the_marks():
    t = {"now": 10.0}
    st = StepTrace("unit", 8, clock=lambda: t["now"])
    st.begin_step()
    with st.phase("serving/admit"):
        t["now"] += 1.0
    with st.phase("serving/decode_build"):
        t["now"] += 2.0
    with st.phase("serving/decode_window"):
        st.dispatched()                     # in flight from 13.0
        t["now"] += 5.0
        st.ready()                          # to 18.0
    t["now"] += 0.5                         # between two phases: the next one's
    with st.phase("serving/emit"):
        t["now"] += 1.0
    rec = st.end_step(admitted=1, decoding=2, emitted=2, queued=3,
                      free_blocks=4, blocked_on="slots", compiles=0)
    assert (rec.step, rec.t_start, rec.t_end) == (1, 10.0, 19.5)
    assert dict(rec.phases) == {"serving/admit": 1.0, "serving/decode_build": 2.0,
                                "serving/decode_window": 5.0,
                                "serving/emit": 1.5}
    assert sum(s for _, s in rec.phases) == rec.t_end - rec.t_start
    assert rec.exposed_s == pytest.approx(9.5 - 5.0)
    assert (rec.admitted, rec.decoding, rec.queued, rec.blocked_on) == \
        (1, 2, 3, "slots")
    assert st.records() == [rec]
    # a phase met twice in a step adds up; records are flat tuples
    st.begin_step()
    for _ in range(2):
        with st.phase("serving/prefill_chunk"):
            t["now"] += 1.0
    rec = st.end_step()
    assert dict(rec.phases) == {"serving/prefill_chunk": 2.0}
    assert all(isinstance(v, (int, float, str, tuple)) for v in rec)


def test_in_flight_work_is_carried_across_steps_until_a_read_back():
    t = {"now": 0.0}
    st = StepTrace("unit", 8, clock=lambda: t["now"])
    st.begin_step()
    with st.phase("serving/prefill_chunk"):
        t["now"] += 1.0
        st.dispatched()                     # no read-back in this step
        t["now"] += 1.0
    first = st.end_step()
    assert first.exposed_s == pytest.approx(1.0)
    t["now"] += 3.0                         # between the steps: nobody's
    st.begin_step()
    with st.phase("serving/decode_window"):
        t["now"] += 2.0
        st.ready()                          # the carried call ends here
        t["now"] += 1.0
    second = st.end_step()
    assert second.exposed_s == pytest.approx(1.0)
    # training: the caller says the device ran dry before this step
    st.begin_step()
    with st.phase("train/dispatch"):
        st.dispatched()
        t["now"] += 1.0
    st.end_step()
    st.begin_step(device_idle=True)
    with st.phase("train/place"):
        t["now"] += 2.0
    assert st.end_step().exposed_s == pytest.approx(2.0)


def test_the_rings_are_bounded_and_every_telemetry_builds_one():
    st = StepTrace("unit", 4)
    for i in range(10):
        st.begin_step()
        st.end_step()
        st.close_request(st.open_request(i, 0.0, 0.1, 5), 0.2, st.step,
                         0.3, 2, "length")
    assert [r.step for r in st.records()] == [7, 8, 9, 10]
    assert len(st._requests) == 4
    with pytest.raises(ValueError):
        StepTrace("unit", 0)
    # no setting turns it off: with no telemetry block, or a disabled one,
    # the subsystem's recorder is there at the one capacity
    for n, config in enumerate((None, TelemetryConfig(enabled=False))):
        on = Telemetry(config, subsystem=f"unit{n}").new_steptrace(
            time.perf_counter)
        assert on.capacity == steptrace.DEFAULT_CAPACITY
        assert on.sink is None
        assert steptrace.latest(f"unit{n}") is on
    assert not hasattr(TelemetryConfig(), "steptrace_capacity")


def test_records_and_requests_are_selected_by_stamp():
    t = {"now": 0.0}
    st = StepTrace("unit", 32, clock=lambda: t["now"])
    opened = []
    for i in range(4):
        st.begin_step()
        with st.phase("serving/admit"):
            t["now"] += 1.0                 # steps end at 1, 2, 3, 4
            opened.append(st.open_request(f"r{i}", t["now"] - 0.5, t["now"],
                                          8, cached_prefix_tokens=16 * i))
        st.end_step()
    assert [r.step for r in st.records(1.0, 3.0)] == [2, 3]
    assert [r.step for r in st.records(since=3.0)] == [4]
    done = st.close_request(opened[0], 2.0, 2, 4.0, 3, "length")
    # one record a request, the newest; the still-running ones are there too
    inside = st.requests(0.0, 2.0)
    assert sorted(r.uid for r in inside) == ["r0", "r1"]
    assert [r for r in inside if r.uid == "r0"] == [done]
    assert done.step_admit == 1 and done.step_finish == 4
    assert done.finish_reason == "length" and done.emitted == 3
    running = [r for r in inside if r.uid == "r1"][0]
    assert running.t_finish is None and running.finish_reason == ""
    assert running.cached_prefix_tokens == 16
    assert [r.uid for r in st.requests(3.0, 4.0, stamp="t_finish")] == ["r0"]


def test_one_step_of_seven_phases_costs_under_50_microseconds():
    st = StepTrace("unit", 64)
    names = sorted(SERVING_PHASES)
    n = 2000

    def mean_of_n():
        t0 = time.perf_counter()
        for _ in range(n):
            st.begin_step()
            for name in names:
                with st.phase(name):
                    pass
            st.end_step()
        return (time.perf_counter() - t0) / n

    # the quietest of five rounds: the other test workers share these cores
    per_step = min(mean_of_n() for _ in range(5))
    assert per_step < 50e-6, f"{per_step * 1e6:.1f} us a step"


def test_a_call_opened_in_one_step_and_read_in_the_next_is_one_record():
    t = {"now": 0.0}
    st = StepTrace("unit", 8, clock=lambda: t["now"])
    st.begin_step()                         # step 1 dispatches call 7
    with st.phase("serving/decode_window", call=7) as ph:
        t["now"] += 1.0
    opened = st.open_call(7, "mixed", ph.t0, ph.t1, queued_behind=True,
                          rows=3, win=4, chunks=2, firsts=1)
    assert opened.t_wait1 is None and opened.step_read == 0
    assert st.calls() == [] and st.in_flight() == [opened]
    st.end_step(device_calls=1)
    t["now"] += 0.5
    st.begin_step()                         # step 2 reads it back
    with st.phase("serving/decode_build"):
        t["now"] += 0.25
    with st.phase("serving/read_back", call=7) as ph:
        t["now"] += 2.0
    read = st.read_call(7, ph.t0, ph.t1)
    with st.phase("serving/emit"):
        t["now"] += 0.5
    rec = st.close_call(read, 11)
    st.end_step()
    assert st.calls() == [rec] and st.in_flight() == []
    assert (rec.id, rec.program, rec.step_launch, rec.step_read) == \
        (7, "mixed", 1, 2)
    assert (rec.t_launch0, rec.t_launch1, rec.t_wait0, rec.t_wait1) == \
        (0.0, 1.0, 1.75, 3.75)
    assert (rec.queued_behind, rec.rows, rec.win, rec.chunks, rec.firsts,
            rec.emitted) == (True, 3, 4, 2, 1, 11)
    assert all(isinstance(v, (int, float, str, bool)) for v in rec)
    # selected by the read's return, as steps are by their end
    assert st.calls(since=3.75) == [] and st.calls(until=3.75) == [rec]
    assert st.calls(since=1.0, until=3.0) == []


def test_the_wait_is_a_phase_of_its_own_and_the_phases_still_tile():
    t = {"now": 0.0}
    st = StepTrace("unit", 8, clock=lambda: t["now"])
    st.begin_step()
    for name, took in (("serving/admit", 0.125), ("serving/decode_build", 0.5),
                       ("serving/decode_window", 0.25),
                       ("serving/read_back", 4.0), ("serving/emit", 1.0),
                       ("serving/housekeeping", 0.125)):
        with st.phase(name):
            t["now"] += took
    rec = st.end_step()
    phases = dict(rec.phases)
    assert phases["serving/read_back"] == 4.0
    assert sum(phases.values()) == rec.t_end - rec.t_start == 6.0
    # the host's own work is the rest of the step
    assert rec.t_end - rec.t_start - phases["serving/read_back"] == 2.0


def test_a_phases_attributes_ride_the_annotation_alone(tmp_path):
    st = StepTrace("unit", 8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        st.begin_step()
        with st.phase("serving/decode_window", call=41):
            pass
        with st.phase("serving/read_back", call=40):
            pass
        rec = st.end_step()
    finally:
        jax.profiler.stop_trace()
    assert [name for name, _ in rec.phases] == \
        ["serving/decode_window", "serving/read_back"]
    data = jax.profiler.ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    seen = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serving/"):
                        seen[ev.name] = dict(ev.stats)
    assert seen["serving/decode_window"]["call"] == 41
    assert seen["serving/read_back"]["call"] == 40


# ----------------------------------------------------------------------
# the serving scheduler
# ----------------------------------------------------------------------


def test_a_call_in_flight_over_a_whole_step_exposes_none_of_it():
    """The caller reads call k-1 back with call k already dispatched behind
    it and says nothing (`ready()` would claim the device ran dry): the
    interval runs on, over the whole of the step, and the step's exposed
    time is nil. The first read-back with nothing behind it ends it."""
    t = {"now": 0.0}
    st = StepTrace("unit", 8, clock=lambda: t["now"])
    st.begin_step()
    with st.phase("serving/decode_window"):
        t["now"] += 1.0
        st.dispatched()                     # call 1, left in flight
        t["now"] += 1.0
    assert st.end_step(device_calls=1).exposed_s == pytest.approx(1.0)
    t["now"] += 3.0
    st.begin_step()
    with st.phase("serving/decode_window"):
        t["now"] += 1.0
        st.dispatched()                     # call 2 behind call 1 ...
        t["now"] += 2.0                     # ... whose read-back: no ready()
    with st.phase("serving/emit"):
        t["now"] += 1.0
    rec = st.end_step(device_calls=1, overlapped_calls=1)
    assert rec.t_end - rec.t_start == pytest.approx(4.0)
    assert rec.exposed_s == 0.0
    assert (rec.device_calls, rec.overlapped_calls) == (1, 1)
    st.begin_step()
    with st.phase("serving/decode_window"):
        t["now"] += 2.0
        st.ready()                          # call 2 read, nothing behind it
        t["now"] += 1.0
    rec = st.end_step()
    assert rec.exposed_s == pytest.approx(1.0)
    assert (rec.device_calls, rec.overlapped_calls) == (0, 0)


def test_overlapped_serving_steps_are_counted_and_expose_nothing():
    """One request decoding alone, one token a call: after the step that
    prefills it, every step dispatches its call behind the one in flight —
    `overlapped_calls` says so, `exposed_s` reads 0 for each of those steps,
    and `stats()` carries the sums of the step records."""
    clock = Ticker()
    serving = _engine().serving(max_slots=2, max_context=128, clock=clock)
    serving.run(_requests(1, max_new=6))
    recs = serving.steptrace.records()
    # the prompt's one chunk is a call of its own (nobody decodes yet): it
    # and the first decode call find nothing in flight; calls 2..5 do; the
    # request left its slot at call 5's dispatch, and the last step reads
    calls = [(r.device_calls, r.overlapped_calls) for r in recs]
    assert calls == [(2, 0)] + [(1, 1)] * 4 + [(0, 0)]
    assert [r.emitted for r in recs] == [1, 1, 1, 1, 1, 1]
    for rec in recs[1:-1]:
        assert rec.exposed_s == 0.0
        assert dict(rec.phases)["serving/decode_window"] > 0
    assert recs[0].exposed_s > 0 and recs[-1].exposed_s > 0
    st = serving.stats()
    assert st["device_calls"] == sum(r.device_calls for r in recs) == 6
    assert st["overlapped_calls"] == sum(r.overlapped_calls
                                         for r in recs) == 4
    assert serving.steptrace._inflight_since is None


def _overlapped(clock):
    """A request decoding alone, a second prompt of three chunks arriving
    behind it: a prompt's chunk as a call of its own (nobody decodes yet),
    decode calls, mixed calls."""
    serving = _engine().serving(max_slots=2, max_context=128, clock=clock,
                                decode_steps_per_sync=2)
    first, second = _requests(2, prompt_len=9, max_new=12)
    second.tokens = np.resize(second.tokens, 40)
    serving.submit(first)
    for _ in range(3):                      # three steps one call deep
        serving.step()
    assert serving.overlapped_calls == 2
    serving.submit(second)
    return serving


def _speculating(clock):
    serving = _engine().serving(
        max_slots=2, max_context=128, clock=clock,
        spec_decode={"drafter": "ngram", "draft_k": 2})
    for req in _requests(2, prompt_len=20, max_new=6):
        serving.submit(req)
    return serving


def _shrunk_to_one_token(clock):
    """The pressure ladder's window rung held on: the one-token program."""
    serving = _engine().serving(max_slots=2, max_context=128, clock=clock,
                                decode_steps_per_sync=4,
                                degradation={"enabled": True})
    serving.pressure.level = 3
    serving.pressure.update = lambda finished: None
    for req in _requests(2, prompt_len=9, max_new=5):
        serving.submit(req)
    return serving


@pytest.mark.parametrize("scenario, programs, one_deep", [
    (_overlapped, {"prefill", "decode", "mixed"}, True),
    (_speculating, {"prefill", "verify"}, False),
    (_shrunk_to_one_token, {"prefill", "decode_w1"}, False)],
    ids=["overlapped", "spec_decode", "window_of_one"])
def test_every_device_call_has_one_record_with_its_work_and_both_steps(
        scenario, programs, one_deep):
    serving = scenario(Ticker())
    while serving.queue or serving.num_active:
        serving.step()
    st = serving.steptrace
    recs, calls = st.records(), st.calls()
    by_step = {r.step: r for r in recs}
    assert st.in_flight() == []
    assert [c.id for c in calls] == list(range(1, serving.device_calls + 1))
    assert len(calls) == sum(r.device_calls for r in recs)
    assert sum(c.queued_behind for c in calls) == serving.overlapped_calls \
        == sum(r.overlapped_calls for r in recs)
    assert sum(c.emitted for c in calls) == sum(r.emitted for r in recs) \
        == serving.tokens_generated
    assert {c.program for c in calls} == programs
    for c in calls:
        # four stamps, in order (a read that follows its dispatch at once
        # starts where the dispatch ended: phases share their boundaries)
        assert c.t_launch0 < c.t_launch1 <= c.t_wait0 < c.t_wait1
        assert c.step_launch <= c.step_read <= c.step_launch + 1
        assert by_step[c.step_launch].device_calls >= 1
        assert by_step[c.step_launch].t_start <= c.t_launch0 \
            and c.t_launch1 <= by_step[c.step_launch].t_end
        assert dict(by_step[c.step_read].phases)["serving/read_back"] >= \
            c.t_wait1 - c.t_wait0
        assert by_step[c.step_read].t_start <= c.t_wait0 \
            and c.t_wait1 <= by_step[c.step_read].t_end
        assert 0 <= c.emitted <= c.rows * c.win + c.firsts
        assert c.chunks > 0 or c.program not in ("mixed", "prefill")
        assert c.firsts <= c.chunks
    # every chunk's progress is confirmed by one call's read-back
    assert sum(c.chunks for c in calls) == serving.prefill_chunks
    assert sum(c.chunks for c in calls if c.program == "mixed") >= \
        serving.fused_chunks > 0 or not one_deep
    # a call put behind another is read by the NEXT step; the others by
    # their own step or, a decode call nothing followed, by a drain
    assert all(c.step_read == c.step_launch + 1
               for c in calls if c.queued_behind and c.program != "prefill")
    assert one_deep == any(c.queued_behind for c in calls)
    assert one_deep or all(c.step_read == c.step_launch for c in calls)
    # the wait is told from the work in every step that read a call
    for rec in recs:
        assert ("serving/read_back" in dict(rec.phases)) == any(
            c.step_read == rec.step for c in calls)


def test_request_spans_name_the_call_that_served_them(tmp_path):
    from deepspeed_tpu.telemetry.tracing import load_spans
    serving = _engine(enabled=True, tracing=True, prometheus=False,
                      jsonl=False, output_path=str(tmp_path)).serving(
        max_slots=2, max_context=128, decode_steps_per_sync=2)
    first, second = _requests(2, prompt_len=9, max_new=8)
    second.tokens = np.resize(second.tokens, 40)
    serving.submit(first)
    for _ in range(3):
        serving.step()
    serving.submit(second)
    while serving.queue or serving.num_active:
        serving.step()
    serving.close()
    calls = {c.id: c for c in serving.steptrace.calls()}
    spans = load_spans(tmp_path / "serving.trace.jsonl")
    windows = [s for s in spans if s["name"] == "decode_window"]
    assert windows and serving.fused_chunks > 0
    for s in windows + [s for s in spans if s["name"] == "prefill_chunk"
                        and "call" in s["attrs"]]:
        c = calls[s["attrs"]["call"]]
        # a span runs from the call's launch to the return of its read
        assert s["ts"] == pytest.approx(c.t_launch0, abs=1e-6)
        assert s["ts"] + s["dur"] == pytest.approx(c.t_wait1, abs=1e-6)
    assert sum(s["attrs"]["emitted"] for s in windows) == sum(
        c.emitted - c.firsts for c in calls.values() if c.rows)
    fused = [s for s in spans if s["name"] == "prefill_chunk"
             and s["attrs"].get("fused")]
    assert len(fused) == serving.fused_chunks
    assert {calls[s["attrs"]["call"]].program for s in fused} == {"mixed"}
    # a chunk that was a call of its own and not a prompt's last names none
    alone = [s for s in spans if s["name"] == "prefill_chunk"
             and not s["attrs"].get("fused")]
    assert sum("call" in s["attrs"] for s in alone) == sum(
        c.program == "prefill" for c in calls.values())


def test_serving_phases_tile_every_step_under_an_injected_clock():
    clock = Ticker()
    serving = _engine().serving(max_slots=2, max_context=128, clock=clock)
    assert serving.steptrace.clock is clock
    serving.run(_requests(3, prompt_len=20))
    recs = serving.steptrace.records()
    assert len(recs) == serving.steps and recs[-1].step == serving.steps
    seen = set()
    for rec in recs:
        assert sum(s for _, s in rec.phases) == rec.t_end - rec.t_start
        assert 0.0 <= rec.exposed_s <= rec.t_end - rec.t_start
        seen.update(name for name, _ in rec.phases)
    assert seen == SERVING_PHASES
    assert sum(r.admitted for r in recs) == 3
    assert sum(r.prefill_chunks for r in recs) == serving.prefill_chunks
    assert sum(r.emitted for r in recs) == serving.tokens_generated == 9
    assert sum(1 for r in recs if r.decoding) == serving.decode_steps
    # every step program compiled once, in the step that first ran it
    assert sum(r.compiles for r in recs) == len(serving.compile_stats())
    assert set(serving.compile_stats().values()) == {1}
    assert recs[-1].queued == 0 and recs[-1].free_blocks == \
        serving.allocator.available
    # set_clock moves the recorder with the engine
    other = Ticker()
    serving.set_clock(other)
    assert serving.steptrace.clock is other


def test_request_records_with_telemetry_off_point_at_their_steps(
        tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    serving = _engine().serving(max_slots=1, max_context=128)
    assert not serving.telemetry.enabled
    for req in _requests(2, prompt_len=20, max_new=3):
        serving.submit(req)
    finished_in = {}
    while serving.queue or serving.num_active:
        for done in serving.step():
            assert done.timing is None          # the disabled contract holds
            finished_in[done.uid] = serving.steps
    st = serving.steptrace
    by_step = {r.step: r for r in st.records()}
    reqs = {r.uid: r for r in st.requests()}
    assert sorted(reqs) == [0, 1]
    for uid, r in reqs.items():
        assert r.t_submit <= r.t_admit <= r.t_first_token <= r.t_finish
        assert (r.prompt_len, r.emitted, r.finish_reason) == (20, 3, "length")
        assert by_step[r.step_admit].admitted == 1
        assert by_step[r.step_first_token].emitted >= 1
        assert by_step[r.step_first_token].prefill_chunks >= 1
        assert r.step_finish == finished_in[uid]
        assert r.step_admit <= r.step_first_token < r.step_finish
    # one slot: the second request queued until the first retired
    assert reqs[1].step_admit > reqs[0].step_finish - 1
    assert reqs[1].t_admit - reqs[1].t_submit > \
        reqs[0].t_admit - reqs[0].t_submit
    assert "latency" not in serving.stats()
    assert list(tmp_path.iterdir()) == []       # and nothing was written


def test_blocked_on_names_the_pool_or_the_slots():
    engine = _engine()
    need = engine.serving(max_slots=2, max_context=128).check_admissible(20, 3)
    # the pool holds one request and is one block short of the second
    # (one block of the pool is the trash block)
    serving = engine.serving(max_slots=2, max_context=128,
                             num_kv_blocks=2 * need)
    assert serving.allocator.capacity == 2 * need - 1
    serving.run(_requests(2, prompt_len=20))
    waits = [r.blocked_on for r in serving.steptrace.records() if r.queued]
    assert waits and set(waits) == {"pool"}
    assert serving.steptrace.records()[-1].blocked_on == ""
    # every slot busy, blocks to spare
    serving = engine.serving(max_slots=1, max_context=128)
    serving.run(_requests(2, prompt_len=20))
    waits = [r.blocked_on for r in serving.steptrace.records() if r.queued]
    assert waits and set(waits) == {"slots"}


@pytest.mark.parametrize("window", [1, 4])
def test_decode_walk_counts_equal_a_hand_count_from_the_lengths(window):
    """`decode_live_blocks`: pos // block + 1 over a decode call's slots and
    tokens; `decode_grid_steps`: what the paged decode kernel's walk takes
    for them (one step a live pair, a token with nothing live one). A
    request of prompt n and m new tokens is fed at positions n .. n + m - 2,
    rounded up to whole windows (a slot rides its last window out)."""
    block, lengths = 16, [(14, 6), (31, 3), (40, 9)]
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, max_new_tokens=m, stop_on_eos=False,
                    tokens=rng.integers(0, 256, (n,)).astype(np.int32))
            for i, (n, m) in enumerate(lengths)]

    def fed(n, m):
        return [n + t for t in range(-(-(m - 1) // window) * window)]

    # one request alone: every decode call's record, position by position
    serving = _engine().serving(max_slots=1, max_context=128,
                                decode_steps_per_sync=window)
    serving.run(reqs[:1])
    calls = [r for r in serving.steptrace.records() if r.decoding]
    positions = fed(*lengths[0])
    assert [r.decode_live_blocks for r in calls] == [
        sum(p // block + 1 for p in positions[i:i + window])
        for i in range(0, len(positions), window)]
    assert [r.decode_grid_steps for r in calls] == \
        [r.decode_live_blocks for r in calls]
    assert all(r.decode_live_blocks == r.decode_grid_steps == 0
               for r in serving.steptrace.records() if not r.decoding)
    # three at once, however the scheduler interleaves them: the sums
    serving = _engine().serving(max_slots=2, max_context=128,
                                decode_steps_per_sync=window)
    serving.run(reqs)
    recs = serving.steptrace.records()
    want = sum(p // block + 1 for n, m in lengths for p in fed(n, m))
    assert sum(r.decode_live_blocks for r in recs) == want
    assert sum(r.decode_grid_steps for r in recs) == want


def test_a_short_tables_decode_walk_serves_generates_tokens_and_books_its_rows():
    """A table of three blocks of 256 through the scheduler with the kernel
    forced (`use_flash_attention`; interpreted here): the walk moves its
    frontier block in tiles of 128 rows (`decode_attention._frontier_rows`),
    the tokens are `generate`'s, and `decode_walk_rows` is the hand count —
    under `decode_live_blocks` x block, which a whole-block walk moves."""
    import dataclasses
    _mk_mesh()
    block, tile = 256, 128
    cfg = dataclasses.replace(TINY, n_head=2, n_kv_head=2, d_model=256,
                              max_seq_len=768, use_flash_attention=True)
    engine = init_inference(
        model=make_gpt_decode_model(cfg=cfg, name="tiny"),
        config={"dtype": "float32", "kv_cache_dtype": "float32",
                "greedy": True, "kv_block_size": block,
                "max_out_tokens": 768})
    serving = engine.serving(max_slots=2, max_context=768, prefill_chunk=128,
                             decode_steps_per_sync=2)
    lengths = [(100, 5), (250, 9), (300, 4)]    # a tile's, a block's edge
    rng = np.random.default_rng(62)
    reqs = [Request(uid=i, max_new_tokens=m, stop_on_eos=False,
                    tokens=rng.integers(0, 256, (n,)).astype(np.int32))
            for i, (n, m) in enumerate(lengths)]
    done = serving.run(reqs)
    for r in reqs:
        want = engine.generate(r.tokens[None], max_new_tokens=r.max_new_tokens)
        np.testing.assert_array_equal(done[r.uid].tokens,
                                      np.asarray(want)[0])
    assert serving.attention_programs()["decode_step"] == "paged_kernel"
    fed = [n + t for n, m in lengths for t in range(-(-(m - 1) // 2) * 2)]
    recs = serving.steptrace.records()
    assert sum(r.decode_live_blocks for r in recs) \
        == sum(p // block + 1 for p in fed)
    moved = sum(p // block * block + (p % block // tile + 1) * tile
                for p in fed)
    assert sum(r.decode_walk_rows for r in recs) == moved \
        < sum(r.decode_live_blocks for r in recs) * block


def test_compiles_names_the_step_that_compiled():
    serving = _engine().serving(max_slots=2, max_context=128)
    serving.run(_requests(2))
    recs = serving.steptrace.records()
    # the first step runs a chunk and a decode call, so it compiles both
    # programs; in the second the other prompt's chunk rides the first one's
    # decode call, the mixed program; nothing after them compiles
    assert [r.compiles for r in recs] == [2, 1] + [0] * (len(recs) - 2)
    assert [r.fused_chunks for r in recs] == [0, 1] + [0] * (len(recs) - 2)
    assert serving.compile_stats() == {"decode_step": 1, "prefill_step": 1,
                                       "mixed_step": 1}
    # a program replaced by a plain function (fault injection) counts 0
    jitted = serving.programs.decode
    serving.programs.decode = lambda *a: jitted(*a)
    assert serving._compiled_programs() == 2
    serving.run(_requests(1, seed=1))
    assert serving.steptrace.records()[-1].compiles == 0


def test_latest_holds_the_rings_and_never_the_engine():
    serving = _engine().serving(max_slots=2, max_context=128)
    serving.run(_requests(1))
    steps = serving.steps
    assert steptrace.latest("serving") is serving.steptrace
    gone = weakref.ref(serving)
    del serving
    gc.collect()
    assert gone() is None
    assert len(steptrace.latest("serving").records()) == steps


def test_a_profiler_session_holds_the_phases_on_the_host_plane(tmp_path):
    serving = _engine().serving(max_slots=2, max_context=128)
    serving.run(_requests(1))                   # compile outside the session
    for req in _requests(2, prompt_len=20, max_new=4, seed=1):
        serving.submit(req)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            serving.step()
    finally:
        jax.profiler.stop_trace()
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert len(files) == 1
    data = jax.profiler.ProfileData.from_file(str(files[0]))
    names = set()
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    assert {"serving/admit", "serving/decode_window", "serving/emit"} <= names


def test_chrome_trace_still_holds_the_phases(tmp_path):
    serving = _engine(enabled=True, output_path=str(tmp_path),
                      chrome_trace=True, prometheus=False,
                      jsonl=False).serving(max_slots=2, max_context=128)
    done = serving.run(_requests(2, prompt_len=20))
    assert all(d.timing is not None for d in done.values())
    serving.close()
    text = (tmp_path / "serving.trace.json").read_text().rstrip().rstrip(",")
    names = {ev["name"] for ev in json.loads(text + "]")}
    assert SERVING_PHASES <= names
    # the same spans fed the ring: one span, three sinks
    assert len(serving.steptrace.records()) == serving.steps


def test_spec_decode_steps_carry_draft_and_verify_phases():
    serving = _engine().serving(
        max_slots=2, max_context=128,
        spec_decode={"drafter": "ngram", "draft_k": 2})
    serving.run(_requests(2, prompt_len=20, max_new=6))
    seen = set()
    for rec in serving.steptrace.records():
        assert sum(s for _, s in rec.phases) == \
            pytest.approx(rec.t_end - rec.t_start)
        seen.update(name for name, _ in rec.phases)
    assert {"serving/draft", "serving/verify", "serving/emit"} <= seen
    assert "serving/decode_window" not in seen


def test_a_handed_off_request_has_a_record_on_both_engines():
    engine = _engine()
    prefill = engine.serving(max_slots=2, max_context=128)
    decode = engine.serving(max_slots=2, max_context=128)
    prefill.submit(_requests(1, prompt_len=20, max_new=4)[0],
                   prefill_only=True)
    while not prefill.handoff_ready():
        prefill.step()
    assert decode.adopt_handoff(prefill.export_handoff(0), prefill.pool)
    prefill.release_handoff(0)
    done = {}
    while decode.num_active:
        done.update({d.uid: d for d in decode.step()})
    source, = prefill.steptrace.requests()
    target, = decode.steptrace.requests()
    assert source.uid == target.uid == 0
    assert source.finish_reason == "handoff" and source.emitted == 1
    assert target.finish_reason == "length" and target.emitted == 4
    # the first-token stamp travels with the request
    assert target.t_first_token == source.t_first_token
    assert target.t_submit == source.t_submit


# ----------------------------------------------------------------------
# the train engine
# ----------------------------------------------------------------------


def test_train_batch_leaves_one_record_a_step_with_its_four_phases():
    # one device: once the loss is fetched, every shard of it is ready (on
    # several devices the fetch waits for one shard, and a step that finds
    # another still running rightly carries it as in flight)
    _mk_mesh()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_gpt_model(cfg=TINY, name="tiny"), config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0}, "steps_per_print": 10**9})
    assert not engine.telemetry.enabled
    assert steptrace.latest("train") is engine.steptrace
    toks = np.random.default_rng(0).integers(
        0, 256, (engine.train_batch_size(), 33)).astype(np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    for _ in range(3):
        float(engine.train_batch(batch))        # the fetch is the fence
    recs = engine.steptrace.records()
    assert [r.step for r in recs] == [1, 2, 3]
    for rec in recs:
        assert [name for name, _ in rec.phases] == [
            "train/place", "train/dispatch", "train/fence",
            "train/after_step"]
        assert sum(s for _, s in rec.phases) == \
            pytest.approx(rec.t_end - rec.t_start)
        assert 0.0 < rec.exposed_s <= rec.t_end - rec.t_start
    assert [r.compiles for r in recs] == [1, 0, 0]
    # the loss was fetched before each next step, so nothing was carried:
    # what is exposed is what ran before the dispatch
    place = dict(recs[2].phases)["train/place"]
    assert recs[2].exposed_s == pytest.approx(place, rel=0.5)


def test_a_data_error_is_not_an_oom_forensics_event():
    # the batch comes from the caller's iterator inside `train/place` but
    # outside the OOM-forensics boundary; placing it and the step are inside
    _mk_mesh()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_gpt_model(cfg=TINY, name="tiny"), config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0}, "steps_per_print": 10**9})
    seen = []

    class Scope:
        def on_step_error(self, e):
            seen.append(e)

    engine.memscope = Scope()
    with pytest.raises(StopIteration):
        engine.train_batch(data_iter=iter(()))
    assert seen == []
    with pytest.raises(Exception):
        engine.train_batch({"tokens": np.zeros((3, 5, 7), np.int32)})
    assert len(seen) == 1
    # the step that failed is dropped; the next one opens clean
    toks = np.zeros((engine.train_batch_size(), 9), np.int32)
    float(engine.train_batch({"tokens": toks[:, :-1], "labels": toks[:, 1:]}))
    rec = engine.steptrace.records()[-1]
    assert sum(s for _, s in rec.phases) == \
        pytest.approx(rec.t_end - rec.t_start)


# ----------------------------------------------------------------------
# stable kernel names, compiled for a described TPU (no chip needed)
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _kernel_lines(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return [line.strip() for line in text.splitlines()
            if "custom-call(" in line and "tpu_custom_call" in line]


def _kernel_instructions(fn, *args):
    return [line.split("=")[0].strip().lstrip("%")
            for line in _kernel_lines(fn, *args)]


def test_kernels_keep_their_names_in_the_compiled_program(one_chip):
    """The HLO instruction of each Pallas kernel on the benchmarked path is
    named for the kernel, also under scan + checkpoint, where it used to take
    the enclosing computation's name (`closed_call.13`, `checkpoint.22`):
    the benchmark's `paged_decode_kernel_time_share.*` match on it."""
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    decode = functools.partial(paged_decode_attention, interpret=False)

    def scanned_decode(q, k, v, tables, pos):
        def body(c, _):
            return c + decode(c, k, v, tables, pos), None
        return jax.lax.scan(jax.checkpoint(body), q, None, length=2)[0]

    names = _kernel_instructions(
        scanned_decode, sds((8, 32, 128), jnp.bfloat16),
        sds((16, 8, 512, 128), jnp.bfloat16),
        sds((16, 8, 512, 128), jnp.bfloat16), sds((8, 4), jnp.int32),
        sds((8,), jnp.int32))
    assert names and all(n.startswith("dstpu_paged_decode") for n in names)

    flash = functools.partial(flash_attention, interpret=False)

    def flash_grad(q, k, v):
        loss = jax.checkpoint(lambda q, k, v: flash(q, k, v).sum()
                              .astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    qkv = sds((2, 1024, 4, 128), jnp.bfloat16)
    names = _kernel_instructions(flash_grad, qkv, qkv, qkv)
    kinds = {n.rsplit(".", 1)[0] for n in names}
    assert kinds == {"dstpu_flash_fwd", "dstpu_flash_dq", "dstpu_flash_dkv"}


def _mosaic_bodies(fn, *args):
    """The Mosaic kernels of `fn` lowered for the described chip, as MLIR
    text without locations (the custom call carries each as base64
    bytecode), in program order."""
    import base64

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir
    text = jax.jit(fn).lower(*args).as_text()
    context = jax_mlir.make_ir_context()
    context.allow_unregistered_dialects = True
    with context:
        return [ir.Module.parse(base64.b64decode(body)).operation.get_asm(
                    enable_debug_info=False)
                for body in re.findall(
                    r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)]


_COLUMN = re.compile(r"vector<(?:\d+x)*1xf32>")
_DEFINES = re.compile(r'^\s*(%\w+) = "stable_mosaic\.([\w.]+)"\((%\w+)?')
_STORES = re.compile(r'^\s*"stable_mosaic\.tpu\.vector_store"\((%\w+), (%\w+)')


@pytest.mark.parametrize("walk", ["dstpu_paged_prefill", "dstpu_mla_prefill",
                                  "dstpu_paged_decode", "dstpu_mla_decode"])
def test_the_walks_carry_their_row_statistics_lane_replicated(one_chip, walk):
    """The online softmax's m, l and alpha stay `[R, 128]` from scratch to
    scratch (PR 44; `decode_attention.py::_online_softmax_update`), in the
    chunk walks at Mistral's and GLM-4.7-Flash's served shapes and in the
    decode walks: the kernel lowered for the described v5e reads NO one-lane
    column of anything (the column form began with `m_ref[:, 0:1]`), a
    `[R, 1]` value exists only as a row reduction's result on its way to one
    lane tile, and nothing stored is a vector's broadcast — the form that
    cost the chunk walk 44% of its bundles (85303 cross-lane operations
    against 34416) cannot come back unseen."""
    from deepspeed_tpu.ops.pallas import (decode_attention, mla_attention,
                                          prefill_attention)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = sds((40, 8, 512, 128), bf16)
    latent = sds((40, 1, 512, 640), bf16)
    fn, args = {
        "dstpu_paged_prefill": (
            prefill_attention.paged_prefill_attention,
            (sds((1, 512, 32, 128), bf16), pool, pool, sds((1, 32), i32),
             sds((1,), i32))),
        "dstpu_mla_prefill": (
            functools.partial(mla_attention.mla_prefill_attention, rank=512,
                              sm_scale=0.07),
            (sds((1, 1024, 20, 640), bf16), latent, sds((1, 32), i32),
             sds((1,), i32))),
        "dstpu_paged_decode": (
            decode_attention.paged_decode_attention,
            (sds((32, 32, 128), bf16), pool, pool, sds((32, 8), i32),
             sds((32,), i32))),
        "dstpu_mla_decode": (
            functools.partial(mla_attention.mla_decode_attention, rank=512,
                              sm_scale=0.07),
            (sds((128, 20, 640), bf16), latent, sds((128, 16), i32),
             sds((128,), i32))),
    }[walk]
    (body,) = _mosaic_bodies(functools.partial(fn, interpret=False), *args)

    defined_by, reductions, stores = {}, 0, 0
    for line in body.splitlines():
        stored = _STORES.match(line)
        if stored:
            value = stored.group(1)
            while defined_by[value][0] == "vector.shape_cast":
                value = defined_by[value][1]
            # (a scalar's broadcast is `_init`'s zeros and NEG_INF)
            assert not (defined_by[value][0] == "vector.broadcast"
                        and "(vector<" in defined_by[value][2]), line
            stores += 1
            continue
        defines = _DEFINES.match(line)
        if not defines:
            continue
        name, op, operand = defines.groups()
        defined_by[name] = (op, operand, line)
        if not _COLUMN.search(line):
            continue
        # a [R, 1] float: a reduction's result, or that widened to 128 lanes
        if op == "vector.shape_cast":
            assert defined_by[operand][0] == "vector.multi_reduction", line
            reductions += 1
        else:
            assert op == "vector.broadcast" and "x128xf32>" in \
                line.rsplit("->", 1)[1], line
    # two reductions an update (the row maximum and the row sum), and the
    # statistics are stored
    assert reductions >= 2 and reductions % 2 == 0 and stores >= 3


def test_the_state_update_with_one_group_and_the_gated_widths_compile(
        one_chip):
    """What the Granite 4.0-H cut brings the kernels (PR 41), at its served
    shapes: `dstpu_ssm_update` with ONE group of B and C — a `(1, 1, N)`
    block all 128 heads read — on the nine layers' flat state, IN PLACE (the
    state is the call's aliased result, nothing of its size beside it); and
    `dstpu_moe_gmm` over 18 held experts at 1536 columns (the 512 tile) and
    768 deep."""
    from deepspeed_tpu.ops.pallas import moe_gmm, ssm

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, b, H, P, N = 9 * 129, 128, 128, 64, 128
    update = jax.jit(functools.partial(ssm.ssm_update, interpret=False),
                     donate_argnums=(0,))
    compiled = update.lower(
        sds((rows, H, P, N), jnp.float32), sds((b,), jnp.int32),
        sds((b, H), jnp.float32), sds((b, H, P), jnp.float32),
        sds((b, 1, N), jnp.bfloat16), sds((b, 1, N), jnp.bfloat16)).compile()
    lines = [line for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(lines) == 1 and "dstpu_ssm_update" in lines[0]
    memory = compiled.memory_analysis()
    state = rows * H * P * N * 4
    assert memory.alias_size_in_bytes >= state
    assert memory.temp_size_in_bytes < state // 64

    for M in (1280, 6400):              # a decode step's rows, a mixed call's
        for K, cols in ((4096, 1536), (768, 4096)):
            lines = _kernel_lines(
                functools.partial(moe_gmm.moe_gmm, interpret=False),
                sds((M, K), jnp.bfloat16), sds((5 * 18, K, cols), jnp.bfloat16),
                sds((18,), jnp.int32), sds((), jnp.int32))
            assert len(lines) == 1 and "%dstpu_moe_gmm" in lines[0]


@pytest.mark.parametrize("G", [1, 8], ids=["granite", "nemotron"])
def test_the_state_update_is_one_aliased_call_with_two_rows_of_vmem(
        one_chip, G):
    """`ssm_update` at the served face (PR 45), one group of B and C and
    eight: ONE `dstpu_ssm_update` call, result types `(f32[b, P, H],
    f32[rows, H, P, N])` — what the trace readers (`ssm_update_roofline.
    hybrid`) and `test_nemotron_h.py`'s breakdown match — whose state is the
    call's aliased result and never a block of the pipeline: the kernel
    copies it itself, a step's two rows read in one burst and written in
    another, so the scoped VMEM it uses is those two rows twice (16 MiB) and
    the small operands' blocks."""
    from deepspeed_tpu.ops.pallas import ssm

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, b, H, P, N = 9 * 129, 128, 128, 64, 128
    lines = _kernel_lines(
        functools.partial(ssm.ssm_update, interpret=False),
        sds((rows, H, P, N), jnp.float32), sds((b,), jnp.int32),
        sds((b, H), jnp.float32), sds((b, H, P), jnp.float32),
        sds((b, G, N), jnp.bfloat16), sds((b, G, N), jnp.bfloat16))
    assert len(lines) == 1, lines
    call = re.sub(r"\{[^{}]*\}", "", lines[0].split(" custom-call(")[0])
    assert call == (f"%dstpu_ssm_update.1 = (f32[{b},{P},{H}], "
                    f"f32[{rows},{H},{P},{N}])"), call
    # operands: rows, a, dtx, B, C, state
    assert "output_to_operand_aliasing={{1}: (5, {})}" in lines[0]
    row = H * P * N * 4
    assert ssm._rows_per_step(b, row) == 2
    limit, used = (int(re.search(
        rf'"{key}":\[\{{"memory_space":"1","offset":"0","size":"(\d+)"',
        lines[0]).group(1))
        for key in ("scoped_memory_configs", "used_scoped_memory_configs"))
    assert limit == 2 * 2 * row + ssm._VMEM_SMALL
    assert 2 * 2 * row < used <= limit


def test_the_delta_rule_update_is_one_aliased_call_on_the_same_shell(
        one_chip):
    """`gdn_update` at Qwen3-Next's served face (PR 47): ONE
    `dstpu_gdn_update` call, result types `(f32[b, H, V], f32[rows, H, K,
    V])` — what `gdn_update_roofline.deltanet` and the breakdown match —
    on `ssm.stream_rows`, the shell `dstpu_ssm_update` runs on: the state
    is the call's aliased result and never a block of the pipeline, a step's
    FOUR 2 MiB rows are read in one burst and written in another, so the
    scoped VMEM is those four rows twice (16 MiB) and the small operands'
    blocks."""
    from deepspeed_tpu.ops.pallas import gdn, ssm

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, b, H, G, K, V = 9 * 193, 192, 32, 16, 128, 128
    lines = _kernel_lines(
        functools.partial(gdn.gdn_update, interpret=False),
        sds((rows, H, K, V), jnp.float32), sds((b,), jnp.int32),
        sds((b, H), jnp.float32), sds((b, H), jnp.float32),
        sds((b, G, K), jnp.float32), sds((b, G, K), jnp.float32),
        sds((b, H, V), jnp.bfloat16))
    assert len(lines) == 1, lines
    call = re.sub(r"\{[^{}]*\}", "", lines[0].split(" custom-call(")[0])
    # no swap of axes follows it (the ROOT): o leaves as [b, H, V]
    assert call == (f"ROOT %dstpu_gdn_update.1 = (f32[{b},{H},{V}], "
                    f"f32[{rows},{H},{K},{V}])"), call
    # operands: rows, a head's scalars, k, q, v, state
    assert "output_to_operand_aliasing={{1}: (5, {})}" in lines[0]
    row = H * K * V * 4
    assert ssm._rows_per_step(b, row) == 4
    limit, used = (int(re.search(
        rf'"{key}":\[\{{"memory_space":"1","offset":"0","size":"(\d+)"',
        lines[0]).group(1))
        for key in ("scoped_memory_configs", "used_scoped_memory_configs"))
    assert limit == 2 * 4 * row + ssm._VMEM_SMALL
    assert 2 * 4 * row < used <= limit


def _compiled_training_gradient(one_chip, monkeypatch, **model):
    """The compiled text of a remat'd GPT loss's gradient — two layers, two
    heads of 128, `[2, 2048]` tokens — for the described chip."""
    from deepspeed_tpu.models.gpt import gpt_init_fn, gpt_loss
    from deepspeed_tpu.platform import device
    mesh_mod.clear_mesh()
    monkeypatch.setattr(device, "on_tpu", lambda: True)   # what the chip sees
    cfg = GPTConfig(n_layer=2, n_head=2, d_model=256, max_seq_len=2048,
                    vocab_size=512, dtype=jnp.bfloat16, remat=True, **model)

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(gpt_init_fn(cfg, dtype=jnp.bfloat16),
                                    jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((2, 2048), jnp.int32, sharding=one_chip)
    return jax.jit(jax.grad(lambda p, b: gpt_loss(p, b, None, cfg))).lower(
        params, {"tokens": toks, "labels": toks}).compile().as_text()


# Pythia's half: a partial rotation, both halves off the block's input
_PYTHIA_LIKE = dict(use_rotary=True, rotary_pct=0.25, parallel_residual=True)


@pytest.mark.parametrize("model", [{}, _PYTHIA_LIKE], ids=["plain", "rotary"])
def test_training_step_holds_the_three_flash_kernels_by_result_signature(
        one_chip, monkeypatch, model):
    """`flash_roofline.train` tells the three flash kernels apart by the
    RESULT of their custom call (`benchmark/layer_metrics/flash_roofline.
    train.json`: fwd a tuple that starts bf16, f32; dkv bf16, bf16; dq a
    single bf16) and counts products by the calls it saw. So the compiled
    training step — a remat'd GPT loss and its gradient — must hold exactly
    the three kernels, each matched by its own pattern and by no other,
    whatever the shapes their operands and results have."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    try:
        import xplane
    finally:
        sys.path.remove(bench)
    with open(os.path.join(bench, "layer_metrics",
                           "flash_roofline.train.json")) as f:
        patterns = json.load(f)["args"]["kernels"]
    assert set(patterns) == {"fwd", "dq", "dkv"}

    text = _compiled_training_gradient(one_chip, monkeypatch, **model)
    # an operation as the reduced trace labels it
    labels = [xplane.parse_op(line.strip().removeprefix("ROOT "))[0]
              for line in text.splitlines()
              if "custom-call(" in line and "tpu_custom_call" in line]
    assert {lab.split(".")[0] for lab in labels} == {
        "dstpu_flash_fwd", "dstpu_flash_dq", "dstpu_flash_dkv"}
    for lab in labels:
        kinds = [k for k, rx in patterns.items() if re.search(rx, lab)]
        assert kinds == [lab.split(".")[0].removeprefix("dstpu_flash_")], lab


def _relayouts(text, *scopes):
    """(name, result) of every instruction outside a fused computation that
    only lays a tensor out anew — a `copy`, a `transpose`, or a fusion the
    compiler named for one (`copy_bitcast_fusion`, `transpose_...`) — and
    whose `op_name` ends in one of `scopes`."""
    found = []
    for comp, lines in _computations(text).items():
        if "fused_computation" in comp:
            continue
        for line in lines:
            parsed = _HLO_LINE.match(line)
            scope = re.search(r'op_name="([^"]*)"', line)
            if not parsed or not scope or not scope.group(1).endswith(scopes):
                continue
            name, result, opcode = parsed.groups()
            if opcode in ("copy", "transpose") or opcode == "fusion" and \
                    name.startswith(("copy", "transpose")):
                found.append((name, result))
    return found


def test_training_step_lays_nothing_out_anew_at_the_flash_kernels_boundary(
        one_chip, monkeypatch):
    """At a head width of 128 the flash kernels address v, o, dO and dv in
    the projections' own `[B, T, H*D]` arrays (`flash_attention.py::
    _column_tiles`) and q, k, dq, dk head-major as the rotation writes and
    reads them, so the compiled gradient of a remat'd Pythia-like block
    holds no relayout named for the kernels' boundary — `attn/transpose`,
    `attn/reshape`: the parent held five there (dO head-major, o back, dq's
    and dk's way into a T-minor rotation) — and none under
    `attn/qkv/reshape` (the parent's seven: v and its recomputed copy, dv,
    the rotation's two results turned `[B, T, H*D]`, ...). What is left
    rides in a fusion that does other work: q's and k's relayout in the
    rotation's (forward and recomputed), dq's and dk's in the update that
    places them in the product's gradient (`models/gpt.py::_rope`'s
    backward holds its result behind an `optimization_barrier`, PERF.md
    section 7)."""
    text = _compiled_training_gradient(one_chip, monkeypatch, **_PYTHIA_LIKE)
    assert _relayouts(text, "attn/transpose", "attn/reshape",
                      "attn/qkv/reshape") == []
    # ... and the rotation still is one fusion a tensor with its matrix
    # product inside: q and k, forward and recomputed, dq and dk
    rotations = [
        line for comp, lines in _computations(text).items()
        if "fused_computation" not in comp for line in lines
        if " fusion(" in line and "attn/qkv/dot_general" in line
        and re.search(r"= bf16\[2,2048,2,128\]", line)]
    assert len(rotations) == 6, rotations


# ----------------------------------------------------------------------
# the paged programs hold nothing of the pool's size but the in-place
# writes (compiled for a described TPU, no chip needed)
# ----------------------------------------------------------------------

_HLO_LINE = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([a-z][a-z0-9\-]*)\(")
_HLO_ARRAY = re.compile(r"\b[a-z]+(\d+)\[([\d,]*)\]")      # f32[8,128]
# instructions that only name, pass on or alias a buffer
_NO_NEW_BUFFER = {"parameter", "tuple", "get-tuple-element", "bitcast",
                  "while", "custom-call"}


def _large_instructions(text, at_least):
    """(name, opcode) of every instruction of the compiled program whose
    result holds an array of `at_least` bytes or more, fused ones included."""
    large = []
    for line in text.splitlines():
        found = _HLO_LINE.match(line)
        if not found:
            continue
        name, result, opcode = found.groups()
        for bits, dims in _HLO_ARRAY.findall(result):
            elements = np.prod([int(d) for d in dims.split(",") if d] or [1])
            if int(elements) * int(bits) // 8 >= at_least:
                large.append((name, opcode))
                break
    return large


def _computations(text):
    """{name: its lines} for every computation of a compiled program."""
    found, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            lines = found[head.group(1)] = []
        elif lines is not None:
            lines.append(line)
    return found


def _assert_decode_walk_is_built_once_a_token(text):
    """The layer loop's body holds ONE call named `dstpu_paged_decode*`, the
    program no other, and nothing of the walk's work list (the operations
    under the `paged_decode_work` scope) is computed inside that body or in
    a computation it calls: the list is built once a token, outside."""
    comps = _computations(text)
    calls = {name: [l.split("=")[0].strip().lstrip("%") for l in lines
                    if "custom-call(" in l and "tpu_custom_call" in l]
             for name, lines in comps.items()}
    holders = [name for name, kernels in calls.items()
               if any(k.startswith("dstpu_paged_decode") for k in kernels)]
    assert len(holders) == 1, holders
    body = holders[0]
    assert len([k for k in calls[body]
                if k.startswith("dstpu_paged_decode")]) == 1, calls[body]
    assert any(re.search(r"\bwhile\(.*body=%?" + re.escape(body) + r"\b", l)
               for lines in comps.values() for l in lines), body
    reached, todo = set(), [body]
    while todo:
        name = todo.pop()
        if name in reached or name not in comps:
            continue
        reached.add(name)
        for l in comps[name]:
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%?"
                               r"([\w.\-]+)", l)
    work = [name for name, lines in comps.items()
            if any("paged_decode_work" in l for l in lines)]
    assert work and not set(work) & reached, (work, sorted(reached))


def _compile_paged_programs(one_chip, pool_dtype, chunk=64, mixed=False):
    """The paged decode and prefill programs of a 2-layer model at the
    served tile widths (Hkv 8, block 512, hd 128), pool donated; the prefill
    chunk `chunk` rows over a table of 8 blocks. `mixed`: the mixed program
    too (the chunk and the 8 decode rows in one call, 16-wide tables)."""
    from deepspeed_tpu.models.gpt import gpt_init_fn

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg = GPTConfig(vocab_size=512, n_layer=2, n_head=8, n_kv_head=8,
                    d_model=1024, d_ff=1024, max_seq_len=8192,
                    use_rotary=True, use_rmsnorm=True, dtype=jnp.bfloat16,
                    remat=False)
    shapes = jax.eval_shape(gpt_init_fn(cfg, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    spec = make_gpt_decode_model(cfg, name="guard", params=shapes)
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), shapes)
    pool = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: spec.init_paged_pool(64, 512, pool_dtype)))
    layer_leaf = pool["k"].size // cfg.n_layer * pool["k"].dtype.itemsize
    i32 = jnp.int32
    # 16-wide tables: an 8192-token context, where the decode kernel engages
    decode = jax.jit(spec.decode_paged_fn, donate_argnums=(3,)).lower(
        params, sds((8,), i32), sds((8,), i32), pool,
        sds((8, 16), i32)).compile()
    prefill = jax.jit(spec.prefill_paged_fn, donate_argnums=(4,)).lower(
        params, sds((1, chunk), i32), sds((1,), i32), sds((1,), i32), pool,
        sds((1, 8), i32)).compile()
    programs = {"decode": decode, "prefill": prefill}
    if mixed:
        programs["mixed"] = jax.jit(
            spec.mixed_paged_fn, donate_argnums=(7,)).lower(
            params, sds((1, chunk), i32), sds((1,), i32), sds((1,), i32),
            sds((1, 16), i32), sds((8,), i32), sds((8,), i32), pool,
            sds((8, 16), i32)).compile()
    return programs, layer_leaf, dict(spec.kv_pool_writers)


def test_paged_programs_hold_nothing_of_the_pools_size(one_chip, monkeypatch):
    """The trap this guards (PERF.md §6, PR 25): an XLA scatter or
    update-slice on a pool that is carried through the layer scan and read
    by a Mosaic call makes XLA copy the WHOLE pool inside the loop. In the
    in-place form the only instructions as large as ONE layer's pool leaf
    are the aliased `dstpu_kv_pool_write` calls, and the program's
    temporaries stay under that size too."""
    from deepspeed_tpu.ops import attention_dispatch
    from deepspeed_tpu.platform import device
    mesh_mod.clear_mesh()
    monkeypatch.setattr(device, "on_tpu", lambda: True)   # what the chip sees

    programs, layer_leaf, writers = _compile_paged_programs(one_chip,
                                                            jnp.bfloat16)
    assert writers == {"paged_decode": attention_dispatch.KV_POOL_WRITE_KERNEL,
                       "prefill_chunk": attention_dispatch.KV_POOL_WRITE_KERNEL}
    for name, program in programs.items():
        text = program.as_text()
        large = _large_instructions(text, layer_leaf)
        assert [x for x in large if x[1] not in _NO_NEW_BUFFER] == [], name
        calls = [n for n, opcode in large if opcode == "custom-call"]
        assert len(calls) == 2 and all(
            n.startswith("dstpu_kv_pool_write") for n in calls), (name, calls)
        assert program.memory_analysis().temp_size_in_bytes < layer_leaf, name
    # and what reads the carried pool is a Mosaic call as well (a chunk of
    # 64 rows is off the lane tile: the gather and the dense attend)
    _assert_decode_walk_is_built_once_a_token(programs["decode"].as_text())
    assert "dstpu_kv_pool_gather" in programs["prefill"].as_text()
    assert "dstpu_paged_prefill" not in programs["prefill"].as_text()

    # the int8 pool: the rule declines, and the program is today's — the pool
    # sliced and restacked by the scan, an XLA scatter on each slice
    programs, layer_leaf, writers = _compile_paged_programs(one_chip, jnp.int8)
    assert set(writers.values()) == {attention_dispatch.KV_POOL_WRITE_SCATTER}
    for name, program in programs.items():
        text = program.as_text()
        assert "dstpu_kv_pool_write" not in text, name
        assert [x for x in _large_instructions(text, layer_leaf)
                if x[1] not in _NO_NEW_BUFFER], name


def _instructions_spanning(text, width):
    """Names of the instructions, fused ones included, whose result has a
    dimension of `width`."""
    found = []
    for line in text.splitlines():
        match = _HLO_LINE.match(line)
        if match and any(str(width) in dims.split(",")
                         for _, dims in _HLO_ARRAY.findall(match.group(2))):
            found.append(match.group(1))
    return found


def test_prefill_chunk_program_holds_nothing_as_wide_as_the_table(
        one_chip, monkeypatch):
    """A 512-row chunk on the in-place pool (PERF.md §6, PR 30): attention is
    the `dstpu_paged_prefill` walk, so the program holds no gather of the
    row's table, no operation whose result spans the table's `nb * block`
    positions (the gathered K/V, the float32 scores and probabilities of the
    dense attend), and — as before — nothing of a pool layer's size but the
    aliased writes. The same chunk on the scatter form is the control: the
    width IS found there."""
    from deepspeed_tpu.ops import attention_dispatch
    from deepspeed_tpu.platform import device
    mesh_mod.clear_mesh()
    table_positions = 8 * 512

    programs, layer_leaf, writers = _compile_paged_programs(
        one_chip, jnp.int8, chunk=512)
    assert writers["prefill_chunk"] == attention_dispatch.KV_POOL_WRITE_SCATTER
    assert _instructions_spanning(programs["prefill"].as_text(),
                                  table_positions)

    monkeypatch.setattr(device, "on_tpu", lambda: True)   # what the chip sees
    programs, layer_leaf, writers = _compile_paged_programs(
        one_chip, jnp.bfloat16, chunk=512)
    assert writers["prefill_chunk"] == attention_dispatch.KV_POOL_WRITE_KERNEL
    text = programs["prefill"].as_text()
    assert set(_mosaic_calls(text)) == {"dstpu_kv_pool_write",
                                        "dstpu_paged_prefill"}
    assert _instructions_spanning(text, table_positions) == []
    large = _large_instructions(text, layer_leaf)
    assert [x for x in large if x[1] not in _NO_NEW_BUFFER] == []
    assert all(n.startswith("dstpu_kv_pool_write")
               for n, opcode in large if opcode == "custom-call")
    assert programs["prefill"].memory_analysis().temp_size_in_bytes \
        < layer_leaf


def _mosaic_calls(text):
    """Names of a compiled program's Mosaic calls, the instruction suffix
    (`.3`) dropped, one entry a call."""
    return [line.split("=")[0].strip().lstrip("%").rsplit(".", 1)[0]
            for line in text.splitlines()
            if "custom-call(" in line and "tpu_custom_call" in line]


def test_mixed_program_holds_both_walks_and_nothing_of_the_pools_size(
        one_chip, monkeypatch):
    """A chunk riding the decode call on the in-place pool (PERF.md §6, PR
    33): the mixed program's layer writes each leaf TWICE (the chunk's rows,
    the slots' rows: `dstpu_kv_pool_write`, aliased), attends the chunk with
    `dstpu_paged_prefill` and the slots with `dstpu_paged_decode` (its work
    list built once, outside the layer loop), and holds nothing of a pool
    layer's size but those writes — PR 25's invariant, in the third
    program."""
    from deepspeed_tpu.ops import attention_dispatch
    from deepspeed_tpu.platform import device
    mesh_mod.clear_mesh()
    monkeypatch.setattr(device, "on_tpu", lambda: True)   # what the chip sees
    programs, layer_leaf, writers = _compile_paged_programs(
        one_chip, jnp.bfloat16, chunk=512, mixed=True)
    assert writers["mixed"] == attention_dispatch.KV_POOL_WRITE_KERNEL
    text = programs["mixed"].as_text()
    calls = _mosaic_calls(text)
    assert sorted(calls) == ["dstpu_kv_pool_write"] * 4 + [
        "dstpu_paged_decode", "dstpu_paged_prefill"]
    large = _large_instructions(text, layer_leaf)
    assert [x for x in large if x[1] not in _NO_NEW_BUFFER] == []
    writes = [n for n, opcode in large if opcode == "custom-call"]
    assert len(writes) == 4 and all(
        n.startswith("dstpu_kv_pool_write") for n in writes), writes
    assert programs["mixed"].memory_analysis().temp_size_in_bytes \
        < layer_leaf
    assert _instructions_spanning(text, 16 * 512) == []
    _assert_decode_walk_is_built_once_a_token(text)


# opcode -> instructions, fused ones included, of the guard model's decode
# and 512-row prefill programs compiled for a described v5e on the in-place
# pool. PR 33 (which put `_paged_attn_half`'s write and attend into
# `_paged_write_attend` for the mixed program to call twice) left them as
# they were, instruction for instruction (PERF.md §6, PR 33); PR 53 meant
# to change them (`_rope` as one multiply-add around a permutation product)
# and read them again: of the `custom-call`s the Mosaic calls stand, counted
# below, and the rest are the gathers' `AssumeGatherIndicesInBound`. A PR
# that means to change these programs reads them again.
_PARENT_OPCODES = {
    "decode": {
        "add": 32, "and": 7, "bitcast": 51, "broadcast": 83, "clamp": 2,
        "compare": 25, "constant": 77, "convert": 37, "convolution": 7,
        "copy": 6, "copy-done": 10, "copy-start": 10, "cosine": 1,
        "custom-call": 5, "dynamic-slice": 11, "fusion": 49, "gather": 1,
        "get-tuple-element": 50, "iota": 8, "maximum": 1, "minimum": 2,
        "multiply": 27, "negate": 4, "or": 1, "pad": 1, "parameter": 151,
        "power": 1, "reduce": 10, "reduce-window": 1, "reshape": 3,
        "rsqrt": 3, "select": 22, "shift-right-logical": 2, "sign": 2,
        "sine": 1, "slice": 13, "slice-done": 2, "slice-start": 2,
        "subtract": 2, "tanh": 1, "transpose": 2, "tuple": 9, "while": 1},
    "prefill": {
        "add": 39, "and": 11, "bitcast": 48, "broadcast": 66, "clamp": 4,
        "compare": 24, "constant": 83, "convert": 41, "convolution": 6,
        "copy": 15, "copy-done": 12, "copy-start": 12, "cosine": 1,
        "custom-call": 8, "dynamic-slice": 13, "fusion": 51, "gather": 3,
        "get-tuple-element": 37, "iota": 10, "minimum": 1, "multiply": 28,
        "negate": 6, "pad": 2, "parameter": 147, "power": 1, "reduce": 11,
        "reshape": 8, "rsqrt": 3, "select": 21, "shift-right-logical": 2,
        "sign": 2, "sine": 1, "slice": 9, "slice-done": 6, "slice-start": 6,
        "subtract": 2, "tanh": 1, "transpose": 6, "tuple": 7, "while": 1},
}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_chunk_only_and_decode_only_programs_are_the_parents(
        one_chip, monkeypatch, program):
    """The two programs that steps holding one kind of work still run take
    the same branches through `_paged_attn_half` as before the mixed program
    existed: same instructions, opcode for opcode, same Mosaic calls."""
    from deepspeed_tpu.platform import device
    mesh_mod.clear_mesh()
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    programs, _, _ = _compile_paged_programs(one_chip, jnp.bfloat16,
                                             chunk=512)
    text = programs[program].as_text()
    opcodes = collections.Counter(
        found.group(3) for found in map(_HLO_LINE.match, text.splitlines())
        if found)
    assert dict(opcodes) == _PARENT_OPCODES[program]
    assert collections.Counter(_mosaic_calls(text)) == {
        "decode": {"dstpu_kv_pool_write": 2, "dstpu_paged_decode": 1},
        "prefill": {"dstpu_kv_pool_write": 2, "dstpu_paged_prefill": 1},
    }[program]


def _compile_routed_paged_programs(one_chip, window):
    """The paged prefill program and the decode WINDOW program (the
    scheduler's scan over `window` steps) of a 2-layer routed-expert model
    at the served tile widths, pool donated."""
    from deepspeed_tpu.models.moe_gpt import (MoEGPTConfig,
                                              make_moe_gpt_decode_model,
                                              moe_gpt_init_fn)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg = MoEGPTConfig(vocab_size=512, n_layer=2, n_head=8, n_kv_head=8,
                       d_model=1024, d_ff=1024, max_seq_len=1536,
                       use_rotary=True, use_rmsnorm=True, use_swiglu=True,
                       qk_norm=True, tie_embeddings=False, num_experts=8,
                       top_k=4, moe_freq=1, use_flash_attention=True,
                       dtype=jnp.bfloat16, remat=False)
    shapes = jax.eval_shape(moe_gpt_init_fn(cfg, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    spec = make_moe_gpt_decode_model(cfg, name="guard", params=shapes)
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), shapes)
    pool = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: spec.init_paged_pool(64, 512, jnp.bfloat16)))
    layer_leaf = pool["k"].size // cfg.n_layer * pool["k"].dtype.itemsize
    experts = shapes["blocks"]["moe_w_gate_up"]
    layer_experts = experts.size // cfg.n_layer * experts.dtype.itemsize
    i32 = jnp.int32

    def decode_window(params, tok, pos, pool, tables):
        def body(carry, _):
            tok, pos, pool, acc = carry
            logits, pool, counts = spec.decode_paged_fn(params, tok, pos,
                                                        pool, tables)
            nxt = jnp.argmax(logits, -1).astype(i32)
            return (nxt, pos + 1, pool, acc + counts), nxt
        (_, _, pool, acc), toks = jax.lax.scan(
            body, (tok, pos, pool, jnp.zeros((4,), i32)), None, length=window)
        return (toks, acc), pool

    decode = jax.jit(decode_window, donate_argnums=(3,)).lower(
        params, sds((64,), i32), sds((64,), i32), pool,
        sds((64, 3), i32)).compile()
    prefill = jax.jit(spec.prefill_paged_fn, donate_argnums=(4,)).lower(
        params, sds((1, 64), i32), sds((1,), i32), sds((1,), i32), pool,
        sds((1, 3), i32)).compile()
    return {"decode": decode, "prefill": prefill}, layer_leaf, \
        layer_experts, dict(spec.kv_pool_writers)


def test_routed_paged_programs_hold_nothing_of_the_pools_or_experts_size(
        one_chip, monkeypatch):
    """The same guard for the routed-expert model (`models/moe_gpt.py`, the
    experts stacked in `blocks`), on the programs the scheduler runs: the
    prefill chunk and the decode window of 4 (a scan of the layer scan).
    The pool stays in place through both loops, and nothing copies a layer
    of experts either: the grouped matmul takes the whole stack and an
    offset (a slice of the stack in front of the Mosaic call would be a
    copy of ~0.8 GB a layer a step at OLMoE's widths)."""
    from deepspeed_tpu.ops import attention_dispatch
    from deepspeed_tpu.platform import device
    mesh_mod.clear_mesh()
    monkeypatch.setattr(device, "on_tpu", lambda: True)

    programs, layer_leaf, layer_experts, writers = \
        _compile_routed_paged_programs(one_chip, window=4)
    assert writers == {"paged_decode": attention_dispatch.KV_POOL_WRITE_KERNEL,
                       "prefill_chunk": attention_dispatch.KV_POOL_WRITE_KERNEL}
    for name, program in programs.items():
        text = program.as_text()
        large = _large_instructions(text, min(layer_leaf, layer_experts))
        assert [x for x in large if x[1] not in _NO_NEW_BUFFER] == [], name
        calls = {n.rsplit(".", 1)[0] for n, opcode in large
                 if opcode == "custom-call"}
        assert calls <= {"dstpu_kv_pool_write", "dstpu_moe_gmm"}, (name, calls)
        assert program.memory_analysis().temp_size_in_bytes \
            < min(layer_leaf, layer_experts), name
        assert "dstpu_moe_gmm" in text, name
    # in the window program the list is built in the token loop's body, once
    # a token, and not in the layer loop's inside it
    _assert_decode_walk_is_built_once_a_token(programs["decode"].as_text())
    assert "dstpu_kv_pool_gather" in programs["prefill"].as_text()


# ----------------------------------------------------------------------
# a generator's block loop (inference/step_programs.py::
# _block_diffusion_steps): the same guard on the loop of loops
# ----------------------------------------------------------------------


def _compile_block_diffusion_programs(one_chip, blocks):
    """`decode_step` and `mixed_step` as `build_resident` makes them for a
    2-layer SDAR-shaped model (routed experts, blocks of 4 rows a slot, two
    denoise steps) at the served tile widths (Hkv 4, pool blocks of 512, hd
    128), `blocks` blocks a call and a group of 2 chunks a riding forward."""
    from deepspeed_tpu.inference import step_programs
    from deepspeed_tpu.inference.config import TpuInferenceConfig
    from deepspeed_tpu.models import sdar_moe
    from deepspeed_tpu.models.moe_gpt import MoEGPTConfig

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, steps, S, C, G, nb = 4, 2, 64, 256, 2, 3
    cfg = MoEGPTConfig(vocab_size=512, n_layer=2, n_head=8, n_kv_head=4,
                       d_model=1024, d_ff=1024, max_seq_len=nb * 512,
                       use_rotary=True, use_rmsnorm=True, use_swiglu=True,
                       qk_norm=True, tie_embeddings=False, num_experts=8,
                       top_k=4, moe_freq=1, use_flash_attention=True,
                       block_length=B, dtype=jnp.bfloat16, remat=False)
    shapes = jax.eval_shape(sdar_moe.sdar_moe_init_fn(cfg, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    spec = sdar_moe.make_sdar_moe_decode_model(
        cfg, sdar_moe.generator(B, 511, steps), params=shapes, name="guard")
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), shapes)
    pool = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: spec.init_paged_pool(256, 512, jnp.bfloat16)))
    layer_leaf = pool["k"].size // cfg.n_layer * pool["k"].dtype.itemsize
    experts = shapes["blocks"]["moe_w_gate_up"]
    layer_experts = experts.size // cfg.n_layer * experts.dtype.itemsize
    W = blocks * (steps + 1) - 2 * (blocks - 1)      # the forwards that ride

    class NoWatchdog:
        def wrap(self, name, fn):
            return fn

    built = dict(step_programs.build_resident(
        spec, TpuInferenceConfig.from_dict({"dtype": "bfloat16",
                                            "greedy": True}),
        lambda fn: fn, window=W, max_slots=S, chunk=C, spec_on=False,
        draft_k=0, replicated=one_chip, watchdog=NoWatchdog(), group=G,
        blocks_per_call=blocks, denoising_steps=steps).built())
    slots = (sds((S, B)), sds((S,)), pool, sds((S, nb)),
             sds((2,), jnp.uint32))
    programs = {
        "decode": built["decode_step"].lower(params, *slots).compile(),
        "mixed": built["mixed_step"].lower(
            params, sds((W, G, C)), sds((W, G)), sds((W, G)),
            sds((W, G, nb)), sds(()), *slots).compile()}
    return programs, layer_leaf, layer_experts, dict(spec.kv_pool_writers)


@pytest.mark.parametrize("blocks", [1, 2])
def test_block_diffusion_programs_hold_nothing_of_the_pools_size(
        one_chip, monkeypatch, blocks):
    """A call's loop runs a traced body a KIND of forward (B rows a slot
    with a chunk group riding, B rows without, 2B rows fused), each a
    `while` of its own inside the call's loop, on the carried pool. The
    trap (PERF.md §6, PR 59): a conditional that the pool passes through —
    a `lax.switch` over the kinds, a `lax.cond` around a forward — does not
    hand it on in place, and XLA copies a whole pool leaf into and out of
    every layer's write (at the cell's size: temporaries 3.95 GiB for
    0.15). So, as for the other families' programs: nothing as large as a
    layer's pool leaf or a layer's experts but the aliased Mosaic calls, and
    temporaries under that size; the head's `lax.cond` takes rows only."""
    from deepspeed_tpu.ops import attention_dispatch
    from deepspeed_tpu.platform import device
    mesh_mod.clear_mesh()
    monkeypatch.setattr(device, "on_tpu", lambda: True)

    programs, layer_leaf, layer_experts, writers = \
        _compile_block_diffusion_programs(one_chip, blocks)
    assert set(writers.values()) == {attention_dispatch.KV_POOL_WRITE_KERNEL}
    for name, program in programs.items():
        text = program.as_text()
        # (by the pool's size: at these widths XLA prefetches a layer of
        # experts into fast memory, which is not what is guarded)
        large = _large_instructions(text, layer_leaf)
        assert [x for x in large if x[1] not in _NO_NEW_BUFFER] == [], name
        calls = {n.rsplit(".", 1)[0] for n, opcode in large
                 if opcode == "custom-call"}
        assert calls == {"dstpu_kv_pool_write"}, (name, calls)
        assert program.memory_analysis().temp_size_in_bytes \
            < min(layer_leaf, layer_experts), name
        assert "dstpu_moe_gmm" in text, name
        # a model body a kind of forward: one walk a group of rows a body
        bodies = {"decode": 1, "mixed": 2}[name] + (blocks > 1)
        walks = [c for c in _mosaic_calls(text)
                 if c.startswith("dstpu_paged_decode")]
        assert len(walks) == bodies + (blocks > 1), (name, walks)


# ----------------------------------------------------------------------
# a pool of TWO KINDS (models/exaone_moe.py): the same guard, both kinds
# ----------------------------------------------------------------------


def _compile_two_kind_paged_programs(one_chip, window, periods=2,
                                     mixed=False, group=1):
    """The paged prefill program and the decode WINDOW program of a
    K-EXAONE-shaped model (a dense window layer, then `periods` periods of
    window, window, window, full with 8 of 32 experts held) at the served
    tile widths: full-kind blocks of 512, window-kind rings of 128-token
    blocks. `mixed`: the mixed program too (a group of `group` chunks and
    the slots' decode rows in one call)."""
    from deepspeed_tpu.inference.kv_cache import ring_blocks
    from deepspeed_tpu.models import exaone_moe as em

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layers = (em.WINDOW,) + (em.WINDOW, em.WINDOW, em.WINDOW,
                             em.FULL) * periods
    cfg = em.ExaoneMoEConfig(
        vocab_size=512, n_layer=len(layers), n_head=16, n_kv_head=8,
        d_model=1024, attn_head_dim=128, d_ff=1024, d_ff_dense=2048,
        max_seq_len=8192, sliding_window=128, tie_embeddings=False,
        num_experts=32, experts_held=(8, 8), top_k=4, norm_topk_prob=True,
        routed_scaling_factor=2.5, layer_types=layers,
        mlp_layer_types=(em.DENSE,) + (em.SPARSE,) * (len(layers) - 1),
        pattern_period=4,
        window_block=128, use_flash_attention=True, dtype=jnp.bfloat16,
        remat=False)
    shapes = jax.eval_shape(em.exaone_moe_init_fn(cfg, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    spec = em.make_exaone_moe_decode_model(cfg, name="guard", params=shapes)
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), shapes)
    # pools large beside the weights: at this width XLA prefetches whole
    # weight leaves (16 MiB) into fast memory, which is not what is guarded
    slots, chunk = 64, 512
    ring = ring_blocks(128, 128, chunk, window)
    pool = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: spec.init_paged_pool(
            128, 512, jnp.bfloat16, window_blocks=1 + slots * ring)))
    # one LAYER's leaf of each kind, in bytes
    leaves = {k: v.size // v.shape[0] * v.dtype.itemsize
              for k, v in pool.items()}
    i32 = jnp.int32
    n_counters = len(spec.step_counters)

    def decode_window(params, tok, pos, pool, tables):
        def body(carry, _):
            tok, pos, pool, acc = carry
            logits, pool, counts = spec.decode_paged_fn(params, tok, pos,
                                                        pool, tables)
            nxt = jnp.argmax(logits, -1).astype(i32)
            return (nxt, pos + 1, pool, acc + counts), nxt
        (_, _, pool, acc), toks = jax.lax.scan(
            body, (tok, pos, pool, jnp.zeros((n_counters,), i32)), None,
            length=window)
        return (toks, acc), pool

    decode = jax.jit(decode_window, donate_argnums=(3,)).lower(
        params, sds((slots,), i32), sds((slots,), i32), pool,
        (sds((slots, 16), i32), sds((slots, 64), i32))).compile()
    prefill = jax.jit(spec.prefill_paged_fn, donate_argnums=(4,)).lower(
        params, sds((1, chunk), i32), sds((1,), i32), sds((1,), i32), pool,
        (sds((1, 16), i32), sds((1, 64), i32))).compile()
    programs = {"decode": decode, "prefill": prefill}
    if mixed:
        G = group       # a traced count of the real chunks where G > 1
        programs["mixed"] = jax.jit(
            spec.mixed_paged_fn, donate_argnums=(7,)).lower(
            params, sds((G, chunk), i32), sds((G,), i32), sds((G,), i32),
            (sds((G, 16), i32), sds((G, 64), i32)), sds((slots,), i32),
            sds((slots,), i32), pool,
            (sds((slots, 16), i32), sds((slots, 64), i32)),
            *([sds((), i32)] if G > 1 else [])).compile()
    return programs, leaves, dict(spec.kv_pool_writers), \
        dict(spec.paged_attn_programs)


@pytest.mark.parametrize("periods,group", [(1, 1), (2, 1), (1, 3), (2, 2)])
def test_two_kind_mixed_program_holds_nothing_of_either_pools_size(
        one_chip, monkeypatch, periods, group):
    """The mixed program on a pool of two kinds (PERF.md §6, PR 33): both
    kinds written twice a layer by the aliased `dstpu_kv_pool_write`, the
    chunk walked by `dstpu_paged_prefill` and the slots by
    `dstpu_paged_decode` from either kind's window, and nothing half as large
    as a layer's leaf made by anything else. One period is the benchmark's
    five layers, whose period scan XLA unrolls. The trap this holds: the
    slots' rows do not depend on the chunk's attention, so without the
    barrier between the two groups (`_paged_attn_half`) XLA may write them
    before the chunk's walk has read the pool and keep the walk's input by
    COPYING a leaf (on the chip at the served size: two copies of 1.5 GB a
    mixed token, 23% of the cell's time). `group` > 1 (PR 51): a token
    carries a group of chunks, written and walked one after another by ONE
    traced copy in a loop that carries the flat leaves — still the same
    Mosaic calls a layer, still nothing of a leaf's size beside them, and
    the fused QKV product made once a layer (no clone of its matmul)."""
    from deepspeed_tpu.ops import attention_dispatch
    from deepspeed_tpu.platform import device
    mesh_mod.clear_mesh()
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    programs, leaves, writers, attention = _compile_two_kind_paged_programs(
        one_chip, window=4, periods=periods, mixed=True, group=group)
    assert writers["mixed"] == attention_dispatch.KV_POOL_WRITE_KERNEL
    assert attention["mixed/prefill_chunk"] == "paged_prefill_kernel"
    assert attention["mixed/paged_decode"] == "paged_kernel"
    text = programs["mixed"].as_text()
    half = min(leaves.values()) // 2
    large = _large_instructions(text, half)
    assert [x for x in large if x[1] not in _NO_NEW_BUFFER] == []
    assert {n.rsplit(".", 1)[0] for n, opcode in large
            if opcode == "custom-call"} == {"dstpu_kv_pool_write"}
    assert programs["mixed"].memory_analysis().temp_size_in_bytes < half
    calls = collections.Counter(_mosaic_calls(text))
    layers = 1 + 4 * periods if periods == 1 else 5     # unrolled | a period
    assert calls["dstpu_kv_pool_write"] == 4 * layers
    assert calls["dstpu_paged_prefill"] == calls["dstpu_paged_decode"] \
        == layers
    assert "dstpu_kv_pool_gather" not in text
    assert not re.search(r"\.remat\d* = [^\n]*attn_\w+/dot_general", text)


def test_two_kind_paged_programs_hold_nothing_of_either_pools_size(
        one_chip, monkeypatch):
    """PR 25's guard for a pool of two kinds, by BYTES and by halves: in the
    prologue and inside the period scan alike, nothing HALF as large as one
    layer's leaf of the smaller kind is made by an operation that is not an
    aliased Mosaic call (`dstpu_kv_pool_write`, on either kind), and the program's
    temporaries stay under that size too; the decode walks' two work lists
    (one a kind) are built once a token, outside the period scan."""
    from deepspeed_tpu.ops import attention_dispatch
    from deepspeed_tpu.platform import device
    mesh_mod.clear_mesh()
    monkeypatch.setattr(device, "on_tpu", lambda: True)

    programs, leaves, writers, attention = \
        _compile_two_kind_paged_programs(one_chip, window=4)
    assert writers == {"paged_decode": attention_dispatch.KV_POOL_WRITE_KERNEL,
                       "prefill_chunk": attention_dispatch.KV_POOL_WRITE_KERNEL}
    assert attention == {"paged_decode": "paged_kernel",
                         "prefill_chunk": "paged_prefill_kernel"}
    half = min(leaves.values()) // 2
    for name, program in programs.items():
        text = program.as_text()
        large = _large_instructions(text, half)
        assert [x for x in large if x[1] not in _NO_NEW_BUFFER] == [], name
        calls = {n.rsplit(".", 1)[0] for n, opcode in large
                 if opcode == "custom-call"}
        assert calls == {"dstpu_kv_pool_write"}, (name, calls)
        assert program.memory_analysis().temp_size_in_bytes < half, name
        assert "dstpu_kv_pool_gather" not in text, name
        assert "dstpu_moe_gmm" in text, name
    assert "dstpu_paged_prefill" in programs["prefill"].as_text()

    # the decode walks: in the token loop's body (the prologue's layer) and
    # in the period scan's body inside it; the work lists in neither scan
    # body's reach but the token loop's own
    comps = _computations(programs["decode"].as_text())
    holders = [name for name, lines in comps.items()
               if any("dstpu_paged_decode" in l and "custom-call(" in l
                      for l in lines)]
    assert len(holders) == 2, holders

    def reach(body):
        reached, todo = set(), [body]
        while todo:
            name = todo.pop()
            if name in reached or name not in comps:
                continue
            reached.add(name)
            for l in comps[name]:
                todo += re.findall(r"(?:calls|to_apply|body|condition)=%?"
                                   r"([\w.\-]+)", l)
        return reached

    inner = [h for h in holders
             if any(h in reach(o) for o in holders if o != h)]
    assert len(inner) == 1, (holders, inner)
    work = [name for name, lines in comps.items()
            if any("paged_decode_work" in l for l in lines)]
    assert work and not set(work) & reach(inner[0]), work
    # four walks a period in the scan's body, one in the prologue
    walks = {h: sum("dstpu_paged_decode" in l and "custom-call(" in l
                    for l in comps[h]) for h in holders}
    assert sorted(walks.values()) == [1, 4], walks


# ----------------------------------------------------------------------
# a pool with a STATE kind (recurrent layers): the state is touched in place
# ----------------------------------------------------------------------


def _nemotron_shaped_guard():
    """Two (LatentMoE, Mamba-2) pairs scanned, then an attention layer."""
    from deepspeed_tpu.models import nemotron_h as nh
    cfg = nh.NemotronHConfig(
        vocab_size=512, pattern="EMEM*", n_head=16, n_kv_head=2,
        d_model=512, attn_head_dim=128, d_ff=256, shared_d_ff=512,
        moe_latent_size=256, max_seq_len=8192, num_experts=32,
        experts_held=(8, 8), top_k=4, norm_topk_prob=True,
        routed_scaling_factor=5.0, mamba_num_heads=16, mamba_head_dim=64,
        ssm_state_size=128, n_groups=2, chunk_size=128,
        use_flash_attention=True, dtype=jnp.bfloat16, remat=False)
    return (cfg, nh.nemotron_h_init_fn, nh.make_nemotron_h_decode_model,
            "dstpu_ssm_update")


def _qwen3_next_shaped_guard():
    """Two (Gated DeltaNet, experts) layers scanned, then a gated-attention
    layer at head width 256 with eight query heads a key-value head."""
    from deepspeed_tpu.models import qwen3_next as qn
    cfg = qn.Qwen3NextConfig(
        vocab_size=512, pattern=("DE", "DE", "*E"), n_head=16, n_kv_head=2,
        d_model=512, attn_head_dim=256, d_ff=256, shared_d_ff=256,
        max_seq_len=8192, num_experts=32, experts_held=(8, 8), top_k=4,
        gdn_key_heads=8, gdn_value_heads=16, gdn_key_dim=128,
        gdn_value_dim=128, chunk_size=64, use_flash_attention=True,
        dtype=jnp.bfloat16, remat=False)
    return (cfg, qn.qwen3_next_init_fn, qn.make_qwen3_next_decode_model,
            "dstpu_gdn_update")


@pytest.mark.parametrize("family", [_nemotron_shaped_guard,
                                    _qwen3_next_shaped_guard],
                         ids=["mamba2", "gated-deltanet"])
def test_state_kind_programs_hold_nothing_of_the_states_size(
        one_chip, monkeypatch, family):
    """PR 25's guard for a pool with a state kind (a Nemotron-H-shaped model
    and a Qwen3-Next-shaped one, at the served tile widths), in all three
    step programs: nothing half as large as
    ONE LAYER's state leaf is made by an operation that is not an aliased
    Mosaic call (`dstpu_ssm_update` or `dstpu_gdn_update`,
    `dstpu_ssm_state_write` on the state;
    `dstpu_kv_pool_write` on the attention layer's blocks), the temporaries
    stay under that size, a decode token updates each layer's state in ONE
    call and a chunk reads and writes its row once."""
    from deepspeed_tpu.ops import attention_dispatch
    from deepspeed_tpu.platform import device
    mesh_mod.clear_mesh()
    monkeypatch.setattr(device, "on_tpu", lambda: True)

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    cfg, init_fn, make_model, update = family()
    shapes = jax.eval_shape(init_fn(cfg, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    spec = make_model(cfg, name="guard", params=shapes)
    params = sds(shapes)
    slots, chunk, window, i32 = 64, 512, 4, jnp.int32
    # attention blocks past the chip's fast memory: a small leaf XLA would
    # prefetch there whole, which is not what is guarded
    pool = sds(jax.eval_shape(lambda: spec.init_paged_pool(
        1024, 512, jnp.bfloat16, state_rows=1 + slots)))
    state_layer = pool["ssm"].size // pool["ssm"].shape[0] * 4
    tables = lambda b: (jax.ShapeDtypeStruct((b, 16), i32, sharding=one_chip),
                        jax.ShapeDtypeStruct((b, 1), i32, sharding=one_chip))
    ints = lambda *s: jax.ShapeDtypeStruct(s, i32, sharding=one_chip)

    def decode_window(params, tok, pos, pool, tables):
        def body(carry, _):
            tok, pos, pool = carry
            logits, pool, counts = spec.decode_paged_fn(params, tok, pos,
                                                        pool, tables)
            nxt = jnp.argmax(logits, -1).astype(i32)
            return (nxt, pos + 1, pool), (nxt, counts)
        (_, _, pool), out = jax.lax.scan(body, (tok, pos, pool), None,
                                         length=window)
        return out, pool

    programs = {
        "decode": jax.jit(decode_window, donate_argnums=(3,)).lower(
            params, ints(slots), ints(slots), pool, tables(slots)).compile(),
        "prefill": jax.jit(spec.prefill_paged_fn, donate_argnums=(4,)).lower(
            params, ints(1, chunk), ints(1), ints(1), pool,
            tables(1)).compile(),
        "mixed": jax.jit(spec.mixed_paged_fn, donate_argnums=(7,)).lower(
            params, ints(1, chunk), ints(1), ints(1), tables(1), ints(slots),
            ints(slots), pool, tables(slots)).compile()}
    assert set(spec.kv_pool_writers.values()) \
        == {attention_dispatch.KV_POOL_WRITE_KERNEL}
    in_place = {update, "dstpu_ssm_state_write", "dstpu_kv_pool_write"}
    for name, program in programs.items():
        text = program.as_text()
        large = _large_instructions(text, state_layer // 2)
        made = [x for x in large if x[1] not in _NO_NEW_BUFFER]
        assert made == [], (name, [
            line.strip()[:160] for line in text.splitlines()
            if any(f"%{n} = " in line for n, _ in made)])
        assert {n.rsplit(".", 1)[0] for n, opcode in large
                if opcode == "custom-call"} <= in_place, name
        assert program.memory_analysis().temp_size_in_bytes \
            < state_layer // 2, name
        calls = collections.Counter(_mosaic_calls(text))
        # the scanned pair's one recurrent layer: a call in the scan's body
        assert calls[update] == (name != "prefill"), (name, calls)
        # state and convolution tail: a chunk reads and writes its row of
        # each once, a decode token reads and writes the tails' rows
        assert calls["dstpu_ssm_state_read"] == \
            {"decode": 1, "prefill": 2, "mixed": 3}[name], (name, calls)
        assert calls["dstpu_ssm_state_write"] == \
            {"decode": 1, "prefill": 2, "mixed": 3}[name], (name, calls)
        assert "dstpu_moe_gmm" in text and "dstpu_kv_pool_gather" not in text


# ----------------------------------------------------------------------
# the Mamba-2 in-projection is computed ONCE a layer a call (PR 42)
# ----------------------------------------------------------------------

_BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.mark.parametrize("family, driver, configuration, half", [
    ("granite_moe_hybrid", "serve_granite_moe_hybrid",
     "granite-4.0-h-small-10l-ep4", ("M", "ssm_in_w", "ssm/in_proj")),
    ("nemotron_h", "serve_nemotron_h", "nemotron-3-super-120b-a12b-11l-ep4",
     ("M", "ssm_in_w", "ssm/in_proj")),
    ("qwen3_next", "serve_qwen3_next", "qwen3-next-80b-a3b-12l-ep8",
     ("D", "gdn_qkvz_w", "gdn/in_proj")),
], ids=["granite", "nemotron", "qwen3-next"])
def test_served_mixed_program_computes_each_in_projection_once(
        one_chip, monkeypatch, family, driver, configuration, half):
    """The mixed program of each hybrid family at the SERVED sizes of its
    configuration file: `[z | xBC | dt] = u @ ssm_in_w` (Gated DeltaNet:
    `[q | k | v | z] = u @ gdn_qkvz_w`) has readers at both
    ends of the half (the convolution takes xBC and dt, the gate z), and
    left alone XLA freed the 20 MiB product in between and computed it a
    second time (`fusion.N.remat`, 7% of Granite's cell; PR 42). No
    instruction under the half's `in_proj` scope is a rematerialised clone,
    and each scanned run makes the product in exactly one fusion. And PR 43's
    lesson, on the served tree and pool: every leaf a Mosaic call reads
    (the pool's four, the expert stacks) ends in whole lane tiles."""
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.platform import device
    mesh_mod.clear_mesh()
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    monkeypatch.syspath_prepend(_BENCHMARK)
    model = importlib.import_module(f"deepspeed_tpu.models.{family}")
    with open(os.path.join(_BENCHMARK, "configs", configuration + ".json")) \
            as f:
        served = json.load(f)
    knobs = served["serving"]
    cfg = importlib.import_module(f"drivers.{driver}").model_config(
        served, knobs["max_context"])

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    shapes = jax.eval_shape(
        getattr(model, f"{family}_init_fn")(cfg, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    spec = getattr(model, f"make_{family}_decode_model")(
        cfg, name="served", params=shapes)
    slots, chunk = knobs["max_slots"], knobs["prefill_chunk"]
    block = knobs["kv_block_size"]
    pool = sds(jax.eval_shape(lambda: spec.init_paged_pool(
        knobs["num_kv_blocks"], block, jnp.bfloat16, state_rows=1 + slots)))
    ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    tables = lambda b: (ints(b, knobs["max_context"] // block), ints(b, 1))
    text = jax.jit(spec.mixed_paged_fn, donate_argnums=(7,)).lower(
        sds(shapes), ints(1, chunk), ints(1), ints(1), tables(1),
        ints(slots), ints(slots), pool, tables(slots)).compile().as_text()

    letter, leaf, scope = half
    read_by_mosaic = list(pool.values()) + [
        a for trees in shapes["runs"] for tree in trees
        for name, a in tree.items() if name.startswith("moe_w_")]
    assert all(a.shape[-1] % 128 == 0 for a in read_by_mosaic), [
        a.shape for a in read_by_mosaic if a.shape[-1] % 128]
    width = hybrid.mixer_shapes(cfg, letter)[leaf][0][-1]
    product = f"bf16[1,{chunk + slots},{width}]"
    made = collections.Counter()
    for name, lines in _computations(text).items():
        for line in lines:
            found = _HLO_LINE.match(line)
            if not found or scope not in line:
                continue
            assert not found.group(1).endswith(".remat"), line.strip()[:200]
            if found.group(3) == "fusion" \
                    and found.group(2).startswith(product):
                made[name] += 1
    # a scanned run is one loop body: a product for each `M` of its unit
    assert sorted(made.values()) == sorted(
        unit.count(letter) for unit, _ in hybrid.layer_runs(cfg)
        if letter in unit), made


# ----------------------------------------------------------------------
# a pool of the LATENT kind (MLA): written in place, projected once (PR 43)
# ----------------------------------------------------------------------


def test_served_latent_mixed_program_writes_in_place_and_projects_once(
        one_chip, monkeypatch):
    """GLM-4.7-Flash's mixed program at the SERVED sizes of its configuration
    file, compiled for the chip: nothing half as large as ONE LAYER's latent
    leaf is made by an operation that is not an aliased Mosaic call
    (`dstpu_kv_pool_write`; the walks `dstpu_mla_prefill` and
    `dstpu_mla_decode` read the leaf where it lies), the temporaries stay
    under that size, a layer body writes the leaf twice (the chunk's rows,
    the slots') and walks it twice, and the `[2048, 576]` down-projection and
    the query's two projections, which have readers on both sides of the
    chunk's walk, are each computed ONCE a layer body: no rematerialised
    clone beside its original (PR 42's lesson)."""
    from deepspeed_tpu.models import glm4_moe_lite as glm
    from deepspeed_tpu.ops import attention_dispatch
    from deepspeed_tpu.platform import device
    mesh_mod.clear_mesh()
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    monkeypatch.syspath_prepend(_BENCHMARK)
    with open(os.path.join(_BENCHMARK, "configs",
                           "glm-4.7-flash-12l-ep8.json")) as f:
        served = json.load(f)
    knobs = served["serving"]
    cfg = importlib.import_module("drivers.serve_glm4_moe_lite").model_config(
        served, knobs["max_context"])

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    shapes = jax.eval_shape(
        glm.glm4_moe_lite_init_fn(cfg, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    spec = glm.make_glm4_moe_lite_decode_model(cfg, name="served",
                                               params=shapes)
    slots, chunk = knobs["max_slots"], knobs["prefill_chunk"]
    block = knobs["kv_block_size"]
    pool = sds(jax.eval_shape(lambda: spec.init_paged_pool(
        knobs["num_kv_blocks"], block, jnp.bfloat16)))
    ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    tables = lambda b: ints(b, -(-knobs["max_context"] // block))
    program = jax.jit(spec.mixed_paged_fn, donate_argnums=(7,)).lower(
        sds(shapes), ints(1, chunk), ints(1), ints(1), tables(1),
        ints(slots), ints(slots), pool, tables(slots)).compile()
    text = program.as_text()
    assert spec.kv_pool_writers == {
        "mixed": attention_dispatch.KV_POOL_WRITE_KERNEL}
    assert spec.paged_attn_programs == {
        "mixed/prefill_chunk": "mla_prefill_kernel",
        "mixed/paged_decode": "mla_decode_kernel"}
    # 576 values a token a layer, stored in whole lane tiles
    assert pool["ckv"].shape == (12, knobs["num_kv_blocks"], 1, block, 640)
    layer_leaf = pool["ckv"].size // pool["ckv"].shape[0] * 2
    large = _large_instructions(text, layer_leaf // 2)
    made = [x for x in large if x[1] not in _NO_NEW_BUFFER]
    assert made == [], [line.strip()[:160] for line in text.splitlines()
                        if any(f"%{n} = " in line for n, _ in made)]
    assert {n.rsplit(".", 1)[0] for n, opcode in large
            if opcode == "custom-call"} == {"dstpu_kv_pool_write"}
    assert program.memory_analysis().temp_size_in_bytes < layer_leaf // 2
    # the dense layer's body and the scanned layers': two writes, two walks
    calls = collections.Counter(_mosaic_calls(text))
    assert (calls["dstpu_kv_pool_write"], calls["dstpu_mla_prefill"],
            calls["dstpu_mla_decode"]) == (4, 2, 2), calls
    assert "dstpu_kv_pool_gather" not in text
    rows = chunk + slots
    # (since PR 53 the compiler keeps the query's product without its
    # leading 1: the rotation reads its slice through a matrix product)
    products = {"mla/kv_down": f"bf16[1,{rows},576]",
                "mla/q_proj": f"bf16[{rows},20,256]"}
    made = collections.Counter()
    for name, lines in _computations(text).items():
        originals = {_HLO_LINE.match(line).group(1) for line in lines
                     if _HLO_LINE.match(line)}
        for line in lines:
            found = _HLO_LINE.match(line)
            if not found or found.group(3) != "fusion":
                continue
            for scope, product in products.items():
                if scope in line and found.group(2).startswith(product):
                    made[name, scope] += 1
                    # a clone is a second computation only beside its
                    # original (XLA keeps the name when it moves one)
                    clone = re.sub(r"\.remat\d*$", "", found.group(1))
                    assert clone == found.group(1) \
                        or clone not in originals, line.strip()[:200]
    assert sorted(made.values()) == [1, 1, 1, 1], made
