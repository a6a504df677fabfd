"""The readers of the program's call ring and of the wait phase
(`benchmark/readers/steptrace_phase_ms.py`, `steptrace_call_ms.py`,
`steptrace_call_stall_share.py`, `steptrace_call_field_ratio.py`,
`benchmark/callring.py`) on a hand-made ring, on the CPU:

    python3 -m pytest benchmark/checks -q

`tests/test_steptrace.py` imports the tests of this file, so the program's
own suite runs them too.
"""

import collections
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH, HERE]

import harness  # noqa: E402
import steprings  # noqa: E402
from deepspeed_tpu.telemetry.steptrace import StepTrace  # noqa: E402
from test_steptrace_readers import _metric  # noqa: E402

LAUNCH, WAIT, EMIT, STALL = 0.1, 0.6, 0.3, 1.0


def hand_made_ring():
    """Eight steps one call deep: step k launches call k (0.1 s), blocks on
    call k-1 (0.6 s; nothing to read in step 1) and emits (0.3 s). The read
    of call 4, in step 5, takes 1.0 s longer: a planted stall. Call 6
    carries a chunk that is a prompt's last; call 3 hands out 5 of its 8
    tokens. Returns (ring, {step: t_end})."""
    t = {"now": 0.0}
    ring = StepTrace("serving", 64, clock=lambda: t["now"])
    ends = {}
    for k in range(1, 9):
        mixed = k == 6
        ring.begin_step()
        with ring.phase("serving/decode_window", call=k) as ph:
            t["now"] += LAUNCH
        ring.open_call(k, "mixed" if mixed else "decode", ph.t0, ph.t1,
                       queued_behind=k > 1, rows=2, win=4, chunks=int(mixed),
                       firsts=int(mixed))
        if k > 1:
            with ring.phase("serving/read_back", call=k - 1) as ph:
                t["now"] += WAIT + (STALL if k == 5 else 0.0)
            read = ring.read_call(k - 1, ph.t0, ph.t1)
        with ring.phase("serving/emit"):
            t["now"] += EMIT
        if k > 1:
            ring.close_call(read, {3: 5, 6: 9}.get(k - 1, 8))
        ends[k] = ring.end_step(device_calls=1).t_end
    return ring, ends


# (reader, its arguments, what steps 2..8 and the calls 1..7 they read give)
CASES = {
    "host_busy_ms_per_step": (
        "steptrace_phase_ms",
        {"phases": ["serving/read_back"], "complement": True, "per": "step"},
        1e3 * (LAUNCH + EMIT)),
    "host_busy_share": (
        "steptrace_phase_ms",
        {"phases": ["serving/read_back"], "complement": True, "per": "wall"},
        100.0 * 7 * (LAUNCH + EMIT) / (7 * (LAUNCH + EMIT + WAIT) + STALL)),
    "read_back_share": (
        "steptrace_phase_ms", {"phases": ["serving/read_back"], "per": "wall"},
        100.0 * (7 * WAIT + STALL) / (7 * (LAUNCH + EMIT + WAIT) + STALL)),
    "launch_ms_per_call": (
        "steptrace_call_ms",
        {"from": "t_launch0", "to": "t_launch1", "stat": "mean"},
        1e3 * LAUNCH),
    "wait_p100_ms": (
        "steptrace_call_ms",
        {"from": "t_wait0", "to": "t_wait1", "stat": "p100"},
        1e3 * (WAIT + STALL)),
    # the six other decode calls take 1.0 s each from the read before them
    # to their own; call 4 takes 2.0: its excess over the window's length
    "call_stall_share": (
        "steptrace_call_stall_share", {"mads": 5, "floor": 0.1},
        100.0 * STALL / (7 * (LAUNCH + EMIT + WAIT) + STALL)),
    "decode_useful_token_share": (
        "steptrace_call_field_ratio",
        {"num": [["emitted"]], "den": [["rows", "win"], ["firsts"]]},
        100.0 * (5 * 8 + 5 + 9) / (7 * 8 + 1)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_call_ring_reader_on_a_hand_made_ring(case, monkeypatch):
    name, args, want = CASES[case]
    read = harness.load_module("readers", name).read
    args = dict(args, subsystem="serving")
    _ring, ends = hand_made_ring()
    obs = {"opened": ends[1], "closed": ends[8]}
    assert read(obs, None, args) == pytest.approx(want)
    # nothing ended or was read there: no reading
    assert read({"opened": 100.0, "closed": 200.0}, None, args) is None
    # the parent: step records whose phases hold no wait, no `calls()`
    Old = collections.namedtuple("StepRecord", "step t_start t_end phases")
    old = collections.namedtuple("Ring", ["records"])(
        lambda since, until: [Old(1, 1.0, 2.0,
                                  (("serving/decode_window", 1.0),))])
    monkeypatch.setattr(steprings, "_ring", lambda subsystem: old)
    assert read({"opened": 0.0, "closed": 9.0}, None, args) is None
    # and a program with no recorder at all
    monkeypatch.setattr(steprings, "_ring", lambda subsystem: None)
    assert read({"opened": 0.0, "closed": 9.0}, None, args) is None


def test_a_stall_is_judged_within_its_kind_of_call():
    """A kind's own spread sets its limit: the one mixed call is slower than
    every decode call and alone of its kind, so it is nobody's stall; with
    the decode calls' times spread out, the same 1.0 s is within five
    deviations and counts for nothing."""
    read = harness.load_module("readers", "steptrace_call_stall_share").read
    args = {"subsystem": "serving", "mads": 5, "floor": 0.1}
    t = {"now": 0.0}
    ring = StepTrace("serving", 64, clock=lambda: t["now"])
    took = [1.0, 1.4, 0.6, 2.0, 1.2, 0.8, 5.0]
    for k, s in enumerate(took, 1):
        ring.open_call(k, "mixed" if s == 5.0 else "decode", t["now"],
                       t["now"], rows=1, win=1)
        t["now"] += s
        ring.close_call(ring.read_call(k, t["now"] - s, t["now"]), 1)
    obs = {"opened": 0.0, "closed": t["now"]}
    # decode: median 1.1, deviations 0.1 0.3 0.5 0.9 0.1 0.3 -> 0.3: limit 2.6
    assert read(obs, None, args) == 0.0
    assert read(obs, None, dict(args, mads=2)) == \
        pytest.approx(100.0 * (2.0 - 1.1) / t["now"])


@pytest.mark.parametrize("name, want", [
    ("sched_host_busy_ms_per_step.latency", CASES["host_busy_ms_per_step"][2]),
    ("sched_host_busy_ms_per_step.throughput",
     CASES["host_busy_ms_per_step"][2]),
    ("sched_call_stall_share.latency", CASES["call_stall_share"][2]),
    ("sched_call_stall_share.throughput", CASES["call_stall_share"][2]),
    ("sched_host_busy_share.latency", CASES["host_busy_share"][2]),
    ("sched_host_busy_share.throughput", CASES["host_busy_share"][2]),
    ("sched_launch_ms_per_call.latency", CASES["launch_ms_per_call"][2]),
    ("sched_launch_ms_per_call.throughput", CASES["launch_ms_per_call"][2]),
    ("sched_decode_useful_token_share.latency",
     CASES["decode_useful_token_share"][2]),
    ("sched_decode_useful_token_share.throughput",
     CASES["decode_useful_token_share"][2]),
    ("sched_admit_to_first_token_p50_ms.latency", 250.0)])
def test_the_metrics_files_name_their_readers_and_arguments(name, want):
    ring, ends = hand_made_ring()
    for uid, took in (("a", 0.1), ("b", 0.25), ("c", 0.7)):
        ring.close_request(ring.open_request(uid, 0.0, ends[2] - took, 64),
                           ends[2], 2, ends[3], 1, "length")
    assert _metric(name, {"opened": ends[1], "closed": ends[8]}) == \
        pytest.approx(want)
