"""The readers of the program's step and request rings
(`benchmark/readers/steptrace_*.py`, `benchmark/steprings.py`) on hand-made
records, on the CPU:

    python3 -m pytest benchmark/checks -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import harness  # noqa: E402
import steprings  # noqa: E402
from deepspeed_tpu.telemetry.steptrace import StepTrace  # noqa: E402


def _metric(name, obs):
    """The value of a per-layer metric as `run.py` computes it: the reader
    and the arguments its data file names."""
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    reader = harness.load_module("readers", spec["reader"])
    return reader.read(obs, None, spec["args"])


@pytest.fixture
def serving_ring():
    """Six steps of 1 s on a hand-driven clock, ending at 1, 2, ... 6.

    step  chunks decoding wall  in flight  queued blocked_on
      1     1       0     1.0     0.5        2     pool
      2     0       2     1.0     0.9        2     pool
      3     1       2     2.0     1.6        1     slots
      4     0       4     1.0     0.9        0
      5     1       4     3.0     2.9        1     pool
      6     0       0     1.0     0.0        0
    """
    t = {"now": 0.0}
    ring = StepTrace("serving", 64, clock=lambda: t["now"])
    rows = [(1, 0, 1.0, 0.5, 2, "pool"), (0, 2, 1.0, 0.9, 2, "pool"),
            (1, 2, 2.0, 1.6, 1, "slots"), (0, 4, 1.0, 0.9, 0, ""),
            (1, 4, 3.0, 2.9, 1, "pool"), (0, 0, 1.0, 0.0, 0, "")]
    t_end = 0.0
    for chunks, decoding, wall, busy, queued, blocked in rows:
        # steps END at whole seconds 1..6 whatever they took
        t_end += 1.0
        t["now"] = t_end - wall
        ring.begin_step()
        with ring.phase("serving/decode_window"):
            t["now"] += wall - busy
            if busy:
                ring.dispatched()
                t["now"] += busy
                ring.ready()
        ring.end_step(prefill_chunks=chunks, decoding=decoding, queued=queued,
                      blocked_on=blocked)
    # requests: (uid, submitted, admitted); r3 is still running
    for uid, t_submit, t_admit in (("r0", 0.5, 0.9), ("r1", 1.0, 1.5),
                                   ("r2", 2.0, 2.1), ("r3", 3.0, 3.4),
                                   ("r4", 5.5, 5.9)):
        rec = ring.open_request(uid, t_submit, t_admit, 100)
        if uid != "r3":
            ring.close_request(rec, t_admit + 0.2, 1, t_admit + 1.0, 5,
                               "length")
    return ring


def test_exposed_host_ms_is_the_mean_over_the_window(serving_ring):
    obs = {"opened": 1.0, "closed": 5.0}            # steps 2, 3, 4, 5
    assert [s.step for s in steprings.steps(obs, "serving")] == [2, 3, 4, 5]
    # the serving copy that is left: OLMoE's, pinned (`spec_keys_pinned.json`)
    assert _metric("sched_exposed_host_ms_per_step.generate", obs) == \
        pytest.approx(1e3 * (0.1 + 0.4 + 0.1 + 0.1) / 4)
    assert _metric("sched_exposed_host_ms_per_step.generate",
                   {"opened": 0.0, "closed": 1.0}) == pytest.approx(500.0)


def test_prefill_stall_share_weighs_steps_by_their_decoding_slots(
        serving_ring):
    obs = {"opened": 1.0, "closed": 5.0}
    # decode-only steps (2 and 4) took 1.0 s; the 12 slot-steps of the window
    # waited 2*1 + 2*2 + 4*1 + 4*3 = 22 slot-seconds
    assert _metric("sched_prefill_stall_share.latency", obs) == \
        pytest.approx(100.0 * (1.0 - 1.0 * 12 / 22))
    # a window with no decode-only step has nothing to compare with
    assert _metric("sched_prefill_stall_share.latency",
                   {"opened": 2.0, "closed": 3.0}) is None


def test_request_ms_percentiles_cover_requests_admitted_in_the_window(
        serving_ring):
    # no metric reads the request ring yet: neither cell queues between
    # `submit()` and admission in a way a percentile can describe (PERF.md
    # section 6, PR 24), so the reader is held here with its arguments
    read = harness.load_module("readers", "steptrace_request_ms").read
    args = {"subsystem": "serving", "from": "t_submit", "to": "t_admit"}
    obs = {"opened": 1.0, "closed": 5.0}            # r1, r2, r3 (running)
    waits = sorted([500.0, 100.0, 400.0])
    assert read(obs, None, dict(args, percentile=50)) == \
        pytest.approx(waits[1])
    assert read(obs, None, dict(args, percentile=90)) == \
        pytest.approx(waits[1] + 0.8 * (waits[2] - waits[1]))
    assert read({"opened": 10.0, "closed": 20.0}, None,
                dict(args, percentile=50)) is None


def test_blocked_pool_share_counts_steps_that_left_a_queue(serving_ring):
    # steps 1, 2, 3, 5 ended with a queue; three of them waited for the pool
    assert _metric("sched_admit_blocked_pool_share.throughput",
                   {"opened": 0.0, "closed": 6.0}) == pytest.approx(75.0)
    assert _metric("sched_admit_blocked_pool_share.throughput",
                   {"opened": 3.0, "closed": 4.0}) is None   # no queue there


def test_an_empty_window_gives_no_value(serving_ring):
    obs = {"opened": 10.0, "closed": 20.0}
    for name in ("sched_exposed_host_ms_per_step.generate",
                 "sched_prefill_stall_share.latency",
                 "sched_admit_blocked_pool_share.throughput"):
        assert _metric(name, obs) is None
    # and so does a program with no recorder of that subsystem
    assert steprings.steps(obs, "no such subsystem") == []
    assert steprings.requests(obs, "no such subsystem", "t_admit") == []


def test_training_window_runs_from_its_opening_to_its_last_step():
    t = {"now": 0.0}
    ring = StepTrace("train", 16, clock=lambda: t["now"])
    for place in (0.5, 0.002, 0.004, 0.003):    # the first is warm-up
        ring.begin_step(device_idle=True)
        with ring.phase("train/place"):
            t["now"] += place
        with ring.phase("train/dispatch"):
            ring.dispatched()
            t["now"] += 1.0
        ring.end_step()
    # the training driver's obs has no `closed`: the window ends with its
    # last step span, and the traced steps after it stay out
    obs = {"opened": 2.0, "seconds": 1.5,
           "step_spans": [(2.0, 2.6), (2.6, 3.6)]}
    assert steprings.window(obs) == (2.0, 3.6)
    assert [s.step for s in steprings.steps(obs, "train")] == [2, 3]
    assert _metric("train_exposed_host_ms_per_step", obs) == \
        pytest.approx(3.0)
    assert _metric("train_exposed_host_ms_per_step",
                   {"opened": 9.0, "step_spans": [(9.0, 9.5)]}) is None
