"""The reader `benchmark/readers/steptrace_field_ratio.py` and the metrics
`paged_decode_live_step_share.*` that use it, on hand-made step records, on
the CPU:

    python3 -m pytest benchmark/checks -q
"""

import collections
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import steprings  # noqa: E402
from deepspeed_tpu.telemetry.steptrace import StepTrace  # noqa: E402
from test_steptrace_readers import _metric  # noqa: E402

METRICS = ["paged_decode_live_step_share." + s
           for s in ("latency", "throughput", "generate")]


def _ring(rows):
    """One step a row of (live blocks, grid steps), ending at 1, 2, ..."""
    t = {"now": 0.0}
    ring = StepTrace("serving", 64, clock=lambda: t["now"])
    for live, grid in rows:
        ring.begin_step()
        t["now"] += 1.0
        ring.end_step(decoding=int(bool(grid)), decode_live_blocks=live,
                      decode_grid_steps=grid)
    return ring


@pytest.mark.parametrize("name", METRICS)
def test_live_step_share_sums_the_window(name):
    _ring([(100, 100), (0, 0), (14, 1024), (0, 1), (30, 30)])
    # steps 2..5: a step with no decode call, a full static grid, a call
    # with nothing live, a live-only grid
    assert _metric(name, {"opened": 1.0, "closed": 5.0}) == \
        pytest.approx(100.0 * 44 / 1055)
    assert _metric(name, {"opened": 4.0, "closed": 5.0}) == 100.0
    # nothing decoded inside the window: no reading
    assert _metric(name, {"opened": 1.0, "closed": 2.0}) is None


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_fields_gives_no_reading(name, monkeypatch):
    """The parent's records end at `counters`; a program older than the step
    ring has none at all."""
    Old = collections.namedtuple("StepRecord", ["step", "t_end", "decoding"])
    old = collections.namedtuple("Ring", ["records"])(
        lambda since, until: [Old(1, 1.5, 4), Old(2, 2.5, 4)])
    monkeypatch.setattr(steprings, "_ring", lambda subsystem: old)
    assert _metric(name, {"opened": 1.0, "closed": 3.0}) is None
    monkeypatch.setattr(steprings, "_ring", lambda subsystem: None)
    assert _metric(name, {"opened": 1.0, "closed": 3.0}) is None
