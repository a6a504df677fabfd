"""The benchmark's checks of itself, on the CPU:

    python3 -m pytest benchmark/checks -q

They hold the yardstick still: the traffic builder, the windowed estimators,
the trace reduction (against a trace recorded on a TPU v5e, kept beside this
file) and the shape of BENCHMARK.json. The last test runs the three one-chip
cells end to end at the rehearsal sizes, one process each, as the driver
would, and reads their last lines.
"""

import collections
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH]

import estimators  # noqa: E402
import roofline  # noqa: E402
import traffic_gen  # noqa: E402
import xplane  # noqa: E402


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCHMARK = _json(ROOT, "BENCHMARK.json")
SPEC_KEYS = {"reader", "args"}
PINNED = set(_json(HERE, "spec_keys_pinned.json")["files"])


# ---------------------------------------------------------------- traffic

def test_open_loop_offers_the_same_load_for_every_seed():
    traffic = _json(BENCH, "traffic", "chat_open_loop.json")
    plan = traffic_gen.open_loop_schedule(traffic, 51)
    assert plan == traffic_gen.open_loop_schedule(traffic, 51)   # no seed in it
    rate = traffic["rate_rps"]
    for part, secs in (("preroll", traffic["preroll_s"]), ("window", 51),
                       ("tailout", traffic["tailout_s"])):
        reqs = [r for r in plan if r["part"] == part]
        n = round(rate * secs)
        assert len(reqs) == n
        for key in ("prompt_tokens", "output_tokens"):
            assert sorted(r[key] for r in reqs) == \
                traffic_gen.quantiles(traffic[key], n)           # the multiset
            assert [r[key] for r in reqs] != sorted(r[key] for r in reqs)
        gaps = sorted(b["due"] - a["due"] for a, b in zip(reqs, reqs[1:]))
        unit = traffic_gen.quantiles(traffic["gaps"], n)
        scale = secs / sum(unit)
        assert len(set(round(g, 9) for g in gaps)
                   - set(round(u * scale, 9) for u in unit)) == 0
    dues = [r["due"] for r in plan]
    assert dues == sorted(dues)
    assert min(r["prompt_tokens"] for r in plan) >= 32
    assert max(r["prompt_tokens"] for r in plan) <= 2048
    # the seed's part: token ids
    a = traffic_gen.token_arrays([dict(r) for r in plan[:3]], 32000, 1)
    b = traffic_gen.token_arrays([dict(r) for r in plan[:3]], 32000, 2**31 + 5)
    assert all((x["tokens"] != y["tokens"]).any() for x, y in zip(a, b))
    assert all(len(x["tokens"]) == x["prompt_tokens"] for x in a)


def test_every_stratum_spans_the_distribution():
    values = list(range(64))
    import numpy as np
    order = traffic_gen.stratified_order(values, 8, np.random.default_rng(3))
    assert sorted(order) == values
    for i in range(0, 64, 8):
        group = sorted(order[i:i + 8])
        assert group[0] < 8 and group[-1] >= 56      # one from each octile
        assert all(b - a == 8 for a, b in zip(group, group[1:]))


def test_backlog_and_train_traffic_repeat_per_seed():
    traffic = _json(BENCH, "traffic", "longprompt_backlog.json")
    grid = traffic["grid"]
    reqs = traffic_gen.backlog_requests(traffic, 5 * grid)
    pairs = [(r["prompt_tokens"], r["output_tokens"]) for r in reqs]
    assert pairs[:grid] * 5 == pairs                     # one cycle, repeated
    assert sorted(p for p, _ in pairs[:grid]) == \
        traffic_gen.quantiles(traffic["prompt_tokens"], grid)
    assert sorted(o for _, o in pairs[:grid]) == \
        traffic_gen.quantiles(traffic["output_tokens"], grid)
    assert traffic_gen._spread_out(8) == [0, 4, 2, 6, 1, 5, 3, 7]
    a = traffic_gen.token_arrays(reqs[:4], 32000, 1)[0]["tokens"].copy()
    b = traffic_gen.token_arrays(reqs[:4], 32000, 2)[0]["tokens"]
    assert a.shape == b.shape and (a != b).any()         # the seed's part
    train = _json(BENCH, "traffic", "train_seq2048.json")
    x = traffic_gen.train_batch(train, 50304, 1, 2**31 + 5)
    y = traffic_gen.train_batch(train, 50304, 1, 2**31 + 5)
    assert (x["tokens"] == y["tokens"]).all()
    assert x["tokens"].shape == (8, 2048)
    assert (x["tokens"][:, 1:] == x["labels"][:, :-1]).all()


# ------------------------------------------------------------- estimators

def _synthetic():
    """Steps return at 1.0, 2.0, ... 8.0; the window opens at 2.0 and asks
    for 4 s, so it closes at 6.0. Request a is cut by the opening edge, b
    lives inside, c is cut by the closing edge, d decodes in 3-token
    windows."""
    events = [
        (1.0, "a", 100, 0), (2.0, "a", 50, 1), (3.0, "a", 0, 1), (4.0, "a", 0, 1),
        (3.0, "b", 200, 0), (4.0, "b", 100, 1), (5.0, "b", 0, 1), (5.5, "b", 0, 1),
        (5.0, "c", 300, 0), (6.0, "c", 300, 1), (7.0, "c", 0, 1), (8.0, "c", 0, 1),
        (2.5, "d", 64, 1), (4.0, "d", 0, 3), (5.5, "d", 0, 3), (7.0, "d", 0, 3),
    ]
    return sorted(events)


def test_tokens_per_s_counts_the_part_inside():
    events = _synthetic()
    # inside (2.0, 6.0]: prefill b 300, c 600, d 64 = 964; a's 150 fell
    # before. emitted: a 2 (3.0, 4.0), b 3, c 1, d 1 + 3 + 3 = 13
    assert estimators.window_tokens(events, 2.0, 6.0) == (964, 13)
    assert estimators.tokens_per_s(events, 2.0, 6.0) == (964 + 13) / 4.0


def test_tpot_counts_tokens_and_intervals_inside_the_window():
    iv = estimators.emission_intervals(_synthetic(), 2.0, 6.0)
    # a: 2.0->3.0, 3.0->4.0; b: 4.0->5.0, 5.0->5.5; c: its first token at 6.0
    # has no predecessor, 7.0 and 8.0 are outside; d: 2.5->4.0 and 4.0->5.5
    # carry 3 tokens each, 7.0 is outside
    assert sorted(iv) == sorted([(1.0, 1), (1.0, 1), (1.0, 1), (0.5, 1),
                                 (1.5, 3), (1.5, 3)])
    assert estimators.tpot_mean_ms(iv) == pytest.approx(1e3 * 6.5 / 10)
    # per token: 6 tokens waited 0.5 s (d's), 1 waited 0.5, 3 waited 1.0
    assert estimators.tpot_percentile_ms(iv, 50) == pytest.approx(500.0)
    assert estimators.tpot_percentile_ms(iv, 90) == pytest.approx(1000.0)
    # a request whose predecessor emission fell before the window opened
    iv = estimators.emission_intervals(_synthetic(), 2.2, 6.0)
    assert (1.0, 1) in iv and len(iv) == 5          # a's 2.0->3.0 dropped


def test_ttft_is_from_the_due_time_and_counts_the_missing():
    events = _synthetic()
    due = {"a": 0.5, "b": 2.5, "c": 4.0, "d": 2.25, "e": 3.0, "f": 6.5}
    got, missing = estimators.ttft_ms(events, due, 2.0, 4.0)
    assert sorted(got) == pytest.approx([250.0, 1500.0, 2000.0])
    assert missing == 1                              # e never got a token
    assert estimators.percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert estimators.percentile([0, 10], 90) == pytest.approx(9.0)


def test_old_estimator_waits_for_finished_requests():
    events = _synthetic()
    finished = {"a": 4.0, "b": 5.5, "c": 8.0, "d": 7.0}
    old = estimators.tpot_per_finished_request_ms(events, finished, 2.0, 6.0)
    # only a ((4-2)/2 = 1.0 s) and b ((5.5-4)/2 = 0.75 s) finished inside
    assert old == pytest.approx(875.0)


def test_train_rate_is_over_whole_steps():
    spans = [(0.0, 0.9), (1.0, 1.9), (2.0, 2.9), (3.0, 4.1), (4.2, 5.0)]
    rate, steps = estimators.train_tokens_per_s(spans, 1000, 1.0, 3.0)
    assert steps == 3 and rate == pytest.approx(3000 / 3.1)


# ------------------------------------------------------------------ trace

def test_interval_arithmetic():
    assert xplane.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert xplane.overlap([(0, 10)], [(2, 3), (5, 7)]) == 3
    nested = [("while.1", 0, 100), ("fusion.1", 0, 40), ("copy.2", 50, 90),
              ("fusion.1", 100, 130)]
    assert sorted(xplane.self_times(nested)) == sorted(
        [("while.1", 20), ("fusion.1", 40), ("copy.2", 40), ("fusion.1", 30)])


def test_reduction_on_synthetic_planes():
    planes = {
        "/device:TPU:0": {
            "XLA Ops": [
                ("%while.1 = (s32[]{:T(128)}, bf16[8,128]{1,0}) while((s32[], "
                 "bf16[8,128]) %tuple), body=%b", 100, 500, {}),
                ("%fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]"
                 "{1,0} %p), kind=kLoop", 100, 300, {}),
                ("%all-gather.3 = bf16[32,128]{1,0} all-gather(bf16[8,128]{1,0} "
                 "%fusion.1), dimensions={0}", 300, 400, {}),
                ("%closed_call.2 = (bf16[8,128]{1,0}, f32[8]{0}) custom-call("
                 "bf16[8,128]{1,0} %x), custom_call_target=\"tpu_custom_call\"",
                 600, 700, {})],
            "XLA Modules": [("jit_step(12)", 100, 500, {}),
                            ("jit_other(13)", 600, 700, {})]},
        "/host:CPU": {"python": [("bench.window", 0, 1000, {}),
                                 ("bench.step", 0, 560, {}),
                                 ("bench.bookkeeping", 560, 1000, {})]},
    }
    s = xplane.reduce_planes(planes)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx(500e-9)
    assert s["collective_s"] == pytest.approx(100e-9)
    assert s["collective_exposed_s"] == pytest.approx(100e-9)
    assert s["ops"]["fusion.1 fusion bf16[8,128]"] == pytest.approx(200e-9)
    assert s["ops"]["closed_call.2 custom-call:tpu_custom_call "
                    "(bf16[8,128], f32[8])"] == pytest.approx(100e-9)
    assert not any(name.startswith("while") for name in s["ops"])
    assert xplane.matching(s["op_counts"], r"custom-call:tpu_custom_call \(bf16"
                           r"\[[\d,]+\], f32\[") == 1
    assert s["modules"] == {"jit_step": pytest.approx(400e-9),
                            "jit_other": pytest.approx(100e-9)}
    # idle: 0-100 and 500-560 under bench.step, 560-600 and 700-1000 under
    # bench.bookkeeping
    assert s["idle_gaps"]["bench.step"] == pytest.approx(160e-9)
    assert s["idle_gaps"]["bench.bookkeeping"] == pytest.approx(340e-9)
    assert s["idle_gaps"]["host.other"] == pytest.approx(0.0)
    with pytest.raises(ValueError):
        xplane.reduce_planes({"/host:CPU": {"t": [("bench.step", 0, 1, {})]}})


def test_reduction_reproduces_the_recorded_trace():
    """`recorded_tiny.xplane.pb` was taken on a TPU v5e (PR 23) around three
    rounds of two small jitted programs with host spans between them;
    `recorded_tiny.expected.json` holds what the reduction gave then."""
    path = os.path.join(HERE, "recorded_tiny.xplane.pb")
    expected = _json(HERE, "recorded_tiny.expected.json")
    s = xplane.reduce_file(path)
    assert s["devices"] == expected["devices"]
    for key in ("window_s", "busy_s"):
        assert s[key] == pytest.approx(expected[key], rel=1e-9)
    assert 0 < s["busy_s"] < s["window_s"]
    assert sum(s["idle_gaps"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-6)
    assert sum(s["ops"].values()) == pytest.approx(s["busy_s"], rel=1e-6)
    for name, secs in expected["modules"].items():
        assert s["modules"][name] == pytest.approx(secs, rel=1e-9)
        assert s["module_counts"][name] == expected["module_counts"][name]
    for name, secs in expected["ops"].items():
        assert s["ops"][name] == pytest.approx(secs, rel=1e-9)
    assert set(expected["idle_gaps"]) <= set(s["idle_gaps"])


def test_roofline_counts():
    peaks = _json(BENCH, "peaks.json")["TPU v5 lite"]
    flops, nbytes = roofline.paged_decode(1000, 2, layers=16, heads=32,
                                          kv_heads=8, head_dim=128)
    assert flops == 16 * 4 * 1000 * 32 * 128
    assert nbytes == 16 * 2 * (2 * 1000 * 8 * 128 + 2 * 2 * 32 * 128)
    assert roofline.least_seconds(flops, nbytes, peaks)[1] == "memory"
    flops, _ = roofline.flash_causal({"fwd": 2, "dq": 1, "dkv": 1}, 8, 16,
                                     2048, 128)
    assert flops == (2 * 2 + 3 + 4) * 8 * 16 * 2048 * 2048 * 128


# --------------------------------------------------------- BENCHMARK.json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_is_well_formed():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    cells = [w["name"] for w in BENCHMARK["workloads"]]
    configs = {c["name"] for c in BENCHMARK["configs"]}
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    names = cells + list(configs) + list(e2e) + \
        [m["name"] for m in BENCHMARK["per_layer"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for w in BENCHMARK["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) <= \
        max(1, len(cells) // 4)
    for c in BENCHMARK["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) == set(_json(ROOT, c["file"])["reduced"])
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        spec = _json(BENCH, "end_to_end_metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))


def test_every_layer_metric_moves_a_metric_its_cells_report():
    cells = [w["name"] for w in BENCHMARK["workloads"]]
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    reported = collections.defaultdict(set)
    for m in BENCHMARK["end_to_end"]:
        for cell in m.get("workloads", cells):
            reported[cell].add(m["name"])
    layered = collections.defaultdict(int)
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        spec = _json(BENCH, "layer_metrics", m["name"] + ".json")
        # a cell's name stands in ONE place, BENCHMARK.json: a spec file is
        # its reader and the reader's arguments (`spec_keys_pinned.json`
        # lists the files a test outside `benchmark/` still reads more from;
        # what they repeat has to agree)
        extra = set(spec) - SPEC_KEYS
        assert SPEC_KEYS <= set(spec), m["name"]
        assert not extra or m["name"] in PINNED, m["name"]
        assert all(spec[k] == m.get(k) for k in extra), m["name"]
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        for cell in m.get("workloads", cells):
            assert m["moves"] in reported[cell], (m["name"], cell)
            layered[cell] += 1
    for cell in cells:
        assert len(reported[cell]) >= 2 and layered[cell] >= 1
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))}
    assert on_disk == {m["name"] for m in BENCHMARK["per_layer"]}


# ------------------------------------------------------------- end to end

@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", [w["name"] for w in BENCHMARK["workloads"]
                                      if w["chips"] == 1])
def test_cell_runs_at_rehearsal_size_on_the_cpu(workload, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
           workload, "--seed", str(2**31 + 17), "--seconds", "2", "--trace",
           str(trace), "--rehearsal"]
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"       # and says so
    kinds = BENCHMARK["per_layer"] if trace else BENCHMARK["end_to_end"]
    allowed = {m["name"]: m for m in kinds
               if workload in m.get("workloads", [workload])}
    assert line["metrics"] and set(line["metrics"]) <= set(allowed)
    assert not any(allowed[n]["source"] == "device_trace"
                   for n in line["metrics"])          # no device metric
    assert "breakdown" not in line and "busy_s" not in line["device"]
    if not trace:
        assert set(line["metrics"]) == set(allowed)
    # without --rehearsal there is no result off the TPU
    refused = subprocess.run(cmd[:-1], cwd=ROOT, env=env, capture_output=True,
                             text=True, timeout=300)
    assert refused.returncode != 0 and not refused.stdout.strip()
