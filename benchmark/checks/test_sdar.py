"""The benchmark's checks of the cell `serve_sdar_blockdiff_generate`, on the
CPU: its files and the lists it joins, and the cell end to end at the
rehearsal sizes of `rehearsal_sdar.json` (`rehearse_cell.py` lays them over
`rehearsal.json`, which a `model_config` PR may not edit).

    python3 -m pytest benchmark/checks/test_sdar.py -q
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

CELL = "serve_sdar_blockdiff_generate"


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_joins_lists_and_adds_no_entry():
    bench = _bench()
    assert len(bench["per_layer"]) == 128       # the table is full
    joined = [m for m in bench["per_layer"]
              if CELL in m.get("workloads", ())]
    assert all(m["workloads"][-1] == CELL for m in joined)
    assert all(m["moves"] == "serve_tokens_per_s" for m in joined)
    for metric in joined:
        path = os.path.join(BENCH, "layer_metrics", metric["name"] + ".json")
        with open(path) as f:
            reader = json.load(f)["reader"]
        assert os.path.exists(os.path.join(BENCH, "readers", reader + ".py"))
    rates = {m["name"]: m for m in bench["end_to_end"]}
    assert rates["serve_tokens_per_s"]["workloads"][-1] == CELL


def test_the_traffic_is_what_the_cell_says():
    with open(os.path.join(BENCH, "traffic",
                           "blockdiff_generate_backlog.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH, "configs",
                           "sdar-30b-a3b-chat-6l.json")) as f:
        config = json.load(f)
    assert traffic["kind"] == "closed_backlog"
    assert traffic["min_queue"] == config["serving"]["max_slots"] == 128
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "min": 64,
                                        "max": 1024}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 256,
                                        "max": 1024}
    assert (traffic["grid"], traffic["preroll_s"],
            traffic["traced_seconds"]) == (64, 30, 6)
    # the longest request fits the table with the call's window
    window = config["serving"]["blocks_per_call"] \
        * config["generator"]["block_length"]
    assert 1024 + 1024 + window <= config["serving"]["max_context"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_the_cpu(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(HERE, "rehearse_cell.py"),
           "--workload", CELL, "--seed", str(2**31 + 17), "--seconds", "2",
           "--trace", str(trace)]
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    notes = line["notes"]
    counted = notes["step_counters"]
    # flat logits: every block takes its 2 denoise forwards and its commit
    assert counted["denoise_forwards"] == 2 * counted["commit_forwards"]
    assert notes["generator"]["forwards_per_block"] == 3.0
    assert notes["logits"]["forwards_compared"] == 84
    assert notes["logits"]["served_call_counters_equal"] is True
    assert notes["logits"]["served_call_token_blocks_differing_share"] == 0
    assert notes["programs"] == {"decode_step": 1, "prefill_step": 1,
                                 "mixed_step": 1}
    if trace:
        # the counter-read metrics need no device; the trace-read ones are
        # left out on the CPU
        assert {"sched_decode_useful_token_share.throughput",
                "moe_expert_load_max_over_mean.throughput",
                "sched_fused_chunk_share.throughput"} <= set(line["metrics"])
        assert line["metrics"]["sched_decode_useful_token_share.throughput"][
            "value"] <= 100 / 3 + 1e-6
        assert "moe_gmm_roofline.mixed" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
