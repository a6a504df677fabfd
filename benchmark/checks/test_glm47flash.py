"""The benchmark's checks of the cell `serve_glm47flash_longctx_queue`, on the
CPU: `roofline_mla.py`'s counts against hand arithmetic at the cell's sizes,
its reader on a made-up step ring, and the cell end to end at the rehearsal
sizes of `rehearsal_glm47flash.json` (`rehearse_cell.py` lays them over
`rehearsal.json`, which a `model_config` PR may not edit).

    python3 -m pytest benchmark/checks/test_glm47flash.py -q
"""

import collections
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH]

import roofline  # noqa: E402
import roofline_mla  # noqa: E402

CELL = "serve_glm47flash_longctx_queue"
with open(os.path.join(BENCH, "configs", "glm-4.7-flash-12l-ep8.json")) as f:
    CONFIG = json.load(f)
WIDTHS = (CONFIG["num_attention_heads"], CONFIG["kv_lora_rank"],
          CONFIG["qk_rope_head_dim"])


def test_a_pair_costs_what_the_issue_counted():
    assert WIDTHS == (20, 512, 64)
    # 2 x (576 + 512) x 20 operations a (query, cached position) pair
    assert roofline_mla.pair_ops(*WIDTHS) == 43_520


def test_decode_walk_counts_whole_blocks_of_the_models_entry():
    # 1000 (slot, block) pairs a layer, 12 layers, blocks of 512 positions
    flops, nbytes = roofline_mla.decode_walk(1000, 12, 512, *WIDTHS)
    rows = 12 * 512 * 1000
    assert flops == rows * 43_520 == 267_386_880_000
    # 1152 bytes a position: the MODEL's 576 values, not the 640 stored
    assert nbytes == rows * 1152 == 7_077_888_000
    assert flops / nbytes == pytest.approx(37.8, abs=0.03)
    # mixed bound at the published peaks: memory time 8.64 ms, compute 1.36
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = roofline.least_seconds(flops, nbytes, peaks)
    assert bound == "memory" and seconds == pytest.approx(8.642e-3, rel=1e-3)


def test_chunk_walk_counts_the_pairs_the_causal_mask_keeps():
    # one chunk of 1024 queries from position 3072: 4096 positions under its
    # frontier; queries see 3072 + 1 .. 3072 + 1024 positions
    chunk, positions = 1024, 3072 + 1024
    flops, nbytes = roofline_mla.chunk_walk(1, positions, 12, chunk, *WIDTHS)
    pairs = sum(3072 + i + 1 for i in range(chunk))
    assert pairs == chunk * positions - chunk * (chunk - 1) // 2
    assert flops == 12 * pairs * 43_520
    assert nbytes == 12 * 2 * (positions * 576 + chunk * 20 * (576 + 512))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert roofline.least_seconds(flops, nbytes, peaks)[1] == "compute"
    # two chunks add up
    two = roofline_mla.chunk_walk(2, positions + chunk, 12, chunk, *WIDTHS)
    first = roofline_mla.chunk_walk(1, chunk, 12, chunk, *WIDTHS)
    assert two[0] == flops + first[0] and two[1] == nbytes + first[1]


Step = collections.namedtuple("Step", "t_end latent_walk_blocks "
                              "latent_chunk_positions prefill_chunks")


@pytest.mark.parametrize("walk, kernel, want", [
    ("decode", "dstpu_mla_decode.3 custom-call:tpu_custom_call bf16[128,20,512]",
     lambda: roofline_mla.decode_walk(300, 12, 512, *WIDTHS)),
    ("chunk", "dstpu_mla_prefill.2 custom-call:tpu_custom_call bf16[1,1024,10240]",
     lambda: roofline_mla.chunk_walk(
         3, 9000, 12, CONFIG["serving"]["prefill_chunk"], *WIDTHS)),
])
def test_reader_turns_the_step_rings_counts_into_a_share(monkeypatch, walk,
                                                         kernel, want):
    import steprings
    from readers import mla_walk_roofline
    steps = [Step(0.5, 1, 1, 1),                  # before the traced seconds
             Step(1.5, 100, 4000, 1), Step(2.5, 200, 5000, 2)]
    monkeypatch.setattr(steprings, "steps", lambda obs, subsystem: steps)
    monkeypatch.setattr(roofline, "share",
                        lambda flops, nbytes, seconds, kind:
                        (flops, nbytes, seconds, kind))
    obs = {"traced": (1.0, 3.0), "config": CONFIG, "device_kind": "TPU v5 lite"}
    trace = {"ops": {kernel: 0.25, "fusion.1 fusion bf16[8]": 1.0}}
    args = {"match": "^dstpu_mla_" + ("decode" if walk == "decode"
                                      else "prefill"),
            "subsystem": "serving", "walk": walk}
    assert mla_walk_roofline.read(obs, trace, args) \
        == (*want(), 0.25, "TPU v5 lite")
    # nothing to read: no trace, no kernel time, a program without the fields
    assert mla_walk_roofline.read(obs, None, args) is None
    assert mla_walk_roofline.read(obs, {"ops": {}}, args) is None
    monkeypatch.setattr(steprings, "steps", lambda obs, subsystem: [
        collections.namedtuple("Old", "t_end")(1.5)])
    assert mla_walk_roofline.read(obs, trace, args) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_at_rehearsal_size_on_the_cpu(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_cell.py"),
         "--workload", CELL, "--seed", str(2**31 + 17), "--seconds", "2",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    note = line["notes"]["logits"]
    assert note["latent_entries_compared"] > 0
    assert all(note[k] <= v for k, v in note["limits"].items())
    assert line["notes"]["programs"] == {"decode_step": 1, "prefill_step": 1,
                                         "mixed_step": 1}
    assert line["notes"]["kv_pool_kinds"]["latent"]["layers"] == 3
