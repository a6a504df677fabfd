"""The benchmark's checks of the cell `serve_keyevl2_longctx_sparse_queue`, on
the CPU: the configuration file against the catalog's row, `roofline_sparse
.py`'s counts against hand arithmetic at the cell's sizes, its reader on a
made-up step ring, and the cell end to end at the rehearsal sizes of
`rehearsal_keyevl2.json` (`rehearse_cell.py` lays them over `rehearsal.json`,
which a `model_config` PR may not edit).

    python3 -m pytest benchmark/checks/test_keyevl2.py -q
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
import roofline_sparse  # noqa: E402

CELL = "serve_keyevl2_longctx_sparse_queue"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(os.path.join(BENCH, "configs",
                       "keye-vl-2.0-30b-a3b-12l-ep8.json")) as f:
    CONFIG = json.load(f)
SA = CONFIG["sa_config"]


def test_every_key_of_the_catalogs_row_is_the_files_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "Keye-VL-2.0-30B-A3B"]
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differ == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert {k: row["config"][k] for k in differ} == CONFIG["reduced_from"]


def test_a_scored_pair_and_a_selected_pair_cost_what_the_issue_counted():
    heads, dim = SA["indexer_num_heads"], SA["indexer_head_dim"]
    assert (heads, dim, SA["topk"]) == (16, 64, 2048)
    # 2 x 16 x 64 operations a (query, position) pair, 128 B of index key a
    # position a call reads
    flops, nbytes = roofline_sparse.index_scores(1, 1, 1, heads, dim)
    assert (flops, nbytes) == (2048, 128)
    # a decode step of 16 rows at 27,600 positions, 12 layers: 0.68 GB of
    # index keys (the issue: 0.7-1.4 GB), memory-bound
    flops, nbytes = roofline_sparse.index_scores(16 * 27_600, 16 * 27_600,
                                                 12, heads, dim)
    assert nbytes == 678_297_600
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert roofline.least_seconds(flops, nbytes, peaks)[1] == "memory"
    # a chunk of 1024 rows at the same depth reads the keys ONCE: compute
    flops, nbytes = roofline_sparse.index_scores(1024 * 27_600, 27_600, 12,
                                                 heads, dim)
    assert roofline.least_seconds(flops, nbytes, peaks)[1] == "compute"
    # the selected entries of the decode step: 16 x 2048 x 12 rows of 2 KiB
    # = 0.8 GB (the issue's gather figure), whatever form reads them
    widths = (CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"],
              CONFIG["head_dim"])
    flops, nbytes = roofline_sparse.sparse_walk(16 * 2048, 16 * 2048, 12,
                                                *widths)
    assert nbytes == 393_216 * 2048 == 805_306_368
    assert flops == 393_216 * 2 * 2 * 32 * 128
    # the selection over the served table of 67,584 positions
    assert roofline_sparse.select_passes(
        CONFIG["serving"]["max_context"]) == 32 + 17 + 3


Step = collections.namedtuple(
    "Step", "t_end index_scored_positions selected_positions "
    "prefill_live_blocks decode_live_blocks decoding")


@pytest.mark.parametrize("what, match, kernel, want", [
    ("index_scores", "^dstpu_sparse_index_scores",
     "dstpu_sparse_index_scores_decode.3 custom-call f32[16,132,1,512]",
     lambda: roofline_sparse.index_scores(9000, (30 + 700) * 512, 12, 16,
                                          64)),
    # 22 decode rows x 4 tokens x 2048 selected entries, under the 700
    # blocks their walks read; the chunks' 30 blocks once
    ("sparse_walk", "^dstpu_paged_(decode|prefill)_sparse",
     "dstpu_paged_prefill_sparse.2 custom-call bf16[1,1024,4096]",
     lambda: roofline_sparse.sparse_walk(
         700, 22 * 4 * 2048 + 30 * 512, 12, 32, 4, 128)),
])
def test_reader_turns_the_step_rings_counts_into_a_share(monkeypatch, what,
                                                         match, kernel, want):
    import steprings
    from readers import sparse_roofline
    steps = [Step(0.5, 1, 1, 1, 1, 1),            # before the traced seconds
             Step(1.5, 4000, 300, 10, 300, 10),
             Step(2.5, 5000, 400, 20, 400, 12)]
    monkeypatch.setattr(steprings, "steps", lambda obs, subsystem: steps)
    monkeypatch.setattr(roofline, "share",
                        lambda flops, nbytes, seconds, kind:
                        (flops, nbytes, seconds, kind))
    obs = {"traced": (1.0, 3.0), "config": CONFIG,
           "device_kind": "TPU v5 lite"}
    trace = {"ops": {kernel: 0.25, "fusion.1 fusion bf16[8]": 1.0}}
    args = {"what": what, "match": match, "subsystem": "serving"}
    assert sparse_roofline.read(obs, trace, args) \
        == (*want(), 0.25, "TPU v5 lite")
    # nothing to read: no trace, no kernel time, a program without the fields
    assert sparse_roofline.read(obs, None, args) is None
    assert sparse_roofline.read(obs, {"ops": {}}, args) is None
    monkeypatch.setattr(steprings, "steps", lambda obs, subsystem: [
        collections.namedtuple("Old", "t_end")(1.5)])
    assert sparse_roofline.read(obs, trace, args) is None


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
    assert note["selected_rows_active"] > 0
    assert all(note[k] <= v for k, v in note["limits"].items())
    assert line["notes"]["programs"] == {"decode_step": 1, "prefill_step": 1,
                                         "mixed_step": 1}
    assert line["notes"]["kv_pool_kinds"]["full"]["layers"] == 3
