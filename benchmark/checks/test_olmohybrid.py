"""The benchmark's checks of the cell `train_olmohybrid_seq32k_1chip`, on the
CPU: its files and the lists it joins, and the cell end to end at the
rehearsal sizes of `rehearsal_olmohybrid.json` (`rehearse_cell.py` lays them
over `rehearsal.json`, which a `model_config` PR may not edit).

    python3 -m pytest benchmark/checks/test_olmohybrid.py -q
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

CELL = "train_olmohybrid_seq32k_1chip"
JOINED = {"train_step_device_ms", "flash_time_share.train",
          "flash_roofline.train", "peak_hbm_share.train",
          "device_idle_share.train", "train_exposed_host_ms_per_step",
          "flash_fwd_calls_per_backward.train"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_the_cell_joins_lists_and_adds_no_entry():
    bench = _json(ROOT, "BENCHMARK.json")
    assert len(bench["per_layer"]) == 128       # the table is full
    joined = [m for m in bench["per_layer"]
              if CELL in m.get("workloads", ())]
    assert {m["name"] for m in joined} == JOINED
    assert all(m["workloads"][-1] == CELL for m in joined)
    assert all(m["moves"] == "train_tokens_per_s_per_chip" for m in joined)
    for metric in joined:
        reader = _json(BENCH, "layer_metrics",
                       metric["name"] + ".json")["reader"]
        assert os.path.exists(os.path.join(BENCH, "readers", reader + ".py"))
    rates = {m["name"]: m for m in bench["end_to_end"]}
    assert rates["train_tokens_per_s_per_chip"]["workloads"][-1] == CELL
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-hybrid-7b-4l-vp8", "train_seq32768", 1)


def test_the_configuration_is_the_published_one_cut_as_it_says():
    bench = _json(ROOT, "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}["olmo-hybrid-7b-4l-vp8"]
    config = _json(ROOT, entry["file"])
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_size"]
    assert entry["source"] == config["source"]
    for key, value in {
            "model_type": "olmo_hybrid", "hidden_size": 3840,
            "intermediate_size": 11008, "num_attention_heads": 30,
            "num_key_value_heads": 30, "hidden_act": "silu",
            "max_position_embeddings": 65536, "attention_bias": False,
            "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
            "linear_num_key_heads": 30, "linear_num_value_heads": 30,
            "linear_key_head_dim": 96, "linear_value_head_dim": 192,
            "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
            "rope_parameters": {"rope_theta": None}}.items():
        assert config[key] == value, key
    # the cut: one whole period, an eighth of the vocabulary
    assert config["num_hidden_layers"] == 4 and config["layer_types"] == [
        "linear_attention"] * 3 + ["full_attention"]
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"] \
        == 100352
    assert config["published"]["num_hidden_layers"] == 32
    assert set(config["assumed"]) >= {"post_norm", "qk_norm", "output_gate",
                                      "no_positions", "weights", "remat"}
    for kind, name in (("drivers", config["driver"] + ".py"),
                       ("references", config["reference"] + ".py")):
        assert os.path.exists(os.path.join(BENCH, kind, name)), name
    # the count of the built tree, from the widths alone
    D, F, V = 3840, 11008, 12544
    mlp = 3 * D * F + 2 * D                     # + the norm and the out bias
    gdn = D * (2 * 30 * 96 + 2 * 30 * 192) + D * 60 + 4 * 11520 + 60 + 192 \
        + 30 * 192 * D + D
    attn = D * 3 * D + 3 * D + D * D + D + 2 * D + D
    assert config["parameters"] == 3 * (gdn + mlp) + attn + mlp + 2 * V * D + D


def test_the_traffic_is_what_the_cell_says():
    traffic = _json(BENCH, "traffic", "train_seq32768.json")
    assert {k: traffic[k] for k in traffic if k != "why"} == {
        "kind": "train_steps", "seq_len": 32768,
        "sequences_per_chip_per_step": 1, "micro_batch_per_chip": 1,
        "mesh": {"data": 1}, "zero_stage": 0, "reference_sequences": 1,
        "warm_steps": 2, "traced_steps": 3}


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
    assert notes["compiles_in_window"] == 0
    assert abs(notes["loss_step1"] - notes["loss_reference_sample"]) \
        <= notes["loss_rtol"] * notes["loss_reference_sample"]
    assert notes["loss_last"] < notes["loss_step1"]
    # the first step's gradient, read back from the optimizer's moment,
    # against the reference's: every leaf, each beside the limit
    first = notes["first_step"]
    assert first["ok"] and set(first["numbers"]) == set(first["limits"])
    assert all(first["numbers"][k] <= first["limits"][k]
               for k in first["limits"])
    assert len(first["by_leaf"]) == 28 and first["by_leaf"][
        first["worst_leaf"]] == first["numbers"]["gradient"]
    assert abs(notes["grad_norm_step1"] / first["gradient_norm_reference"]
               - 1.0) < 0.01
    if trace:
        # the counter- and span-read metrics need no device; the trace-read
        # ones are left out on the CPU
        assert {"train_exposed_host_ms_per_step", "init_s",
                "compile_s"} <= set(line["metrics"])
        assert "flash_roofline.train" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s_per_chip",
                                        "setup_s"}
