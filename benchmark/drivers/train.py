"""Driver for configurations trained through `deepspeed_tpu.initialize(...)`
and `engine.train_batch(...)`.

A run: the engine builds its state on the devices from the seed -> the loss
of a seeded sample of the batch through the plain reference, at the initial
weights -> the first step (compiles, or loads from the cache) and the
warm-up steps, whose losses must be finite and start at the reference's -> the window: whole optimizer steps back to back on the same
prepared batch, each ended by fetching its loss, until `--seconds` have
passed; over the run the loss has to fall.
"""

import time

import jax
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.models.gpt import make_gpt_model

import harness
import traffic_gen
from drivers import gpt_family

# step-1 loss against the float32 reference's loss on a SAMPLE of the batch.
# bfloat16 compute and the flash kernel moved the loss by 1e-5 of itself at
# this width (PR 21: 11.2296 against 11.2295); the sample's mean differs from
# the batch's by about 0.3 / sqrt(tokens in the sample) / 11 < 4e-4. A model
# that drops a layer, a mask or the rotary moves it by percents.
LOSS_RTOL = 5e-3


def _engine_config(traffic, assumed, chips, seed):
    gas = traffic["sequences_per_chip_per_step"] // traffic["micro_batch_per_chip"]
    return {
        "train_batch_size": traffic["sequences_per_chip_per_step"] * chips,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True, "master_weights": False},
        "data_types": {"grad_accum_dtype": "bf16"},
        "gradient_clipping": assumed["gradient_clipping"],
        "zero_optimization": {"stage": traffic["zero_stage"]},
        "mesh": traffic["mesh"],
        "seed": seed % (2**31 - 1),
        "steps_per_print": 10**9,
    }


def _reference_loss(cell, engine, batch, rows, devices):
    ref = harness.load_module("references", cell["config_json"]["reference"])
    arch = ref.arch_from_config(cell["config_json"])
    params = engine.params
    if len(devices) > 1:
        params = jax.device_put(params, devices[0])   # gather the shards
    return ref.loss(params, batch["tokens"][rows], batch["labels"][rows], arch)


def run(cell, seconds, seed, devices, profiler, compiles, t_process):
    cfg, traffic = cell["config_json"], cell["traffic_json"]
    chips = len(devices)
    if traffic["mesh"].get("data", 1) != chips:
        raise SystemExit(f"traffic {cell['traffic']!r} is laid out for "
                         f"{traffic['mesh']} and the cell asks for {chips} chips")
    seq = traffic["seq_len"]
    gcfg = gpt_family.gpt_config(cfg, max_seq_len=seq)
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(**traffic["mesh"]),
                       devices=list(devices))

    t0 = time.perf_counter()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_gpt_model(cfg=gcfg, name=cell["config"], abstract=True),
        config=_engine_config(traffic, cfg["assumed"], chips, seed))
    jax.block_until_ready(engine.state.params)
    init_s = time.perf_counter() - t0
    assert engine.micro_batch_size == traffic["micro_batch_per_chip"], \
        engine.micro_batch_size

    batch = traffic_gen.train_batch(traffic, gcfg.vocab_size, chips, seed)
    tokens_per_step = batch["tokens"].size
    rows = np.random.default_rng([seed, 0x5A3B]).choice(
        batch["tokens"].shape[0], traffic["reference_sequences"], replace=False)
    t0 = time.perf_counter()
    ref_loss = _reference_loss(cell, engine, batch, np.sort(rows), devices)
    check_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    losses = [float(engine.train_batch(batch))]
    compile_s = time.perf_counter() - t0
    for _ in range(traffic["warm_steps"]):
        losses.append(float(engine.train_batch(batch)))
    loss_ok = bool(np.isfinite(losses).all()
                   and abs(losses[0] - ref_loss) <= LOSS_RTOL * abs(ref_loss))

    spans, window_losses = [], []
    traced_steps = traffic.get("traced_steps", 0) if profiler.enabled else 0
    with harness.quiet_host():
        opened = time.perf_counter()
        setup_s = opened - t_process
        compiles_open = compiles.count
        while True:
            t_start = time.perf_counter()
            with profiler.annotate("bench.step"):
                loss = float(engine.train_batch(batch))   # fetch = the fence
            t_end = time.perf_counter()
            spans.append((t_start, t_end))
            window_losses.append(loss)
            if t_end >= opened + seconds:
                break
        compiles_close = compiles.count
        # the traced steps follow the window, so that the profiler's start
        # and stop cost the window nothing; they are the same steps
        traced = []
        if traced_steps:
            profiler.start()
            for _ in range(traced_steps):
                t_start = time.perf_counter()
                with profiler.annotate("bench.step"):
                    window_losses.append(float(engine.train_batch(batch)))
                traced.append((t_start, time.perf_counter()))
            profiler.stop()

    failed = int(np.sum(~np.isfinite(window_losses)))
    in_window_compiles = compiles_close - compiles_open
    # one batch repeated: the loss has to fall over the run. Not step by
    # step: bfloat16 AdamW without a master copy went 11.23, 10.91, 11.36
    # over its first three steps on the chip and was at 9.36 after ten
    falling = min(window_losses[-3:]) < losses[0]
    correct = bool(loss_ok and falling and failed == 0
                   and in_window_compiles == 0)
    peak = limit = 0
    for d in devices:
        memory = d.memory_stats() or {}
        if memory.get("peak_bytes_in_use", 0) >= peak:
            peak = memory.get("peak_bytes_in_use", 0)
            limit = memory.get("bytes_limit", 0)
    obs = {"setup_s": setup_s, "init_s": init_s, "compile_s": compile_s,
           "step_spans": spans, "traced_spans": traced,
           "tokens_per_step": tokens_per_step, "chips": chips,
           "opened": opened, "seconds": seconds,
           "traced": (profiler.started_at, profiler.closed_at),
           "memory_peak_bytes": peak, "memory_limit_bytes": limit,
           "micro_batch_per_chip": traffic["micro_batch_per_chip"],
           "seq_len": seq, "config": cfg}
    notes = {"loss_step1": losses[0], "loss_reference_sample": ref_loss,
             "loss_rtol": LOSS_RTOL, "warm_losses": losses,
             "loss_last": window_losses[-1],
             "compiles_in_window": in_window_compiles, "steps": len(spans),
             "seconds": {"init": init_s, "reference": check_s,
                         "first_step": compile_s}}
    return {"correct": correct, "attempted": len(window_losses),
            "failed": failed, "obs": obs, "notes": notes}
