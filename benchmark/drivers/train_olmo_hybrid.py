"""Driver for the Olmo-Hybrid configuration, trained through
`deepspeed_tpu.initialize(...)` and `engine.train_batch(...)`:
`drivers/train.py::run`'s sequence with `models/olmo_hybrid.py`'s model and
`references/olmo_hybrid.py`'s loss AND GRADIENT, under the same engine
configuration (`train.py::_engine_config`) and with the same `obs` keys, so
that the training cells' readers read it unchanged.

A run: the engine builds its state on the device from the seed -> loss and
gradient of the batch's one sequence through the plain reference, at the
initial weights (`reference`) -> the first step (compiles, or loads from the
cache), whose loss must be the reference's and whose gradient, read back
from the optimizer's first moment, the reference's a leaf (`compare`) -> the
warm-up steps -> the window: whole optimizer steps back to back on the same
prepared sequence, each ended by fetching its loss, until `--seconds` have
passed; over the run the loss has to fall.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.models.olmo_hybrid import (make_olmo_hybrid_model,
                                              olmo_hybrid_config)

import harness
import traffic_gen
from drivers.train import _engine_config

# What the first step is held to, each the distance of the program's number
# from the float32 reference's on the SAME 32768 positions at the same
# weights (the batch has one sequence, so no sample stands for it). Each
# limit lies between two readings on the chip (PERF.md section 6, PR 56):
# the largest the program gave over its seeds, and what the SAME reference
# gives in a lower precision, run through `compare` in the program's place
# (`arch_from_config(round_to=)`: every weight, every product's input, the
# state after each position and the stream rounded through that type).
#
# gradient: one step's gradient a leaf, `|got - want| / |want|` in the
#   leaf's norm, the WORST leaf; `got` read back from what the step left in
#   the optimizer's first moment (after one step from zero it holds (1 -
#   beta_1) times the clipped gradient, in bfloat16), so a state the step
#   left unchanged reads 1. It reads every layer forward and backward, the
#   chunked scan's own backward among them, at the timed length. The program
#   read 0.076 to 0.097 (the decay's two leaves of the Gated DeltaNet
#   layers; the attention layer's matrices 0.009); the reference through
#   bfloat16 — the configuration's OWN precision: the program's products are
#   bfloat16 — reads the same leaf for leaf, 0.080; through float8_e4m3fn,
#   the precision below, 0.69 (no leaf under 0.137). (The parameters' own
#   change says less: bfloat16 weights of ~0.02 move in steps of 6e-5 to
#   2.4e-4 and lr is 1e-4, so a leaf's change is none or one step.)
# loss: near ln V a loss hardly reads the logits — the program is within
#   2.0e-5 of the reference's, bfloat16 everywhere within 1.3e-5, float8
#   within 7.4e-5 — but it does read WHICH weights and tokens it was taken
#   on: two draws differ by 1e-3 and more (10.204 to 10.221 over the seeds).
#   The limit holds the two sides to one function; it is not the one a
#   lower precision fails.
LIMITS = {"loss": 1e-4, "gradient": 0.25}


def reference(cell, params, tokens, labels, round_to=None):
    """(loss, gradient a leaf: float32 numpy) of the plain reference at
    `params` on the sequences `tokens`, `labels` [n, T]."""
    ref = harness.load_module("references", cell["config_json"]["reference"])
    arch = ref.arch_from_config(cell["config_json"], round_to=round_to)
    return ref.loss_and_grads(params, tokens, labels, arch)


def step_gradient(engine, cfg):
    """(the first step's gradient a leaf as the step left it in the AdamW
    first moment: the moment itself, and the number to multiply it by; the
    gradient's norm as the step computed it)."""
    holder = [s for s in jax.tree_util.tree_leaves(
        engine.state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
        if hasattr(s, "mu")]
    assert len(holder) == 1 and int(holder[0].count) == 1, holder
    moment, norm = holder[0].mu, engine.get_global_grad_norm()
    # the step's own arithmetic: the clip's factor in the gradients' dtype
    # (the moments'), beta_1 the optimizer's default (`_engine_config`
    # names none)
    factor = jnp.minimum(1.0, cfg["assumed"]["gradient_clipping"]
                         / (jnp.float32(norm) + 1e-6))
    factor = float(factor.astype(jax.tree_util.tree_leaves(moment)[0].dtype))
    return moment, 1.0 / ((1.0 - 0.9) * factor), norm


@jax.jit
def _distance(got, scale, want):
    got = got.astype(jnp.float32) * scale
    return jnp.sum(jnp.square(got - want)), jnp.sum(jnp.square(want))


def compare(loss, gradient, scale, want_loss, want_gradient):
    """The two numbers `LIMITS` names, and what they were made of: `loss`
    and `gradient` (a tree of the parameters' shape, times `scale`) against
    the reference's."""
    got = jax.tree_util.tree_leaves_with_path(gradient)
    want = jax.tree_util.tree_leaves(want_gradient)
    assert len(got) == len(want)
    off, whole, by_leaf = 0.0, 0.0, {}
    for (path, leaf), w in zip(got, want):
        assert leaf.shape == w.shape, (path, leaf.shape, w.shape)
        d, n = (float(x) for x in _distance(leaf, scale, w))
        # (a leaf that is not finite is as far off as a leaf can be)
        by_leaf[jax.tree_util.keystr(path)] = float(np.sqrt(d / n)) \
            if np.isfinite(d) else float("inf")
        off, whole = off + d, whole + n
    worst = max(by_leaf, key=by_leaf.get)
    numbers = {"loss": abs(loss - want_loss) / abs(want_loss),
               "gradient": by_leaf[worst]}
    return {"ok": all(numbers[k] <= LIMITS[k] for k in LIMITS),
            "numbers": numbers, "limits": LIMITS, "worst_leaf": worst,
            "gradient_whole": float(np.sqrt(off / whole)),
            "gradient_norm_reference": float(np.sqrt(whole)),
            "by_leaf": by_leaf}


def run(cell, seconds, seed, devices, profiler, compiles, t_process):
    cfg, traffic = cell["config_json"], cell["traffic_json"]
    chips = len(devices)
    if traffic["mesh"].get("data", 1) != chips:
        raise SystemExit(f"traffic {cell['traffic']!r} is laid out for "
                         f"{traffic['mesh']} and the cell asks for {chips} chips")
    seq = traffic["seq_len"]
    mcfg = olmo_hybrid_config(cfg, max_seq_len=seq)
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(**traffic["mesh"]),
                       devices=list(devices))

    t0 = time.perf_counter()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_olmo_hybrid_model(mcfg, name=cell["config"],
                                     abstract=True),
        config=_engine_config(traffic, cfg["assumed"], chips, seed))
    jax.block_until_ready(engine.state.params)
    init_s = time.perf_counter() - t0
    assert engine.micro_batch_size == traffic["micro_batch_per_chip"], \
        engine.micro_batch_size

    batch = traffic_gen.train_batch(traffic, mcfg.vocab_size, chips, seed)
    tokens_per_step = batch["tokens"].size
    rows = np.random.default_rng([seed, 0x5A3B]).choice(
        batch["tokens"].shape[0], traffic["reference_sequences"],
        replace=False)
    rows = np.sort(rows)
    # the step's gradient is the whole batch's: the reference takes it all
    assert len(rows) == batch["tokens"].shape[0], traffic
    t0 = time.perf_counter()
    ref_loss, ref_gradient = reference(
        cell, engine.params, batch["tokens"][rows], batch["labels"][rows])
    check_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    losses = [float(engine.train_batch(batch))]
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    moment, scale, grad_norm = step_gradient(engine, cfg)
    first = compare(losses[0], moment, scale, ref_loss, ref_gradient)
    del moment, ref_gradient
    check_s += time.perf_counter() - t0
    for _ in range(traffic["warm_steps"]):
        losses.append(float(engine.train_batch(batch)))
    loss_ok = bool(np.isfinite(losses).all() and first["ok"])

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
    # one sequence repeated: the loss has to fall over the run (not step by
    # step: bfloat16 AdamW without a master copy wanders over its first steps)
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
             "loss_rtol": LIMITS["loss"], "first_step": first,
             "grad_norm_step1": grad_norm, "warm_losses": losses,
             "loss_last": window_losses[-1],
             "compiles_in_window": in_window_compiles, "steps": len(spans),
             # a window holds ~20 steps, so one stalled step is the run's
             # reading: each step's own time says which it was
             "step_ms": [round(1e3 * (b - a), 1) for a, b in spans],
             "seconds": {"init": init_s, "reference": check_s,
                         "first_step": compile_s}}
    return {"correct": correct, "attempted": len(window_losses),
            "failed": failed, "obs": obs, "notes": notes}
