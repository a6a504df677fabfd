"""`bin/dstpu_tune` — the whole-stack tuner as a command.

Runs a `TuneSession` over the default serving or training search space —
against the built-in tiny-GPT demo model (the CPU-harness walkthrough in
docs/autotuning.md; 8 virtual devices, virtual clock, fully
deterministic) — and writes the tuned-config artifact. Programs tuning a
real model build a `TuneSession` directly with their own profile and
`measure_fn`; this CLI is the end-to-end recipe and the smoke lane.

    dstpu_tune serving --objective slo --ttft-p99 8 --tpot-p99 4 \
        --capacity 16M --out tuned.json
    dstpu_tune serving --dry-run            # planner ledger only
    dstpu_tune train --trials 6
"""

import argparse
import functools
import json
import sys


def _demo_gpt_cfg():
    return dict(n_layer=2, n_head=4, d_model=64, max_seq_len=256,
                vocab_size=256, dtype="float32", remat=False)


def _serving_measure_fn(args, base_config, trace, model_cfg):
    from deepspeed_tpu.autotuning.measure import (measure_serving,
                                                  run_trial_child)
    if args.isolation == "process":
        def measure(overrides):
            return run_trial_child({
                "kind": "serving",
                "model": {"kind": "tiny_gpt", "cfg": model_cfg},
                "base_config": base_config, "overrides": overrides,
                "trace": trace, "clock": args.clock,
            }, timeout=args.trial_timeout)
        return measure

    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model

    def spec_factory():
        cfg = dict(model_cfg, dtype=jnp.dtype(model_cfg["dtype"]))
        return make_gpt_decode_model(cfg=GPTConfig(**cfg), name="tuned")

    return functools.partial(measure_serving, spec_factory, base_config,
                             trace=trace, clock=args.clock)


def _train_measure_fn(args, base_config, model_cfg):
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.autotuning.measure import measure_training
    from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_model
    seq = 32

    def model_factory():
        cfg = dict(model_cfg, max_seq_len=seq,
                   dtype=jnp.dtype(model_cfg["dtype"]))
        return make_gpt_model(cfg=GPTConfig(**cfg))

    def batch_factory(n):
        toks = np.random.default_rng(args.seed).integers(
            0, model_cfg["vocab_size"], (n, seq))
        return {"tokens": toks.astype(np.int32)}

    def measure(overrides):
        return measure_training(model_factory, batch_factory, base_config,
                                overrides, steps=2, warmup=1)
    return measure


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="dstpu_tune",
        description="planner-pruned whole-stack autotuner: search space -> "
                    "constraint+planner prune (zero allocations) -> "
                    "measured trials -> reproducible tuned-config artifact")
    ap.add_argument("mode", choices=("serving", "train"))
    ap.add_argument("--capacity", default="0",
                    help="per-device memory budget the planner judges "
                         "against (e.g. 16G, 512M; 0 = unknown: planner "
                         "records peaks but refuses nothing)")
    ap.add_argument("--min-headroom", type=float, default=0.0,
                    help="refuse candidates with predicted headroom under "
                         "this fraction of capacity")
    ap.add_argument("--objective", default=None,
                    help="slo | throughput (serving); train_throughput | "
                         "mfu (train)")
    ap.add_argument("--ttft-p99", type=float, default=None,
                    help="SLO target: TTFT p99 in clock ms (virtual clock: "
                         "scheduler syncs)")
    ap.add_argument("--tpot-p99", type=float, default=None,
                    help="SLO target: TPOT p99 in clock ms")
    ap.add_argument("--tuner", default="gridsearch",
                    choices=("gridsearch", "random", "model_based"))
    ap.add_argument("--trials", type=int, default=None,
                    help="measurement budget (default: every survivor)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-seed", type=int, default=None,
                    help="ragged-trace seed (default: --seed)")
    ap.add_argument("--requests", type=int, default=12,
                    help="requests in the replayed trace")
    ap.add_argument("--max-new", type=int, default=12,
                    help="tokens generated per request")
    ap.add_argument("--clock", default="virtual",
                    choices=("virtual", "wall"),
                    help="virtual = deterministic sync-count latencies "
                         "(the reproducibility contract); wall = real "
                         "time on hardware")
    ap.add_argument("--isolation", default="inprocess",
                    choices=("inprocess", "process"),
                    help="process = each trial in a child process (a "
                         "trial crash costs one trial)")
    ap.add_argument("--trial-timeout", type=float, default=None)
    ap.add_argument("--dry-run", action="store_true",
                    help="stop after the planner stage: artifact holds "
                         "the prune ledger, no measurements")
    ap.add_argument("--out", default="tuned_config.json")
    args = ap.parse_args(argv)

    from deepspeed_tpu.autotuning.measure import ragged_trace
    from deepspeed_tpu.autotuning.session import (TuneSession,
                                                  write_artifact)
    from deepspeed_tpu.autotuning.space import (ModelProfile,
                                                default_serving_space,
                                                default_training_space)
    from deepspeed_tpu.telemetry.memscope import _parse_size, fmt_bytes

    capacity = _parse_size(args.capacity)
    model_cfg = _demo_gpt_cfg()

    class _Cfg:                           # profile view of the demo dict
        pass
    view = _Cfg()
    for k, v in model_cfg.items():
        setattr(view, k, v)
    view.d_ff = None
    view.n_kv_head = None
    profile = ModelProfile.from_gpt_config(view)

    if args.mode == "serving":
        import jax
        base_config = {"dtype": "float32", "kv_cache_dtype": "float32",
                       "greedy": True, "kv_block_size": 16,
                       "max_out_tokens": 64, "serving": {"max_slots": 4}}
        trace = ragged_trace(
            seed=args.trace_seed if args.trace_seed is not None
            else args.seed,
            n_requests=args.requests, max_new=args.max_new,
            vocab=model_cfg["vocab_size"])
        objective = args.objective or (
            "slo" if (args.ttft_p99 or args.tpot_p99) else "throughput")
        if objective == "slo":
            objective = {"name": "slo", "ttft_p99_ms": args.ttft_p99,
                         "tpot_p99_ms": args.tpot_p99}
        session = TuneSession(
            default_serving_space(), objective,
            _serving_measure_fn(args, base_config, trace, model_cfg),
            profile, base_config=base_config, capacity_bytes=capacity,
            min_headroom_frac=args.min_headroom,
            n_devices=jax.device_count(), tuner_type=args.tuner,
            seed=args.seed, max_trials=args.trials, trace=trace)
    else:
        import jax
        base_config = {"optimizer": {"type": "Adam",
                                     "params": {"lr": 1e-3}},
                       "train_micro_batch_size_per_gpu": 1,
                       "mesh": {"data": -1}, "steps_per_print": 10**9}
        session = TuneSession(
            default_training_space(),
            args.objective or "train_throughput",
            _train_measure_fn(args, base_config, model_cfg),
            profile, base_config=base_config, capacity_bytes=capacity,
            min_headroom_frac=args.min_headroom,
            n_devices=jax.device_count(), tuner_type=args.tuner,
            seed=args.seed, max_trials=args.trials)

    artifact = session.run(dry_run=args.dry_run)
    path = write_artifact(artifact, args.out)
    counts = artifact["prune_ledger"]["counts"]
    print(f"dstpu_tune: {counts['candidates']} candidates, "
          f"{counts['constraint_refused']} constraint-refused, "
          f"{counts['planner_refused']} planner-refused "
          f"(capacity {fmt_bytes(capacity) if capacity else 'unknown'}), "
          f"{counts['kept']} measured-stage survivors")
    if artifact["winner"] is not None:
        base = artifact["baseline"]["objective"] \
            if artifact["baseline"] else None
        print(f"winner objective {artifact['winner']['objective']:.4g}"
              + (f" vs baseline {base:.4g}" if base is not None else "")
              + f" — overrides {json.dumps(artifact['winner']['overrides'], sort_keys=True)}")
    elif not args.dry_run:
        print("no feasible candidate survived to the measured stage")
    print(f"artifact written to {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
