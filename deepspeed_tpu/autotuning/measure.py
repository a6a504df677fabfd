"""Measured trials — short runs scoring the planner's survivors.

The measured stage replays ONE deterministic ragged trace (seeded lengths
and tokens, `ragged_trace`) through a serving engine built from the
candidate's config, or times a few training steps, and returns a plain
JSON-able measurement record the objective scores.

Determinism is the contract the reproducible-artifact promise rests on:
serving trials drive an injectable `VirtualClock` that advances one tick
per scheduler sync, so every latency histogram — and therefore every SLO
score, and therefore the winner — is a pure function of (trace seed,
candidate config), byte-identical across runs and machines. `clock="wall"`
swaps in `time.monotonic` for real-hardware tuning, same code path.

Trials can run in-process (the CPU-harness default: one engine at a time,
torn down between trials) or in a child process via `run_trial_child` —
the env-in, JSON-line-out recipe of `utils/subproc.py`, which a crash or
real OOM cannot take the tuner down with.
"""

import copy
import gc
import json
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from deepspeed_tpu.autotuning.space import apply_overrides
from deepspeed_tpu.utils.subproc import run_json_child

TRIAL_ENV = "DSTPU_TUNE_TRIAL"       # the child reads its spec from here


class VirtualClock:
    """Deterministic engine clock: one tick per scheduler sync. With the
    stamps in "seconds" and one sync ticking 1e-3, the serving latency
    histograms read in SYNCS when formatted as milliseconds — TTFT p99 of
    7.0 means the 99th-percentile request saw its first token 7 syncs
    after arrival."""

    TICK = 1e-3

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def tick(self):
        self.t += self.TICK


def ragged_trace(seed: int = 0, n_requests: int = 12, min_len: int = 2,
                 max_len: int = 48, max_new: int = 12,
                 vocab: int = 256) -> Dict[str, Any]:
    """A serving workload as a JSON-able spec: seeded ragged prompt
    lengths (and, derived from the same seed, the prompt tokens —
    `trace_requests` materializes them). A shared prefix rides the first
    third of the requests so prefix caching has something to win on."""
    rng = np.random.default_rng(int(seed))
    lens = [int(rng.integers(min_len, max_len + 1))
            for _ in range(int(n_requests))]
    return {"seed": int(seed), "n_requests": int(n_requests),
            "lens": lens, "max_new": int(max_new), "vocab": int(vocab),
            "shared_prefix": int(min_len)}


def trace_requests(trace: Dict[str, Any]) -> List[Any]:
    """Materialize the trace's `Request` list (deterministic from the
    spec). `stop_on_eos=False`: every request generates its full budget,
    so the token count — the throughput numerator — is config-invariant
    and objectives compare time, not luck."""
    from deepspeed_tpu.inference.scheduler import Request
    rng = np.random.default_rng(int(trace["seed"]))
    vocab = int(trace["vocab"])
    prefix = rng.integers(0, vocab, (int(trace.get("shared_prefix", 0)),))
    reqs = []
    for i, length in enumerate(trace["lens"]):
        body = rng.integers(0, vocab, (int(length),)).astype(np.int32)
        if trace.get("shared_prefix") and i < len(trace["lens"]) // 3:
            body[:len(prefix)] = prefix
        reqs.append(Request(uid=i, tokens=body,
                            max_new_tokens=int(trace["max_new"]),
                            stop_on_eos=False))
    return reqs


def _merged_config(base_config, overrides, telemetry):
    cfg = copy.deepcopy(dict(base_config or {}))
    apply_overrides(cfg, dict(overrides or {}))
    if telemetry and "telemetry" not in cfg:
        # registry-only: histograms exist, no files are written
        cfg["telemetry"] = {"enabled": True, "prometheus": False,
                            "jsonl": False, "monitor_bridge": False}
    return cfg


def measure_serving(spec_factory, base_config: Dict[str, Any],
                    overrides: Dict[str, Any], trace: Dict[str, Any],
                    clock: str = "virtual", draft_factory=None,
                    ) -> Dict[str, Any]:
    """One serving trial: build an engine from base_config+overrides,
    replay the trace, return the measurement record. Never raises for a
    config-shaped failure — the record carries ok=False and the error
    text (the tuner maps it to infeasible)."""
    from deepspeed_tpu.inference.engine import init_inference
    cfg = _merged_config(base_config, overrides, telemetry=True)
    vc = VirtualClock() if clock == "virtual" else None
    engine = serving = None
    try:
        engine = init_inference(model=spec_factory(), config=cfg)
        draft_spec = draft_factory() if (
            draft_factory is not None and
            str(cfg.get("serving", {}).get("spec_decode", {})
                .get("drafter", "off")) == "model") else None
        serving = engine.serving(draft_spec=draft_spec,
                                 clock=(vc if vc is not None else None))
        for r in trace_requests(trace):
            serving.submit(r)
        t0 = time.perf_counter()
        done: Dict[Any, Any] = {}
        while serving.queue or serving.num_active:
            before = (serving.prefill_chunks, serving.decode_steps,
                      len(serving.queue))
            if vc is not None:
                vc.tick()
            for c in serving.step():
                done[c.uid] = c
            after = (serving.prefill_chunks, serving.decode_steps,
                     len(serving.queue))
            if after == before:
                raise RuntimeError("serving trial made no progress")
        wall_s = time.perf_counter() - t0
        generated = int(sum(len(c.tokens) for c in done.values()))
        elapsed = float(vc.t) if vc is not None else wall_s
        rec = {"ok": True, "kind": "serving",
               "generated_tokens": generated,
               "syncs": int(serving.steps),
               "elapsed": elapsed, "wall_s": wall_s,
               "tokens_per_time": generated / max(elapsed, 1e-9),
               "latency": serving.latency_snapshot(),
               "compile_stats": serving.compile_stats()}
        stats = serving.stats()
        if "prefix_cache" in stats:
            rec["prefix_cache"] = {
                "hit_tokens": stats["prefix_cache"]["hit_tokens"]}
        if "spec_decode" in stats:
            rec["spec_decode"] = {
                "acceptance_rate": stats["spec_decode"]["acceptance_rate"]}
        return rec
    except Exception as e:
        return {"ok": False, "kind": "serving",
                "error": f"{type(e).__name__}: {str(e)[:300]}"}
    finally:
        del serving, engine
        gc.collect()


def measure_training(model_factory, batch_factory,
                     base_config: Dict[str, Any], overrides: Dict[str, Any],
                     steps: int = 3, warmup: int = 1) -> Dict[str, Any]:
    """One training trial: a few timed steps with an honest scalar-readback
    fence (the seed Autotuner's measurement, behind the same record
    contract as the serving trial)."""
    import deepspeed_tpu
    from deepspeed_tpu.comm import mesh as mesh_mod
    cfg = _merged_config(base_config, overrides, telemetry=False)
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    engine = None
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(model=model_factory(),
                                                   config=cfg)
        batch = batch_factory(engine.train_batch_size())
        loss = None
        for _ in range(max(0, int(warmup))):
            loss = engine.train_batch(batch)
        if loss is not None:
            float(loss)
        t0 = time.perf_counter()
        for _ in range(max(1, int(steps))):
            loss = engine.train_batch(batch)
        float(loss)
        dt = (time.perf_counter() - t0) / max(1, int(steps))
        return {"ok": True, "kind": "train", "step_ms": dt * 1e3,
                "samples_per_sec": engine.train_batch_size() / dt}
    except Exception as e:
        return {"ok": False, "kind": "train",
                "error": f"{type(e).__name__}: {str(e)[:300]}"}
    finally:
        del engine
        gc.collect()


def run_trial_child(spec: Dict[str, Any],
                    timeout: Optional[float] = None) -> Dict[str, Any]:
    """Run one trial in a child process (`python -m
    deepspeed_tpu.autotuning.trial` reading `DSTPU_TUNE_TRIAL`), so a
    segfault or a real device OOM costs one trial, not the tuner. Only specs the trial module can
    reconstruct from JSON are supported (the built-in demo model zoo —
    see `trial.py`); in-process measurement has no such limit."""
    # `--isolation process` from a session that already measured in-process
    # (and so owns the chip) cannot work: every trial child would die at
    # backend start-up
    from deepspeed_tpu.platform.device import \
        refuse_spawn_if_holding_accelerator
    refuse_spawn_if_holding_accelerator("dstpu_tune --isolation process")
    rec, proc = run_json_child(
        [sys.executable, "-m", "deepspeed_tpu.autotuning.trial"],
        {TRIAL_ENV: json.dumps(spec, sort_keys=True)},
        clear_prefixes=("DSTPU_TUNE_",), key="ok",
        timeout=timeout)
    if rec is None:
        return {"ok": False, "kind": spec.get("kind", "?"),
                "error": f"trial child produced no result "
                         f"(rc={proc.returncode}): "
                         f"{(proc.stderr or '').strip()[-300:]}"}
    return rec
