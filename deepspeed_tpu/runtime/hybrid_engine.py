"""Hybrid engine — one model flipping between training and fast generation (RLHF).

Reference: `runtime/hybrid_engine.py:32` (`DeepSpeedHybridEngine`): inside an
RLHF step the actor both generates rollouts (inference-optimized: gathered
params, injected kernels, KV cache) and trains (ZeRO-3 partitioned). The
reference juggles this with param gather/release and module swapping.

TPU-native: params are global sharded arrays, so "flipping" is free — the decode
program simply reads the CURRENT training params (XLA re-gathers per program as
its sharding demands); no cache retake machinery needed. LoRA-based RLHF uses
`runtime/lora.py` (apply/fuse/unfuse — the reference's LoRA lifecycle as pure
functions). `HybridEngine` = training Engine + a decode path compiled against
the live params, with the reference's `generate()` surface.
"""

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# one sampling rule across the framework (hoisted: this used to be a local
# import inside _build_generate — the serving scheduler, the spill engine
# and this rollout all share the exact same sampler)
from deepspeed_tpu.inference.engine import sample_logits
from deepspeed_tpu.runtime.engine import Engine, ModelSpec
from deepspeed_tpu.utils.logging import logger, log_dist


class HybridEngine(Engine):
    """Engine + generate(). Construct via `initialize(..., hybrid_engine=...)` or
    directly with a DecodeModelSpec for the generation path."""

    def __init__(self, model: ModelSpec, config, decode_spec=None, **kw):
        super().__init__(model, config, **kw)
        self._decode_spec = decode_spec
        self._generate_fn = None
        self.latency = 0.0
        self.generate_count = 0

    def set_decode_spec(self, decode_spec):
        self._decode_spec = decode_spec
        self._generate_fn = None

    def as_draft_spec(self):
        """This engine's decode spec bound to the CURRENT training params —
        the reusable draft-model path: the RLHF actor (or any model this
        engine trains) can draft for a bigger serving target via
        ``target.serving(draft_spec=hybrid.as_draft_spec(),
        spec_decode={"drafter": "model"})``, and conversely a small frozen
        copy of the actor speeds up the rollout itself when rollouts run
        through a ServingEngine. Params are live sharded arrays, so
        "binding" is a dataclass field swap — no gather, no copy."""
        assert self._decode_spec is not None, \
            "HybridEngine needs a DecodeModelSpec (set_decode_spec)"
        return dataclasses.replace(self._decode_spec,
                                   params=self.state.params)

    def _build_generate(self, max_new, greedy, temperature, top_k, top_p):
        spec = self._decode_spec
        assert spec is not None, "HybridEngine needs a DecodeModelSpec (set_decode_spec)"
        # one sampling rule across the framework: the inference engines'
        # sample_logits (module-level import) — the RLHF rollout path must
        # not grow a second, weaker sampler (reference `hybrid_engine.py:174`
        # generates through its inference module)
        def sample(logits, rng):
            return sample_logits(logits, None if greedy else rng, greedy=greedy,
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p)

        def generate(params, tokens, cache, prompt_len, rng):
            logits, cache = spec.prefill_fn(params, tokens, cache, None)
            last = jnp.take_along_axis(logits, (prompt_len - 1)[:, None, None],
                                       axis=1)[:, 0, :]
            first = sample(last, rng)

            def body(carry, _):
                tok, pos, cache, rng = carry
                rng, sub = jax.random.split(rng)
                lg, cache = spec.decode_fn(params, tok, pos, cache)
                nxt = sample(lg, sub)
                return (nxt, pos + 1, cache, rng), tok

            (_, _, cache, _), toks = jax.lax.scan(
                body, (first, prompt_len, cache, rng), None, length=max_new)
            return jnp.moveaxis(toks, 0, 1)

        return jax.jit(generate)

    def generate(self, tokens, max_new_tokens=32, greedy=True, temperature=1.0,
                 top_k=0, top_p=1.0, rng=None):
        """Rollout with the CURRENT training params (reference `generate` :174)."""
        key = (max_new_tokens, greedy, float(temperature), int(top_k),
               float(top_p))
        if self._generate_fn is None or getattr(self, "_gen_key", None) != key:
            self._generate_fn = self._build_generate(max_new_tokens, greedy,
                                                     temperature, top_k, top_p)
            self._gen_key = key
        tokens = jnp.asarray(tokens)
        B, T = tokens.shape
        cache = self._decode_spec.init_cache(B, T + max_new_tokens,
                                             self.compute_dtype)
        prompt_len = jnp.full((B,), T, jnp.int32)
        if rng is None:
            # independent draws per call and per training step
            rng = jax.random.fold_in(
                jax.random.fold_in(self.state.rng, int(self.state.step)),
                self.generate_count)
        t0 = time.perf_counter()
        out = self._generate_fn(self.state.params, tokens, cache, prompt_len, rng)
        # dstpu: ignore[DT001]: rollout API boundary — RLHF consumers take host tokens, one transfer per generate()
        out = np.asarray(jax.device_get(out))
        self.latency = time.perf_counter() - t0     # the fetch was the fence
        self.generate_count += 1
        return out


def make_gpt_hybrid_engine(cfg, ds_config, name="gpt-hybrid", seed=0, mesh=None):
    """Convenience: GPT model wired for RLHF-style train+generate."""
    from deepspeed_tpu.models.gpt import make_gpt_model, make_gpt_decode_model
    model = make_gpt_model(cfg=cfg, name=name, seed=seed)
    engine = HybridEngine(model, ds_config, mesh=mesh)
    decode = make_gpt_decode_model(cfg=cfg, name=name, params=model.params)
    engine.set_decode_spec(decode)
    return engine
