"""Shared tiny serving-engine factory for the multi-process fabric.

The kill -9 soak, `bin/dstpu_pool`'s demo config and
the in-thread transport tests all need the SAME engine on both sides of a
process boundary: parameters are seeded (`seed`), so a replica subprocess
built from this factory is bit-identical to the parent's oracle engine —
greedy decoding then makes token parity a hard equality, not a tolerance.

This lives in `deepspeed_tpu.testing` (shipped with the package, like
`chaos.py`) because the replica-server child resolves the factory by
import path: ``--factory deepspeed_tpu.testing.fabric:tiny_serving_engine``.
"""

from typing import Any, Dict

TINY_DEFAULTS: Dict[str, Any] = dict(
    n_layer=2, n_head=4, d_model=64, max_seq_len=256, vocab_size=256)
BS = 16   # kv_block_size == prefill_chunk, the test_router convention


def tiny_serving_engine(seed: int = 0, max_slots: int = 2,
                        max_context: int = 96, telemetry=False,
                        **model_overrides):
    """A fresh `ServingEngine` over a tiny seeded fp32 GPT on a 1-chip
    mesh. Every kwarg is JSON-safe, so the whole recipe ships through
    `dstpu_replica --kwargs`.

    `telemetry` is either a bool (True = bare enabled registry) or a full
    telemetry config dict — the pod-observability tests pass
    ``{"enabled": True, "tracing": True, "output_path": <per-replica dir>}``
    so each subprocess replica records (and spools) into its OWN dir."""
    import jax.numpy as jnp

    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.config.core import MeshConfig
    from deepspeed_tpu.inference.engine import init_inference
    from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model

    mk = dict(TINY_DEFAULTS)
    mk.update(model_overrides)
    cfg = GPTConfig(dtype=jnp.float32, remat=False, **mk)
    if mesh_mod._CURRENT_MESH is None:
        mesh_mod.init_mesh(MeshConfig(data=1, tensor=1, sequence=1,
                                      expert=1, pipe=1))
    spec = make_gpt_decode_model(cfg=cfg, name="fabric-tiny", seed=seed)
    inf_cfg: Dict[str, Any] = {
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": BS, "max_out_tokens": 64}
    if isinstance(telemetry, dict):
        inf_cfg["telemetry"] = dict(telemetry)
    elif telemetry:
        inf_cfg["telemetry"] = {"enabled": True}
    engine = init_inference(model=spec, config=inf_cfg)
    return engine.serving(max_slots=max_slots, max_context=max_context,
                          prefill_chunk=BS, enable_prefix_caching=True)


def tiny_oracle(prompts, news, seed: int = 0, **model_overrides):
    """Single-engine greedy reference completions for `prompts` — the
    parity baseline every fabric test compares the pool against."""
    import numpy as np

    serving = tiny_serving_engine(seed=seed, **model_overrides)
    refs = [serving.engine.generate(np.asarray(p)[None], max_new_tokens=n,
                                    stop_on_eos=False)[0]
            for p, n in zip(prompts, news)]
    return refs
