"""What the MiMo-V2-Flash tests share (`test_mimo_v2_flash.py`): a small
configuration with the published RATIOS (keys 48 wide beside values of 32, a
third of a key rotated, 2 KV heads in the full layers and 4 in the window
layers, a period of six), its parameters, a serving engine on it, and the
float32 reference (`benchmark/references/mimo_v2_flash.py`, which imports
nothing of the program)."""

import importlib.util
import os

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.models import mimo_v2_flash as mm


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "references", "mimo_v2_flash.py")
    spec = importlib.util.spec_from_file_location("ref_mimo_v2_flash", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()

# the published order, cut like the benchmark's: full, window x 4, then whole
# periods of `full, window x 5`
PATTERN = (0, 1, 1, 1, 1) + (0, 1, 1, 1, 1, 1)


def _cfg(dtype=jnp.float32, held=(0, 16), pattern=PATTERN, **over):
    kw = dict(vocab_size=128, n_layer=len(pattern), n_head=8, n_kv_head=2,
              swa_n_kv_head=4, d_model=32, attn_head_dim=48,
              attn_value_dim=32, rotary_pct=0.334, attn_value_scale=0.707,
              d_ff=16, d_ff_dense=48, max_seq_len=256, sliding_window=8,
              rope_theta=5e6, swa_rope_theta=1e4, norm_eps=1e-5,
              tie_embeddings=False, num_experts=16, top_k=4,
              norm_topk_prob=True, experts_held=held,
              layer_types=mm.layer_types(pattern), pattern_period=6,
              window_block=8, dtype=dtype, use_flash_attention=False)
    kw.update(over)
    return mm.MiMoV2FlashConfig(**kw)


def _arch(cfg, held="cfg", **over):
    kw = dict(
        layer_types=cfg.layer_types, mlp_layer_types=cfg.mlp_layer_types,
        n_head=cfg.n_head,
        kv_heads=((ref.FULL, cfg.n_kv_head), (ref.WINDOW, cfg.swa_n_kv_head)),
        thetas=((ref.FULL, cfg.rope_theta),
                (ref.WINDOW, cfg.swa_rope_theta)),
        sinks=tuple(k for k, on in ((ref.WINDOW, cfg.swa_sink),
                                    (ref.FULL, cfg.full_sink)) if on),
        head_dim=cfg.head_dim, value_dim=cfg.value_dim,
        rotary_dims=int(cfg.rotary_pct * cfg.head_dim) // 2 * 2,
        value_scale=cfg.attn_value_scale, d_model=cfg.d_model,
        window=cfg.sliding_window, num_experts=cfg.num_experts,
        experts_held=cfg.experts_held if held == "cfg" else held,
        top_k=cfg.top_k, pattern_period=cfg.pattern_period,
        norm_eps=cfg.norm_eps)
    kw.update(over)
    return ref.Arch(**kw)


def _params(cfg, seed=0, dtype=jnp.float32):
    """The family's initializer, with the fused QKV matrices ten times the
    zoo's 0.02: at width 32 the scores are then of order 1 (at 0.02 every
    softmax is uniform and neither a rotary base nor a sink moves a logit)."""
    params = mm.mimo_v2_flash_init_fn(cfg, dtype=dtype, embedding_std=1.0)(
        jax.random.PRNGKey(seed))

    def sharpen(tree):
        return {k: v * 10 if k == "attn_qkv_w" else v
                for k, v in tree.items()}
    return {**params, "prologue": [sharpen(t) for t in params["prologue"]],
            "period": [sharpen(t) for t in params["period"]]}


def _serving(cfg, params, dtype="float32", one_device=False, block=16,
             **knobs):
    mesh_mod.clear_mesh()
    if one_device:      # else `init_inference` spans every device there is
        mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    spec = mm.make_mimo_v2_flash_decode_model(cfg, params=params, name="tiny")
    engine = deepspeed_tpu.init_inference(
        spec, config={"dtype": dtype, "kv_cache_dtype": dtype, "greedy": True,
                      "kv_block_size": block,
                      "max_out_tokens": knobs.get("max_context", 256)})
    knobs = {"max_slots": 3, "max_context": 256, "prefill_chunk": block,
             "num_kv_blocks": 40, "decode_steps_per_sync": 3, **knobs}
    return engine, engine.serving(**knobs)
