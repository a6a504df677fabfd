"""What the GLM-4.7-Flash tests share (`test_glm4_moe_lite.py`): a small
configuration, its parameters, a serving engine on it, and the float32
reference (`benchmark/references/glm4_moe_lite.py`, which imports nothing of
the program)."""

import importlib.util
import os

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.models import glm4_moe_lite as gm


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "references", "glm4_moe_lite.py")
    spec = importlib.util.spec_from_file_location("ref_glm4_moe_lite", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def _cfg(dtype=jnp.float32, held=(0, 16), layers=3, **over):
    kw = dict(vocab_size=128, n_layer=layers, n_head=4, d_model=32, d_ff=16,
              d_ff_dense=48, max_seq_len=256, rope_theta=1e6, norm_eps=1e-5,
              tie_embeddings=False, num_experts=16, top_k=4,
              norm_topk_prob=True, routed_scaling_factor=1.8,
              experts_held=held, q_lora_rank=24, kv_lora_rank=32,
              qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
              dtype=dtype, use_flash_attention=False)
    kw.update(over)
    return gm.Glm4MoeLiteConfig(**kw)


def _arch(cfg, held="cfg", **rounding):
    return ref.Arch(
        n_layer=cfg.n_layer, dense_layers=cfg.first_k_dense_replace,
        n_head=cfg.n_head, d_model=cfg.d_model, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        num_experts=cfg.num_experts,
        experts_held=cfg.experts_held if held == "cfg" else held,
        top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps, **rounding)


def _params(cfg, seed=0, dtype=jnp.float32):
    return gm.glm4_moe_lite_init_fn(cfg, dtype=dtype)(jax.random.PRNGKey(seed))


def _serving(cfg, params, dtype="float32", one_device=False, block=16,
             **knobs):
    mesh_mod.clear_mesh()
    if one_device:      # else `init_inference` spans every device there is
        mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    spec = gm.make_glm4_moe_lite_decode_model(cfg, params=params, name="tiny")
    engine = deepspeed_tpu.init_inference(
        spec, config={"dtype": dtype, "kv_cache_dtype": dtype, "greedy": True,
                      "kv_block_size": block,
                      "max_out_tokens": knobs.get("max_context", 256)})
    knobs = {"max_slots": 3, "max_context": 256, "prefill_chunk": block,
             "num_kv_blocks": 40, "decode_steps_per_sync": 3, **knobs}
    return engine, engine.serving(**knobs)
