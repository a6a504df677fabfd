"""What the K-EXAONE tests share (`test_exaone_moe.py`: the serving path;
`test_exaone_moe_layers.py`: the layer's pieces): a small configuration, its
parameters, a serving engine on it, and the float32 reference
(`benchmark/references/exaone_moe.py`, which imports nothing of the
program)."""

import importlib.util
import os

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.models import exaone_moe as em

L, G = em.WINDOW, em.FULL


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "references", "exaone_moe.py")
    spec = importlib.util.spec_from_file_location("ref_exaone_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()


def _cfg(dtype=jnp.float32, held=(0, 16), periods=1, **over):
    layers = (L,) + (L, L, L, G) * periods
    kw = dict(vocab_size=128, n_layer=len(layers), n_head=4, n_kv_head=2,
              d_model=32, attn_head_dim=16, d_ff=16, d_ff_dense=48,
              max_seq_len=256, sliding_window=8, rope_theta=1e6,
              norm_eps=1e-5, tie_embeddings=False, num_experts=16, top_k=4,
              norm_topk_prob=True, routed_scaling_factor=2.5,
              experts_held=held, layer_types=layers,
              mlp_layer_types=(em.DENSE,) + (em.SPARSE,) * (len(layers) - 1),
              pattern_period=4, window_block=8, dtype=dtype,
              use_flash_attention=False)
    kw.update(over)
    return em.ExaoneMoEConfig(**kw)


def _arch(cfg, held="cfg"):
    return ref.Arch(
        layer_types=cfg.layer_types, mlp_layer_types=cfg.mlp_layer_types,
        n_head=cfg.n_head, n_kv_head=cfg.n_kv_head, head_dim=cfg.head_dim,
        d_model=cfg.d_model, window=cfg.sliding_window,
        num_experts=cfg.num_experts,
        experts_held=cfg.experts_held if held == "cfg" else held,
        top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        pattern_period=cfg.pattern_period, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps)


def _params(cfg, seed=0, dtype=jnp.float32):
    return em.exaone_moe_init_fn(cfg, dtype=dtype)(jax.random.PRNGKey(seed))


def _serving(cfg, params, dtype="float32", one_device=False, **knobs):
    mesh_mod.clear_mesh()
    if one_device:      # else `init_inference` spans every device there is
        mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    spec = em.make_exaone_moe_decode_model(cfg, params=params, name="tiny")
    engine = deepspeed_tpu.init_inference(
        spec, config={"dtype": dtype, "kv_cache_dtype": dtype, "greedy": True,
                      "kv_block_size": 16, "max_out_tokens": 256})
    knobs = {"max_slots": 3, "max_context": 256, "prefill_chunk": 16,
             "num_kv_blocks": 40, "decode_steps_per_sync": 3, **knobs}
    return engine, engine.serving(**knobs)
