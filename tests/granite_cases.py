"""What the Granite 4.0-H tests share (`test_granite_moe_hybrid.py`: the
serving path; `test_granite_moe_hybrid_layers.py`: the halves' pieces): a small
configuration, its parameters, a serving engine on it, and the float32
reference (`benchmark/references/granite_moe_hybrid.py`, which imports
nothing of the program)."""

import importlib.util
import os

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.models import granite_moe_hybrid as gh


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "references", "granite_moe_hybrid.py")
    spec = importlib.util.spec_from_file_location("ref_granite_moe_hybrid",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()

# the published order at a fifth of its period: M M * M M, two halves a layer
LAYERS = ("mamba", "mamba", "attention", "mamba", "mamba")
# the family's four, as published
MULTIPLIERS = dict(scale_attn=0.0078125, embedding_multiplier=12.0,
                   residual_multiplier=0.22, logits_scaling=16.0)


def _cfg(dtype=jnp.float32, held=(0, 16), layers=LAYERS, **over):
    kw = dict(vocab_size=128, pattern=tuple(gh.BLOCKS[t] for t in layers),
              n_head=4, n_kv_head=2, d_model=32, attn_head_dim=16, d_ff=24,
              shared_d_ff=40, max_seq_len=256, norm_eps=1e-5, num_experts=16,
              top_k=4, experts_held=held, mamba_num_heads=8,
              mamba_head_dim=8, ssm_state_size=16, n_groups=1, conv_kernel=4,
              chunk_size=8, dtype=dtype, use_flash_attention=False,
              **MULTIPLIERS)
    kw.update(over)
    return gh.GraniteMoEHybridConfig(**kw)


def _arch(cfg, held="cfg", **over):
    kw = dict(
        blocks=tuple(cfg.pattern), runs=ref.pattern_runs(cfg.pattern),
        d_model=cfg.d_model, n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
        head_dim=cfg.head_dim, mamba_num_heads=cfg.mamba_num_heads,
        mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.n_groups,
        ssm_state_size=cfg.ssm_state_size, conv_kernel=cfg.conv_kernel,
        num_experts=cfg.num_experts,
        experts_held=cfg.experts_held if held == "cfg" else held,
        top_k=cfg.top_k, embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        attention_multiplier=cfg.scale_attn,
        logits_scaling=cfg.logits_scaling, norm_eps=cfg.norm_eps)
    kw.update(over)
    return ref.Arch(**kw)


def _params(cfg, seed=0, dtype=jnp.float32, **ranges):
    return gh.granite_moe_hybrid_init_fn(cfg, dtype=dtype, **ranges)(
        jax.random.PRNGKey(seed))


def _serving(cfg, params, dtype="float32", one_device=False, **knobs):
    mesh_mod.clear_mesh()
    if one_device:      # else `init_inference` spans every device there is
        mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    spec = gh.make_granite_moe_hybrid_decode_model(cfg, params=params,
                                                   name="tiny")
    engine = deepspeed_tpu.init_inference(
        spec, config={"dtype": dtype, "kv_cache_dtype": dtype, "greedy": True,
                      "kv_block_size": 16, "max_out_tokens": 256})
    knobs = {"max_slots": 3, "max_context": 256, "prefill_chunk": 16,
             "num_kv_blocks": 40, "decode_steps_per_sync": 3, **knobs}
    return engine, engine.serving(**knobs)
