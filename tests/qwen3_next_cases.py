"""What the Qwen3-Next tests share (`test_qwen3_next.py`: the serving path;
`test_qwen3_next_layers.py`: the halves' pieces): a small configuration, its
parameters, a serving engine on it, and the float32 reference
(`benchmark/references/qwen3_next.py`, which imports nothing of the
program)."""

import importlib.util
import os

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.models import qwen3_next as qn


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "references", "qwen3_next.py")
    spec = importlib.util.spec_from_file_location("ref_qwen3_next", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()

# the published period once and a layer more: D D D * D
LAYERS = qn.layer_types(5, 4)


def _cfg(dtype=jnp.float32, held=(0, 16), layers=LAYERS, **over):
    kw = dict(vocab_size=128, pattern=tuple(qn.BLOCKS[t] for t in layers),
              n_head=4, n_kv_head=2, d_model=32, attn_head_dim=16, d_ff=24,
              shared_d_ff=24, max_seq_len=256, num_experts=16, top_k=4,
              experts_held=held, gdn_key_heads=2, gdn_value_heads=4,
              gdn_key_dim=8, gdn_value_dim=16, conv_kernel=4, chunk_size=8,
              rope_theta=100.0, dtype=dtype, use_flash_attention=False)
    kw.update(over)
    return qn.Qwen3NextConfig(**kw)


def _arch(cfg, held="cfg", **over):
    kw = dict(
        blocks=tuple(cfg.pattern), runs=ref.pattern_runs(cfg.pattern),
        d_model=cfg.d_model, n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
        head_dim=cfg.head_dim,
        rotary_dims=int(cfg.rotary_pct * cfg.head_dim) // 2 * 2,
        rope_theta=cfg.rope_theta, key_heads=cfg.gdn_key_heads,
        value_heads=cfg.gdn_value_heads, key_dim=cfg.gdn_key_dim,
        value_dim=cfg.gdn_value_dim, conv_kernel=cfg.conv_kernel,
        num_experts=cfg.num_experts,
        experts_held=cfg.experts_held if held == "cfg" else held,
        top_k=cfg.top_k, norm_eps=cfg.norm_eps)
    kw.update(over)
    return ref.Arch(**kw)


def _params(cfg, seed=0, dtype=jnp.float32, **ranges):
    return qn.qwen3_next_init_fn(cfg, dtype=dtype, **ranges)(
        jax.random.PRNGKey(seed))


def _serving(cfg, params, dtype="float32", one_device=False, **knobs):
    mesh_mod.clear_mesh()
    if one_device:      # else `init_inference` spans every device there is
        mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    spec = qn.make_qwen3_next_decode_model(cfg, params=params, name="tiny")
    engine = deepspeed_tpu.init_inference(
        spec, config={"dtype": dtype, "kv_cache_dtype": dtype, "greedy": True,
                      "kv_block_size": 16, "max_out_tokens": 256})
    knobs = {"max_slots": 3, "max_context": 256, "prefill_chunk": 16,
             "num_kv_blocks": 40, "decode_steps_per_sync": 3, **knobs}
    return engine, engine.serving(**knobs)
