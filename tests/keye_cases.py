"""What the Keye-VL-2.0 tests share (`test_keye_vl2.py`): a small
configuration with the published RATIOS (8 query heads over 2 key-value
heads, 4 index heads of half a head's width, `topk` a fraction of the
context so the selection bites), its parameters, a serving engine on it, and
the float32 reference (`benchmark/references/keye_vl2.py`, which imports
nothing of the program)."""

import importlib.util
import os

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.models import keye_vl2 as kv2


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "references", "keye_vl2.py")
    spec = importlib.util.spec_from_file_location("ref_keye_vl2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _reference()

TOPK = 12


def _cfg(dtype=jnp.float32, held=(0, 16), **over):
    kw = dict(vocab_size=128, n_layer=3, n_head=8, n_kv_head=2, d_model=32,
              attn_head_dim=16, d_ff=16, max_seq_len=256, rope_theta=1e7,
              norm_eps=1e-6, tie_embeddings=False, num_experts=16, top_k=4,
              norm_topk_prob=True, experts_held=held, index_n_head=4,
              index_head_dim=8, index_topk=TOPK, dtype=dtype,
              use_flash_attention=False)
    kw.update(over)
    return kv2.KeyeVL2Config(**kw)


def _arch(cfg, held="cfg", **over):
    kw = dict(n_layer=cfg.n_layer, n_head=cfg.n_head,
              n_kv_head=cfg.n_kv_head, head_dim=cfg.head_dim,
              d_model=cfg.d_model, index_heads=cfg.index_n_head,
              index_dim=cfg.index_head_dim, topk=cfg.index_topk,
              num_experts=cfg.num_experts,
              experts_held=cfg.experts_held if held == "cfg" else held,
              top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
              rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps)
    kw.update(over)
    return ref.Arch(**kw)


def _params(cfg, seed=0, dtype=jnp.float32):
    """The family's initializer, with the fused QKV matrices and the
    indexer's ten times the zoo's 0.02: at width 32 the scores are then of
    order 1 (at 0.02 every softmax is uniform, every index score a rounding
    of zero, and a wrong selection would move no logit)."""
    params = kv2.keye_vl2_init_fn(cfg, dtype=dtype, embedding_std=1.0)(
        jax.random.PRNGKey(seed))
    sharp = ("attn_qkv_w", "idx_q_w", "idx_k_w", "idx_w_w")
    return {**params,
            "period": [{k: v * 10 if k in sharp else v
                        for k, v in tree.items()}
                       for tree in params["period"]]}


def _serving(cfg, params, dtype="float32", one_device=False, block=16,
             **knobs):
    mesh_mod.clear_mesh()
    if one_device:      # else `init_inference` spans every device there is
        mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    spec = kv2.make_keye_vl2_decode_model(cfg, params=params, name="tiny")
    engine = deepspeed_tpu.init_inference(
        spec, config={"dtype": dtype, "kv_cache_dtype": dtype, "greedy": True,
                      "kv_block_size": block,
                      "max_out_tokens": knobs.get("max_context", 256)})
    knobs = {"max_slots": 3, "max_context": 256, "prefill_chunk": block,
             "num_kv_blocks": 40, "decode_steps_per_sync": 3, **knobs}
    return engine, engine.serving(**knobs)
