"""SDAR-MoE — the family `model_type: sdar_moe` (SDAR-30B-A3B-Chat) on the
paged serving path: the Qwen3-MoE layer under a BLOCK-CAUSAL mask, generated
by DIFFUSION OVER BLOCKS.

A layer, as `benchmark/references/sdar_moe.py` computes it in float32
(pre-norm, eps 1e-6, no biases; every layer alike):

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))
    q [H x hd], k, v [Hkv x hd]; RMSNorm over EACH head's hd columns of q
    and of k (one scale vector the heads share); rotary on all hd columns
    s_ij = q_i . k_j / sqrt(hd), kept iff j // B <= i // B: causal over
    blocks of B positions, bidirectional inside one
    MoE: p = softmax(h W_r) in float32, the `top_k` largest, renormalised
         to sum 1; sum_e p_e W_down,e (silu(h W_gate,e) * (h W_up,e)); no
         shared expert, no dense layer

and the generator (`inference/engine.py::BlockDiffusion`): a block of B
positions starts as mask tokens, up to S denoise forwards unmask its rows by
confidence, one commit forward writes its K/V; a masked row's own logits
predict its token (no shift).

This file is data over `models/moe_gpt.py`: the stacked `moe_freq` 1 form
(experts in `params["blocks"]`, the layer inside `gpt.py::scan_paged` on the
carried pool), `GPTConfig.block_length` for the mask, `qk_norm_per_head`,
and the generator on the `DecodeModelSpec`. The serving scheduler builds the
block-diffusion call from them (`inference/step_programs.py`).

Not here: training of the block-diffusion objective; the contiguous cache's
`generate()` / `forward()` (causal, a token a forward: `InferenceEngine`
refuses a spec with a generator by name); speculative decoding, the int8
pool, the prefix cache and block transplant under this generator
(`ServingEngine` refuses them with the reason).
"""

import jax.numpy as jnp

from deepspeed_tpu.inference.engine import BlockDiffusion
from deepspeed_tpu.models.moe_gpt import (MoEGPTConfig,
                                          make_moe_gpt_decode_model,
                                          moe_gpt_init_fn)

# the keys of a published `config.json` that `sdar_moe_config` reads: every
# one is required, nothing is defaulted
PUBLISHED_KEYS = (
    "vocab_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "hidden_size", "moe_intermediate_size",
    "rope_theta", "rms_norm_eps", "tie_word_embeddings", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "hidden_act", "attention_bias",
    "rope_scaling", "decoder_sparse_step", "mlp_only_layers",
    "use_sliding_window")


def sdar_moe_config(cfg, max_seq_len, block_length, dtype=jnp.bfloat16,
                    use_flash_attention=None):
    """The program's configuration for a published SDAR-MoE `config.json`
    (a dict) and the generator's block length. Every width is the file's."""
    if cfg["model_type"] != "sdar_moe":
        raise ValueError(f"model_type {cfg['model_type']!r} is not sdar_moe")
    if cfg["attention_bias"] or cfg["rope_scaling"] is not None \
            or cfg["use_sliding_window"] or cfg["hidden_act"] != "silu" \
            or cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError(
            "sdar_moe is served with every layer routed (decoder_sparse_step "
            "1, no mlp_only_layers), silu experts, no attention bias, no "
            "rope scaling and no sliding window")
    return MoEGPTConfig(
        vocab_size=cfg["vocab_size"], n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_model=cfg["hidden_size"],
        attn_head_dim=cfg["head_dim"], d_ff=cfg["moe_intermediate_size"],
        max_seq_len=max_seq_len, use_rotary=True,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        use_swiglu=True, use_rmsnorm=True, qk_norm_per_head=True,
        tie_embeddings=cfg["tie_word_embeddings"],
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"], moe_freq=1,
        block_length=block_length, use_flash_attention=use_flash_attention,
        dtype=dtype)


def generator(block_length, mask_token_id, denoising_steps=0,
              remasking="low_confidence_dynamic", confidence_threshold=0.9):
    """The family's released generate script as data (`block_length` 4,
    `low_confidence_dynamic` at 0.9 and `mask_token_id` 151669 are its
    settings for SDAR-30B-A3B-Chat; none is in `config.json`)."""
    return BlockDiffusion(block_length, mask_token_id, denoising_steps,
                          remasking, confidence_threshold)


sdar_moe_init_fn = moe_gpt_init_fn


def make_sdar_moe_decode_model(cfg: MoEGPTConfig, generator: BlockDiffusion,
                               params=None, name="sdar-moe", seed=0):
    """The paged serving contract (`DecodeModelSpec`) of the family:
    `moe_gpt.py::make_moe_gpt_decode_model` with the generator."""
    if params is None:
        import jax
        params = sdar_moe_init_fn(cfg, dtype=cfg.dtype)(
            jax.random.PRNGKey(seed))
    return make_moe_gpt_decode_model(cfg, params=params, name=name,
                                     generator=generator)
