"""Shared by the drivers of this repo's GPT family (`models/gpt.py`): a
published `config.json` -> the program's `GPTConfig`, and weights made on the
device from the seed in one jitted call."""

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import GPTConfig, gpt_init_fn


def gpt_config(cfg, max_seq_len=None):
    """The program's configuration for a published GPT-NeoX or Mistral
    `config.json`. Every width is the file's; nothing is defaulted."""
    common = dict(vocab_size=cfg["vocab_size"],
                  n_layer=cfg["num_hidden_layers"],
                  n_head=cfg["num_attention_heads"],
                  d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
                  max_seq_len=max_seq_len or cfg["max_position_embeddings"],
                  use_rotary=True,
                  tie_embeddings=cfg["tie_word_embeddings"],
                  dtype=jnp.bfloat16)
    if cfg["model_type"] == "gpt_neox":
        return GPTConfig(rotary_pct=cfg["rotary_pct"],
                         rope_theta=float(cfg["rotary_emb_base"]),
                         norm_eps=cfg["layer_norm_eps"], activation="gelu",
                         parallel_residual=cfg["use_parallel_residual"],
                         **common)
    if cfg["model_type"] == "mistral":
        if cfg["sliding_window"] is not None:
            raise ValueError("this driver serves full attention only")
        return GPTConfig(n_kv_head=cfg["num_key_value_heads"],
                         rope_theta=float(cfg["rope_theta"]),
                         norm_eps=cfg["rms_norm_eps"], use_swiglu=True,
                         use_rmsnorm=True, **common)
    raise ValueError(f"model_type {cfg['model_type']!r} is not of this family")


def seed_key(seed):
    """A PRNG key from any whole number up to 2**32 and beyond (a key seeded
    directly takes 32 signed bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)),
                              seed // (2**31 - 1))


def device_weights(gcfg, seed, dtype, device):
    """The whole parameter tree, made on `device` in ONE jitted call from the
    seed, in the type it is served in (not leaf by leaf, not on the host)."""
    init = jax.jit(gpt_init_fn(gcfg, dtype=dtype),
                   out_shardings=jax.sharding.SingleDeviceSharding(device))
    return init(seed_key(seed))
