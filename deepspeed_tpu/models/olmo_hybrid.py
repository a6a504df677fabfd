"""Olmo-Hybrid — the hybrid family `model_type: olmo_hybrid` on the TRAINING
path (`deepspeed_tpu.initialize(model=make_olmo_hybrid_model(cfg,
abstract=True), ...)`, `engine.train_batch`): Gated DeltaNet with negative
eigenvalues in three layers of four, full attention WITHOUT positions in the
fourth, a dense SwiGLU in every layer, the Olmo-2 / Olmo-3 norm order.

A layer is two halves of `models/hybrid.py`'s loop, as
`benchmark/references/olmo_hybrid.py` computes them in float32:

    h      = x + RMSNorm(mixer(x))                          "D" or "*"
    x'     = h + RMSNorm(SwiGLU(h))                         "F"
    logits = RMSNorm(x_L) W_head^T                          (untied head)

    D  Gated DeltaNet, `hybrid.py::_gdn_half`: one key head a value head (H
       = G), keys K wide and values V wide, `beta = 2 sigmoid(b)`
       (`linear_allow_neg_eigval`: `I - beta k k^T` has the eigenvalue `1 -
       beta` in (-1, 1)); a K x V float32 state a head, through
       `ops/pallas/gdn.py::gdn_chunk_scan` and its own backward
    *  attention, `gpt.py::_attn_half`: RMSNorm over the WHOLE projected
       query and key (`qk_norm`), NO rotation (`rope_theta` null), causal
    F  `gpt.py::_mlp`'s gated feed-forward

The halves read the stream un-normed and their OUTPUT is normed
(`post_norm`). Every norm's scale is the value that multiplies. The loop, the
halves, the loss and the parameter layout are `hybrid.py`'s and `gpt.py`'s;
this file is the family's data.

Not here: a decode spec (no cell would guard it; the paged programs refuse
`post_norm`), packed documents, sequence parallelism (`hybrid.py` says why).
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.hybrid import (HybridConfig, hybrid_forward,
                                         hybrid_init_fn, hybrid_loss,
                                         hybrid_param_specs, mixer_shapes)
from deepspeed_tpu.runtime.engine import ModelSpec

# a published `layer_types` entry -> the layer's two halves
BLOCKS = {"linear_attention": "DF", "full_attention": "*F"}


@dataclasses.dataclass
class OlmoHybridConfig(HybridConfig):
    pattern: tuple = ()                 # a block a layer: `BLOCKS`' values
    chunk_size: int = 64                # positions a chunk of the delta rule
    norm_eps: float = 1e-6

    def __post_init__(self):
        # what the family fixes beside `HybridConfig`'s: gated MLPs, an untied
        # head, the halves' norm on their output, q and k normed over the
        # whole projection, no positions anywhere, negative eigenvalues
        self.use_swiglu = self.post_norm = self.qk_norm = True
        self.tie_embeddings = self.rotary_attention = False
        super().__post_init__()


def olmo_hybrid_config(cfg, dtype=jnp.bfloat16, **over):
    """The published `config.json` keys (a dict) -> `OlmoHybridConfig`;
    nothing is defaulted. `rope_parameters.rope_theta` must be null: the
    attention layers carry no positions."""
    if cfg["rope_parameters"]["rope_theta"] is not None \
            or cfg["attention_bias"] or cfg["hidden_act"] != "silu":
        raise ValueError("olmo_hybrid: this family is built without rotation "
                         "or attention biases, with silu")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types names num_hidden_layers layers")
    kw = dict(
        vocab_size=cfg["vocab_size"],
        pattern=tuple(BLOCKS[t] for t in cfg["layer_types"]),
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        gdn_key_heads=cfg["linear_num_key_heads"],
        gdn_value_heads=cfg["linear_num_value_heads"],
        gdn_key_dim=cfg["linear_key_head_dim"],
        gdn_value_dim=cfg["linear_value_head_dim"],
        gdn_beta_scale=2.0 if cfg["linear_allow_neg_eigval"] else 1.0,
        conv_kernel=cfg["linear_conv_kernel_dim"],
        norm_eps=cfg["rms_norm_eps"], dtype=dtype)
    kw.update(over)
    return OlmoHybridConfig(**kw)


def _layer_shapes(cfg: OlmoHybridConfig, kind, router_std=0.02):
    """One half's leaves (`hybrid.py::mixer_shapes`' form): the family has
    no expert half, so they are the loop's own."""
    return mixer_shapes(cfg, kind)


def olmo_hybrid_init_fn(cfg: OlmoHybridConfig, dtype=jnp.float32):
    """`hybrid.py::hybrid_init_fn` of the family's leaves: `runs`, `wte`,
    `lm_head`, `lnf_scale`."""
    return hybrid_init_fn(cfg, _layer_shapes, dtype)


def olmo_hybrid_forward(params, tokens, cfg: OlmoHybridConfig):
    """tokens [B, T] -> logits [B, T, V] (`hybrid.py::hybrid_forward`)."""
    return hybrid_forward(params, tokens, cfg)


def make_olmo_hybrid_model(cfg: OlmoHybridConfig, name="olmo-hybrid", seed=0,
                           abstract=False) -> ModelSpec:
    """ModelSpec for the training engine. `abstract=True`: `init_fn` in
    place of parameters, so that the engine makes each leaf in its ZeRO
    shard, in the type it trains in."""
    init = olmo_hybrid_init_fn(cfg, dtype=cfg.dtype)
    return ModelSpec(
        loss_fn=partial(hybrid_loss, cfg=cfg),
        params=None if abstract else init(jax.random.PRNGKey(seed)),
        init_fn=init if abstract else None, arch_cfg=cfg,
        apply_fn=partial(olmo_hybrid_forward, cfg=cfg),
        param_specs=hybrid_param_specs(cfg, _layer_shapes), name=name)
