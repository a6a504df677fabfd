"""MiMo-V2-Flash — the family `model_type: mimo_v2_flash` on the paged
serving path: window and full attention layers mixed, EACH KIND WITH ITS OWN
KV HEADS AND ROTARY BASE, keys wider than values, a learned sink logit in the
window layers' softmax, a leading dense layer, then routed layers with a
sigmoid router and no shared expert, served as ONE CHIP'S SHARE of an
expert-parallel deployment.

A layer, as `benchmark/references/mimo_v2_flash.py` computes it in float32
(pre-norm, eps 1e-5, no biases):

    h = x + Attn_kind(RMSNorm(x));  y = h + MLP(RMSNorm(h))
    q [H x 192], k [Hkv x 192], v [Hkv x 128] * 0.707
    rotary on the first int(192 * 0.334) = 64 columns of q and k
    s_ij = q_i . k_j / sqrt(192), causal; window kind: only i - j < 128
    window kind: p_ij = exp(s_ij) / (exp(sink_h) + sum_j' exp(s_ij'))
    full kind:   p_ij = softmax_j(s_ij)
    full:   4 KV heads, theta 5,000,000      window: 8 KV heads, theta 10,000
    dense MLP (layer 0): SwiGLU of width `d_ff_dense`
    sparse MLP: sigmoid scores in float32, the `top_k` largest `score +
                bias`, weights renormalised over the chosen; the routed
                experts' weighted sum, NO shared expert

This file is data over `models/exaone_moe.py`: its layer plan (prologue +
scanned periods), its expert half, its paged programs on a pool of two kinds.
What differs is said in `kind_values` — what each kind's layers are traced
with — and in four `GPTConfig` fields (`attn_value_dim`, `attn_value_scale`,
`attn_sink`, `rotary_pct`); the pool's leaves and the parameter tree follow
from them (`exaone_moe.py::AttnKind`). A 192-wide key on a 128-lane chip is
kept in two leaves (`ops/pallas/kv_pool.py::kv_leaf_shapes`): an entry is
stored at exactly the model's 320 values a KV head.

Not here: training, the contiguous-cache `generate()` path, the three
multi-token-prediction layers (they propose tokens; the main model's logits
do not depend on them), the int8 pool, prefix caching and block transplant
on a two-kind pool (`ServingEngine` refuses them with the reason).
"""

import dataclasses

from deepspeed_tpu.models.exaone_moe import (DENSE, FULL, SPARSE, WINDOW,
                                             ExaoneMoEConfig,
                                             exaone_moe_forward,
                                             exaone_moe_init_fn,
                                             make_exaone_moe_decode_model)


@dataclasses.dataclass
class MiMoV2FlashConfig(ExaoneMoEConfig):
    # `n_kv_head`, `rope_theta`: the FULL layers'; the published `swa_*` keys
    # are the window layers'
    swa_n_kv_head: int = 8
    swa_rope_theta: float = 10000.0
    swa_sink: bool = True           # `add_swa_attention_sink_bias`
    full_sink: bool = False         # `add_full_attention_sink_bias`
    num_shared_experts: int = 0

    def __post_init__(self):
        if not self.mlp_layer_types:    # the published order: layer 0 dense
            self.mlp_layer_types = (DENSE,) + (SPARSE,) * (self.n_layer - 1)
        # both kinds rotate; each has its heads, its base and its sink
        self.kind_values = {
            FULL: dict(sliding_window=None, attn_sink=self.full_sink),
            WINDOW: dict(n_kv_head=self.swa_n_kv_head,
                         rope_theta=self.swa_rope_theta,
                         attn_sink=self.swa_sink)}
        super().__post_init__()
        assert self.n_head % self.swa_n_kv_head == 0
        # the family is pre-norm and norms no head
        self.post_norm = self.qk_norm_per_head = False


mimo_v2_flash_init_fn = exaone_moe_init_fn
mimo_v2_flash_forward = exaone_moe_forward


def layer_types(hybrid_layer_pattern):
    """The published `hybrid_layer_pattern` (0 full, 1 window) as this
    package's layer types."""
    return tuple(WINDOW if flag else FULL for flag in hybrid_layer_pattern)


def mlp_layer_types(moe_layer_freq):
    """The published `moe_layer_freq` (0 dense, 1 routed) likewise."""
    return tuple(SPARSE if flag else DENSE for flag in moe_layer_freq)


def mimo_v2_flash_cache_identity(cfg: MiMoV2FlashConfig,
                                 name: str = "") -> str:
    return (f"mimo_v2_flash:{name}|{cfg.n_layer}|{cfg.d_model}|{cfg.n_head}|"
            f"{cfg.n_kv_head}|{cfg.swa_n_kv_head}|{cfg.head_dim}|"
            f"{cfg.value_dim}|{cfg.rotary_pct}|{cfg.attn_value_scale}|"
            f"{cfg.sliding_window}|{cfg.swa_sink}|{cfg.full_sink}|"
            f"{','.join(t[0] for t in cfg.layer_types)}|"
            f"{','.join(t[0] for t in cfg.mlp_layer_types)}|"
            f"{cfg.num_experts}|{cfg.experts_held}|{cfg.top_k}|"
            f"{cfg.rope_theta}|{cfg.swa_rope_theta}|{cfg.norm_eps}")


def make_mimo_v2_flash_decode_model(cfg: MiMoV2FlashConfig, params=None,
                                    name="mimo-v2-flash", seed=0):
    """The paged serving contract (`DecodeModelSpec`) of the family:
    `exaone_moe.py::make_exaone_moe_decode_model` on a pool of two kinds,
    each with its own heads and leaves."""
    return make_exaone_moe_decode_model(
        cfg, params, name, seed, family="mimo_v2_flash",
        fingerprint=mimo_v2_flash_cache_identity(cfg, name))
