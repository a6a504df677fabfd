"""GLM-4.7-Flash — the family `model_type: glm4_moe_lite` on the paged
serving path: LATENT attention (MLA) in every layer, a leading dense layer,
then routed layers with a sigmoid router and one shared expert, served as
ONE CHIP'S SHARE of an expert-parallel deployment.

A layer, as `benchmark/references/glm4_moe_lite.py` computes it in float32
(pre-norm, eps 1e-5, no biases):

    h = x + MLA(RMSNorm(x))                    `models/mla.py`
    y = h + MLP(RMSNorm(h))
    dense MLP (layer 0): SwiGLU of width `d_ff_dense`
    sparse MLP: sigmoid scores in float32, the `top_k` largest `score +
                bias`, weights renormalised over the chosen and scaled; the
                routed experts' weighted sum + one shared SwiGLU expert

Everything but the attention half is K-EXAONE's family, and this file is
data over `models/exaone_moe.py`: its layer plan (prologue + scanned
periods), its expert half (`_sparse_mlp`, `routed_experts(held=)`), its paged
programs — with every layer of the LATENT kind (`ATTN_KINDS`), whose cache is
one entry a token a layer (`[c | k_r]`: `kv_lora_rank + qk_rope_head_dim`
values, stored in whole lane tiles) in allocator blocks. THE EXPERT SHARE is
K-EXAONE's too: the router routes over all `num_experts`, this chip holds
`experts_held = (first, count)`, and what the others would add is left out,
here and in the reference alike.

What a latent pool takes of the scheduler: prefix caching and block
transplant (its blocks are the allocator's, content-immutable once full);
what it refuses, by name: the int8 pool (an entry has no scale leaves) and
speculative decoding (no verify program). Not here: training, the
contiguous-cache `generate()` path, the multi-token-prediction layer
(`num_nextn_predict_layers`: it proposes tokens; the main model's logits do
not depend on it).
"""

import dataclasses

from deepspeed_tpu.models.exaone_moe import (DENSE, LATENT, SPARSE,
                                             ExaoneMoEConfig,
                                             exaone_moe_forward,
                                             exaone_moe_init_fn,
                                             make_exaone_moe_decode_model)


@dataclasses.dataclass
class Glm4MoeLiteConfig(ExaoneMoEConfig):
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    first_k_dense_replace: int = 1      # leading layers with a dense MLP

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = (LATENT,) * self.n_layer
        if not self.mlp_layer_types:
            dense = min(self.first_k_dense_replace, self.n_layer)
            self.mlp_layer_types = (DENSE,) * dense \
                + (SPARSE,) * (self.n_layer - dense)
        # a head's query-key width: the scores' scale is 1 / sqrt of it
        self.attn_head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        super().__post_init__()
        # the family is pre-norm and norms its low-rank latents, not heads
        self.post_norm = self.qk_norm_per_head = False


glm4_moe_lite_init_fn = exaone_moe_init_fn
glm4_moe_lite_forward = exaone_moe_forward


def glm4_moe_lite_cache_identity(cfg: Glm4MoeLiteConfig,
                                 name: str = "") -> str:
    return (f"glm4_moe_lite:{name}|{cfg.n_layer}|{cfg.d_model}|{cfg.n_head}|"
            f"{cfg.q_lora_rank}|{cfg.kv_lora_rank}|{cfg.qk_nope_head_dim}|"
            f"{cfg.qk_rope_head_dim}|{cfg.v_head_dim}|"
            f"{','.join(t[0] for t in cfg.mlp_layer_types)}|"
            f"{cfg.num_experts}|{cfg.experts_held}|{cfg.top_k}|"
            f"{cfg.routed_scaling_factor}|{cfg.rope_theta}|{cfg.norm_eps}")


def make_glm4_moe_lite_decode_model(cfg: Glm4MoeLiteConfig, params=None,
                                    name="glm-4.7-flash", seed=0):
    """The paged serving contract (`DecodeModelSpec`) of the family:
    `exaone_moe.py::make_exaone_moe_decode_model` on a pool of the latent
    kind (its programs take the block tables bare)."""
    return make_exaone_moe_decode_model(
        cfg, params, name, seed, family="glm4_moe_lite",
        fingerprint=glm4_moe_lite_cache_identity(cfg, name))
