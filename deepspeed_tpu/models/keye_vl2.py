"""Keye-VL-2.0 — the language model of `model_type: KeyeVL2`
(Keye-VL-2.0-30B-A3B) on the paged serving path: the Qwen3-MoE block with a
learned SPARSE-ATTENTION INDEXER in every layer (`sa_config`), served as ONE
CHIP'S SHARE of an expert-parallel deployment.

A layer, as `benchmark/references/keye_vl2.py` computes it in float32
(pre-norm, eps 1e-6, no biases; every layer alike):

    h = x + SparseAttn(RMSNorm(x));  y = h + MoE(RMSNorm(h))
    q [H x hd], k, v [Hkv x hd]; RMSNorm over EACH head's hd columns of q
    and of k (one scale vector the heads share); rotary on all hd columns
    the indexer, the selection and the attention over the selected set:
    `models/sparse_attn.py` (Hi heads of d, ONE key head, `topk` a query)
    MoE: p = softmax(h W_r) in float32, the `top_k` largest, renormalised
         to sum 1; the held experts' weighted sum; no shared expert, no
         dense layer

This file is data over `models/exaone_moe.py`: every layer of the SELECTED
kind (`ATTN_KINDS`), every MLP routed, softmax scoring, `experts_held`. The
pool is ONE kind — the full kind's allocator blocks, a position's entry `k`,
`v` and the index key `ik` — so the prefix cache and block transplant take it
as they take any K/V pool.

Not here: training (of the indexer or anything else), the contiguous-cache
`generate()` path, the vision tower (a clip's or a page's tokens arrive as
ids of a long prompt), `mrope_section` beyond text (three equal position
components ARE the ordinary rotary), the int8 pool and speculative decoding
(`ServingEngine` refuses them with the reason).
"""

import dataclasses

from deepspeed_tpu.models.exaone_moe import (SELECTED, SPARSE,
                                             ExaoneMoEConfig,
                                             exaone_moe_forward,
                                             exaone_moe_init_fn,
                                             make_exaone_moe_decode_model)


@dataclasses.dataclass
class KeyeVL2Config(ExaoneMoEConfig):
    index_n_head: int = 16          # `sa_config.indexer_num_heads`
    index_head_dim: int = 64        # `sa_config.indexer_head_dim`
    index_topk: int = 2048          # `sa_config.topk`
    num_shared_experts: int = 0
    router_scoring: str = "softmax"

    def __post_init__(self):
        self.layer_types = (SELECTED,) * self.n_layer
        self.mlp_layer_types = (SPARSE,) * self.n_layer
        # every layer rotates and none has a window
        self.kind_values = {SELECTED: dict(sliding_window=None)}
        super().__post_init__()
        if self.index_head_dim > 128 or self.index_head_dim % 2 \
                or self.index_topk < 1:
            raise ValueError("the index key is stored in one lane tile "
                             "(`index_head_dim` even and at most 128) and a "
                             "query keeps `index_topk` >= 1 positions")
        # the family is pre-norm and norms every head's q and k
        self.post_norm = False


keye_vl2_init_fn = exaone_moe_init_fn
keye_vl2_forward = exaone_moe_forward


def keye_vl2_cache_identity(cfg: KeyeVL2Config, name: str = "") -> str:
    return (f"keye_vl2:{name}|{cfg.n_layer}|{cfg.d_model}|{cfg.n_head}|"
            f"{cfg.n_kv_head}|{cfg.head_dim}|{cfg.index_n_head}|"
            f"{cfg.index_head_dim}|{cfg.index_topk}|{cfg.num_experts}|"
            f"{cfg.experts_held}|{cfg.top_k}|{cfg.norm_topk_prob}|"
            f"{cfg.rope_theta}|{cfg.norm_eps}")


def make_keye_vl2_decode_model(cfg: KeyeVL2Config, params=None,
                               name="keye-vl2", seed=0):
    """The paged serving contract (`DecodeModelSpec`) of the family:
    `exaone_moe.py::make_exaone_moe_decode_model` on a pool of one kind."""
    return make_exaone_moe_decode_model(
        cfg, params, name, seed, family="keye_vl2",
        fingerprint=keye_vl2_cache_identity(cfg, name))
