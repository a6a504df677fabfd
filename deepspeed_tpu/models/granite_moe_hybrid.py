"""Granite 4.0-H — the hybrid family `model_type: granitemoehybrid` on the
paged serving path: a mixer AND routed experts in EVERY layer, nine Mamba-2
mixers in ten, the family's four multipliers, served as ONE CHIP'S SHARE of
an expert-parallel deployment.

A layer is two halves of `models/hybrid.py`'s loop, as
`benchmark/references/granite_moe_hybrid.py` computes them in float32:

    x_0    = embedding_multiplier * wte[tokens]
    h      = x + residual_multiplier * mixer(RMSNorm(x))     "M" or "*"
    x'     = h + residual_multiplier * (routed(u) + shared(u)),  u = RMSNorm(h)
    logits = RMSNorm(x_L) wte^T / logits_scaling             (tied head)

    M  Mamba-2 with ONE group (every head reads the same B and C; the gated
       norm runs over all the inner columns), `hybrid.py::_mamba_half`
    *  attention without positions, scores times `attention_multiplier`
       (`GPTConfig.scale_attn` as a value), `gpt.py::_attn_half`
    E  router u W_r, the `top_k` largest logits, softmax over those
       (`topk_routing`'s softmax renormalised over the chosen); gated experts
       (silu(u W_g) * (u W_u)) W_d beside one shared SwiGLU: the half
       `models/exaone_moe.py::_sparse_mlp` computes for K-EXAONE, here after
       a norm of its own

The loop, the Mamba-2 half, the state kind of cache and the paged programs
are `hybrid.py`'s, shared with `models/nemotron_h.py`; this file is the
family's data. THE EXPERT SHARE is K-EXAONE's: the router routes over all
`num_experts`, this chip holds `experts_held = (first, count)`, what the
others would add is left out, here and in the reference alike; the shared
expert, the router and the mixers are every chip's.

Not here: training, the contiguous-cache `generate()` path, and what
`hybrid.py` lists for a pool with a state kind.
"""

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.exaone_moe import _sparse_mlp
from deepspeed_tpu.models.gpt import _norm
from deepspeed_tpu.models.hybrid import (MOE, HybridConfig, hybrid_forward,
                                         hybrid_init_fn,
                                         make_hybrid_decode_model,
                                         mixer_shapes, stream_range)

# a published `layer_types` entry -> the layer's two halves
BLOCKS = {"mamba": "ME", "attention": "*E"}


@dataclasses.dataclass
class GraniteMoEHybridConfig(HybridConfig):
    pattern: tuple = ()                 # a block a layer: `BLOCKS`' values
    n_groups: int = 1
    chunk_size: int = 256               # `mamba_chunk_size`
    # `_sparse_mlp` reads its router from these: logits, no bias, no scale
    router_scoring: str = "softmax"
    routed_scaling_factor: Optional[float] = None

    def __post_init__(self):
        # what the family fixes beside `HybridConfig`'s: gated MLPs, a tied
        # head, the chosen experts' weights a softmax over the chosen
        self.use_swiglu = self.tie_embeddings = self.norm_topk_prob = True
        super().__post_init__()


def _layer_shapes(cfg: GraniteMoEHybridConfig, kind, router_std=0.02):
    """One half's leaves (`hybrid.py::mixer_shapes`' form)."""
    shapes = mixer_shapes(cfg, kind)
    if kind == MOE:
        D, F, Fs, held = (cfg.d_model, cfg.d_ff, cfg.shared_d_ff,
                          cfg.experts_held[1])
        down = stream_range(cfg)
        shapes.update({
            "moe_gate_w": ((D, cfg.num_experts), router_std),
            "moe_w_gate_up": ((held, D, 2 * F), 0.02),
            "moe_w_down": ((held, F, D), down),
            "shared_gate_w": ((D, Fs), 0.02), "shared_up_w": ((D, Fs), 0.02),
            "shared_down_w": ((Fs, D), down)})
    return shapes


def granite_moe_hybrid_init_fn(cfg: GraniteMoEHybridConfig,
                               dtype=jnp.float32, embedding_std=0.02,
                               router_std=0.02):
    """`hybrid.py::hybrid_init_fn` of the family's leaves: `runs`, `wte`
    (the head too), `lnf_scale`."""
    return hybrid_init_fn(cfg, _layer_shapes, dtype, embedding_std,
                          router_std)


_EXPERT_STACKS = ("moe_w_gate_up", "moe_w_down")


def _gated_moe(x, p, cfg: GraniteMoEHybridConfig, stacks=None, expert_base=0):
    """`f` of the expert half on x [B, T, D] -> (f(RMSNorm(x)), counters
    int32[5] in `HELD_ROUTED_COUNTERS` order, chosen experts [B*T, top_k]);
    `stacks`, `expert_base`: as `_sparse_mlp`'s."""
    u = _norm(x, p["ln1_scale"], None, True, cfg.norm_eps)
    return _sparse_mlp(u, p, cfg, stacks, expert_base)


def granite_moe_hybrid_forward(params, tokens, cfg: GraniteMoEHybridConfig,
                               routing=None):
    """tokens [B, T] -> logits [B, T, V] without a cache
    (`hybrid.py::hybrid_forward`). `routing`: a list that takes each layer's
    chosen experts [B*T, top_k]."""
    return hybrid_forward(params, tokens, cfg, _gated_moe, routing)


def granite_moe_hybrid_cache_identity(cfg: GraniteMoEHybridConfig,
                                      name: str = "") -> str:
    return (f"granitemoehybrid:{name}|{cfg.halves}|{cfg.d_model}|"
            f"{cfg.n_head}|{cfg.n_kv_head}|{cfg.head_dim}|"
            f"{cfg.mamba_num_heads}|{cfg.mamba_head_dim}|"
            f"{cfg.ssm_state_size}|{cfg.n_groups}|{cfg.conv_kernel}|"
            f"{cfg.num_experts}|{cfg.experts_held}|{cfg.top_k}|{cfg.d_ff}|"
            f"{cfg.shared_d_ff}|{cfg.scale_attn}|{cfg.embedding_multiplier}|"
            f"{cfg.residual_multiplier}|{cfg.logits_scaling}|{cfg.norm_eps}")


def make_granite_moe_hybrid_decode_model(cfg: GraniteMoEHybridConfig,
                                         params=None, name="granite-4.0-h",
                                         seed=0):
    """The paged serving contract (`DecodeModelSpec`) of the family:
    `hybrid.py::make_hybrid_decode_model` with the gated expert half."""
    if params is None:
        params = granite_moe_hybrid_init_fn(cfg)(jax.random.PRNGKey(seed))
    return make_hybrid_decode_model(
        cfg, params, name, _gated_moe, _EXPERT_STACKS,
        granite_moe_hybrid_cache_identity(cfg, name))
