"""Nemotron-H — the hybrid family (`model_type: nemotron_h`) on the paged
serving path: Mamba-2 layers whose recurrent state lives per SLOT beside the
KV pool, LatentMoE layers (plain `relu2` experts in a latent space between two
shared projections) and a few grouped-query attention layers, served as ONE
CHIP'S SHARE of an expert-parallel deployment.

A layer is a mixer OR a feed-forward part ALONE, `x <- x + f(RMSNorm(x))`, in
the order a pattern string gives (`hybrid_override_pattern`, a letter a
layer), as `benchmark/references/nemotron_h.py` computes it in float32. The
layer loop, the Mamba-2 half (`M`), the attention half (`*`), the state kind
of cache and the paged programs are `models/hybrid.py`'s, which the Granite
hybrid family runs too; this file gives that loop the family's data — an
untied head, no multipliers, a half a layer — and its `E` half:

    E  LatentMoE: sigmoid scores of the full-width input, the `top_k` largest
                  `score + bias`, weights renormalised and scaled;
                  l = u W_lat_down; r = sum_e w_e relu2(l W_up_e) W_down_e;
                  out = r W_lat_up + relu2(u W_s_up) W_s_down

THE EXPERT SHARE, as K-EXAONE's: the router routes over all `num_experts`,
this chip holds `experts_held = (first, count)`, and what the others would
add is left out, here and in the reference alike. The latent projections,
the router and the shared expert are replicated; `W_lat_up` is linear, so
the chips' parts add up.

Not here: training, the contiguous-cache `generate()` path, the multi-token-
prediction module, and what `models/hybrid.py` lists for a pool with a state
kind.
"""

import dataclasses

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import _norm
# the loop's halves under the names this module had them (the tests' names)
from deepspeed_tpu.models.gpt import _attn_half  # noqa: F401
from deepspeed_tpu.models.hybrid import (_attention_cfg,  # noqa: F401
                                         _mamba_half)
from deepspeed_tpu.models.hybrid import (MOE, HybridConfig, hybrid_forward,
                                         hybrid_init_fn,
                                         make_hybrid_decode_model,
                                         mixer_shapes, stream_range)
from deepspeed_tpu.parallel.moe import relu2, routed_experts, topk_routing


@dataclasses.dataclass
class NemotronHConfig(HybridConfig):
    pattern: str = ""                   # `hybrid_override_pattern`
    moe_latent_size: int = 1024         # `d_ff` is one expert's width there
    routed_scaling_factor: float = 1.0

    def __post_init__(self):
        # what the family fixes beside `HybridConfig`'s
        self.use_swiglu = self.tie_embeddings = False
        super().__post_init__()


# ----------------------------------------------------------------------
# parameters
# ----------------------------------------------------------------------


def _layer_shapes(cfg: NemotronHConfig, kind, router_std=0.02):
    """One layer's leaves (`hybrid.py::mixer_shapes`' form)."""
    shapes = mixer_shapes(cfg, kind)
    if kind == MOE:
        D, Ld, F, held = (cfg.d_model, cfg.moe_latent_size, cfg.d_ff,
                          cfg.experts_held[1])
        down = stream_range(cfg)
        shapes.update({
            "moe_gate_w": ((D, cfg.num_experts), router_std),
            "moe_gate_bias": ((cfg.num_experts,), 0.0),
            "lat_down_w": ((D, Ld), 0.02), "lat_up_w": ((Ld, D), down),
            "moe_w_up": ((held, Ld, F), 0.02),
            "moe_w_down": ((held, F, Ld), 0.02),
            "shared_up_w": ((D, cfg.shared_d_ff), 0.02),
            "shared_down_w": ((cfg.shared_d_ff, D), down)})
    return shapes


def nemotron_h_init_fn(cfg: NemotronHConfig, dtype=jnp.float32,
                       embedding_std=0.02, router_std=0.02):
    """`hybrid.py::hybrid_init_fn` of the family's leaves: `runs`, `wte`,
    `lm_head`, `lnf_scale`."""
    return hybrid_init_fn(cfg, _layer_shapes, dtype, embedding_std,
                          router_std)


_EXPERT_STACKS = ("moe_w_up", "moe_w_down")


# ----------------------------------------------------------------------
# the LatentMoE half
# ----------------------------------------------------------------------


def _latent_moe(x, p, cfg: NemotronHConfig, stacks=None, expert_base=0):
    """`f` of a LatentMoE layer on x [B, T, D] -> (f(RMSNorm(x)), counters
    int32[5] in `HELD_ROUTED_COUNTERS` order, chosen experts [B*T, top_k]).
    `stacks`: the experts' weights where they are not `p`'s own leaves, a
    whole stack of the scanned layers' experts with `expert_base` where this
    layer's begin."""
    B, T, D = x.shape
    u = _norm(x, p["ln1_scale"], None, True, cfg.norm_eps).reshape(B * T, D)
    top_p, top_e = topk_routing(
        u, p["moe_gate_w"], cfg.top_k, cfg.norm_topk_prob, scoring="sigmoid",
        bias=p["moe_gate_bias"], scale=cfg.routed_scaling_factor)
    if stacks is None:
        stacks = {"w_up": p["moe_w_up"], "w_down": p["moe_w_down"]}
    with jax.named_scope("moe/latent_down"):
        latent = u @ p["lat_down_w"]
    routed, counters = routed_experts(latent, top_p, top_e, stacks,
                                      activation=relu2,
                                      expert_base=expert_base,
                                      held=cfg.experts_held)
    with jax.named_scope("moe/latent_up"):
        out = routed @ p["lat_up_w"]
    with jax.named_scope("moe/shared_expert"):
        out = out + relu2(u @ p["shared_up_w"]) @ p["shared_down_w"]
    return out.reshape(B, T, D), counters, top_e


def nemotron_h_forward(params, tokens, cfg: NemotronHConfig, routing=None):
    """tokens [B, T] -> logits [B, T, V] without a cache
    (`hybrid.py::hybrid_forward`). `routing`: a list that takes each
    LatentMoE layer's chosen experts [B*T, top_k]."""
    return hybrid_forward(params, tokens, cfg, _latent_moe, routing)


def nemotron_h_cache_identity(cfg: NemotronHConfig, name: str = "") -> str:
    return (f"nemotron_h:{name}|{cfg.pattern}|{cfg.d_model}|{cfg.n_head}|"
            f"{cfg.n_kv_head}|{cfg.head_dim}|{cfg.mamba_num_heads}|"
            f"{cfg.mamba_head_dim}|{cfg.ssm_state_size}|{cfg.n_groups}|"
            f"{cfg.conv_kernel}|{cfg.num_experts}|{cfg.experts_held}|"
            f"{cfg.top_k}|{cfg.moe_latent_size}|{cfg.routed_scaling_factor}|"
            f"{cfg.norm_eps}")


def make_nemotron_h_decode_model(cfg: NemotronHConfig, params=None,
                                 name="nemotron-h", seed=0):
    """The paged serving contract (`DecodeModelSpec`) of the family:
    `hybrid.py::make_hybrid_decode_model` with the LatentMoE half."""
    if params is None:
        params = nemotron_h_init_fn(cfg)(jax.random.PRNGKey(seed))
    return make_hybrid_decode_model(
        cfg, params, name, _latent_moe, _EXPERT_STACKS,
        nemotron_h_cache_identity(cfg, name))
