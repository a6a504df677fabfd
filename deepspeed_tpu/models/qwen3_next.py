"""Qwen3-Next — the hybrid family `model_type: qwen3_next` on the paged
serving path: Gated DeltaNet (the gated delta rule on a matrix state a head)
in three layers of four, GATED attention in the fourth, and routed experts
beside a sigmoid-gated shared one in EVERY layer, served as ONE CHIP'S SHARE
of an expert-parallel deployment.

A layer is two halves of `models/hybrid.py`'s loop, as
`benchmark/references/qwen3_next.py` computes them in float32:

    h      = x + mixer(RMSNorm(x))                         "D" or "*"
    x'     = h + routed(u) + sigmoid(u w_s) shared(u),     u = RMSNorm(h)
    logits = RMSNorm(x_L) W_head^T                         (untied head)

    D  Gated DeltaNet, `hybrid.py::_gdn_half`: G key heads' q and k (unit
       length) serve H value heads; a K x V float32 state a value head on the
       per-slot STATE kind, `S <- exp(g) S + k (outer) beta (v - exp(g) S^T k)`
    *  attention, `gpt.py::_attn_half` / `_paged_attn_half`: per-head RMSNorm
       on q and k (`qk_norm_per_head`), rotary on the first `rotary_pct` of a
       head's columns, the result times `sigmoid(gate)`, the gate the second
       half of a twice-as-wide query projection (`attn_output_gate`)
    E  router u W_r over all `num_experts`, softmax, the `top_k` largest
       renormalised; gated experts (silu(u W_g) * (u W_u)) W_d beside one
       shared SwiGLU times `sigmoid(u w_s)`, a scalar a token:
       `models/exaone_moe.py::_sparse_mlp` with the `shared_scale_w` leaf

Every norm's scale is stored as the value that multiplies (`1 + w` of the
published zero-centred form; the gated norm's plain `w`). The loop, both
mixers, the state kind of cache and the paged programs are `hybrid.py`'s and
`gpt.py`'s; this file is the family's data and its expert half. THE EXPERT
SHARE is K-EXAONE's: the router routes over all `num_experts`, this chip
holds `experts_held = (first, count)`, what the others would add is left
out, here and in the reference alike.

Not here: the multi-token-prediction module (the published `config.json` has
no key for it; unserved, as K-EXAONE's, Nemotron's and GLM's are),
`intermediate_size` (no layer is dense: `mlp_only_layers` is empty and
`decoder_sparse_step` 1), the contiguous-cache `generate()` path, what
`hybrid.py` lists for a pool with a state kind, and TRAINING this family:
`hybrid.py::hybrid_loss` trains the loop's Gated DeltaNet, attention and
dense halves (`models/olmo_hybrid.py`), not the expert half `E` every layer
here has.
"""

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import granite_moe_hybrid as granite
from deepspeed_tpu.models.hybrid import (MOE, HybridConfig, hybrid_forward,
                                         hybrid_init_fn,
                                         make_hybrid_decode_model)

# a layer's two halves: full attention every `full_attention_interval`-th
# layer, Gated DeltaNet in the others
BLOCKS = {"linear_attention": "DE", "full_attention": "*E"}


def layer_types(num_layers, full_attention_interval):
    """The published rule: layer i is full attention where `(i + 1) %
    full_attention_interval == 0`."""
    return tuple("full_attention" if (i + 1) % full_attention_interval == 0
                 else "linear_attention" for i in range(num_layers))


@dataclasses.dataclass
class Qwen3NextConfig(HybridConfig):
    pattern: tuple = ()                 # a block a layer: `BLOCKS`' values
    chunk_size: int = 64                # positions a chunk of the delta rule
    rotary_pct: float = 0.25            # `partial_rotary_factor`
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    # `_sparse_mlp` reads its router from these: softmax, no bias, no scale
    router_scoring: str = "softmax"
    routed_scaling_factor: Optional[float] = None

    def __post_init__(self):
        # what the family fixes beside `HybridConfig`'s: gated MLPs, an untied
        # head, the chosen experts' weights renormalised, and an attention
        # half that rotates, norms q and k a head and gates its output
        self.use_swiglu = self.norm_topk_prob = True
        self.tie_embeddings = False
        super().__post_init__()
        self.rotary_attention = self.qk_norm_per_head = True
        self.attn_output_gate = True


def _layer_shapes(cfg: Qwen3NextConfig, kind, router_std=0.02):
    """One half's leaves (`hybrid.py::mixer_shapes`' form): Granite 4.0-H's
    expert half — router, fused gate-up and down stacks, a shared SwiGLU —
    and the shared expert's gate `w_s`."""
    shapes = granite._layer_shapes(cfg, kind, router_std)
    if kind == MOE:
        shapes["shared_scale_w"] = ((cfg.d_model,), 0.02)
    return shapes


def qwen3_next_init_fn(cfg: Qwen3NextConfig, dtype=jnp.float32,
                       embedding_std=0.02, router_std=0.02):
    """`hybrid.py::hybrid_init_fn` of the family's leaves: `runs`, `wte`,
    `lm_head`, `lnf_scale`."""
    return hybrid_init_fn(cfg, _layer_shapes, dtype, embedding_std,
                          router_std)


# `f` of the expert half on x [B, T, D] -> (f(RMSNorm(x)), counters int32[5]
# in `HELD_ROUTED_COUNTERS` order, chosen experts [B*T, top_k]): Granite's
# half — a norm, then `exaone_moe._sparse_mlp`, which GATES the shared expert
# where the half's leaves hold a `shared_scale_w`, as this family's do
_gated_shared_moe = granite._gated_moe


def qwen3_next_forward(params, tokens, cfg: Qwen3NextConfig, routing=None):
    """tokens [B, T] -> logits [B, T, V] without a cache
    (`hybrid.py::hybrid_forward`). `routing`: a list that takes each layer's
    chosen experts [B*T, top_k]."""
    return hybrid_forward(params, tokens, cfg, _gated_shared_moe, routing)


def qwen3_next_cache_identity(cfg: Qwen3NextConfig, name: str = "") -> str:
    return (f"qwen3_next:{name}|{cfg.halves}|{cfg.d_model}|{cfg.n_head}|"
            f"{cfg.n_kv_head}|{cfg.head_dim}|{cfg.rotary_pct}|"
            f"{cfg.rope_theta}|{cfg.gdn_key_heads}|{cfg.gdn_value_heads}|"
            f"{cfg.gdn_key_dim}|{cfg.gdn_value_dim}|{cfg.conv_kernel}|"
            f"{cfg.num_experts}|{cfg.experts_held}|{cfg.top_k}|{cfg.d_ff}|"
            f"{cfg.shared_d_ff}|{cfg.norm_eps}")


def make_qwen3_next_decode_model(cfg: Qwen3NextConfig, params=None,
                                 name="qwen3-next", seed=0):
    """The paged serving contract (`DecodeModelSpec`) of the family:
    `hybrid.py::make_hybrid_decode_model` with the gated-shared expert
    half."""
    if params is None:
        params = qwen3_next_init_fn(cfg)(jax.random.PRNGKey(seed))
    return make_hybrid_decode_model(
        cfg, params, name, _gated_shared_moe, granite._EXPERT_STACKS,
        qwen3_next_cache_identity(cfg, name))
