"""Plain reference for OLMoE (`model_type: olmoe`): the forward pass in
straightforward `jax.numpy`, float32, one sequence at a time, dense attention,
a dense loop over the experts, no kernels, no cache, no batching, no scan, no
sort. Every matrix product runs under `jax.default_matmul_precision("highest")`
— on a TPU a float32 product otherwise runs in bfloat16 passes. Written like
`decoder.py`, the dense decoders' reference beside it.

The layer (OlmoeDecoderLayer of the published code): RMSNorm -> q, k, v
projections without bias -> RMSNorm over the WHOLE projected query and the
whole projected key (`q_norm`, `k_norm`, all heads' columns together) -> heads
split, rotary on the whole head -> causal attention -> output projection ->
residual; RMSNorm -> router: softmax over the 64 experts' logits in float32,
the 8 largest probabilities kept AS THEY ARE (`norm_topk_prob: false`) -> each
chosen expert's SwiGLU (`down(silu(gate(h)) * up(h))`) weighted by its
probability and summed -> residual. No shared expert, no expert bias.

It reads the PROGRAM'S parameter tree (`models/moe_gpt.py`, the `moe_freq` 1
layout: blocks stacked on a leading layer axis, q/k/v fused in one
`[D, 3 H hd]` matrix in that order, `moe_gate_w [D, E]`, `moe_w_gate_up
[E, D, 2F]` with gate in the first F columns and up in the last,
`moe_w_down [E, F, D]`), because "the same weights" is what is compared;
layers are cast to float32 one at a time, so the reference fits beside a
served model.

The expert loop runs EVERY row through each expert and weights the rows that
did not choose it by zero: the same sum as the published code's gather of each
expert's rows, in plain array operations.

Departures from the published code, shared with the program and stated in the
configuration file: rotary pairs are interleaved (even, odd) rather than split
in halves — the published layout up to a fixed permutation of each head's
columns; the attention biases the program's tree carries are zero.

`round_to`: None for the reference itself. A dtype (e.g. `float8_e4m3fn`)
rounds every weight and every matrix product's input through that type — the
reference "computed in a lower precision", which the benchmark's limits are
set against (PERF.md) and which no check uses.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Arch:
    n_layer: int
    n_head: int
    n_kv_head: int
    d_model: int
    expert_width: int
    num_experts: int
    top_k: int
    norm_topk_prob: bool
    rope_theta: float
    norm_eps: float
    round_to: object = None

    @property
    def head_dim(self):
        return self.d_model // self.n_head


def arch_from_config(cfg, round_to=None):
    """The published `config.json` keys -> what the equations need."""
    if cfg["model_type"] != "olmoe":
        raise ValueError(f"no reference for model_type {cfg['model_type']!r}")
    if cfg.get("clip_qkv") is not None or cfg.get("rope_scaling") is not None:
        raise ValueError("this reference has no clip_qkv and no rope scaling")
    return Arch(n_layer=cfg["num_hidden_layers"],
                n_head=cfg["num_attention_heads"],
                n_kv_head=cfg["num_key_value_heads"],
                d_model=cfg["hidden_size"],
                expert_width=cfg["intermediate_size"],
                num_experts=cfg["num_experts"],
                top_k=cfg["num_experts_per_tok"],
                norm_topk_prob=cfg["norm_topk_prob"],
                rope_theta=float(cfg["rope_theta"]),
                norm_eps=cfg["rms_norm_eps"], round_to=round_to)


def _rounded(x, arch):
    if arch.round_to is None:
        return x
    return x.astype(arch.round_to).astype(jnp.float32)


def _matmul(x, w, arch):
    return _rounded(x, arch) @ _rounded(w, arch)


def _rms_norm(x, scale, arch):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + arch.norm_eps) * scale


def _rope(x, positions, arch):
    """x: [T, heads, hd]. Rotates the whole head, in (even, odd) pairs."""
    hd = x.shape[-1]
    freqs = arch.rope_theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _experts(h, p, arch):
    """h: [T, D] -> (the routed experts' sum [T, D], the chosen experts
    [T, k] int32 in ascending order)."""
    F = arch.expert_width
    probs = jax.nn.softmax(_matmul(h, p["moe_gate_w"], arch), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, arch.top_k)
    if arch.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    out = jnp.zeros_like(h)
    for e in range(arch.num_experts):
        weight = jnp.sum(jnp.where(top_e == e, top_p, 0.0), axis=-1)   # [T]
        both = _matmul(h, p["moe_w_gate_up"][e], arch)
        inner = jax.nn.silu(both[:, :F]) * both[:, F:]
        out = out + weight[:, None] * _matmul(inner, p["moe_w_down"][e], arch)
    return out, jnp.sort(top_e.astype(jnp.int32), axis=-1)


def _layer(x, p, arch):
    """One block on one sequence. x: [T, D] float32; p: that layer's leaves.
    Returns (x, chosen experts [T, k])."""
    p = jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.float32), p)
    T = x.shape[0]
    H, Hkv, hd = arch.n_head, arch.n_kv_head, arch.head_dim
    positions = jnp.arange(T)
    h = _rms_norm(x, p["ln1_scale"], arch)
    qkv = _matmul(h, p["attn_qkv_w"], arch) + p["attn_qkv_b"]
    q = _rms_norm(qkv[:, :H * hd], p["q_norm_scale"], arch)
    k = _rms_norm(qkv[:, H * hd:(H + Hkv) * hd], p["k_norm_scale"], arch)
    q = _rope(q.reshape(T, H, hd), positions, arch)
    k = _rope(k.reshape(T, Hkv, hd), positions, arch)
    v = qkv[:, (H + Hkv) * hd:].reshape(T, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    scores = jnp.einsum("thd,shd->hts", _rounded(q, arch),
                        _rounded(k, arch)) / math.sqrt(hd)
    causal = positions[:, None] >= positions[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("hts,shd->thd", _rounded(probs, arch),
                      _rounded(v, arch)).reshape(T, H * hd)
    x = x + _matmul(attn, p["attn_out_w"], arch) + p["attn_out_b"]
    h2 = _rms_norm(x, p["ln2_scale"], arch)
    routed, chosen = _experts(h2, p, arch)
    return x + routed, chosen


_layer_jit = jax.jit(_layer, static_argnums=2)


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


def _head(x, scale, table, arch):
    x = _rms_norm(x, scale.astype(jnp.float32), arch)
    return _matmul(x, table.astype(jnp.float32).T, arch)


_head_jit = jax.jit(_head, static_argnums=3)


def forward(params, tokens, arch):
    """tokens: [T] int32 -> (float32 logits [T, vocab], the experts each
    layer chose [L, T, k] int32, ascending) of one sequence."""
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _embed(params["wte"], tokens)
        for layer in range(arch.n_layer):
            p = jax.tree_util.tree_map(lambda leaf: leaf[layer],
                                       params["blocks"])
            x, experts = _layer_jit(x, p, arch)
            chosen.append(experts)
        out = _head_jit(x, params["lnf_scale"], params["lm_head"], arch)
    return out, jnp.stack(chosen)


def logits(params, tokens, arch):
    """tokens: [T] int32 -> float32 logits [T, vocab] of one sequence."""
    return forward(params, tokens, arch)[0]
