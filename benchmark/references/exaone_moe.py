"""Plain reference for K-EXAONE (`model_type: exaone_moe`): the forward pass in
straightforward `jax.numpy`, float32, one sequence at a time, dense attention
in blocks of query rows, a dense loop over the experts, no kernels, no cache,
no batching, no scan, no sort. Every matrix product runs under
`jax.default_matmul_precision("highest")` — on a TPU a float32 product
otherwise runs in bfloat16 passes. Imports `jax` only.

Layer l has an attention kind `layer_types[l]` (sliding_attention |
full_attention) and an MLP kind `mlp_layer_types[l]` (dense | sparse):

1. `q = x Wq` as [T, H, hd], `k = x Wk`, `v = x Wv` as [T, Hkv, hd]; no bias.
2. `q <- RMSNorm_hd(q; g_q)`, `k <- RMSNorm_hd(k; g_k)`: over each head's
   columns, one scale vector of hd shared by the heads (`Exaone4Attention`).
3. window layer: rotary on q and k at the absolute position, whole head.
   Full layer: NO rotary.
4. `s_ij = q_i . k_j / sqrt(hd)` for `j <= i`, on a window layer also
   `i - j < window`; softmax in float32; `o = concat_heads(softmax(s) v) Wo`.
5. `h = x + RMSNorm_D(o; g_1)`; `y = h + RMSNorm_D(MLP(h); g_2)` (post-norm,
   `Exaone4DecoderLayer`).
6. dense MLP: `(silu(h Wg) * (h Wu)) Wd`.
7. sparse MLP: `z = h Wr` in float32, `s = sigmoid(z)`; the k experts with the
   largest `s + b` (`n_group` 1 and `topk_group` 1 make DeepseekV3TopkRouter's
   group step the identity); `w_e = scale * s_e / (sum of the chosen s +
   1e-20)`; `MLP(h) = sum over the chosen e of w_e SwiGLU_e(h) +
   SwiGLU_shared(h)`.
8. after the last layer `RMSNorm_D`, then the head.

THE SHARE. `experts_held = (first, count)`: the routed sum runs over the held
experts only — what the others would add is left out, as in the program; the
weights `w_e` are still normalised over all k chosen. `experts_held = None`
(with a tree that holds every expert): the whole layer.

It reads the PROGRAM'S parameter tree (`models/exaone_moe.py`: `prologue`, a
list of layer trees, then `period`, one tree a position of the period with a
leading `[periods]` axis; q/k/v fused in one `[D, (H + 2 Hkv) hd]` matrix in
that order; `moe_gate_w [D, E]`, `moe_gate_bias [E]`, `moe_w_gate_up
[held, D, 2F]` with gate in the first F columns, `moe_w_down [held, F, D]`,
`shared_*`), because "the same weights" is what is compared. Weights are cast
to float32 a matrix at a time, so the reference fits beside a served model.

Departures from the published code, shared with the program and stated in the
configuration file: rotary pairs are interleaved (even, odd) rather than split
in halves — the published layout up to a fixed permutation of each head's
columns; the attention biases the program's tree carries are zero.

FORCED ROUTING. `forward(..., forced=sets)` computes the scores as above but
takes the experts it is GIVEN (`[sparse layers, T, k]`, e.g. the ones the
program chose) in place of its own top k: weights from its own float32
scores of those experts, normalised over them. Top-k is discontinuous, a
score within rounding of the k-th swaps, and on one chip's share a swap
turns a layer's output by tens of percent; with the choice held equal, what
is left in the logits is arithmetic, at every position. The experts it
WOULD have chosen on that stream are returned all the same, so the choice
is compared on its own.

`round_to`: None for the reference itself. A dtype (e.g. `float8_e4m3fn`)
rounds every weight and every matrix product's input through that type — the
reference "computed in a lower precision", which the benchmark's limits are
set against (PERF.md) and which no check uses. A type with a short range
(float8_e4m3fn ends at 448) is given a scale a row, the row's largest
magnitude at the type's largest, as 8-bit matrix products are run: it then
fails by its precision and not by overflow.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp

WINDOW, FULL = "sliding_attention", "full_attention"
ROW_BLOCK = 256         # query rows an attention block scores at a time


@dataclasses.dataclass(frozen=True)
class Arch:
    layer_types: tuple
    mlp_layer_types: tuple
    n_head: int
    n_kv_head: int
    head_dim: int
    d_model: int
    window: int
    num_experts: int        # the router's width
    experts_held: object    # (first, count) or None = all
    top_k: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    pattern_period: int
    rope_theta: float
    norm_eps: float
    round_to: object = None


def arch_from_config(cfg, round_to=None):
    """The configuration file's keys -> what the equations need."""
    if cfg["model_type"] != "exaone_moe":
        raise ValueError(f"no reference for model_type {cfg['model_type']!r}")
    if cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["num_shared_experts"] != 1:
        raise ValueError("this reference has the sigmoid router without "
                         "groups and one shared expert")
    return Arch(layer_types=tuple(cfg["layer_types"]),
                mlp_layer_types=tuple(cfg["mlp_layer_types"]),
                n_head=cfg["num_attention_heads"],
                n_kv_head=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], d_model=cfg["hidden_size"],
                window=cfg["sliding_window"],
                num_experts=cfg["published_num_experts"],
                experts_held=tuple(cfg["experts_held_range"]),
                top_k=cfg["num_experts_per_tok"],
                norm_topk_prob=cfg["norm_topk_prob"],
                routed_scaling_factor=cfg["routed_scaling_factor"],
                pattern_period=len(cfg["sliding_window_pattern"]),
                rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
                norm_eps=cfg["rms_norm_eps"], round_to=round_to)


class _LayerOf:
    """Layer `n` of a leaf stacked `[periods, ...]`, indexed further on use:
    `_LayerOf(stack, n)[e]` is `stack[n, e]`, so one expert's matrices are
    read out of the stack and never a whole layer of them (a layer's 16
    experts are 1.2 GB at the published widths)."""

    def __init__(self, stack, n):
        self.stack, self.n = stack, n

    def __getitem__(self, e):
        return self.stack[self.n, e]


def layer_trees(params, arch):
    """Every layer's own leaves, in model order, one layer at a time (a
    generator: a scanned layer's small leaves are sliced out of their stacks
    when the layer is reached, its experts only when each is used)."""
    yield from params["prologue"]
    periods = (len(arch.layer_types) - len(params["prologue"])) \
        // arch.pattern_period
    for n in range(periods):
        for tree in params["period"]:
            yield {k: (_LayerOf(v, n) if k.startswith("moe_w_") else v[n])
                   for k, v in tree.items()}


def _rounded(x, arch):
    if arch.round_to is None:
        return x
    top = float(jnp.finfo(arch.round_to).max)
    if top > 1e30:                      # bfloat16: float32's range
        return x.astype(arch.round_to).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / top
    return (x / scale).astype(arch.round_to).astype(jnp.float32) * scale


def _matmul(x, w, arch):
    return _rounded(x, arch) @ _rounded(w.astype(jnp.float32), arch)


def _rms_norm(x, scale, arch):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + arch.norm_eps) * scale.astype(jnp.float32)


def _rope(x, positions, arch):
    """x: [T, heads, hd]. Rotates the whole head, in (even, odd) pairs."""
    hd = x.shape[-1]
    freqs = arch.rope_theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _attention(x, p, kind, arch):
    """Steps 1-4 and the first half of 5 on one sequence x [T, D]."""
    T = x.shape[0]
    H, Hkv, hd = arch.n_head, arch.n_kv_head, arch.head_dim
    positions = jnp.arange(T)
    qkv = _matmul(x, p["attn_qkv_w"], arch) + p["attn_qkv_b"]
    q = _rms_norm(qkv[:, :H * hd].reshape(T, H, hd), p["q_norm_scale"], arch)
    k = _rms_norm(qkv[:, H * hd:(H + Hkv) * hd].reshape(T, Hkv, hd),
                  p["k_norm_scale"], arch)
    v = qkv[:, (H + Hkv) * hd:].reshape(T, Hkv, hd)
    if kind == WINDOW:
        q, k = _rope(q, positions, arch), _rope(k, positions, arch)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    out = []
    for lo in range(0, T, ROW_BLOCK):           # blocks of query rows
        rows = positions[lo:lo + ROW_BLOCK]
        scores = jnp.einsum("thd,shd->hts", _rounded(q[lo:lo + ROW_BLOCK],
                                                     arch),
                            _rounded(k, arch)) / math.sqrt(hd)
        seen = rows[:, None] >= positions[None, :]
        if kind == WINDOW:
            seen = seen & (rows[:, None] - positions[None, :] < arch.window)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("hts,shd->thd", _rounded(probs, arch),
                              _rounded(v, arch)).reshape(-1, H * hd))
    attn = jnp.concatenate(out, axis=0)
    o = _matmul(attn, p["attn_out_w"], arch) + p["attn_out_b"]
    return x + _rms_norm(o, p["ln1_scale"], arch)


_attention_jit = jax.jit(_attention, static_argnums=(2, 3))
_ATTENTION_LEAVES = ("attn_qkv_w", "attn_qkv_b", "attn_out_w", "attn_out_b",
                     "q_norm_scale", "k_norm_scale", "ln1_scale")


def _swiglu(h, gate_w, up_w, down_w, arch):
    return _matmul(jax.nn.silu(_matmul(h, gate_w, arch))
                   * _matmul(h, up_w, arch), down_w, arch)


_swiglu_jit = jax.jit(_swiglu, static_argnums=4)


def _route(h, gate_w, bias, arch, forced=None):
    """-> (weights [T, k] float32 of the experts USED, the experts used
    [T, k], the experts chosen [T, k]); used = chosen unless `forced`."""
    scores = jax.nn.sigmoid(_matmul(h, gate_w, arch))
    _, top_e = jax.lax.top_k(scores + bias.astype(jnp.float32), arch.top_k)
    top_e = top_e.astype(jnp.int32)
    used = top_e if forced is None else forced
    top_w = jnp.take_along_axis(scores, used, axis=-1)
    if arch.norm_topk_prob:
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    return top_w * arch.routed_scaling_factor, used, top_e


_route_jit = jax.jit(_route, static_argnums=3)


def route(h, gate_w, bias, arch):
    """Step 7's router on h [T, D] -> (weights [T, k] float32, experts
    [T, k] int32): a transcription of `DeepseekV3TopkRouter` with one group."""
    top_w, _, top_e = _route(h, gate_w, bias, arch)
    return top_w, top_e


def _expert_part(h, weight, gate_up, down, arch):
    """One expert's weighted SwiGLU on EVERY row (rows that did not choose it
    carry weight zero): the same sum as the published gather of its rows."""
    F = down.shape[0]
    both = _matmul(h, gate_up, arch)
    inner = jax.nn.silu(both[:, :F]) * both[:, F:]
    return weight[:, None] * _matmul(inner, down, arch)


_expert_jit = jax.jit(_expert_part, static_argnums=4)


def routed_sum(h, p, arch, held=None, forced=None):
    """The routed experts' weighted sum over the experts `held = (first,
    count)` (None: `arch.experts_held`), whose weights are `p`'s
    `moe_w_gate_up` / `moe_w_down` in that order -> (sum [T, D], chosen
    experts [T, k] ascending). `forced` [T, k]: the sum is over THESE
    experts; the chosen ones are returned all the same."""
    first, count = held or arch.experts_held or (0, arch.num_experts)
    top_w, used, top_e = _route_jit(h, p["moe_gate_w"], p["moe_gate_bias"],
                                    arch, forced)
    out = jnp.zeros_like(h)
    for local in range(count):
        weight = jnp.sum(jnp.where(used == first + local, top_w, 0.0), -1)
        out = out + _expert_jit(h, weight, p["moe_w_gate_up"][local],
                                p["moe_w_down"][local], arch)
    return out, jnp.sort(top_e, axis=-1)


def shared_expert(h, p, arch):
    return _swiglu_jit(h, p["shared_gate_w"], p["shared_up_w"],
                       p["shared_down_w"], arch)


def _post_norm_add(h, y, scale, arch):
    return h + _rms_norm(y, scale, arch)


_post_norm_add_jit = jax.jit(_post_norm_add, static_argnums=3)


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


def _head(x, scale, table, arch):
    return _matmul(_rms_norm(x, scale, arch), table.astype(jnp.float32).T,
                   arch)


_head_jit = jax.jit(_head, static_argnums=3)


def forward(params, tokens, arch, forced=None):
    """tokens: [T] int32 -> (float32 logits [T, vocab], the experts each
    sparse layer chose [sparse layers, T, k] int32, ascending) of one
    sequence. `forced` [sparse layers, T, k]: the experts each sparse layer
    USES instead (module docstring)."""
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _embed(params["wte"], tokens)
        kinds = zip(arch.layer_types, arch.mlp_layer_types)
        for p, (attn_kind, mlp_kind) in zip(layer_trees(params, arch), kinds):
            h = _attention_jit(x, {k: p[k] for k in _ATTENTION_LEAVES},
                               attn_kind, arch)
            if mlp_kind == "dense":
                y = _swiglu_jit(h, p["mlp_gate_w"], p["mlp_up_w"],
                                p["mlp_down_w"], arch)
            else:
                y, experts = routed_sum(
                    h, p, arch, forced=None if forced is None
                    else jnp.asarray(forced[len(chosen)], jnp.int32))
                y = y + shared_expert(h, p, arch)
                chosen.append(experts)
            x = _post_norm_add_jit(h, y, p["ln2_scale"], arch)
        out = _head_jit(x, params["lnf_scale"], params["lm_head"], arch)
    return out, jnp.stack(chosen)


def logits(params, tokens, arch):
    """tokens: [T] int32 -> float32 logits [T, vocab] of one sequence."""
    return forward(params, tokens, arch)[0]
