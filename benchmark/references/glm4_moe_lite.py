"""Plain reference for GLM-4.7-Flash (`model_type: glm4_moe_lite`): the forward
pass in straightforward `jax.numpy`, float32, one sequence at a time, latent
attention in the EXPANDED form (every head's keys and values rebuilt from the
latent, as the published code computes it) in blocks of query rows, a dense
loop over the experts, no kernels, no cache, no batching, no scan, no sort.
Every matrix product runs under `jax.default_matmul_precision("highest")` —
on a TPU a float32 product otherwise runs in bfloat16 passes. Imports `jax`
only.

Layer l (pre-norm, no biases), with H heads, d_n / d_r a head's un-rotated /
rotated query-key columns, d_v its value columns, r_q / r the low-rank widths:

1. `a = RMSNorm_D(x; g_1)`.
2. `c_q = RMSNorm(a W_qa; g_qa)` [r_q]; `[q_n | q_r]_h = c_q W_qb` as
   [T, H, d_n + d_r]; `q_r <- RoPE(q_r)` over all d_r columns.
3. `[c' | k'] = a W_kva` [r + d_r]; `c = RMSNorm(c'; g_kva)`, `k_r = RoPE(k')`
   — ONE of each a token for all heads; they are what a cache keeps, and
   `forward` returns them a layer (`latents`).
4. `[k_n | v]_h = c W_kb` as [T, H, d_n + d_v].
5. `s_h(i, j) = (q_n,h(i) . k_n,h(j) + q_r,h(i) . k_r(j)) / sqrt(d_n + d_r)`
   for `j <= i`; softmax in float32; `o = concat_h(softmax(s_h) v_h) W_o`
   (H d_v -> D).
6. `h = x + o`; `y = h + MLP(RMSNorm_D(h; g_2))`.
7. dense MLP (the first `first_k_dense_replace` layers): `(silu(u Wg) * (u
   Wu)) Wd`.
8. sparse MLP: `z = u Wr` in float32, `s = sigmoid(z)`; the k experts with the
   largest `s + b` (`n_group` 1 and `topk_group` 1 make the router's group
   step the identity); `w_e = scale * s_e / (sum of the chosen s + 1e-20)`;
   `MLP(u) = sum over the chosen e of w_e SwiGLU_e(u) + SwiGLU_shared(u)`.
9. after the last layer `RMSNorm_D`, then the head.

THE SHARE. `experts_held = (first, count)`: the routed sum runs over the held
experts only — what the others would add is left out, as in the program; the
weights `w_e` are still normalised over all k chosen. `experts_held = None`
(with a tree that holds every expert): the whole layer.

It reads the PROGRAM'S parameter tree (`models/exaone_moe.py`'s layout with
`models/mla.py`'s attention leaves: `prologue`, a list of layer trees, then
`period`, one tree a position of the period with a leading `[periods]` axis;
`attn_q_a_w`, `q_a_norm_scale`, `attn_q_b_w`, `attn_kv_a_w` with the latent
in the first r columns, `kv_a_norm_scale`, `attn_kv_b_w` with a head's keys
in its first d_n columns, `attn_out_w`; `moe_gate_w [D, E]`, `moe_gate_bias`,
`moe_w_gate_up [held, D, 2F]` gate first, `moe_w_down`, `shared_*`), because
"the same weights" is what is compared. Weights are cast to float32 a matrix
at a time, so the reference fits beside a served model.

Departures from the published code, shared with the program and stated in the
configuration file: rotary pairs are interleaved (even, odd) — for a dot
product of two vectors rotated alike, the published layout up to a fixed
permutation of the columns; the multi-token-prediction layer is not computed.

FORCED ROUTING (`forward(..., forced=sets)`), as `references/exaone_moe.py`:
the experts a sparse layer USES are the ones given (`[sparse layers, T, k]`),
weighted by its own float32 scores of them; the experts it WOULD have chosen
are returned all the same.

`round_to` / `latent_round_to`: None for the reference itself. `round_to` (a
dtype) rounds every weight and every matrix product's input through that
type; `latent_round_to` rounds ONLY what a cache would keep — `c` and `k_r`,
before anything reads them — as a pool of that type would: the two "lower
precision" controls the benchmark's limits are set against (PERF.md), which
no check uses. Rounding is `lax.reduce_precision`; a type with a short range
is given a scale a row.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 256         # query rows an attention block scores at a time


@dataclasses.dataclass(frozen=True)
class Arch:
    n_layer: int
    dense_layers: int       # leading layers with a dense MLP
    n_head: int
    d_model: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    num_experts: int        # the router's width
    experts_held: object    # (first, count) or None = all
    top_k: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    rope_theta: float
    norm_eps: float
    round_to: object = None
    latent_round_to: object = None


def arch_from_config(cfg, round_to=None, latent_round_to=None):
    """The configuration file's keys -> what the equations need."""
    if cfg["model_type"] != "glm4_moe_lite":
        raise ValueError(f"no reference for model_type {cfg['model_type']!r}")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["n_shared_experts"] != 1 \
            or cfg.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError("this reference has the sigmoid router without "
                         "groups, one shared expert and a whole rotation")
    return Arch(n_layer=cfg["num_hidden_layers"],
                dense_layers=cfg["first_k_dense_replace"],
                n_head=cfg["num_attention_heads"], d_model=cfg["hidden_size"],
                q_lora_rank=cfg["q_lora_rank"],
                kv_lora_rank=cfg["kv_lora_rank"],
                qk_nope_head_dim=cfg["qk_nope_head_dim"],
                qk_rope_head_dim=cfg["qk_rope_head_dim"],
                v_head_dim=cfg["v_head_dim"],
                num_experts=cfg["published_n_routed_experts"],
                experts_held=tuple(cfg["experts_held_range"]),
                top_k=cfg["num_experts_per_tok"],
                norm_topk_prob=cfg["norm_topk_prob"],
                routed_scaling_factor=cfg["routed_scaling_factor"],
                rope_theta=float(cfg["rope_theta"]),
                norm_eps=cfg["rms_norm_eps"], round_to=round_to,
                latent_round_to=latent_round_to)


class _LayerOf:
    """Layer `n` of a leaf stacked `[periods, ...]`, indexed further on use
    (one expert's matrices are read out of the stack, never a layer's)."""

    def __init__(self, stack, n):
        self.stack, self.n = stack, n

    def __getitem__(self, e):
        return self.stack[self.n, e]


def layer_trees(params, arch):
    """Every layer's own leaves, in model order, one layer at a time."""
    yield from params["prologue"]
    for n in range(arch.n_layer - len(params["prologue"])):
        for tree in params["period"]:       # the period is one layer
            yield {k: (_LayerOf(v, n) if k.startswith("moe_w_") else v[n])
                   for k, v in tree.items()}


def _through(x, dtype):
    """x rounded through `dtype`'s exponent and mantissa bits
    (`lax.reduce_precision`: a pair of casts is dropped by the TPU's
    compiler). A type with a short range is given a scale a row, the row's
    largest magnitude at the largest value those bits hold."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    top = 2.0 ** (2 ** (info.nexp - 1) - 1) * (2.0 - 2.0 ** -info.nmant)
    scale = 1.0 if top > 1e30 else jnp.maximum(
        jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / top
    return jax.lax.reduce_precision(x / scale, info.nexp, info.nmant) * scale


def _rounded(x, arch):
    return _through(x, arch.round_to)


def _matmul(x, w, arch):
    return _rounded(x, arch) @ _rounded(w.astype(jnp.float32), arch)


def _rms_norm(x, scale, arch):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + arch.norm_eps) * scale.astype(jnp.float32)


def _rope(x, positions, arch):
    """x: [T, ..., d]. Rotates all d columns, in (even, odd) pairs."""
    d = x.shape[-1]
    freqs = arch.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _attention(x, p, arch):
    """Steps 1-5 and the first half of 6 on one sequence x [T, D] ->
    (h [T, D], c [T, r], k_r [T, d_r])."""
    T = x.shape[0]
    H, r = arch.n_head, arch.kv_lora_rank
    dn, dr, dv = (arch.qk_nope_head_dim, arch.qk_rope_head_dim,
                  arch.v_head_dim)
    positions = jnp.arange(T)
    a = _rms_norm(x, p["ln1_scale"], arch)
    c_q = _rms_norm(_matmul(a, p["attn_q_a_w"], arch), p["q_a_norm_scale"],
                    arch)
    q = _matmul(c_q, p["attn_q_b_w"], arch).reshape(T, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], positions, arch)
    kv = _matmul(a, p["attn_kv_a_w"], arch)
    c = _through(_rms_norm(kv[:, :r], p["kv_a_norm_scale"], arch),
                 arch.latent_round_to)
    k_r = _through(_rope(kv[:, r:], positions, arch), arch.latent_round_to)
    kv_up = _matmul(c, p["attn_kv_b_w"], arch).reshape(T, H, dn + dv)
    k_n, v = kv_up[..., :dn], kv_up[..., dn:]
    out = []
    for lo in range(0, T, ROW_BLOCK):           # blocks of query rows
        rows = positions[lo:lo + ROW_BLOCK]
        scores = (jnp.einsum("thd,shd->hts",
                             _rounded(q_n[lo:lo + ROW_BLOCK], arch),
                             _rounded(k_n, arch))
                  + jnp.einsum("thd,sd->hts",
                               _rounded(q_r[lo:lo + ROW_BLOCK], arch),
                               _rounded(k_r, arch))) / math.sqrt(dn + dr)
        seen = rows[:, None] >= positions[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("hts,shd->thd", _rounded(probs, arch),
                              _rounded(v, arch)).reshape(-1, H * dv))
    attn = jnp.concatenate(out, axis=0)
    return x + _matmul(attn, p["attn_out_w"], arch), c, k_r


_attention_jit = jax.jit(_attention, static_argnums=2)
_ATTENTION_LEAVES = ("ln1_scale", "attn_q_a_w", "q_a_norm_scale",
                     "attn_q_b_w", "attn_kv_a_w", "kv_a_norm_scale",
                     "attn_kv_b_w", "attn_out_w")


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
    """Step 8's router on h [T, D] -> (weights [T, k] float32, experts
    [T, k] int32)."""
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


def _pre_norm(h, scale, arch):
    return _rms_norm(h, scale, arch)


_pre_norm_jit = jax.jit(_pre_norm, static_argnums=2)


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


def _head(x, scale, table, arch):
    return _matmul(_rms_norm(x, scale, arch), table.astype(jnp.float32).T,
                   arch)


_head_jit = jax.jit(_head, static_argnums=3)


def forward(params, tokens, arch, forced=None, head_rows=None):
    """tokens: [T] int32 -> (float32 logits [T, vocab], the experts each
    sparse layer chose [sparse layers, T, k] int32 ascending, and what a
    cache keeps of the sequence `latents` [layers, T, r + d_r] float32:
    `c` then `k_r`) of one sequence. `forced` [sparse layers, T, k]: the
    experts each sparse layer USES instead (module docstring). `head_rows`
    (positions): the logits of THOSE rows only, `[len(head_rows), vocab]` —
    every position's are 0.6 GB at 8k tokens."""
    chosen, latents = [], []
    with jax.default_matmul_precision("highest"):
        x = _embed(params["wte"], tokens)
        for index, p in enumerate(layer_trees(params, arch)):
            h, c, k_r = _attention_jit(
                x, {k: p[k] for k in _ATTENTION_LEAVES}, arch)
            latents.append(jnp.concatenate([c, k_r], axis=-1))
            u = _pre_norm_jit(h, p["ln2_scale"], arch)
            if index < arch.dense_layers:
                y = _swiglu_jit(u, p["mlp_gate_w"], p["mlp_up_w"],
                                p["mlp_down_w"], arch)
            else:
                y, experts = routed_sum(
                    u, p, arch, forced=None if forced is None
                    else jnp.asarray(forced[len(chosen)], jnp.int32))
                y = y + shared_expert(u, p, arch)
                chosen.append(experts)
            x = h + y
        if head_rows is not None:
            x = x[jnp.asarray(head_rows, jnp.int32)]
        out = _head_jit(x, params["lnf_scale"], params["lm_head"], arch)
    return out, jnp.stack(chosen), jnp.stack(latents)


def logits(params, tokens, arch):
    """tokens: [T] int32 -> float32 logits [T, vocab] of one sequence."""
    return forward(params, tokens, arch)[0]
