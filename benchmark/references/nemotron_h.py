"""Plain reference for the Nemotron-H family (`model_type: nemotron_h`): the
forward pass in straightforward `jax.numpy`, float32, one sequence at a time,
the recurrence a position at a time (a sequential `lax.scan`, NOT the chunked
form), dense attention in blocks of query rows, an expert at a time, no
kernels, no cache, no batching, no sort. Every matrix product runs under
`jax.default_matmul_precision("highest")` — on a TPU a float32 product
otherwise runs in bfloat16 passes. Imports `jax` only.

Every layer is `x <- x + f(RMSNorm_D(x; g))` (eps `norm_eps`), `f` by the
letter of `pattern` at the layer; then a final RMSNorm and the untied head.

`M`, Mamba-2 (H heads of P, inner width I = H P, G groups, state N, kernel K):
1. `[z | xBC | dt] = u W_in`, widths I | I + 2 G N | H; no bias.
2. `xBC_t <- silu(b + sum_k w_k xBC_(t-K+1+k))` over k = 0..K-1, per column
   (causal, depthwise; zeros before the sequence).
3. `xBC = [x | B | C]`: x as [H, P], B and C as [G, N]; head h uses group
   `h // (H / G)`.
4. `dt_t = softplus(dt_t + dt_bias)`, `A_h = -exp(A_log_h)`,
   `S_t = exp(dt_t A_h) S_(t-1) + dt_t x_t (outer) B_t` (S in R^(P x N), float32,
   S_(-1) = 0), `y_t = S_t C_t + D_h x_t`.
5. `y <- RMSNorm_(I/G)(y * silu(z)) * w`: gate, then a norm over each group's
   I / G columns; `f = y W_out`.

`*`, attention: `q = u Wq` as [T, Hq, hd], k, v as [T, Hkv, hd], no bias, NO
rotary (the family's published modelling code applies none); `s_ij = q_i .
k_j / sqrt(hd)` for `j <= i`, softmax in float32; `f = concat_heads(softmax(s)
v) Wo`.

`E`, LatentMoE: `z = u W_r` in float32, `s = sigmoid(z)`; the k experts with
the largest `s + b`; `w_e = scale * s_e / (sum of the chosen s + 1e-20)`;
`l = u W_lat_down`; `r = sum over the chosen e of w_e relu(l W_up_e)^2
W_down_e`; `f = r W_lat_up + relu(u W_s_up)^2 W_s_down`.

THE SHARE. `experts_held = (first, count)`: `r` runs over the held experts
only — what the others would add is left out, as in the program; the weights
are still normalised over all k chosen. `W_lat_up` is linear, so the parts of
the chips that share a layer add up to the whole layer's `r W_lat_up`; the
shared expert, the router and both latent projections are every chip's.

It reads the PROGRAM'S parameter tree (`models/nemotron_h.py`: `runs`, a list
of runs of the pattern, each a list of one tree a position of the run's unit
with a leading `[repeats]` axis; q/k/v fused in one `[D, (Hq + 2 Hkv) hd]`
matrix in that order), because "the same weights" is what is compared.
Weights are cast to float32 a matrix at a time.

FORCED ROUTING, as `references/exaone_moe.py`: `forward(..., forced=sets)`
takes the experts it is GIVEN (`[LatentMoE layers, T, k]`) in place of its
own top k, weights from its own float32 scores of those experts; the experts
it WOULD have chosen are returned all the same.

STATES. `forward(..., states=[])` also hands back each Mamba-2 layer's state
after the sequence's last position: what a serving cache must hold for the
sequence at that point, compared on its own.

`round_to` / `state_round_to`: None for the reference itself. `round_to` (a
dtype, e.g. `float8_e4m3fn`) rounds every weight and every matrix product's
input through that type, a scale a row for a type with a short range;
`state_round_to` (e.g. `bfloat16`) rounds the recurrent state after every
position — the reference "computed in a lower precision" that the
benchmark's limits are set against, which no check uses.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp

MAMBA, ATTENTION, MOE = "M", "*", "E"
ROW_BLOCK = 256         # query rows an attention block scores at a time


@dataclasses.dataclass(frozen=True)
class Arch:
    pattern: str
    runs: tuple             # ((unit length, repeats), ...): the tree's layout
    d_model: int
    n_head: int
    n_kv_head: int
    head_dim: int
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    num_experts: int        # the router's width
    experts_held: object    # (first, count) or None = all
    top_k: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    norm_eps: float
    round_to: object = None
    state_round_to: object = None


def pattern_runs(pattern):
    """The layout of the program's tree: the pattern as consecutive runs
    (unit length, repeats), from the front the repeated unit that covers the
    most layers (`models/layer_pattern.py::repeated_runs`, restated: this
    file imports nothing of the program)."""
    runs, at = [], 0
    while at < len(pattern):
        best = (1, 1)
        for length in range(1, (len(pattern) - at) // 2 + 1):
            unit, repeats = pattern[at:at + length], 1
            while pattern[at + repeats * length:
                          at + (repeats + 1) * length] == unit:
                repeats += 1
            if repeats > 1 and length * repeats > best[0] * best[1]:
                best = (length, repeats)
        runs.append(best)
        at += best[0] * best[1]
    return tuple(runs)


def arch_from_config(cfg, round_to=None, state_round_to=None):
    """The configuration file's keys -> what the equations need."""
    if cfg["model_type"] != "nemotron_h":
        raise ValueError(f"no reference for model_type {cfg['model_type']!r}")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["n_shared_experts"] != 1 \
            or cfg["mlp_hidden_act"] != "relu2" \
            or cfg["mamba_hidden_act"] != "silu":
        raise ValueError("this reference has the sigmoid router without "
                         "groups, one shared expert, relu2 experts and a "
                         "SiLU convolution")
    pattern = cfg["hybrid_override_pattern"]
    return Arch(pattern=pattern, runs=pattern_runs(pattern),
                d_model=cfg["hidden_size"],
                n_head=cfg["num_attention_heads"],
                n_kv_head=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"],
                mamba_num_heads=cfg["mamba_num_heads"],
                mamba_head_dim=cfg["mamba_head_dim"],
                n_groups=cfg["n_groups"],
                ssm_state_size=cfg["ssm_state_size"],
                conv_kernel=cfg["conv_kernel"],
                num_experts=cfg["published_n_routed_experts"],
                experts_held=tuple(cfg["experts_held_range"]),
                top_k=cfg["num_experts_per_tok"],
                norm_topk_prob=cfg["norm_topk_prob"],
                routed_scaling_factor=cfg["routed_scaling_factor"],
                norm_eps=cfg["norm_eps"], round_to=round_to,
                state_round_to=state_round_to)


class _LayerOf:
    """Layer `n` of a leaf stacked `[repeats, ...]`, indexed further on use:
    `_LayerOf(stack, n)[e]` is `stack[n, e]`, so one expert's matrices are
    read out of the stack and never a whole layer of them."""

    def __init__(self, stack, n):
        self.stack, self.n = stack, n

    def __getitem__(self, e):
        return self.stack[self.n, e]


def layer_trees(params, arch):
    """Every layer's (letter, own leaves), in model order, one layer at a
    time (a generator: a layer's small leaves are sliced out of their stacks
    when the layer is reached, its experts only when each is used)."""
    at = 0
    for (length, repeats), trees in zip(arch.runs, params["runs"]):
        for n in range(repeats):
            for i, tree in enumerate(trees):
                yield arch.pattern[at + i], {
                    k: (_LayerOf(v, n) if k.startswith("moe_w_") else v[n])
                    for k, v in tree.items()}
        at += length * repeats


def _through(x, dtype):
    """float32 x rounded through a type of float32's range. Not a pair of
    casts: XLA may drop those (`xla_allow_excess_precision`), and on the TPU
    it does."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _rounded(x, arch):
    if arch.round_to is None:
        return x
    top = float(jnp.finfo(arch.round_to).max)
    if top > 1e30:                      # bfloat16: float32's range
        return _through(x, arch.round_to)
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / top
    return (x / scale).astype(arch.round_to).astype(jnp.float32) * scale


def _matmul(x, w, arch):
    return _rounded(x, arch) @ _rounded(w.astype(jnp.float32), arch)


def _rms_norm(x, scale, arch):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + arch.norm_eps) * scale.astype(jnp.float32)


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


# ----------------------------------------------------------------------
# M
# ----------------------------------------------------------------------


def _mamba(x, p, arch):
    """Steps 1-5 on one sequence x [T, D] -> (f(RMSNorm(x)), the state after
    the last position [H, P, N])."""
    T = x.shape[0]
    H, P = arch.mamba_num_heads, arch.mamba_head_dim
    G, N, K = arch.n_groups, arch.ssm_state_size, arch.conv_kernel
    inner, f32 = H * P, jnp.float32
    u = _rms_norm(x, p["ln1_scale"], arch)
    zxbcdt = _matmul(u, p["ssm_in_w"], arch)
    z, xBC, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * G * N], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1]), f32), xBC])
    w = p["conv_w"].astype(f32)
    xBC = jax.nn.silu(p["conv_b"].astype(f32) + sum(
        w[k] * padded[k:k + T] for k in range(K)))
    xs = xBC[:, :inner].reshape(T, H, P)
    Bs = jnp.repeat(xBC[:, inner:inner + G * N].reshape(T, G, N), H // G, 1)
    Cs = jnp.repeat(xBC[:, inner + G * N:].reshape(T, G, N), H // G, 1)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(f32))         # [T, H]
    A = -jnp.exp(p["A_log"].astype(f32))

    def step(S, inputs):
        x_t, B_t, C_t, dt_t = inputs
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        if arch.state_round_to is not None:
            S = _through(S, arch.state_round_to)
        return S, jnp.sum(S * C_t[:, None, :], axis=-1)

    S, y = jax.lax.scan(step, jnp.zeros((H, P, N), f32), (xs, Bs, Cs, dt))
    y = y + p["ssm_D"].astype(f32)[:, None] * xs
    gated = (y.reshape(T, inner) * jax.nn.silu(z)).reshape(T, G, inner // G)
    gated = gated * jax.lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True)
                                  + arch.norm_eps)
    return _matmul(gated.reshape(T, inner)
                   * p["gate_norm_scale"].astype(f32), p["ssm_out_w"],
                   arch), S


_mamba_jit = jax.jit(_mamba, static_argnums=2)


# ----------------------------------------------------------------------
# *
# ----------------------------------------------------------------------


def _attention(x, p, arch):
    T = x.shape[0]
    H, Hkv, hd = arch.n_head, arch.n_kv_head, arch.head_dim
    positions = jnp.arange(T)
    u = _rms_norm(x, p["ln1_scale"], arch)
    qkv = _matmul(u, p["attn_qkv_w"], arch) + p["attn_qkv_b"]
    q = qkv[:, :H * hd].reshape(T, H, hd)
    k = jnp.repeat(qkv[:, H * hd:(H + Hkv) * hd].reshape(T, Hkv, hd),
                   H // Hkv, axis=1)
    v = jnp.repeat(qkv[:, (H + Hkv) * hd:].reshape(T, Hkv, hd), H // Hkv,
                   axis=1)
    out = []
    for lo in range(0, T, ROW_BLOCK):           # blocks of query rows
        rows = positions[lo:lo + ROW_BLOCK]
        scores = jnp.einsum("thd,shd->hts", _rounded(q[lo:lo + ROW_BLOCK],
                                                     arch),
                            _rounded(k, arch)) / math.sqrt(hd)
        seen = rows[:, None] >= positions[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("hts,shd->thd", _rounded(probs, arch),
                              _rounded(v, arch)).reshape(-1, H * hd))
    return _matmul(jnp.concatenate(out, axis=0), p["attn_out_w"], arch) \
        + p["attn_out_b"]


_attention_jit = jax.jit(_attention, static_argnums=2)
_ATTENTION_LEAVES = ("ln1_scale", "attn_qkv_w", "attn_qkv_b", "attn_out_w",
                     "attn_out_b")


# ----------------------------------------------------------------------
# E
# ----------------------------------------------------------------------


def _route(u, gate_w, bias, arch, forced=None):
    """-> (weights [T, k] float32 of the experts USED, the experts used
    [T, k], the experts chosen [T, k]); used = chosen unless `forced`."""
    scores = jax.nn.sigmoid(_matmul(u, gate_w, arch))
    _, top_e = jax.lax.top_k(scores + bias.astype(jnp.float32), arch.top_k)
    top_e = top_e.astype(jnp.int32)
    used = top_e if forced is None else forced
    top_w = jnp.take_along_axis(scores, used, axis=-1)
    if arch.norm_topk_prob:
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    return top_w * arch.routed_scaling_factor, used, top_e


_route_jit = jax.jit(_route, static_argnums=3)


def route(u, gate_w, bias, arch):
    """The router on normed rows u [T, D] -> (weights [T, k] float32, experts
    [T, k] int32)."""
    top_w, _, top_e = _route(u, gate_w, bias, arch)
    return top_w, top_e


def _expert_part(latent, weight, up, down, arch):
    """One expert's weighted `relu2` MLP on EVERY latent row (rows that did
    not choose it carry weight zero): the same sum as a gather of its rows."""
    return weight[:, None] * _matmul(_relu2(_matmul(latent, up, arch)), down,
                                     arch)


_expert_jit = jax.jit(_expert_part, static_argnums=4)


_normed_jit = jax.jit(_rms_norm, static_argnums=2)
_matmul_jit = jax.jit(_matmul, static_argnums=2)


def routed_latent(u, p, arch, held=None, forced=None):
    """The routed experts' weighted sum IN THE LATENT SPACE over the experts
    `held = (first, count)` (None: `arch.experts_held`), whose weights are
    `p`'s `moe_w_up` / `moe_w_down` in that order, on normed rows u [T, D]
    -> (sum [T, latent], chosen experts [T, k] ascending). `forced` [T, k]:
    the sum is over THESE experts."""
    first, count = held or arch.experts_held or (0, arch.num_experts)
    top_w, used, top_e = _route_jit(u, p["moe_gate_w"], p["moe_gate_bias"],
                                    arch, forced)
    latent = _matmul_jit(u, p["lat_down_w"], arch)
    out = jnp.zeros_like(latent)
    for local in range(count):
        weight = jnp.sum(jnp.where(used == first + local, top_w, 0.0), -1)
        out = out + _expert_jit(latent, weight, p["moe_w_up"][local],
                                p["moe_w_down"][local], arch)
    return out, jnp.sort(top_e, axis=-1)


def _shared(u, up, down, arch):
    return _matmul(_relu2(_matmul(u, up, arch)), down, arch)


_shared_jit = jax.jit(_shared, static_argnums=3)


def latent_moe(x, p, arch, held=None, forced=None, shared=True):
    """`f(RMSNorm(x))` of a LatentMoE layer on one sequence x [T, D] ->
    (f, chosen experts). `shared=False`: the routed part alone (one chip's
    part of the sum, `W_lat_up` applied)."""
    u = _normed_jit(x, p["ln1_scale"], arch)
    routed, experts = routed_latent(u, p, arch, held, forced)
    out = _matmul_jit(routed, p["lat_up_w"], arch)
    if shared:
        out = out + _shared_jit(u, p["shared_up_w"], p["shared_down_w"], arch)
    return out, experts


# ----------------------------------------------------------------------


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


def _head(x, scale, table, arch):
    return _matmul(_rms_norm(x, scale, arch), table.astype(jnp.float32).T,
                   arch)


_head_jit = jax.jit(_head, static_argnums=3)
_MAMBA_LEAVES = ("ln1_scale", "ssm_in_w", "conv_w", "conv_b", "dt_bias",
                 "A_log", "ssm_D", "gate_norm_scale", "ssm_out_w")


def forward(params, tokens, arch, forced=None, states=None):
    """tokens: [T] int32 -> (float32 logits [T, vocab], the experts each
    LatentMoE layer chose [layers, T, k] int32, ascending) of one sequence.
    `forced` [layers, T, k]: the experts each such layer USES instead.
    `states`: a list that takes each Mamba-2 layer's state after the last
    position, [H, P, N] float32."""
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _embed(params["wte"], tokens)
        for kind, p in layer_trees(params, arch):
            if kind == MAMBA:
                out, state = _mamba_jit(x, {k: p[k] for k in _MAMBA_LEAVES},
                                        arch)
                x = x + out
                if states is not None:
                    states.append(state)
            elif kind == ATTENTION:
                x = x + _attention_jit(
                    x, {k: p[k] for k in _ATTENTION_LEAVES}, arch)
            else:
                out, experts = latent_moe(
                    x, p, arch, forced=None if forced is None
                    else jnp.asarray(forced[len(chosen)], jnp.int32))
                x = x + out
                chosen.append(experts)
        out = _head_jit(x, params["lnf_scale"], params["lm_head"], arch)
    return out, (jnp.stack(chosen) if chosen else jnp.zeros((0,), jnp.int32))


def logits(params, tokens, arch):
    """tokens: [T] int32 -> float32 logits [T, vocab] of one sequence."""
    return forward(params, tokens, arch)[0]
