"""Plain reference for the Granite 4.0-H family (`model_type:
granitemoehybrid`): the forward pass in straightforward `jax.numpy`, float32,
one sequence at a time, a Python loop over the halves (two a layer), the
recurrence a position at a time (a sequential `lax.scan`, NOT the chunked
form), dense attention in blocks of query rows, an expert at a time, no
kernels, no cache, no batching, no sort. Every matrix product runs under
`jax.default_matmul_precision("highest")` — on a TPU a float32 product
otherwise runs in bfloat16 passes. Imports `jax` only, nothing of
`deepspeed_tpu/`.

With `e`, `r`, `a`, `s` the family's four multipliers (`embedding_multiplier`,
`residual_multiplier`, `attention_multiplier`, `logits_scaling`):

    x_0    = e * wte[tokens]
    h      = x + r * mixer(RMSNorm_D(x; g1))        mixer by `layer_types`
    x'     = h + r * (routed(u) + shared(u)),       u = RMSNorm_D(h; g2)
    logits = RMSNorm_D(x_L; g) wte^T / s            (the head is the embedding)

`mamba`, Mamba-2 (H heads of P, inner width I = H P, ONE group, state N,
kernel K):
1. `[z | xBC | dt] = u W_in`, widths I | I + 2 N | H; no bias.
2. `xBC_t <- silu(b + sum_k w_k xBC_(t-K+1+k))` over k = 0..K-1, per column
   (causal, depthwise, WITH bias; zeros before the sequence).
3. `xBC = [x | B | C]`: x as [H, P], B and C as [N], shared by all heads
   (`mamba_n_groups` G: head h uses group `h // (H / G)`; published G = 1).
4. `dt_t = softplus(dt_t + dt_bias)`, `A_h = -exp(A_log_h)`,
   `S_t = exp(dt_t A_h) S_(t-1) + dt_t x_t (outer) B_t` (S in R^(P x N), float32,
   S_(-1) = 0), `y_t = S_t C_t + D_h x_t`.
5. `y <- RMSNorm_(I/G)(y * silu(z)) * w`: gate, then the norm (over all I
   columns at G = 1); `f = y W_out`.

`attention`: `q = u Wq` as [T, Hq, hd], k, v as [T, Hkv, hd], no bias, NO
positional embedding (`position_embedding_type: nope`); `s_ij = a q_i . k_j`
for `j <= i` (`a` = 0.0078125 = 1/128 as published, NOT 1/sqrt(128)),
softmax in float32; `f = concat_heads(softmax(s) v) Wo`.

The expert half: `z = u W_r` in float32 (no bias), the k experts with the
largest `z`, `w = softmax(z over those k)`; `routed = sum over the chosen e of
w_e (silu(u W_g_e) * (u W_u_e)) W_d_e`; `shared = (silu(u W_sg) * (u W_su))
W_sd`.

THE SHARE. `experts_held = (first, count)`: `routed` runs over the held
experts only — what the others would add is left out, as in the program; the
weights are still a softmax over all k chosen. The routed sum is linear in
its experts, so the parts of the chips that share a layer add up to the whole
layer's; the shared expert, the router and the mixers are every chip's.

DEPARTURES from the published description, each the program's too:
- it reads the PROGRAM'S parameter tree (`models/hybrid.py`: `runs`, a list
  of runs of the block pattern, each a list of one tree a half of the run's
  unit with a leading `[repeats]` axis), because "the same weights" is what
  is compared: q/k/v fused in one `[D, (Hq + 2 Hkv) hd]` matrix in that order
  (with a zero bias the tree carries), an expert's gate and up projections
  fused as `[D, 2F]` (gate first: the published `input_linear`'s halves), the
  shared expert's gate and up as two matrices (published: one fused
  `input_linear`); weights are cast to float32 a matrix at a time;
- the multi-group form of steps 3 and 5 is kept (the program's tests run two
  groups); at the published G = 1 it is the description above.

FORCED ROUTING, as `references/nemotron_h.py`: `forward(..., forced=sets)`
takes the experts it is GIVEN (`[layers, T, k]`) in place of its own top k,
weights from its own float32 logits of those experts; the experts it WOULD
have chosen are returned all the same.

STATES. `forward(..., states=[])` also hands back each Mamba-2 mixer's state
after the sequence's last position.

`round_to` / `state_round_to` / `router_round_to`: None for the reference
itself. `round_to` (a dtype, e.g. `float8_e4m3fn`) rounds every weight and
every matrix product's input through that type, a scale a row for a type
with a short range; `state_round_to` (e.g. `bfloat16`) rounds the recurrent
state after every position; `router_round_to` rounds the router's input,
weight and logits — the reference "computed in a lower precision" that the
benchmark's limits are set against, which no check uses.
"""

import dataclasses

import jax
import jax.numpy as jnp

MAMBA, ATTENTION, MOE = "M", "*", "E"
BLOCKS = {"mamba": "ME", "attention": "*E"}
ROW_BLOCK = 256         # query rows an attention block scores at a time


@dataclasses.dataclass(frozen=True)
class Arch:
    blocks: tuple           # a layer's halves, "ME" or "*E"
    runs: tuple             # ((unit length in blocks, repeats), ...)
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
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    norm_eps: float
    round_to: object = None
    state_round_to: object = None
    router_round_to: object = None


def pattern_runs(blocks):
    """The layout of the program's tree: the blocks as consecutive runs
    (unit length, repeats), from the front the repeated unit that covers the
    most layers (`models/layer_pattern.py::repeated_runs`, restated: this
    file imports nothing of the program)."""
    blocks = list(blocks)
    runs, at = [], 0
    while at < len(blocks):
        best = (1, 1)
        for length in range(1, (len(blocks) - at) // 2 + 1):
            unit, repeats = blocks[at:at + length], 1
            while blocks[at + repeats * length:
                         at + (repeats + 1) * length] == unit:
                repeats += 1
            if repeats > 1 and length * repeats > best[0] * best[1]:
                best = (length, repeats)
        runs.append(best)
        at += best[0] * best[1]
    return tuple(runs)


def arch_from_config(cfg, **rounding):
    """The configuration file's keys -> what the equations need."""
    if cfg["model_type"] != "granitemoehybrid":
        raise ValueError(f"no reference for model_type {cfg['model_type']!r}")
    if cfg["position_embedding_type"] != "nope" or cfg["attention_bias"] \
            or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"] \
            or cfg["hidden_act"] != "silu" \
            or cfg["normalization_function"] != "rmsnorm" \
            or not cfg["tie_word_embeddings"]:
        raise ValueError("this reference has no positions, no bias but the "
                         "convolution's, SiLU, RMSNorm and a tied head")
    blocks = tuple(BLOCKS[t] for t in cfg["layer_types"])
    return Arch(blocks=blocks, runs=pattern_runs(blocks),
                d_model=cfg["hidden_size"],
                n_head=cfg["num_attention_heads"],
                n_kv_head=cfg["num_key_value_heads"],
                head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
                mamba_num_heads=cfg["mamba_n_heads"],
                mamba_head_dim=cfg["mamba_d_head"],
                n_groups=cfg["mamba_n_groups"],
                ssm_state_size=cfg["mamba_d_state"],
                conv_kernel=cfg["mamba_d_conv"],
                num_experts=cfg["published_num_local_experts"],
                experts_held=tuple(cfg["experts_held_range"]),
                top_k=cfg["num_experts_per_tok"],
                embedding_multiplier=cfg["embedding_multiplier"],
                residual_multiplier=cfg["residual_multiplier"],
                attention_multiplier=cfg["attention_multiplier"],
                logits_scaling=cfg["logits_scaling"],
                norm_eps=cfg["rms_norm_eps"], **rounding)


class _LayerOf:
    """Layer `n` of a leaf stacked `[repeats, ...]`, indexed further on use:
    `_LayerOf(stack, n)[e]` is `stack[n, e]`, so one expert's matrices are
    read out of the stack and never a whole layer of them."""

    def __init__(self, stack, n):
        self.stack, self.n = stack, n

    def __getitem__(self, e):
        return self.stack[self.n, e]


def layer_trees(params, arch):
    """Every half's (letter, own leaves), in model order, one half at a time
    (a generator: a half's small leaves are sliced out of their stacks when
    it is reached, its experts only when each is used)."""
    at = 0
    for (length, repeats), trees in zip(arch.runs, params["runs"]):
        unit = "".join(arch.blocks[at:at + length])
        for n in range(repeats):
            for kind, tree in zip(unit, trees):
                yield kind, {
                    k: (_LayerOf(v, n) if k.startswith("moe_w_") else v[n])
                    for k, v in tree.items()}
        at += length * repeats


def _through(x, dtype):
    """float32 x rounded through a type of float32's range. Not a pair of
    casts: XLA may drop those (`xla_allow_excess_precision`), and on the TPU
    it does."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _rounded(x, dtype):
    if dtype is None:
        return x
    top = float(jnp.finfo(dtype).max)
    if top > 1e30:                      # bfloat16: float32's range
        return _through(x, dtype)
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _matmul(x, w, arch):
    return _rounded(x, arch.round_to) \
        @ _rounded(w.astype(jnp.float32), arch.round_to)


def _rms_norm(x, scale, arch):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + arch.norm_eps) * scale.astype(jnp.float32)


# ----------------------------------------------------------------------
# the mixers
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
_MAMBA_LEAVES = ("ln1_scale", "ssm_in_w", "conv_w", "conv_b", "dt_bias",
                 "A_log", "ssm_D", "gate_norm_scale", "ssm_out_w")


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
        scores = jnp.einsum(
            "thd,shd->hts", _rounded(q[lo:lo + ROW_BLOCK], arch.round_to),
            _rounded(k, arch.round_to)) * arch.attention_multiplier
        seen = rows[:, None] >= positions[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("hts,shd->thd", _rounded(probs, arch.round_to),
                              _rounded(v, arch.round_to)).reshape(-1, H * hd))
    return _matmul(jnp.concatenate(out, axis=0), p["attn_out_w"], arch) \
        + p["attn_out_b"]


_attention_jit = jax.jit(_attention, static_argnums=2)
_ATTENTION_LEAVES = ("ln1_scale", "attn_qkv_w", "attn_qkv_b", "attn_out_w",
                     "attn_out_b")


# ----------------------------------------------------------------------
# the expert half
# ----------------------------------------------------------------------


def _route(u, gate_w, arch, forced=None):
    """-> (weights [T, k] float32 of the experts USED, the experts used
    [T, k], the experts chosen [T, k]); used = chosen unless `forced`."""
    how = arch.router_round_to or arch.round_to
    logits = _rounded(_rounded(u, how) @ _rounded(gate_w.astype(jnp.float32),
                                                  how), arch.router_round_to)
    _, top_e = jax.lax.top_k(logits, arch.top_k)
    top_e = top_e.astype(jnp.int32)
    used = top_e if forced is None else forced
    return jax.nn.softmax(jnp.take_along_axis(logits, used, axis=-1), -1), \
        used, top_e


_route_jit = jax.jit(_route, static_argnums=2)


def route(u, gate_w, arch):
    """The router on normed rows u [T, D] -> (weights [T, k] float32, experts
    [T, k] int32)."""
    top_w, _, top_e = _route(u, gate_w, arch)
    return top_w, top_e


def _swiglu(u, gate, up, down, arch):
    return _matmul(jax.nn.silu(_matmul(u, gate, arch))
                   * _matmul(u, up, arch), down, arch)


def _expert_part(u, weight, gate_up, down, arch):
    """One expert's weighted gated MLP on EVERY row (rows that did not choose
    it carry weight zero): the same sum as a gather of its rows."""
    F = down.shape[0]
    return weight[:, None] * _swiglu(u, gate_up[:, :F], gate_up[:, F:], down,
                                     arch)


_expert_jit = jax.jit(_expert_part, static_argnums=4)
_shared_jit = jax.jit(_swiglu, static_argnums=4)
_normed_jit = jax.jit(_rms_norm, static_argnums=2)


def routed(u, p, arch, held=None, forced=None):
    """The routed experts' weighted sum over the experts `held = (first,
    count)` (None: `arch.experts_held`), whose weights are `p`'s
    `moe_w_gate_up` / `moe_w_down` in that order, on normed rows u [T, D] ->
    (sum [T, D], chosen experts [T, k] ascending). `forced` [T, k]: the sum
    is over THESE experts."""
    first, count = held or arch.experts_held or (0, arch.num_experts)
    top_w, used, top_e = _route_jit(u, p["moe_gate_w"], arch, forced)
    out = jnp.zeros_like(u)
    for local in range(count):
        weight = jnp.sum(jnp.where(used == first + local, top_w, 0.0), -1)
        out = out + _expert_jit(u, weight, p["moe_w_gate_up"][local],
                                p["moe_w_down"][local], arch)
    return out, jnp.sort(top_e, axis=-1)


def experts(x, p, arch, held=None, forced=None, shared=True):
    """`f(RMSNorm(x))` of the expert half on one sequence x [T, D] -> (f,
    chosen experts). `shared=False`: the routed part alone (one chip's part
    of the sum)."""
    u = _normed_jit(x, p["ln1_scale"], arch)
    out, chosen = routed(u, p, arch, held, forced)
    if shared:
        out = out + _shared_jit(u, p["shared_gate_w"], p["shared_up_w"],
                                p["shared_down_w"], arch)
    return out, chosen


# ----------------------------------------------------------------------


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


def _head(x, scale, table, arch):
    return _matmul(_rms_norm(x, scale, arch), table.astype(jnp.float32).T,
                   arch) / arch.logits_scaling


_head_jit = jax.jit(_head, static_argnums=3)


def forward(params, tokens, arch, forced=None, states=None):
    """tokens: [T] int32 -> (float32 logits [T, vocab], the experts each
    layer chose [layers, T, k] int32, ascending) of one sequence. `forced`
    [layers, T, k]: the experts each layer USES instead. `states`: a list
    that takes each Mamba-2 mixer's state after the last position,
    [H, P, N] float32."""
    chosen = []
    r = arch.residual_multiplier
    with jax.default_matmul_precision("highest"):
        x = arch.embedding_multiplier * _embed(params["wte"], tokens)
        for kind, p in layer_trees(params, arch):       # the 2 L halves
            if kind == MAMBA:
                out, state = _mamba_jit(x, {k: p[k] for k in _MAMBA_LEAVES},
                                        arch)
                if states is not None:
                    states.append(state)
            elif kind == ATTENTION:
                out = _attention_jit(
                    x, {k: p[k] for k in _ATTENTION_LEAVES}, arch)
            else:
                out, sets = experts(
                    x, p, arch, forced=None if forced is None
                    else jnp.asarray(forced[len(chosen)], jnp.int32))
                chosen.append(sets)
            x = x + r * out
        out = _head_jit(x, params["lnf_scale"], params["wte"], arch)
    return out, (jnp.stack(chosen) if chosen else jnp.zeros((0,), jnp.int32))


def logits(params, tokens, arch):
    """tokens: [T] int32 -> float32 logits [T, vocab] of one sequence."""
    return forward(params, tokens, arch)[0]
