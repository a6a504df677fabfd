"""Plain reference for the Qwen3-Next family (`model_type: qwen3_next`): the
forward pass in straightforward `jax.numpy`, float32, one sequence at a
time, a Python loop over the halves (two a layer), the delta rule a position
at a time (a sequential `lax.scan`, NEVER the chunked form), dense attention
in blocks of query rows, an expert at a time, no kernels, no cache, no
batching, no sort. Every matrix product runs under
`jax.default_matmul_precision("highest")` — on a TPU a float32 product
otherwise runs in bfloat16 passes. Imports `jax` only, nothing of
`deepspeed_tpu/`.

    x_0    = wte[tokens]
    h      = x + mixer(RMSNorm_D(x; g1))            mixer by the layer's kind
    x'     = h + routed(u) + sigmoid(u . w_s) shared(u),    u = RMSNorm_D(h; g2)
    logits = RMSNorm_D(x_L; g) W_head^T             (the head is its own matrix)

Layer i is `full_attention` where `(i + 1) % full_attention_interval == 0`,
else `linear_attention`. `RMSNorm(x; g) = x rsqrt(mean x^2 + eps) g` with the
stored `g` the value that multiplies (the published zero-centred `1 + w`).

`linear_attention`, Gated DeltaNet (G key heads and H value heads, widths K
and V, kernel C):
1. `[q | k | v | z] = u W_qkvz`, widths G K | G K | H V | H V; `[b | a] =
   u W_ba`, widths H | H; no bias.
2. `[q | k | v]_t <- silu(sum_c w_c [q | k | v]_(t-C+1+c))` per column
   (causal, depthwise, NO bias; zeros before the sequence).
3. q, k as [G, K]: `q <- q rsqrt(sum q^2 + 1e-6) / sqrt(K)`, `k <- k rsqrt(sum
   k^2 + 1e-6)`; value head h reads key head `h // (H / G)`. v as [H, V].
4. `beta_t = sigmoid(b_t)`, `g_t = -exp(A_log) softplus(a_t + dt_bias)`, a
   scalar a value head. Per value head, S in R^(K x V) float32, S_(-1) = 0:
   `S <- exp(g_t) S`; `r = S^T k_t`; `u = beta_t (v_t - r)`;
   `S <- S + k_t (outer) u`; `o_t = S^T q_t`.
5. `o <- RMSNorm_V(o; w) * silu(z)` a head (the norm FIRST, then the gate);
   `f = o W_out`.

`full_attention`: `[q | k | v | gate] = u W_qkv` (q and gate as [Hq, hd], k,
v as [Hkv, hd]); `q <- RMSNorm_hd(q; gq)`, `k <- RMSNorm_hd(k; gk)` a head;
rotary at the absolute position on the first `partial_rotary_factor * hd`
columns; `s_ij = q_i . k_j / sqrt(hd)` for `j <= i`, softmax in float32;
`f = (concat_heads(softmax(s) v) * sigmoid(gate)) Wo`.

The expert half: `z = u W_r` in float32 (no bias), `p = softmax(z)` over all
the router's outputs, the k experts with the largest p, their p renormalised
(`norm_topk_prob`: the same numbers as a softmax over the chosen k logits,
which is how it is computed); `routed = sum over the chosen e of w_e (silu(u
W_g_e) * (u W_u_e)) W_d_e`; `shared = (silu(u W_sg) * (u W_su)) W_sd`, times
`sigmoid(u . w_s)`, a scalar a token.

THE SHARE. `experts_held = (first, count)`: `routed` runs over the held
experts only — what the others would add is left out, as in the program; the
weights are still renormalised over all k chosen. The routed sum is linear
in its experts, so the parts of the chips that share a layer add up to the
whole layer's; the gated shared expert, the router and the mixers are every
chip's.

DEPARTURES from the published description, each the program's too:
- it reads the PROGRAM'S parameter tree (`models/hybrid.py`: `runs`, a list
  of runs of the block pattern, each a list of one tree a half of the run's
  unit with a leading `[repeats]` axis), because "the same weights" is what
  is compared: `W_qkvz`'s columns are `[q | k | v | z]` whole and `W_ba`'s
  `[b | a]` (published: interleaved a key head); q, k, v and the gate of an
  attention layer are one `[D, (2 Hq + 2 Hkv) hd]` matrix in the order
  `q | k | v | gate` (published: `q_proj` twice as wide, a head's columns
  `[q | gate]`; with a zero bias the tree carries); an expert's gate and up
  projections fused as `[D, 2F]` (gate first); weights are cast to float32 a
  matrix at a time;
- rotary pairs are interleaved (`x[0::2]`, `x[1::2]` of the rotary columns)
  where the published code rotates halves: a fixed permutation of a head's
  rotary columns of q and k alike, invisible to random weights;
- the multi-token-prediction module is not computed.

FORCED ROUTING, as `references/granite_moe_hybrid.py`: `forward(...,
forced=sets)` takes the experts it is GIVEN (`[layers, T, k]`) in place of
its own top k, weights from its own float32 logits of those experts; the
experts it WOULD have chosen are returned all the same.

STATES. `forward(..., states=[])` also hands back each Gated DeltaNet
mixer's state after the sequence's last position, `[H, K, V]`.

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

DELTANET, ATTENTION, MOE = "D", "*", "E"
BLOCKS = {"linear_attention": "DE", "full_attention": "*E"}
ROW_BLOCK = 256         # query rows an attention block scores at a time
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Arch:
    blocks: tuple           # a layer's halves, "DE" or "*E"
    runs: tuple             # ((unit length in blocks, repeats), ...)
    d_model: int
    n_head: int
    n_kv_head: int
    head_dim: int
    rotary_dims: int        # a head's first columns that rotate
    rope_theta: float
    key_heads: int          # Gated DeltaNet: G
    value_heads: int        # H
    key_dim: int            # K
    value_dim: int          # V
    conv_kernel: int
    num_experts: int        # the router's width
    experts_held: object    # (first, count) or None = all
    top_k: int
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


def layer_blocks(num_layers, full_attention_interval):
    return tuple(BLOCKS["full_attention"
                        if (i + 1) % full_attention_interval == 0
                        else "linear_attention"] for i in range(num_layers))


def arch_from_config(cfg, **rounding):
    """The configuration file's keys -> what the equations need."""
    if cfg["model_type"] != "qwen3_next":
        raise ValueError(f"no reference for model_type {cfg['model_type']!r}")
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"] \
            or not cfg["norm_topk_prob"] or cfg["mlp_only_layers"] \
            or cfg["decoder_sparse_step"] != 1 or cfg["rope_scaling"] \
            or cfg["use_sliding_window"]:
        raise ValueError("this reference has SiLU, an untied head, "
                         "renormalised top-k weights, experts in every "
                         "layer, plain rotary and no window")
    blocks = layer_blocks(cfg["num_hidden_layers"],
                          cfg["full_attention_interval"])
    hd = cfg["head_dim"]
    return Arch(blocks=blocks, runs=pattern_runs(blocks),
                d_model=cfg["hidden_size"],
                n_head=cfg["num_attention_heads"],
                n_kv_head=cfg["num_key_value_heads"], head_dim=hd,
                rotary_dims=int(cfg["partial_rotary_factor"] * hd) // 2 * 2,
                rope_theta=float(cfg["rope_theta"]),
                key_heads=cfg["linear_num_key_heads"],
                value_heads=cfg["linear_num_value_heads"],
                key_dim=cfg["linear_key_head_dim"],
                value_dim=cfg["linear_value_head_dim"],
                conv_kernel=cfg["linear_conv_kernel_dim"],
                num_experts=cfg["published_num_experts"],
                experts_held=tuple(cfg["experts_held_range"]),
                top_k=cfg["num_experts_per_tok"],
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


def _deltanet(x, p, arch):
    """Steps 1-5 on one sequence x [T, D] -> (f(RMSNorm(x)), the state after
    the last position [H, K, V])."""
    T = x.shape[0]
    G, H = arch.key_heads, arch.value_heads
    K, V, C = arch.key_dim, arch.value_dim, arch.conv_kernel
    f32 = jnp.float32
    u = _rms_norm(x, p["ln1_scale"], arch)
    qkv, z = jnp.split(_matmul(u, p["gdn_qkvz_w"], arch),
                       [2 * G * K + H * V], axis=-1)
    b, a = jnp.split(_matmul(u, p["gdn_ba_w"], arch), 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((C - 1, qkv.shape[1]), f32), qkv])
    w = p["conv_w"].astype(f32)
    qkv = jax.nn.silu(sum(w[c] * padded[c:c + T] for c in range(C)))

    def unit(v):
        v = v.reshape(T, G, K)
        v = v * jax.lax.rsqrt(jnp.sum(v * v, -1, keepdims=True) + L2_EPS)
        return jnp.repeat(v, H // G, axis=1)                # [T, H, K]

    qs = unit(qkv[:, :G * K]) / jnp.sqrt(f32(K))
    ks = unit(qkv[:, G * K:2 * G * K])
    vs = qkv[:, 2 * G * K:].reshape(T, H, V)
    beta = jax.nn.sigmoid(b)                                # [T, H]
    g = -jnp.exp(p["A_log"].astype(f32)) \
        * jax.nn.softplus(a + p["dt_bias"].astype(f32))

    def step(S, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs
        S = jnp.exp(g_t)[:, None, None] * S
        held = jnp.sum(S * k_t[:, :, None], axis=1)         # S^T k: [H, V]
        S = S + k_t[:, :, None] * (beta_t[:, None] * (v_t - held))[:, None, :]
        if arch.state_round_to is not None:
            S = _through(S, arch.state_round_to)
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    S, o = jax.lax.scan(step, jnp.zeros((H, K, V), f32),
                        (qs, ks, vs, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + arch.norm_eps)
    gated = o * p["gate_norm_scale"].astype(f32) \
        * jax.nn.silu(z.reshape(T, H, V))
    return _matmul(gated.reshape(T, H * V), p["gdn_out_w"], arch), S


_deltanet_jit = jax.jit(_deltanet, static_argnums=2)
_DELTANET_LEAVES = ("ln1_scale", "gdn_qkvz_w", "gdn_ba_w", "conv_w",
                    "dt_bias", "A_log", "gate_norm_scale", "gdn_out_w")


def _rope(x, positions, arch):
    """x: [T, heads, hd]. Rotates the first `rotary_dims` columns, in (even,
    odd) pairs."""
    rd = arch.rotary_dims
    freqs = arch.rope_theta ** (-jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    angles = positions[:, None].astype(jnp.float32) * freqs     # [T, rd/2]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., 0:rd:2], x[..., 1:rd:2]
    rotated = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(x.shape[:-1] + (rd,))
    return jnp.concatenate([rotated, x[..., rd:]], axis=-1)


def _attention(x, p, arch):
    T = x.shape[0]
    H, Hkv, hd = arch.n_head, arch.n_kv_head, arch.head_dim
    positions = jnp.arange(T)
    u = _rms_norm(x, p["ln1_scale"], arch)
    qkv = _matmul(u, p["attn_qkv_w"], arch) + p["attn_qkv_b"]
    q, k, v, gate = jnp.split(
        qkv, [H * hd, (H + Hkv) * hd, (H + 2 * Hkv) * hd], axis=-1)
    q = _rope(_rms_norm(q.reshape(T, H, hd), p["q_norm_scale"], arch),
              positions, arch)
    k = _rope(_rms_norm(k.reshape(T, Hkv, hd), p["k_norm_scale"], arch),
              positions, arch)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v.reshape(T, Hkv, hd), H // Hkv, axis=1)
    out = []
    for lo in range(0, T, ROW_BLOCK):           # blocks of query rows
        rows = positions[lo:lo + ROW_BLOCK]
        scores = jnp.einsum(
            "thd,shd->hts", _rounded(q[lo:lo + ROW_BLOCK], arch.round_to),
            _rounded(k, arch.round_to)) / jnp.sqrt(jnp.float32(hd))
        seen = rows[:, None] >= positions[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("hts,shd->thd", _rounded(probs, arch.round_to),
                              _rounded(v, arch.round_to)).reshape(-1, H * hd))
    gated = jnp.concatenate(out, axis=0) * jax.nn.sigmoid(gate)
    return _matmul(gated, p["attn_out_w"], arch) + p["attn_out_b"]


_attention_jit = jax.jit(_attention, static_argnums=2)
_ATTENTION_LEAVES = ("ln1_scale", "attn_qkv_w", "attn_qkv_b", "q_norm_scale",
                     "k_norm_scale", "attn_out_w", "attn_out_b")


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


def _shared(u, gate, up, down, scale, arch):
    """The shared expert on every row, times `sigmoid(u . w_s)`."""
    return jax.nn.sigmoid(_matmul(u, scale[:, None], arch)) \
        * _swiglu(u, gate, up, down, arch)


def _expert_part(u, weight, gate_up, down, arch):
    """One expert's weighted gated MLP on EVERY row (rows that did not choose
    it carry weight zero): the same sum as a gather of its rows."""
    F = down.shape[0]
    return weight[:, None] * _swiglu(u, gate_up[:, :F], gate_up[:, F:], down,
                                     arch)


_expert_jit = jax.jit(_expert_part, static_argnums=4)
_shared_jit = jax.jit(_shared, static_argnums=5)
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
                                p["shared_down_w"], p["shared_scale_w"], arch)
    return out, chosen


# ----------------------------------------------------------------------


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


def _head(x, scale, table, arch):
    return _matmul(_rms_norm(x, scale, arch), table.astype(jnp.float32).T,
                   arch)


_head_jit = jax.jit(_head, static_argnums=3)


def forward(params, tokens, arch, forced=None, states=None):
    """tokens: [T] int32 -> (float32 logits [T, vocab], the experts each
    layer chose [layers, T, k] int32, ascending) of one sequence. `forced`
    [layers, T, k]: the experts each layer USES instead. `states`: a list
    that takes each Gated DeltaNet mixer's state after the last position,
    [H, K, V] float32."""
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _embed(params["wte"], tokens)
        for kind, p in layer_trees(params, arch):       # the 2 L halves
            if kind == DELTANET:
                out, state = _deltanet_jit(
                    x, {k: p[k] for k in _DELTANET_LEAVES}, arch)
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
            x = x + out
        out = _head_jit(x, params["lnf_scale"], params["lm_head"], arch)
    return out, (jnp.stack(chosen) if chosen else jnp.zeros((0,), jnp.int32))


def logits(params, tokens, arch):
    """tokens: [T] int32 -> float32 logits [T, vocab] of one sequence."""
    return forward(params, tokens, arch)[0]
