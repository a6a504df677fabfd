"""Plain reference for the Olmo-Hybrid family (`model_type: olmo_hybrid`): the
forward pass, the loss and (for the tests) the gradients in straightforward
`jax.numpy`, float32, one sequence at a time, a Python loop over the halves
(two a layer), the delta rule ONE POSITION AT A TIME (a sequential `lax.scan`
over T, never the chunked form), dense causal attention in blocks of query
rows so that 32768 positions fit, the feed-forward and the head in blocks of
rows, no kernels, no cache, no batching. The gradients are autodiff of these
same functions, a half at a time in reverse (`loss_and_grads`): what makes
that fit beside a training state is bookkeeping alone — a half's input is
parked on the host between the two sweeps, and a half's forward is made
again for its backward a segment of positions at a time on the carried
state. Every matrix
product runs under `jax.default_matmul_precision("highest")` — on a TPU a
float32 product otherwise runs in bfloat16 passes. Imports `jax` only,
nothing of `deepspeed_tpu/`.

    x_0    = wte[tokens]
    h      = x + RMSNorm_D(mixer(x); g1)            mixer by the layer's kind
    x'     = h + RMSNorm_D(swiglu(h); g2)           (silu(h W_g) * (h W_u)) W_d
    logits = RMSNorm_D(x_L; g) W_head^T             (the head is its own matrix)
    loss   = mean over positions of logsumexp(logits) - logits[label]

`layer_types` names each layer `linear_attention` or `full_attention`.
`RMSNorm(x; g) = x rsqrt(mean x^2 + eps) g`. The halves read the stream
UN-NORMED; the norm is on what they give (the Olmo-2 / Olmo-3 order).

`linear_attention`, Gated DeltaNet (H heads, key width K, value width V,
kernel C; one key head a value head):
1. `[q | k | v | z] = x W_qkvz`, widths H K | H K | H V | H V; `[b | a] = x
   W_ba`, widths H | H; no bias.
2. `[q | k | v]_t <- silu(sum_c w_c [q | k | v]_(t-C+1+c))` per column
   (causal, depthwise, NO bias; zeros before the sequence).
3. q, k as [H, K]: `q <- q rsqrt(sum q^2 + 1e-6) / sqrt(K)`, `k <- k rsqrt(sum
   k^2 + 1e-6)`. v as [H, V].
4. `beta_t = 2 sigmoid(b_t)` (`linear_allow_neg_eigval`; 1 sigmoid without),
   `g_t = -exp(A_log) softplus(a_t + dt_bias)`, a scalar a head. Per head, S
   in R^(K x V) float32, S_(-1) = 0: `S <- exp(g_t) S`; `r = S^T k_t`; `u =
   beta_t (v_t - r)`; `S <- S + k_t (outer) u`; `o_t = S^T q_t`.
5. `o <- RMSNorm_V(o; w) * silu(z)` a head (the norm FIRST, then the gate);
   `f = o W_out`.

`full_attention` (H heads of hd, Hkv key-value heads): `[q | k | v] = x W_qkv`;
`q <- RMSNorm_(H hd)(q; gq)`, `k <- RMSNorm_(Hkv hd)(k; gk)` over the WHOLE
projection, before the heads are split; NO rotation, no positions of any
kind; `s_ij = q_i . k_j / sqrt(hd)` for `j <= i`, softmax in float32; `f =
concat_heads(softmax(s) v) Wo`.

DEPARTURES from the published description, each the program's too:
- it reads the PROGRAM'S parameter tree (`models/hybrid.py`: `runs`, a list
  of runs of the block pattern, each a list of one tree a half of the run's
  unit with a leading `[repeats]` axis), because "the same weights" is what
  is compared: `W_qkvz`'s columns are `[q | k | v | z]` whole and `W_ba`'s
  `[b | a]` (published: separate projections); q, k and v of an attention
  layer are one `[D, (H + 2 Hkv) hd]` matrix; weights are cast to float32 a
  matrix at a time;
- the tree carries a bias on the attention's two projections and on the
  feed-forward's output (zero at the start, `gpt.py`'s halves read them);
  the published model has none. They are added here too, so that a step's
  gradients are of the same function;
- four readings the published `config.json` does not spell, the family's
  convention (`assumed` in the configuration file): the norm on the halves'
  OUTPUTS, q / k normed over the whole projection, `silu` on the output
  gate, `rope_theta: null` read as no positions.

`round_to` (None for the reference itself; `bfloat16` or `float8_e4m3fn`
for a control "in a lower precision"): every weight, every matrix product's
input, the recurrent state after each position and the stream after each
half are rounded through that type.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp

DELTANET, ATTENTION, DENSE = "D", "*", "F"
BLOCKS = {"linear_attention": "DF", "full_attention": "*F"}
ROW_BLOCK = 128         # query rows an attention block scores at a time
MLP_ROWS = 4096         # rows a block of the feed-forward and of the head
SEGMENT = 4096          # positions a block of a position-wise or recurrent
                        # half, where its backward follows (`_half_blocked`)
STATES = 64             # positions between two states the recurrence's
                        # backward keeps (the ones between are made again)
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Arch:
    blocks: tuple           # a layer's halves, "DF" or "*F"
    runs: tuple             # ((unit length in blocks, repeats), ...)
    d_model: int
    n_head: int
    n_kv_head: int
    head_dim: int
    gdn_heads: int          # Gated DeltaNet: H
    key_dim: int            # K
    value_dim: int          # V
    conv_kernel: int
    beta_scale: float       # 2.0 with `linear_allow_neg_eigval`
    norm_eps: float
    round_to: object = None


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


def arch_from_config(cfg, round_to=None):
    """The configuration file's keys -> what the equations need."""
    if cfg["model_type"] != "olmo_hybrid":
        raise ValueError(f"no reference for model_type {cfg['model_type']!r}")
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"] \
            or cfg["attention_bias"] \
            or cfg["rope_parameters"]["rope_theta"] is not None \
            or cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("this reference has SiLU, an untied head, no "
                         "attention bias, no rotation and one key head a "
                         "value head")
    blocks = tuple(BLOCKS[kind] for kind in cfg["layer_types"])
    assert len(blocks) == cfg["num_hidden_layers"]
    return Arch(blocks=blocks, runs=pattern_runs(blocks),
                d_model=cfg["hidden_size"],
                n_head=cfg["num_attention_heads"],
                n_kv_head=cfg["num_key_value_heads"],
                head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
                gdn_heads=cfg["linear_num_value_heads"],
                key_dim=cfg["linear_key_head_dim"],
                value_dim=cfg["linear_value_head_dim"],
                conv_kernel=cfg["linear_conv_kernel_dim"],
                beta_scale=2.0 if cfg["linear_allow_neg_eigval"] else 1.0,
                norm_eps=cfg["rms_norm_eps"], round_to=round_to)


def layer_trees(params, arch):
    """Every half's (letter, own leaves), in model order, one half at a time
    (a generator: a half's leaves are sliced out of their stacks when it is
    reached)."""
    at = 0
    for (length, repeats), trees in zip(arch.runs, params["runs"]):
        unit = "".join(arch.blocks[at:at + length])
        for n in range(repeats):
            for kind, tree in zip(unit, trees):
                yield kind, {k: v[n] for k, v in tree.items()}
        at += length * repeats


def _rounded(x, arch):
    """float32 x through `arch.round_to`: a type of float32's range by
    `reduce_precision` (a pair of casts is dropped on the TPU; a cotangent
    is rounded the same way), one of a short range (`float8_e4m3fn`) with a
    scale a row, a cotangent passing as it is (cast to that type it would
    underflow to zero)."""
    if arch.round_to is None:
        return x
    info = jnp.finfo(arch.round_to)
    if float(info.max) > 1e30:
        return jax.lax.reduce_precision(x, info.nexp, info.nmant)
    top = float(info.max)
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / top
    # (the quotient may pass the type's largest by a rounding, and past it
    # `float8_e4m3fn` has only NaN)
    through = jnp.clip(x / scale, -top, top).astype(arch.round_to).astype(
        jnp.float32) * scale
    return x + jax.lax.stop_gradient(through - x)


def _f32(w, arch):
    return _rounded(w.astype(jnp.float32), arch)


def _matmul(x, w, arch):
    return _rounded(x, arch) @ _f32(w, arch)


def _rms_norm(x, scale, arch):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + arch.norm_eps) * _f32(scale, arch)


def _by_rows(f, x, rows):
    """`f` over blocks of `rows` rows of x [T, .] (one block where T is not
    a multiple: the tests' sizes)."""
    T = x.shape[0]
    if T <= rows or T % rows:
        return f(x)
    out = jax.lax.map(jax.checkpoint(f), x.reshape(T // rows, rows, -1))
    return out.reshape(T, -1)


# ----------------------------------------------------------------------
# the halves: x [T, D] float32 -> f(x), un-normed
# ----------------------------------------------------------------------


def _recurrence(step, S, inputs):
    """`lax.scan(step, S, inputs)` over the positions; where a backward
    follows over many positions, in runs of `STATES` whose inner states are
    made again (a state a position is 2.2 MB at the published widths)."""
    T = inputs[0].shape[0]
    if T <= STATES or T % STATES:
        return jax.lax.scan(step, S, inputs)
    run = jax.checkpoint(lambda S, xs: jax.lax.scan(step, S, xs))
    S, o = jax.lax.scan(run, S, tuple(
        x.reshape((T // STATES, STATES) + x.shape[1:]) for x in inputs))
    return S, o.reshape((T,) + o.shape[2:])


def _deltanet(x, p, arch, carry=None):
    """Steps 1-5 on one sequence, or on a stretch of one: `carry` is (the
    state before its first position, the convolution's last C - 1 inputs
    before it), zeros at a sequence's start. -> (f, the carry after)."""
    T = x.shape[0]
    H, K, V, C = (arch.gdn_heads, arch.key_dim, arch.value_dim,
                  arch.conv_kernel)
    f32 = jnp.float32
    wide = 2 * H * K + H * V            # q | k | v; z is projected at the end
    if carry is None:
        carry = (jnp.zeros((H, K, V), f32), jnp.zeros((C - 1, wide), f32))
    S, tail = carry
    qkv = _matmul(x, p["gdn_qkvz_w"][:, :wide], arch)
    b, a = jnp.split(_matmul(x, p["gdn_ba_w"], arch), 2, axis=-1)
    padded = jnp.concatenate([tail, qkv])
    w = _f32(p["conv_w"], arch)
    qkv = jax.nn.silu(sum(w[c] * padded[c:c + T] for c in range(C)))

    def unit(v):
        v = v.reshape(T, H, K)
        return v * jax.lax.rsqrt(jnp.sum(v * v, -1, keepdims=True) + L2_EPS)

    qs = unit(qkv[:, :H * K]) / jnp.sqrt(f32(K))
    ks = unit(qkv[:, H * K:2 * H * K])
    vs = qkv[:, 2 * H * K:].reshape(T, H, V)
    beta = arch.beta_scale * jax.nn.sigmoid(b)              # [T, H]
    g = -jnp.exp(_f32(p["A_log"], arch)) \
        * jax.nn.softplus(a + _f32(p["dt_bias"], arch))

    def step(S, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs
        S = jnp.exp(g_t)[:, None, None] * S
        held = jnp.sum(S * k_t[:, :, None], axis=1)         # S^T k: [H, V]
        S = _rounded(S + k_t[:, :, None]
                     * (beta_t[:, None] * (v_t - held))[:, None, :], arch)
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    S, o = _recurrence(step, S, (qs, ks, vs, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + arch.norm_eps)
    z = _matmul(x, p["gdn_qkvz_w"][:, wide:], arch)
    gated = o * _f32(p["gate_norm_scale"], arch) \
        * jax.nn.silu(z.reshape(T, H, V))
    return (_matmul(gated.reshape(T, H * V), p["gdn_out_w"], arch),
            (S, padded[T:]))


def _attention(x, p, arch):
    T = x.shape[0]
    H, Hkv, hd = arch.n_head, arch.n_kv_head, arch.head_dim
    qkv = _matmul(x, p["attn_qkv_w"], arch) + _f32(p["attn_qkv_b"], arch)
    q, k, v = jnp.split(qkv, [H * hd, (H + Hkv) * hd], axis=-1)
    q = _rounded(_rms_norm(q, p["q_norm_scale"], arch), arch)
    k = _rounded(_rms_norm(k, p["k_norm_scale"], arch), arch)
    q = q.reshape(T, H, hd)
    k = jnp.repeat(k.reshape(T, Hkv, hd), H // Hkv, axis=1)
    v = jnp.repeat(_rounded(v, arch).reshape(T, Hkv, hd), H // Hkv, axis=1)
    positions = jnp.arange(T)
    rows = min(ROW_BLOCK, T)
    pad = -T % rows

    def block(inputs):
        q_rows, at = inputs             # [rows, H, hd], their positions
        scores = jnp.einsum("thd,shd->hts", q_rows, k) \
            / jnp.sqrt(jnp.float32(hd))
        seen = at[:, None] >= positions[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hts,shd->thd", _rounded(probs, arch),
                          v).reshape(rows, H * hd)

    # a padded row sits at the last position: it sees every key, its result
    # is dropped
    q = jnp.pad(q, [(0, pad), (0, 0), (0, 0)])
    at = jnp.pad(positions, (0, pad), constant_values=T - 1)
    out = jax.lax.map(jax.checkpoint(block), (q.reshape(-1, rows, H, hd),
                                              at.reshape(-1, rows)))
    out = out.reshape(-1, H * hd)[:T]
    return _matmul(out, p["attn_out_w"], arch) + _f32(p["attn_out_b"], arch)


def _swiglu(x, p, arch):
    def rows(x):
        inner = jax.nn.silu(_matmul(x, p["mlp_gate_w"], arch)) \
            * _matmul(x, p["mlp_up_w"], arch)
        return _matmul(inner, p["mlp_down_w"], arch)
    return _by_rows(rows, x, MLP_ROWS) + _f32(p["mlp_out_b"], arch)


def _stretch(carry, x, p, kind, arch):
    """`x + RMSNorm(f(x))` of a half on a stretch of positions x [rows, D]
    -> (the carry after, x'): `carry` is a recurrent half's (`_deltanet`),
    None at a sequence's start and for the halves that carry nothing."""
    if kind == DELTANET:
        f, carry = _deltanet(x, p, arch, carry)
    else:
        f = {ATTENTION: _attention, DENSE: _swiglu}[kind](x, p, arch)
    return carry, _rounded(x + _rms_norm(f, p["ln1_scale"], arch), arch)


def _half(x, p, kind, arch):
    """`x + RMSNorm(f(x))` of one half, the sequence whole."""
    return _stretch(None, x, p, kind, arch)[1]


_half_jit = jax.jit(_half, static_argnums=(2, 3))


def _half_blocked(x, p, kind, arch):
    """`_half` again, for where a backward follows over many positions: a
    half in which a position reads nothing of a later one but a carried
    state (the recurrent and the feed-forward halves) runs `SEGMENT`
    positions at a time, each stretch made again for its backward. The
    attention half stays whole: its rows are blocked inside it."""
    T, D = x.shape
    if kind == ATTENTION or T <= SEGMENT or T % SEGMENT:
        return _half(x, p, kind, arch)
    stretch = jax.checkpoint(
        lambda carry, x: _stretch(carry, x, p, kind, arch))
    carry, first = stretch(None, x[:SEGMENT])   # from zeros: a carry's shape
    _, rest = jax.lax.scan(stretch, carry,
                           x[SEGMENT:].reshape(-1, SEGMENT, D))
    return jnp.concatenate([first, rest.reshape(-1, D)])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _half_grads(x, p, dy, kind, arch):
    """(dx, dp) of `_half` at float32 leaves p, from its result's dy."""
    return jax.vjp(lambda x, p: _half_blocked(x, p, kind, arch), x, p)[1](dy)


def _nll(x, labels, scale, table, arch):
    """The final norm, the head and each position's loss, a block of rows at
    a time: x [T, D], labels [T] -> [T]."""
    def rows(inputs):
        x, labels = inputs
        lg = _matmul(_rms_norm(x, scale, arch), table.T, arch)
        gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(lg, axis=-1) - gold
    T = x.shape[0]
    if T <= MLP_ROWS or T % MLP_ROWS:
        return rows((x, labels))
    return jax.lax.map(jax.checkpoint(rows),
                       (x.reshape(-1, MLP_ROWS, x.shape[1]),
                        labels.reshape(-1, MLP_ROWS))).reshape(T)


_nll_jit = jax.jit(_nll, static_argnums=4)


def _hidden(params, tokens, arch, half=_half_jit):
    x = params["wte"][tokens].astype(jnp.float32)
    for kind, p in layer_trees(params, arch):
        x = half(x, p, kind, arch)
    return x


def logits(params, tokens, arch):
    """tokens: [T] int32 -> float32 logits [T, vocab] of one sequence."""
    with jax.default_matmul_precision("highest"):
        x = _hidden(params, jnp.asarray(tokens), arch)
        return _matmul(_rms_norm(x, params["lnf_scale"], arch),
                       params["lm_head"].T, arch)


def loss(params, tokens, labels, arch):
    """Mean next-token cross entropy over sequences. tokens, labels:
    [n, T] int32; one sequence at a time, a half at a time (each jitted
    apart, its weights cast as it is reached)."""
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for row in range(tokens.shape[0]):
            x = _hidden(params, jnp.asarray(tokens[row]), arch)
            total += float(jnp.mean(_nll_jit(
                x, jnp.asarray(labels[row]), params["lnf_scale"],
                params["lm_head"], arch)))
    return total / tokens.shape[0]


@functools.partial(jax.jit, static_argnums=4)
def _head_grads(x, labels, scale, table, arch):
    """(the sequence's loss, its gradient by x, the final norm's scale and
    the head) at float32 leaves."""
    return jax.value_and_grad(
        lambda x, scale, table: jnp.mean(_nll(x, labels, scale, table, arch)),
        argnums=(0, 1, 2))(x, scale, table)


def loss_and_grads(params, tokens, labels, arch):
    """(`loss`'s number, its gradient by every leaf of `params`: float32
    numpy arrays in the tree's own layout): autodiff of the equations above,
    one sequence at a time. Forward, each half's input is parked on the
    host; in reverse, a half at a time, `jax.vjp` of that half at its
    input and its float32 leaves, the half's gradients parked on the host
    as they come. The device holds one half's arrays at a time (its forward
    made again a stretch at a time: `_half_blocked`), which is what lets a
    32768-position sequence at the published widths stand beside a
    training state."""
    import numpy as np
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float32), tree)
    halves = list(layer_trees(params, arch))
    total = 0.0
    sums = None
    with jax.default_matmul_precision("highest"):
        for row in range(tokens.shape[0]):
            ids = jnp.asarray(tokens[row])
            x = params["wte"][ids].astype(jnp.float32)
            parked = []
            for kind, p in halves:
                parked.append(np.asarray(x))
                x = _half_jit(x, p, kind, arch)
            value, (dx, dscale, dhead) = _head_grads(
                x, jnp.asarray(labels[row]), f32(params["lnf_scale"]),
                f32(params["lm_head"]), arch)
            total += float(value)
            by_half = []
            for (kind, p), x_in in zip(reversed(halves), reversed(parked)):
                dx, dp = _half_grads(jnp.asarray(x_in), f32(p), dx, kind,
                                     arch)
                by_half.append(jax.tree_util.tree_map(np.asarray, dp))
            flat = [np.asarray(jnp.zeros(params["wte"].shape, jnp.float32)
                               .at[ids].add(dx)),
                    np.asarray(dscale), np.asarray(dhead), by_half[::-1]]
            sums = flat if sums is None else jax.tree_util.tree_map(
                np.add, sums, flat)
    n = tokens.shape[0]
    dwte, dscale, dhead, by_half = jax.tree_util.tree_map(
        lambda g: g / n, sums)
    # the halves' gradients, back into the tree's runs: a leading [repeats]
    by_half, runs = iter(by_half), []
    for (length, repeats), trees in zip(arch.runs, params["runs"]):
        each = [[next(by_half) for _ in trees] for _ in range(repeats)]
        runs.append([{name: np.stack([each[n][i][name]
                                      for n in range(repeats)])
                      for name in tree} for i, tree in enumerate(trees)])
    return total / n, {"wte": dwte, "lnf_scale": dscale, "lm_head": dhead,
                       "runs": runs}
