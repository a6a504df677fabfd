"""Plain reference for MiMo-V2-Flash (`model_type: mimo_v2_flash`): the forward
pass in straightforward `jax.numpy`, float32, one sequence at a time, dense
attention in blocks of query rows, a dense loop over the experts, no kernels,
no cache, no ring, no batching, no scan, no sort. Every matrix product runs
under `jax.default_matmul_precision("highest")` — on a TPU a float32 product
otherwise runs in bfloat16 passes. Imports `jax` only.

Layer l has an attention kind `layer_types[l]` (sliding_attention |
full_attention: the published `hybrid_layer_pattern`, 1 | 0) and an MLP kind
`mlp_layer_types[l]` (dense | sparse: `moe_layer_freq`, 0 | 1). Pre-norm,
RMSNorm eps `norm_eps`, no biases:

1. `u = RMSNorm_D(x; g_1)`; `q = u Wq` as [T, H, dk]; `k = u Wk` as
   [T, Hkv, dk]; `v = attention_value_scale * (u Wv)` as [T, Hkv, dv]. `Hkv`
   and the rotary base are the KIND's (full: 4 heads, theta 5,000,000;
   window: 8 heads, theta 10,000); dk 192, dv 128.
2. rotary on the first `int(dk * partial_rotary_factor)` (64) columns of q and
   k at the absolute position, both kinds; the other columns pass.
3. `s_ij = q_i . k_j / sqrt(dk)` for `j <= i`, on a window layer also
   `i - j < window`.
4. window layer (`add_swa_attention_sink_bias`): a learned logit `sink_h` a
   head is CONCATENATED to a row's scores as one more column, the softmax is
   taken over all of them in float32, and the column is dropped — it has no
   value, so a row's weights sum to less than 1 (the published code's form).
   Full layer (`add_full_attention_sink_bias` false): the plain softmax.
5. `h = x + concat_heads(p v) Wo` with Wo [H dv, D].
6. `y = h + MLP(RMSNorm_D(h; g_2))`; dense MLP: `(silu(u Wg) * (u Wu)) Wd`.
7. sparse MLP: `z = u Wr` in float32, `s = sigmoid(z)`; the k experts with the
   largest `s + b` (`n_group` 1, `topk_group` 1: no group step); `w_e = s_e /
   (sum of the chosen s + 1e-20)` (`norm_topk_prob`; `routed_scaling_factor`
   null = 1); `MLP(u) = sum over the chosen e of w_e SwiGLU_e(u)`. NO shared
   expert.
8. after the last layer `RMSNorm_D`, then the untied head.

THE SHARE. `experts_held = (first, count)`: the routed sum runs over the held
experts only — what the others would add is left out, as in the program; the
weights `w_e` are still normalised over all k chosen. `experts_held = None`
(with a tree that holds every expert): the whole layer.

It reads the PROGRAM'S parameter tree (`models/exaone_moe.py`: `prologue`, a
list of layer trees, then `period`, one tree a position of the period with a
leading `[periods]` axis; q/k/v fused in one `[D, H dk + Hkv dk + Hkv dv]`
matrix in that order; `attn_sink [H]` float32 in a layer with a sink;
`moe_gate_w [D, E]`, `moe_gate_bias [E]`, `moe_w_gate_up [held, D, 2F]` with
gate in the first F columns, `moe_w_down [held, F, D]`), because "the same
weights" is what is compared. Weights are cast to float32 a matrix at a time,
so the reference fits beside a served model.

Departures from the published code, shared with the program and stated in the
configuration file: rotary pairs are interleaved (even, odd) rather than split
in halves — the published layout up to a fixed permutation of each head's
rotated columns; the biases the program's tree carries are zero.

FORCED ROUTING. `forward(..., forced=sets)` computes the scores as above but
takes the experts it is GIVEN (`[sparse layers, T, k]`, e.g. the ones the
program chose) in place of its own top k: weights from its own float32
scores of those experts, normalised over them. Top-k is discontinuous, a
score within rounding of the k-th swaps, and on one chip's share a swap moves
a layer's output visibly; with the choice held equal, what is left in the
logits is arithmetic, at every position. The experts it WOULD have chosen on
that stream are returned all the same, so the choice is compared on its own.

CONTROLS. `round_to`: None for the reference itself; a dtype (e.g.
`float8_e4m3fn`) rounds every weight and every matrix product's input through
that type (`lax.reduce_precision`: a pair of casts is dropped by the TPU's
compiler; a type with a short range is given a scale a row) — the reference
"computed in a lower precision", which the benchmark's limits are set against.
`without` (a set of names) leaves ONE mechanism out, each a control that the
tests and the limits must see: "sink" (the plain softmax in the window
layers), "value_scale" (1.0), "kind_theta" (the window layers' base on the
full layers too), "kind_heads" (each kind's query heads grouped over its KV
heads as the OTHER kind groups them: sixteen a KV head where it is eight and
the reverse, the KV heads taken in turn). No check uses them.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp

WINDOW, FULL = "sliding_attention", "full_attention"
ROW_BLOCK = 64          # query rows an attention block scores at a time
MLP_ROWS = 2048         # rows a projection or a dense SwiGLU takes at a time


@dataclasses.dataclass(frozen=True)
class Arch:
    layer_types: tuple
    mlp_layer_types: tuple
    n_head: int
    kv_heads: tuple         # ((kind, KV heads), ...)
    thetas: tuple           # ((kind, rotary base), ...)
    sinks: tuple            # the kinds whose softmax has the sink column
    head_dim: int           # query-key width
    value_dim: int
    rotary_dims: int
    value_scale: float
    d_model: int
    window: int
    num_experts: int        # the router's width
    experts_held: object    # (first, count) or None = all
    top_k: int
    pattern_period: int
    norm_eps: float
    round_to: object = None
    without: frozenset = frozenset()


def arch_from_config(cfg, round_to=None, without=()):
    """The configuration file's keys -> what the equations need."""
    if cfg["model_type"] != "mimo_v2_flash":
        raise ValueError(f"no reference for model_type {cfg['model_type']!r}")
    if cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["n_shared_experts"] \
            or not cfg["norm_topk_prob"] or cfg["attention_bias"] \
            or cfg["routed_scaling_factor"] not in (None, 1, 1.0) \
            or cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"] \
            or cfg["swa_head_dim"] != cfg["head_dim"] \
            or cfg["swa_v_head_dim"] != cfg["v_head_dim"] \
            or cfg["swa_num_attention_heads"] != cfg["num_attention_heads"]:
        raise ValueError("this reference has the sigmoid router without "
                         "groups, shared expert or scale, renormalised "
                         "weights, SiLU, no bias, an untied head and one "
                         "head count and width for both kinds' queries")
    kinds = tuple(WINDOW if flag else FULL
                  for flag in cfg["hybrid_layer_pattern"])
    sinks = tuple(kind for kind, key in (
        (WINDOW, "add_swa_attention_sink_bias"),
        (FULL, "add_full_attention_sink_bias")) if cfg[key])
    return Arch(
        layer_types=kinds,
        mlp_layer_types=tuple("sparse" if flag else "dense"
                              for flag in cfg["moe_layer_freq"]),
        n_head=cfg["num_attention_heads"],
        kv_heads=((FULL, cfg["num_key_value_heads"]),
                  (WINDOW, cfg["swa_num_key_value_heads"])),
        thetas=((FULL, float(cfg["rope_theta"])),
                (WINDOW, float(cfg["swa_rope_theta"]))),
        sinks=sinks, head_dim=cfg["head_dim"], value_dim=cfg["v_head_dim"],
        rotary_dims=int(cfg["head_dim"] * cfg["partial_rotary_factor"])
        // 2 * 2,
        value_scale=cfg["attention_value_scale"], d_model=cfg["hidden_size"],
        window=cfg["sliding_window"],
        num_experts=cfg["published_n_routed_experts"],
        experts_held=tuple(cfg["experts_held_range"]),
        top_k=cfg["num_experts_per_tok"],
        pattern_period=cfg["pattern_period"],
        norm_eps=cfg["layernorm_epsilon"], round_to=round_to,
        without=frozenset(without))


class _LayerOf:
    """Layer `n` of a leaf stacked `[periods, ...]`, indexed further on use:
    `_LayerOf(stack, n)[e]` is `stack[n, e]`, so one expert's matrices are
    read out of the stack and never a whole layer of them."""

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
    """`x` through `arch.round_to`'s exponent and mantissa bits, kept in
    float32 (`lax.reduce_precision`). A type with a short range is given a
    scale a row, the row's largest magnitude at the largest value those bits
    hold (an IEEE layout of them: 240 for 4 + 3 bits)."""
    if arch.round_to is None:
        return x
    info = jnp.finfo(arch.round_to)
    top = 2.0 ** (2 ** (info.nexp - 1) - 1) * (2.0 - 2.0 ** -info.nmant)
    scale = 1.0 if top > 1e30 else jnp.maximum(
        jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / top
    return jax.lax.reduce_precision(x / scale, info.nexp, info.nmant) * scale


def _matmul(x, w, arch):
    return _rounded(x, arch) @ _rounded(w.astype(jnp.float32), arch)


def _rms_norm(x, scale, arch):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + arch.norm_eps) * scale.astype(jnp.float32)


def _rope(x, positions, theta, arch):
    """x: [T, heads, dk]. Rotates the first `rotary_dims` columns, in (even,
    odd) pairs; the rest pass."""
    rd = arch.rotary_dims
    freqs = theta ** (-jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0:rd:2], x[..., 1:rd:2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       axis=-1).reshape(x.shape[:-1] + (rd,))
    return jnp.concatenate([turned, x[..., rd:]], axis=-1)


def _project(x, p, lo, kind, arch):
    """Steps 1-2 on the rows `lo ..` of one sequence, x [T, D] -> (q [T, H,
    dk], k [T, Hkv, dk], v [T, Hkv, dv])."""
    T = x.shape[0]
    H, dk, dv = arch.n_head, arch.head_dim, arch.value_dim
    Hkv = dict(arch.kv_heads)[kind]
    theta = dict(arch.thetas)[WINDOW if "kind_theta" in arch.without
                              else kind]
    positions = lo + jnp.arange(T)
    u = _rms_norm(x, p["ln1_scale"], arch)
    qkv = _matmul(u, p["attn_qkv_w"], arch) + p["attn_qkv_b"]
    q = qkv[:, :H * dk].reshape(T, H, dk)
    k = qkv[:, H * dk:(H + Hkv) * dk].reshape(T, Hkv, dk)
    v = qkv[:, (H + Hkv) * dk:(H + Hkv) * dk + Hkv * dv].reshape(T, Hkv, dv)
    if "value_scale" not in arch.without:
        v = v * arch.value_scale
    return (_rope(q, positions, theta, arch), _rope(k, positions, theta, arch),
            v)


def _attend_rows(q, k, v, sink, lo, kind, arch):
    """Steps 3-4 for the query rows `lo ..` (q [R, H, dk]) against every
    position (k [T, Hkv, dk], v [T, Hkv, dv]) -> [R, H * dv]. `sink` [H] or
    None."""
    R, H, dk = q.shape
    T, Hkv, dv = v.shape
    group, back = H // Hkv, None
    if "kind_heads" in arch.without:
        # the OTHER kind's grouping, the KV heads taken in turn: the query
        # heads sorted by the KV head they then share, over the KV heads
        # that are used at all
        other = H // dict(arch.kv_heads)[FULL if kind == WINDOW else WINDOW]
        shared = [(h // other) % Hkv for h in range(H)]
        order = sorted(range(H), key=lambda h: (shared[h], h))
        used = sorted(set(shared))
        back = jnp.asarray(sorted(range(H), key=order.__getitem__))
        q = q[:, jnp.asarray(order)]
        k, v = k[:, jnp.asarray(used)], v[:, jnp.asarray(used)]
        if sink is not None:
            sink = sink[jnp.asarray(order)]
        Hkv, group = len(used), H // len(used)
    rows, positions = lo + jnp.arange(R), jnp.arange(T)
    scores = jnp.einsum("tkgd,skd->kgts",
                        _rounded(q, arch).reshape(R, Hkv, group, dk),
                        _rounded(k, arch)) / math.sqrt(dk)
    seen = rows[:, None] >= positions[None, :]
    if kind == WINDOW:
        seen = seen & (rows[:, None] - positions[None, :] < arch.window)
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    if sink is None:
        probs = jax.nn.softmax(scores, -1)
    else:
        column = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(Hkv, group)[:, :, None, None],
            scores.shape[:3] + (1,))
        probs = jax.nn.softmax(
            jnp.concatenate([scores, column], axis=-1), -1)[..., :-1]
    out = jnp.einsum("kgts,skd->tkgd", _rounded(probs, arch),
                     _rounded(v, arch)).reshape(R, H, dv)
    if back is not None:
        out = out[:, back]
    return out.reshape(R, H * dv)


def _attn_out(x, attn, p, arch):
    return x + _matmul(attn, p["attn_out_w"], arch) + p["attn_out_b"]


_project_jit = jax.jit(_project, static_argnums=(3, 4))
_attend_rows_jit = jax.jit(_attend_rows, static_argnums=(5, 6))
_attn_out_jit = jax.jit(_attn_out, static_argnums=3)


def _attention(x, p, kind, arch):
    """Steps 1-5 on one sequence x [T, D]: the rows' blocks one program
    call each, so that one block's products and scores are live at a time
    (64 heads x 64 rows x 16k positions in float32 is 0.25 GiB, and a
    window layer's softmax holds them four times over; the
    reference runs beside a served model)."""
    q, k, v = (jnp.concatenate(parts, axis=0) for parts in zip(*(
        _project_jit(x[lo:lo + MLP_ROWS], p, lo, kind, arch)
        for lo in range(0, x.shape[0], MLP_ROWS))))
    sink = p["attn_sink"] if kind in arch.sinks \
        and "sink" not in arch.without else None
    attn = jnp.concatenate([
        _attend_rows_jit(q[lo:lo + ROW_BLOCK], k, v, sink, lo, kind, arch)
        for lo in range(0, x.shape[0], ROW_BLOCK)], axis=0)
    return _attn_out_jit(x, attn, p, arch)


_ATTENTION_LEAVES = ("attn_qkv_w", "attn_qkv_b", "attn_out_w", "attn_out_b",
                     "attn_sink", "ln1_scale")


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
    return top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20), used, top_e


_route_jit = jax.jit(_route, static_argnums=3)


def route(h, gate_w, bias, arch):
    """Step 7's router on h [T, D] -> (weights [T, k] float32, experts
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
    sparse layer chose [sparse layers, T, k] int32, ascending) of one
    sequence. `forced` [sparse layers, T, k]: the experts each sparse layer
    USES instead (module docstring). `head_rows` (positions): the logits of
    THOSE rows only, `[len(head_rows), vocab]`."""
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _embed(params["wte"], tokens)
        kinds = zip(arch.layer_types, arch.mlp_layer_types)
        for p, (attn_kind, mlp_kind) in zip(layer_trees(params, arch), kinds):
            h = _attention(
                x, {k: p[k] for k in _ATTENTION_LEAVES if k in p},
                attn_kind, arch)
            u = _pre_norm_jit(h, p["ln2_scale"], arch)
            if mlp_kind == "dense":
                y = jnp.concatenate([
                    _swiglu_jit(u[lo:lo + MLP_ROWS], p["mlp_gate_w"],
                                p["mlp_up_w"], p["mlp_down_w"], arch)
                    for lo in range(0, u.shape[0], MLP_ROWS)], axis=0)
            else:
                y, experts = routed_sum(
                    u, p, arch, forced=None if forced is None
                    else jnp.asarray(forced[len(chosen)], jnp.int32))
                chosen.append(experts)
            x = h + y
        if head_rows is not None:
            x = x[jnp.asarray(head_rows, jnp.int32)]
        out = _head_jit(x, params["lnf_scale"], params["lm_head"], arch)
    return out, jnp.stack(chosen)


def logits(params, tokens, arch):
    """tokens: [T] int32 -> float32 logits [T, vocab] of one sequence."""
    return forward(params, tokens, arch)[0]
