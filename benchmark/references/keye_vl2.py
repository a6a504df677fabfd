"""Plain reference for Keye-VL-2.0's language model (`model_type: KeyeVL2`,
Keye-VL-2.0-30B-A3B): the forward pass over a WHOLE sequence — plain
`jax.numpy`, float32, one sequence at a time, dense scores, no kernels, no
cache, no batching, nothing imported from the program. Every matrix product
runs under `jax.default_matmul_precision("highest")` (on a TPU a float32
product otherwise runs in bfloat16 passes). Written like `glm4_moe_lite.py`
beside it.

The layer (every one alike; pre-norm, eps 1e-6, no biases; `u = RMSNorm(x)`;
positions s <= t of one sequence):

1. main heads: `q_{t,h} = RoPE(RMSNorm_hd((u_t W_q)_h))`, h = 1..H;
   `k_{s,g} = RoPE(RMSNorm_hd((u_s W_k)_g))`, `v_{s,g} = (u_s W_v)_g`,
   g = 1..Hkv, H / Hkv query heads a key-value head; the per-head norms have
   one scale vector of `head_dim` each; rotary over all `head_dim` columns.
2. indexer (`sa_config`; DeepSeek-V3.2's lightning indexer): `qI_{t,j} =
   RoPE((u_t W_qI)_j)` in R^d, j = 1..Hi; `kI_s = RoPE(LayerNorm(u_s W_kI))`
   in R^d, ONE key head; `w_{t,j} = (u_t W_wI)_j * d^-1/2 * Hi^-1/2`;
   `I_{t,s} = sum_j w_{t,j} * relu(qI_{t,j} . kI_s)`.
3. selection: `S_t` = the `topk` positions s <= t with the largest `I_{t,s}`
   (every s <= t while t < topk), by a STABLE sort of the scores, descending:
   ties go to the earlier position. One set a token a layer, for all heads.
4. attention: `o_{t,h} = sum_{s in S_t} softmax_{s in S_t}(q_{t,h} .
   k_{s,g(h)} / sqrt(hd)) v_{s,g(h)}`; `h_t = x_t + [o_{t,1} .. o_{t,H}] W_o`.
5. `y_t = h_t + MoE(RMSNorm(h_t))`: softmax over the router's logits in
   float32, the `top_k` largest, renormalised to sum 1 (`norm_topk_prob`);
   each chosen expert's SwiGLU weighted and summed. No shared expert, no
   dense layer. Final RMSNorm, untied head.

THE SHARE: the router routes over all `num_experts`; `experts_held = (first,
count)` are this chip's, and what the others would add is left out, as the
program leaves it out. `forced` (the chip check): the experts each token
uses are GIVEN, [layers, T, k] — the program's own choice on its bfloat16
activations — and weighted by THIS forward's probabilities of them,
renormalised; what this forward would have chosen comes back either way.

It reads the PROGRAM'S parameter tree (`models/exaone_moe.py`: no prologue,
`params["period"][0]` every leaf stacked `[layers, ...]`; q, k, v fused in
one `[D, (H + 2 Hkv) hd]` matrix in that order; `moe_w_gate_up [held, D, 2F]`
with gate in the first F columns), because "the same weights" is what is
compared. Scores and attention are computed a block of `ROW_BLOCK` query rows
at a time and each block is ONE program call, so that 64k positions fit
beside a served model that fills the chip.

Departures from the published description, shared with the program and
listed under `assumed` in the configuration file: rotary pairs are
interleaved (even, odd) rather than split in halves — the published layout
up to a fixed permutation of each head's columns; the indexer reads the
NORMED stream, rotates its query and key over all `d` columns with the
layer's base, and its key passes a LayerNorm first; the attention biases the
program's tree carries are zero.

`round_to`: None for the reference itself. A dtype (e.g. `float8_e4m3fn`)
rounds every matrix product's inputs through that type — the reference
"computed in a lower precision", which the benchmark's limits are set against
(PERF.md) and which no check uses. `score_round_to`: the index scores alone
rounded through a dtype before the selection (bfloat16: the control for the
rule that scores, the threshold and the comparison are float32).
"""

import dataclasses
import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 128         # query rows scored, selected and attended at a time
MLP_ROWS = 4096         # rows a projection or an expert takes at a time


@dataclasses.dataclass(frozen=True)
class Arch:
    n_layer: int
    n_head: int
    n_kv_head: int
    head_dim: int
    d_model: int
    index_heads: int
    index_dim: int
    topk: int
    num_experts: int        # the router's width
    experts_held: object    # (first, count)
    top_k: int
    norm_topk_prob: bool
    rope_theta: float
    norm_eps: float
    round_to: object = None
    score_round_to: object = None


def arch_from_config(cfg, round_to=None, score_round_to=None):
    """The configuration file's keys -> what the equations need."""
    if cfg["model_type"] != "KeyeVL2":
        raise ValueError(f"no reference for model_type {cfg['model_type']!r}")
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"] \
            or cfg["attention_bias"] or cfg["hidden_act"] != "silu":
        raise ValueError("this reference has every layer routed, silu "
                         "experts and no attention bias")
    sa = cfg["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("this reference has ONE index key head")
    return Arch(n_layer=cfg["num_hidden_layers"],
                n_head=cfg["num_attention_heads"],
                n_kv_head=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], d_model=cfg["hidden_size"],
                index_heads=sa["indexer_num_heads"],
                index_dim=sa["indexer_head_dim"], topk=sa["topk"],
                num_experts=cfg["published_num_experts"],
                experts_held=tuple(cfg["experts_held_range"]),
                top_k=cfg["num_experts_per_tok"],
                norm_topk_prob=cfg["norm_topk_prob"],
                rope_theta=float(cfg["rope_theta"]),
                norm_eps=cfg["rms_norm_eps"], round_to=round_to,
                score_round_to=score_round_to)


def _through(x, dtype):
    """x rounded through `dtype`'s exponent and mantissa bits
    (`lax.reduce_precision`: a pair of casts is dropped by the TPU's
    compiler). A type with a short range is given a scale a row."""
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


def _layer_norm(x, scale, bias, arch):
    centred = x - jnp.mean(x, -1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(centred * centred, -1, keepdims=True) + arch.norm_eps) \
        * scale.astype(jnp.float32) + bias.astype(jnp.float32)


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


def _projections(x, p, lo, arch):
    """Steps 1 and 2's projections of the rows x [R, D] at positions lo ..
    -> (q [R, H, hd], k [R, Hkv, hd], v, qI [R, Hi, d], kI [R, d], w
    [R, Hi])."""
    R = x.shape[0]
    H, Hkv, hd = arch.n_head, arch.n_kv_head, arch.head_dim
    Hi, d = arch.index_heads, arch.index_dim
    positions = lo + jnp.arange(R)
    u = _rms_norm(x, p["ln1_scale"], arch)
    qkv = _matmul(u, p["attn_qkv_w"], arch) + p["attn_qkv_b"].astype(
        jnp.float32)
    q = _rms_norm(qkv[:, :H * hd].reshape(R, H, hd), p["q_norm_scale"], arch)
    k = _rms_norm(qkv[:, H * hd:(H + Hkv) * hd].reshape(R, Hkv, hd),
                  p["k_norm_scale"], arch)
    v = qkv[:, (H + Hkv) * hd:].reshape(R, Hkv, hd)
    qi = _rope(_matmul(u, p["idx_q_w"], arch).reshape(R, Hi, d), positions,
               arch)
    ki = _rope(_layer_norm(_matmul(u, p["idx_k_w"], arch),
                           p["idx_k_norm_scale"], p["idx_k_norm_bias"], arch),
               positions, arch)
    w = _matmul(u, p["idx_w_w"], arch) * (d ** -0.5 * Hi ** -0.5)
    return (_rope(q, positions, arch), _rope(k, positions, arch), v, qi, ki,
            w)


_projections_jit = jax.jit(_projections, static_argnums=3)
_PROJECTION_LEAVES = ("ln1_scale", "attn_qkv_w", "attn_qkv_b",
                      "q_norm_scale", "k_norm_scale", "idx_q_w", "idx_k_w",
                      "idx_w_w", "idx_k_norm_scale", "idx_k_norm_bias")


def index_scores(qi, w, ki, arch):
    """Step 2's `I` for the rows qi [R, Hi, d], w [R, Hi] against every
    position's key ki [T, d] -> [R, T] float32."""
    s = jnp.einsum("rjd,sd->rjs", _rounded(qi, arch), _rounded(ki, arch))
    return _through(jnp.sum(w[:, :, None] * jnp.maximum(s, 0.0), axis=1),
                    arch.score_round_to)


def select(scores, lo, arch):
    """Step 3 for rows at positions lo .. : scores [R, T] -> bool [R, T],
    the `topk` best positions s <= t of each row by a stable descending sort
    (ties to the earlier position), every s <= t where t < topk."""
    R, T = scores.shape
    seen = jnp.arange(T)[None, :] <= (lo + jnp.arange(R))[:, None]
    # -0.0 and 0.0 sort as equals: both become the same zero first
    scores = jnp.where(scores == 0.0, 0.0, scores)
    order = jnp.argsort(jnp.where(seen, -scores, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.zeros((R, T), jnp.int32).at[
        jnp.arange(R)[:, None], order].set(jnp.arange(T, dtype=jnp.int32)[None])
    return seen & (rank < arch.topk)


def _attend_block(q, qi, w, k, v, ki, lo, arch):
    """Steps 2-4 for ONE block of query rows (q [R, H, hd], qI, w) at
    positions lo .. against the whole sequence's k, v [T, Hkv, hd] and ki
    [T, d] -> (o [R, H * hd], I [R, T], selection [R, T])."""
    R, H, hd = q.shape
    Hkv = k.shape[1]
    scores = index_scores(qi, w, ki, arch)
    chosen = select(scores, lo, arch)
    qg = _rounded(q, arch).reshape(R, Hkv, H // Hkv, hd)
    logits = jnp.einsum("rkgd,skd->kgrs", qg, _rounded(k, arch)) \
        / math.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(chosen[None, None], logits, -jnp.inf),
                           axis=-1)
    o = jnp.einsum("kgrs,skd->rkgd", _rounded(probs, arch), _rounded(v, arch))
    return o.reshape(R, H * hd), scores, chosen


_attend_block_jit = jax.jit(_attend_block, static_argnums=7)


def _out(x, attn, out_w, arch):
    return x + _matmul(attn, out_w, arch)


_out_jit = jax.jit(_out, static_argnums=3)


def _route(h, gate_w, arch, forced=None):
    """-> (weights [T, k] float32 of the experts USED, the experts used
    [T, k], the experts chosen [T, k]); used = chosen unless `forced`."""
    probs = jax.nn.softmax(_matmul(h, gate_w, arch), axis=-1)
    _, top_e = jax.lax.top_k(probs, arch.top_k)
    top_e = top_e.astype(jnp.int32)
    used = top_e if forced is None else forced
    top_w = jnp.take_along_axis(probs, used, axis=-1)
    if arch.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    return top_w, used, top_e


_route_jit = jax.jit(_route, static_argnums=2)


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
    first, count = held or arch.experts_held
    top_w, used, top_e = _route_jit(h, p["moe_gate_w"], arch, forced)
    out = jnp.zeros_like(h)
    for local in range(count):
        weight = jnp.sum(jnp.where(used == first + local, top_w, 0.0), -1)
        out = out + jnp.concatenate([
            _expert_jit(h[lo:lo + MLP_ROWS], weight[lo:lo + MLP_ROWS],
                        p["moe_w_gate_up"][local], p["moe_w_down"][local],
                        arch)
            for lo in range(0, h.shape[0], MLP_ROWS)])
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


class _LayerOf:
    """Layer `n` of a leaf stacked `[layers, ...]`, indexed further on use
    (one expert's matrices are read out of the stack, never a layer's)."""

    def __init__(self, stack, n):
        self.stack, self.n = stack, n

    def __getitem__(self, e):
        return self.stack[self.n, e]


def layer_trees(params, arch):
    """Every layer's own leaves, in model order, one layer at a time."""
    tree, = params["period"]            # the period is one layer
    for n in range(arch.n_layer):
        yield {k: (_LayerOf(v, n) if k.startswith("moe_w_") else v[n])
               for k, v in tree.items()}


def attention(x, p, arch, probe_rows=()):
    """Steps 1-4 on one sequence x [T, D] -> (h [T, D], {probed row: (its
    index scores [T], its selection [T])})."""
    T = x.shape[0]
    proj = {k: p[k] for k in _PROJECTION_LEAVES}
    parts = [_projections_jit(x[lo:lo + MLP_ROWS], proj, lo, arch)
             for lo in range(0, T, MLP_ROWS)]
    q, k, v, qi, ki, w = (jnp.concatenate(a) for a in zip(*parts))
    out, probed = [], {}
    for lo in range(0, T, ROW_BLOCK):           # blocks of query rows
        rows = slice(lo, lo + ROW_BLOCK)
        o, scores, chosen = _attend_block_jit(q[rows], qi[rows], w[rows], k,
                                              v, ki, lo, arch)
        out.append(o)
        for t in probe_rows:
            if lo <= t < lo + ROW_BLOCK:
                probed[t] = (scores[t - lo], chosen[t - lo])
    return _out_jit(x, jnp.concatenate(out), p["attn_out_w"], arch), probed


def forward(params, tokens, arch, forced=None, head_rows=None,
            probe_rows=()):
    """tokens: [T] int32 -> (float32 logits [T, vocab], the experts each
    layer chose [layers, T, k] int32 ascending, and what the indexer made of
    the rows `probe_rows`: [{row: (index scores [T], selection [T])} a
    layer]) of one sequence. `forced` [layers, T, k]: the experts each layer
    USES instead (module docstring). `head_rows` (positions): the logits of
    THOSE rows only, `[len(head_rows), vocab]`."""
    chosen, probes = [], []
    with jax.default_matmul_precision("highest"):
        x = _embed(params["wte"], tokens)
        for p in layer_trees(params, arch):
            h, probed = attention(x, p, arch, probe_rows)
            probes.append(probed)
            u = _pre_norm_jit(h, p["ln2_scale"], arch)
            y, experts = routed_sum(
                u, p, arch, forced=None if forced is None
                else jnp.asarray(forced[len(chosen)], jnp.int32))
            chosen.append(experts)
            x = h + y
        if head_rows is not None:
            x = x[jnp.asarray(head_rows, jnp.int32)]
        out = _head_jit(x, params["lnf_scale"], params["lm_head"], arch)
    return out, jnp.stack(chosen), probes


def logits(params, tokens, arch):
    """tokens: [T] int32 -> float32 logits [T, vocab] of one sequence."""
    return forward(params, tokens, arch)[0]
