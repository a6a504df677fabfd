"""Mixture-of-Experts with expert parallelism, TPU-native.

Reference: `deepspeed/moe/` — `MoE` layer (`moe/layer.py:16`), `MOELayer` +
`top1gating`/`top2gating` with capacity/jitter/load-balance loss
(`moe/sharded_moe.py:184,282,425`), `_AllToAll` dispatch (:95), expert groups
(`utils/groups.py:113,207`).

TPU-native formulation (GShard-style, fully static shapes): gating produces
dispatch/combine tensors; capacity overflow drops tokens by masking (no
dynamic shapes under jit — the "hard part" called out in SURVEY §7). Token
routing runs one of three ways:

  * **facade-routed** (`expert_parallel_moe`) — the first-class path when a
    mesh with `expert` axis size > 1 is active: gating + dispatch/combine run
    inside `shard_map`, and the expert exchange is two explicit
    `comm/collectives.py` all_to_alls (the reference `_AllToAll` pair). The
    facade records trace-time byte/call stats (`comm/all_to_all_bytes`) and
    the wire is `WireTransform`-compressible (``dispatch_wire="int8"``).
    Tokens shard over (data, zero, expert) jointly; experts shard over
    `expert`. Each shard gates its own tokens against a *local* capacity
    ``ceil(n_local/E · cf)`` — the reference's per-rank gating.
  * **einsum fallback** — no mesh / ep==1 / a composition the shard_map path
    does not cover (tensor- or sequence-sharded activations): dispatch is an
    einsum plus a sharding constraint that puts the expert dim on the
    `expert` mesh axis, and XLA emits the all-to-all pair itself (invisible
    to the facade's byte accounting).
  * **dropless** (`dropless_moe`) — no capacity, no drops: the Pallas token
    sort kernel (`ops/pallas/token_sort.py`) ranks each token within its
    expert's queue and tokens scatter into an [E, N] buffer (capacity = N is
    the only static dropless bound; memory E·N·D — for moderate N).

Serving routes a fourth way, `topk_routing` + `routed_experts`: top-k without
capacity on a sorted, grouped dispatch (`ops/pallas/moe_gmm.py`), whose work
grows with the N·k assignments and not with E·C. The assignments are carried
K-MAJOR (assignment `a` is choice `a // N` of token `a % N`): a token's k
results come back as `[k, N, D]`, a bitcast of the gathered `[N·k, D]` rows,
and are summed over the LEADING axis. Token-major they would be `[N, k, D]`
with k on the sublanes, which the chip's (8, 128) tiling pads to the next
multiple of 8 (10 -> 16, 4 -> 8): the reshape became a relayout of every row
and the sum a reduction across sublanes over the padded tensor.
"""

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm import collectives as coll
from deepspeed_tpu.comm.mesh import (BATCH_AXES, EXPERT_AXIS, SEQ_AXIS,
                                     TENSOR_AXIS, shard_constraint)
from jax import shard_map


def _capacity(num_tokens, num_experts, capacity_factor, min_capacity):
    cap = int(np.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def _one_hot(x, n, dtype=jnp.float32):
    return jax.nn.one_hot(x, n, dtype=dtype)


def top1_gating(logits, capacity_factor=1.0, min_capacity=4, noisy_gate_policy=None,
                rng=None, used_token_mask=None):
    """Top-1 gating (reference `top1gating`, `moe/sharded_moe.py:184`).

    logits: [N, E] (N = flattened tokens). Returns (l_aux, dispatch [N,E,C] bool,
    combine [N,E,C] float, exp_counts [E]).
    """
    N, E = logits.shape
    C = _capacity(N, E, capacity_factor, min_capacity)

    if noisy_gate_policy == "RSample" and rng is not None:
        logits = logits + jax.random.gumbel(rng, logits.shape) * 1e-2
    gates = jax.nn.softmax(logits, axis=-1)                       # [N, E]
    expert_idx = jnp.argmax(gates, axis=-1)                       # [N]
    mask1 = _one_hot(expert_idx, E)                               # [N, E]
    if used_token_mask is not None:
        mask1 = mask1 * used_token_mask[:, None]

    # load-balancing aux loss (me·ce formulation of the reference)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    # position of each token within its expert queue
    pos_in_expert = jnp.cumsum(mask1, axis=0) * mask1             # [N, E], 1-based
    keep = (pos_in_expert <= C) & (mask1 > 0)
    pos = (pos_in_expert - 1.0) * mask1                           # 0-based
    exp_counts = jnp.sum(mask1, axis=0)

    gate_val = jnp.sum(gates * mask1, axis=-1, keepdims=True)     # [N, 1]
    slot = jnp.sum(pos, axis=-1).astype(jnp.int32)                # [N] 0-based slot
    dispatch = keep[..., None] * _one_hot(slot, C)[:, None, :]    # [N, E, C]
    combine = dispatch * gate_val[..., None]
    return l_aux, dispatch.astype(jnp.bool_), combine, exp_counts


def top2_gating(logits, capacity_factor=1.0, min_capacity=4, rng=None):
    """Top-2 gating (reference `top2gating`, `moe/sharded_moe.py:282`).

    The second-expert tie-breaking jitter takes an **explicit** `jax.random`
    key — no hidden seed state, so replay under the chaos/parity harnesses is
    deterministic; ``rng=None`` means no jitter. Top-2 weights are
    renormalized **after** the capacity drop: a token whose second expert
    overflowed gives its full combine weight to the surviving expert (the
    pre-drop renorm leaked the dropped expert's share to nobody).
    """
    N, E = logits.shape
    C = _capacity(N, E, 2 * capacity_factor, min_capacity)

    gates = jax.nn.softmax(logits, axis=-1)
    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot(idx1, E)
    gates_wo1 = gates * (1 - mask1)
    if rng is not None:
        # jitter only the *selection* of the second expert (reference RSample);
        # combine weights below still come from the clean gate probabilities.
        noisy = gates_wo1 + jax.random.gumbel(rng, gates_wo1.shape) * 1e-2
        idx2 = jnp.argmax(jnp.where(mask1 > 0, -jnp.inf, noisy), axis=-1)
    else:
        idx2 = jnp.argmax(gates_wo1, axis=-1)
    mask2 = _one_hot(idx2, E)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    pos1 = jnp.cumsum(mask1, axis=0) * mask1
    pos2 = (jnp.cumsum(mask2, axis=0) + jnp.sum(mask1, axis=0, keepdims=True)) * mask2
    keep1 = (pos1 <= C) & (mask1 > 0)
    keep2 = (pos2 <= C) & (mask2 > 0)

    # renormalize over the experts that *survived* the capacity drop
    g1 = jnp.sum(gates * mask1, axis=-1) * jnp.any(keep1, axis=-1)
    g2 = jnp.sum(gates * mask2, axis=-1) * jnp.any(keep2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    def build(keep, mask, pos, g):
        slot = jnp.sum((pos - 1.0) * mask, axis=-1).astype(jnp.int32)
        d = keep[..., None] * _one_hot(slot, C)[:, None, :]
        return d, d * g[:, None, None]

    d1, c1 = build(keep1, mask1, pos1, g1)
    d2, c2 = build(keep2, mask2, pos2, g2)
    dispatch = (d1 + d2) > 0
    combine = c1 + c2
    exp_counts = jnp.sum(mask1 + mask2, axis=0)
    return l_aux, dispatch, combine, exp_counts


def gating_drop_stats(dispatch, exp_counts):
    """Capacity-overflow accounting from a gating result.

    Returns f32 scalars {routed, kept, overflow_tokens, dropped_frac}:
    `routed` = token→expert assignments the router made, `kept` = assignments
    that fit under capacity, the rest overflowed (token masked to zero output
    for top-1; weight renormalized away for top-2). These feed the `moe/*`
    telemetry gauges.
    """
    routed = jnp.sum(exp_counts).astype(jnp.float32)
    kept = jnp.sum(dispatch.astype(jnp.float32))
    overflow = routed - kept
    return {
        "routed": routed,
        "kept": kept,
        "overflow_tokens": overflow,
        "dropped_frac": overflow / jnp.maximum(routed, 1.0),
    }


# ----------------------------------------------------------------------
# facade-routed expert dispatch (shard_map over the expert mesh axis)
# ----------------------------------------------------------------------


def _expert_token_axes(mesh):
    """Mesh axes the flattened token dim shards over in the facade path."""
    names = tuple(BATCH_AXES) + (EXPERT_AXIS,)
    return tuple(a for a in names if a in mesh.shape)


def can_use_expert_shard_map(mesh, num_experts, num_tokens):
    """True iff `expert_parallel_moe` covers this (mesh, problem) combo:
    expert axis > 1, experts and tokens divide evenly, and no tensor/
    sequence/pipe sharding (those compositions stay on the einsum path)."""
    if mesh is None:
        return False
    shape = dict(mesh.shape)
    if shape.get(EXPERT_AXIS, 1) <= 1:
        return False
    if num_experts % shape[EXPERT_AXIS] != 0:
        return False
    token_axes = _expert_token_axes(mesh)
    for name, size in shape.items():
        if name not in token_axes and size != 1:
            return False
    n_shards = int(np.prod([shape[a] for a in token_axes]))
    return num_tokens % n_shards == 0


def expert_parallel_moe(flat, gate_w, expert_params, ffn_fn, mesh, *,
                        num_experts, capacity_factor, min_capacity=4, k=1,
                        noisy_gate_policy=None, rng=None,
                        dispatch_wire="none",
                        wire_group_size=coll.DEFAULT_GROUP_SIZE):
    """Expert dispatch through the comm facade's instrumented all_to_all.

    flat: [N, D] tokens (N sharded over data×zero×expert jointly); gate_w:
    [D, E] (replicated); expert_params: pytree whose every leaf has leading
    dim E (sharded over the `expert` axis inside the body); ffn_fn(xe,
    local_params) maps [E_local, T, D] → [E_local, T, D] and must not issue
    sharding constraints (it runs under manual sharding).

    Per shard: local gating (capacity from the *local* token count) → dispatch
    einsum [E, C, D] → facade all_to_all (split experts, concat capacity) →
    local expert FFN → reverse all_to_all → combine. ``dispatch_wire="int8"``
    quantizes both exchanges groupwise (ZeRO++ qgZ on activations).

    Returns (out [N, D], l_aux, exp_counts [E], stats dict) — l_aux is the
    shard-mean aux loss, counts/stats are summed over shards, all replicated.
    """
    N, D = flat.shape
    E = num_experts
    shape = dict(mesh.shape)
    ep = shape.get(EXPERT_AXIS, 1)
    if E % ep != 0:
        raise ValueError(
            f"expert_parallel_moe: num_experts={E} not divisible by expert "
            f"axis size {ep}")
    token_axes = _expert_token_axes(mesh)
    n_shards = int(np.prod([shape[a] for a in token_axes]))
    if N % n_shards != 0:
        raise ValueError(
            f"expert_parallel_moe: {N} tokens not divisible by the "
            f"{n_shards}-way token sharding over mesh axes {token_axes}")
    for name, size in shape.items():
        if name not in token_axes and size != 1:
            raise ValueError(
                f"expert_parallel_moe: mesh axis {name!r} has size {size}; "
                "tensor/sequence/pipe sharding composes via the einsum "
                "fallback path, not the shard_map dispatch")

    def local(flat_l, gate_w_l, eparams_l):
        r = rng
        if r is not None:
            for a in token_axes:
                r = jax.random.fold_in(r, jax.lax.axis_index(a))
        logits = flat_l.astype(jnp.float32) @ gate_w_l.astype(jnp.float32)
        if k == 1:
            l_aux, dispatch, combine, counts = top1_gating(
                logits, capacity_factor, min_capacity, noisy_gate_policy, r)
        else:
            l_aux, dispatch, combine, counts = top2_gating(
                logits, capacity_factor, min_capacity, r)
        drop = gating_drop_stats(dispatch, counts)

        # [n_loc, E, C] x [n_loc, D] → [E, C, D] expert slots, then the wire:
        # split the expert dim across the axis, concat peers' slots — each
        # expert shard now holds its E/ep experts' tokens from every peer.
        xe = jnp.einsum("nec,nd->ecd", dispatch.astype(flat_l.dtype), flat_l)
        xe = coll.transform_all_to_all(
            xe, EXPERT_AXIS, split_axis=0, concat_axis=1,
            transform=dispatch_wire, group_size=wire_group_size,
            out_dtype=flat_l.dtype)                    # [E/ep, ep*C, D]
        ye = ffn_fn(xe, eparams_l)
        ye = coll.transform_all_to_all(
            ye, EXPERT_AXIS, split_axis=1, concat_axis=0,
            transform=dispatch_wire, group_size=wire_group_size,
            out_dtype=flat_l.dtype)                    # [E, C, D]
        out = jnp.einsum("nec,ecd->nd", combine.astype(flat_l.dtype), ye)

        l_aux = coll.pmean(l_aux, token_axes)
        counts = coll.psum(counts, token_axes)
        routed = coll.psum(drop["routed"], token_axes)
        kept = coll.psum(drop["kept"], token_axes)
        return out, l_aux, counts, routed, kept

    ep_specs = jax.tree_util.tree_map(
        lambda a: P(EXPERT_AXIS, *([None] * (a.ndim - 1))), expert_params)
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(token_axes, None), P(None, None), ep_specs),
        out_specs=(P(token_axes, None), P(), P(), P(), P()),
        check_vma=False)
    out, l_aux, exp_counts, routed, kept = fn(flat, gate_w, expert_params)
    stats = {
        "routed": routed,
        "kept": kept,
        "overflow_tokens": routed - kept,
        "dropped_frac": (routed - kept) / jnp.maximum(routed, 1.0),
    }
    return out, l_aux, exp_counts, stats


# ----------------------------------------------------------------------
# dropless variant (Pallas token sort)
# ----------------------------------------------------------------------


def dropless_moe(flat, gate_w, ffn_fn, num_experts, *, interpret=None):
    """Capacity-free top-1 MoE: no token is ever dropped.

    The Pallas token sort kernel ranks each token within its expert's queue
    (stable counting sort); tokens scatter into an [E, N, D] buffer — N is
    the only static capacity bound that can never overflow — and gather back
    after the expert FFN. Memory is E·N·D, so this is for moderate N (the
    capacity path is the at-scale default).

    Returns (out [N, D], l_aux, exp_counts [E]).
    """
    from deepspeed_tpu.ops.pallas.token_sort import token_sort

    N, D = flat.shape
    E = num_experts
    logits = flat.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)
    gate_val = jnp.max(gates, axis=-1).astype(flat.dtype)

    mask1 = _one_hot(expert_idx, E)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E
    exp_counts = jnp.sum(mask1, axis=0)

    pos, _counts = token_sort(expert_idx, E, interpret=interpret)
    xe = jnp.zeros((E, N, D), flat.dtype).at[expert_idx, pos].set(flat)
    xe = shard_constraint(xe, EXPERT_AXIS, None, None)
    ye = ffn_fn(xe)
    out = ye[expert_idx, pos] * gate_val[:, None]
    return out, l_aux, exp_counts


# ----------------------------------------------------------------------
# routed top-k experts on a sorted, grouped dispatch (serving)
# ----------------------------------------------------------------------

# what `routed_experts` counts a call, in this order (int32[4]); the serving
# scheduler sums them over layers and steps (`ServingEngine.stats()["moe"]`)
ROUTED_COUNTERS = ("moe_router_calls", "moe_assignments",
                   "moe_active_experts", "moe_max_expert_load")
# ... of an expert layer that holds a share of its experts (`held=`): the
# three after the first count the HELD experts' rows, what `dstpu_moe_gmm`
# multiplies; the fifth the assignments whose expert lives on another chip
HELD_ROUTED_COUNTERS = ROUTED_COUNTERS + ("moe_routed_elsewhere",)


def relu2(x):
    """`relu(x)^2`, the plain experts' activation in the Nemotron-H family
    (`mlp_hidden_act: relu2`)."""
    return jnp.square(jax.nn.relu(x))


def topk_routing(x, gate_w, top_k, normalize=False, scoring="softmax",
                 bias=None, scale=None):
    """The router without capacity: x [N, D] -> (weights [N, k] float32,
    experts [N, k] int32).

    Logits (float32 accumulation) and scores are float32 whatever `x` is.
    `scoring` "softmax" (OLMoE): the k largest probabilities by `lax.top_k`;
    `normalize=False` (`norm_topk_prob: false`) uses them as they are, True
    rescales them to sum to one. `scoring` "sigmoid" (the DeepSeek-V3
    router, K-EXAONE's): scores are `sigmoid(logits)`, the k experts are
    those with the largest `score + bias` (`e_score_correction_bias`, [E]
    float32; it moves the CHOICE and never the weight), the weights are the
    chosen experts' scores, under `normalize` divided by their sum + 1e-20,
    then times `scale` (`routed_scaling_factor`). A token's routing depends
    on that token alone: any batching or chunking of the same tokens routes
    them the same way."""
    with jax.named_scope("moe/router"):
        logits = jnp.dot(x, gate_w.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        if scoring == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
            top_p, top_e = jax.lax.top_k(probs, top_k)
            if normalize:
                top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        elif scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            choice = scores if bias is None else \
                scores + bias.astype(jnp.float32)
            _, top_e = jax.lax.top_k(choice, top_k)
            top_p = jnp.take_along_axis(scores, top_e, axis=-1)
            if normalize:
                top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True)
                                 + 1e-20)
        else:
            raise ValueError(f"unknown router scoring {scoring!r} "
                             f"(expected 'softmax' or 'sigmoid')")
        if scale is not None:
            top_p = top_p * scale
    return top_p, top_e.astype(jnp.int32)


def routed_experts(x, top_p, top_e, experts, activation=None,
                   num_experts=None, expert_base=0, held=None, groups=1):
    """The expert MLPs of N tokens on their k chosen experts each, with no
    capacity and no dropped token: x [N, D] -> (out [N, D] in `x.dtype`,
    counters int32[4] in `ROUTED_COUNTERS` order).

    The N*k assignments, K-MAJOR (assignment `a` is choice `a // N` of token
    `a % N`), are sorted by expert (stable), the token rows are gathered in
    that order, each projection is ONE grouped matmul over the sorted rows
    (`ops/pallas/moe_gmm.py`: an expert with no rows is never read; a row's
    result depends on that row alone, so its place within its expert's run
    does not matter), and the results go back to assignment order as
    `[k, N, D]`: each token's k results are weighted and summed in float32
    over that LEADING axis, in the token's own top-k order, so the sum does
    not depend on what else is in the batch. Nothing here has the shape
    `[N, k, D]` (the module's header says what k on the sublanes costs);
    where N is a multiple of the tile, as in every served program,
    `[k, N, D]` is a bitcast of the gathered rows. The same path for a
    prefill chunk, a decode row and a verify chunk.

    `experts`: gated (SwiGLU) `{"w_gate_up": [E, D, 2F], "w_down":
    [E, F, D]}`, or plain `{"w_up": [E, D, F], "w_down": [E, F, D]}` with
    `activation` between (`relu2` for the Nemotron-H family's experts, which
    live in a latent space: `x` is then the tokens' LATENT rows and D the
    latent width) and, where the tree has them, biases `"b_up": [E, F]`,
    `"b_down": [E, D]` (per-layer trees only: they are indexed from 0). The leading
    dimension may be a longer stack (every layer's experts, `[L * E, ...]`):
    then `num_experts` is E and `expert_base` (traced: `layer * E`) is where
    this layer's begin — the whole stack goes to the kernel, nothing is
    sliced out of it.

    `held=(first, count)`: this chip's share of an expert-parallel layer.
    The router chose among ALL its experts; the weights here are those of
    experts `first .. first + count - 1` (`count` a layer in a stack). The
    held experts' rows sort to the front, in expert order, and are the only
    rows multiplied (`moe_gmm` leaves rows past `sum(group_sizes)` alone);
    an assignment to an expert that lives elsewhere contributes ZERO — what
    that expert would add is the other chip's part of the sum — and is
    counted (`HELD_ROUTED_COUNTERS`, five counters). No exchange, and
    nothing that stands in for one.

    `groups` > 1: COMBINE IN EQUAL RUNS. The N tokens are that many equal
    runs of rows (run after run); they are dispatched and multiplied
    TOGETHER — an expert's weights are read once — and the weighted sum is
    made a run at a time, at a run's shapes. The sum is float32 and its order
    is the compiler's, by shape, so a run's rows come out as a call of that
    run alone would leave them, to the bit (on the chip, PR 59: summed over
    1024 tokens for 512, one element in 30,000 rounded one bfloat16 step
    apart; every other product of the layer was equal already)."""
    from deepspeed_tpu.ops.pallas.moe_gmm import moe_gmm

    N, D = x.shape
    k = top_e.shape[1]
    M = N * k
    gated = "w_gate_up" in experts
    gmm = lambda rows, w: moe_gmm(rows, w, sizes, expert_base)
    with jax.named_scope("moe/dispatch"):
        flat_e = top_e.T.reshape(M)                   # k-major: [choice, token]
        if held is None:
            E = num_experts or experts["w_down"].shape[0]
        else:
            first, E = held
            local = flat_e - first
            # the sort key: the held experts by their place here, every
            # other assignment after them all
            flat_e = jnp.where((local >= 0) & (local < E), local, E)
        order = jnp.argsort(flat_e, stable=True)      # sorted row -> assignment
        sizes = jnp.sum(flat_e[:, None] == jnp.arange(E, dtype=jnp.int32),
                        axis=0, dtype=jnp.int32)
        # both gathers' indices are in bounds by construction (`order` and
        # `back` are permutations of the assignments): "clip" spares the
        # default mode's fill, a second pass over the gathered [M, D]
        rows = jnp.take(x, order % N, axis=0, mode="clip")    # [M, D]
    with jax.named_scope("moe/experts"):
        if gated:
            gate, up = jnp.split(gmm(rows, experts["w_gate_up"]), 2, axis=-1)
            y = gmm(jax.nn.silu(gate) * up, experts["w_down"])
        else:
            def biased(y, name):        # per-layer trees: base 0
                if name not in experts:
                    return y
                return y + jnp.take(experts[name], jnp.take(flat_e, order),
                                    axis=0)
            h = biased(gmm(rows, experts["w_up"]), "b_up")
            y = biased(gmm(activation(h), experts["w_down"]), "b_down")
    def combine(back, top_p, flat_e):
        """The n tokens whose assignments [k * n] (their sort keys `flat_e`)
        lie at the sorted rows `back`: their k results weighted by `top_p`
        [n, k] and summed, [n, D]."""
        rows = jnp.take(y, back, axis=0, mode="clip").reshape(k, -1, D)
        if held is not None:
            # rows past the held ones are memory nobody wrote
            rows = jnp.where((flat_e < E).reshape(k, -1, 1), rows, 0)
        return jnp.sum(rows.astype(jnp.float32) * top_p.T[:, :, None],
                       axis=0).astype(x.dtype)

    with jax.named_scope("moe/combine"):
        back = jnp.zeros((M,), jnp.int32).at[order].set(
            jnp.arange(M, dtype=jnp.int32))           # assignment -> sorted row
        if groups == 1:
            out = combine(back, top_p, flat_e)
        else:
            run = lambda a, g: a.reshape(k, groups, -1)[:, g].reshape(-1)
            out = jnp.concatenate([
                combine(run(back, g), jnp.split(top_p, groups)[g],
                        run(flat_e, g) if held else None)
                for g in range(groups)])
    if held is None:
        counters = jnp.stack([jnp.int32(1), jnp.int32(M),
                              jnp.sum(sizes > 0, dtype=jnp.int32),
                              jnp.max(sizes)])
    else:
        here = jnp.sum(sizes)
        counters = jnp.stack([jnp.int32(1), here,
                              jnp.sum(sizes > 0, dtype=jnp.int32),
                              jnp.max(sizes), jnp.int32(M) - here])
    return out, counters


@dataclasses.dataclass
class MoELayer:
    """Functional expert-parallel FFN layer.

    Params layout (stacked over experts, expert dim sharded on the `expert` axis):
      {"gate_w": [D, E], "wi": [E, D, F], "wo": [E, F, D]}  (+ optional biases)

    Call: (params, x[B,S,D], rng) -> (y[B,S,D], l_aux, exp_counts). Pass
    ``mesh=`` to route dispatch through the comm facade's all_to_all inside
    shard_map (when `can_use_expert_shard_map` holds); otherwise the einsum
    fallback runs. ``dropless=True`` switches to the token-sort path.
    """
    num_experts: int
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    activation: Callable = jax.nn.gelu
    use_residual: bool = False     # residual MoE (DS-MoE paper)
    dropless: bool = False         # token-sort scatter, no capacity drops
    dispatch_wire: str = "none"    # WireTransform for the facade a2a pair

    def init_params(self, d_model, d_ff, seed=0, dtype=jnp.float32):
        rng = np.random.default_rng(seed)
        E, D, F = self.num_experts, d_model, d_ff
        p = {
            "gate_w": jnp.asarray(rng.normal(0, 0.02, (D, E)), jnp.float32),
            "wi": jnp.asarray(rng.normal(0, 0.02, (E, D, F)), dtype),
            "wi_b": jnp.zeros((E, F), dtype),
            "wo": jnp.asarray(rng.normal(0, 0.02, (E, F, D)), dtype),
            "wo_b": jnp.zeros((E, D), dtype),
        }
        if self.use_residual:
            p["res_wi"] = jnp.asarray(rng.normal(0, 0.02, (D, F)), dtype)
            p["res_wo"] = jnp.asarray(rng.normal(0, 0.02, (F, D)), dtype)
            p["res_coef"] = jnp.asarray(rng.normal(0, 0.02, (D, 2)), jnp.float32)
        return p

    def param_specs(self):
        e, t = EXPERT_AXIS, TENSOR_AXIS
        specs = {
            "gate_w": P(None, None),
            "wi": P(e, None, t),
            "wi_b": P(e, t),
            "wo": P(e, t, None),
            "wo_b": P(e, None),
        }
        if self.use_residual:
            specs["res_wi"] = P(None, t)
            specs["res_wo"] = P(t, None)
            specs["res_coef"] = P(None, None)
        return specs

    def _ffn(self, xe, p, constrain=True):
        h = jnp.einsum("ecd,edf->ecf", xe, p["wi"]) + p["wi_b"][:, None, :]
        h = self.activation(h)
        if constrain:
            h = shard_constraint(h, EXPERT_AXIS, None, TENSOR_AXIS)
        return jnp.einsum("ecf,efd->ecd", h, p["wo"]) + p["wo_b"][:, None, :]

    def __call__(self, params, x, rng=None, training=True, mesh=None):
        B, S, D = x.shape
        E = self.num_experts
        N = B * S
        flat = x.reshape(N, D)
        cf = self.capacity_factor if training else self.eval_capacity_factor
        eparams = {k: params[k] for k in ("wi", "wi_b", "wo", "wo_b")}

        if self.dropless:
            y, l_aux, exp_counts = dropless_moe(
                flat, params["gate_w"], lambda xe: self._ffn(xe, eparams), E)
        elif can_use_expert_shard_map(mesh, E, N):
            y, l_aux, exp_counts, _stats = expert_parallel_moe(
                flat, params["gate_w"], eparams,
                lambda xe, p: self._ffn(xe, p, constrain=False), mesh,
                num_experts=E, capacity_factor=cf,
                min_capacity=self.min_capacity, k=self.k,
                noisy_gate_policy=self.noisy_gate_policy if training else None,
                rng=rng if training else None,
                dispatch_wire=self.dispatch_wire)
        else:
            logits = flat.astype(jnp.float32) @ params["gate_w"]
            if self.k == 1:
                l_aux, dispatch, combine, exp_counts = top1_gating(
                    logits, cf, self.min_capacity, self.noisy_gate_policy, rng)
            else:
                l_aux, dispatch, combine, exp_counts = top2_gating(
                    logits, cf, self.min_capacity, rng)

            # dispatch: [N,E,C] → expert inputs [E,C,D]; constraint puts E on the
            # expert mesh axis (XLA all-to-all = reference _AllToAll, sharded_moe.py:95)
            exp_in = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), flat)
            exp_in = shard_constraint(exp_in, EXPERT_AXIS, None, None)
            out = self._ffn(exp_in, eparams)
            out = shard_constraint(out, EXPERT_AXIS, None, None)
            y = jnp.einsum("nec,ecd->nd", combine.astype(x.dtype), out)

        y = y.reshape(B, S, D)
        if self.use_residual:
            mlp = self.activation(x @ params["res_wi"]) @ params["res_wo"]
            coef = jax.nn.softmax(x.astype(jnp.float32) @ params["res_coef"], axis=-1)
            y = y * coef[..., 0:1].astype(x.dtype) + mlp * coef[..., 1:2].astype(x.dtype)
        return y, l_aux, exp_counts


class MoE:
    """API-parity wrapper (reference `moe/layer.py:16` signature)."""

    def __init__(self, hidden_size, expert=None, num_experts=1, ep_size=1, k=1,
                 capacity_factor=1.0, eval_capacity_factor=1.0, min_capacity=4,
                 use_residual=False, noisy_gate_policy=None, drop_tokens=True,
                 use_rts=True, use_tutel=False, enable_expert_tensor_parallelism=False):
        self.hidden_size = hidden_size
        self.num_experts = num_experts
        self.ep_size = ep_size
        self.layer = MoELayer(num_experts=num_experts, k=k,
                              capacity_factor=capacity_factor,
                              eval_capacity_factor=eval_capacity_factor,
                              min_capacity=min_capacity,
                              noisy_gate_policy=noisy_gate_policy,
                              use_residual=use_residual,
                              dropless=not drop_tokens)

    def init_params(self, d_ff, seed=0, dtype=jnp.float32):
        return self.layer.init_params(self.hidden_size, d_ff, seed=seed, dtype=dtype)

    def param_specs(self):
        return self.layer.param_specs()

    def __call__(self, params, hidden_states, rng=None, used_token=None, mesh=None):
        y, l_aux, exp_counts = self.layer(params, hidden_states, rng, mesh=mesh)
        return y, l_aux, exp_counts
