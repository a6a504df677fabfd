"""Pipeline parallelism — looped SPMD pipelining over the `pipe` mesh axis.

Reference: `runtime/pipe/` (3.1k LoC) — `PipelineModule` (`pipe/module.py:130`,
LayerSpec list partitioned by parameters/uniform), `PipelineEngine`
(`pipe/engine.py:55`) interpreting instruction schedules (`pipe/schedule.py:189`
TrainSchedule/1F1B) with explicit P2P (`pipe/p2p.py`).

TPU-native formulation: ONE compiled SPMD program. Stage parameters are stacked
[PP, layers_per_stage, ...] and sharded on `pipe`; a schedule is a `lax.scan`
of ticks inside `shard_map`; stage handoff is a `ppermute` shift — the
instruction stream, P2P meta exchange and schedule interpreter of the
reference collapse into this loop. Two schedules:

* `pipeline_loss_fn` — fill-drain (GPipe) forward; backward by autodiff
  through the scan (O(M) live activations, used for eval / as a fallback).
* `pipeline_grad_fn` — 1F1B training schedule (reference `TrainSchedule`,
  `pipe/schedule.py:189`): forward and delayed backward micro-steps
  interleaved in one scan, stage inputs stashed in a 2*PP ring buffer,
  backward recomputed via `jax.vjp` — O(PP) live activations.

Embedding lives on stage 0, LM head + loss on the last stage; their params are
replicated over `pipe` but their compute runs under `lax.cond` on the owning
stage only. Bubble overhead is the standard (PP-1)/M fill-drain cost.
"""

import dataclasses
from functools import partial
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm import collectives as coll
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.comm.mesh import (BATCH_AXES, DATA_AXIS, PIPE_AXIS, SEQ_AXIS,
                                     TENSOR_AXIS, ZERO_INNER_AXIS)
from deepspeed_tpu.utils.logging import logger


# ----------------------------------------------------------------------
# LayerSpec-style container (API parity with deepspeed.pipe)
# ----------------------------------------------------------------------


@dataclasses.dataclass
class LayerSpec:
    """Deferred layer (reference `deepspeed/pipe` LayerSpec): builds params lazily
    so each stage only materializes its own layers."""
    init_fn: Callable[..., Any]       # () -> params
    apply_fn: Callable[..., Any]      # (params, x) -> x
    name: str = "layer"


class TiedLayerSpec(LayerSpec):
    """Weight tying across stages (reference TiedLayerSpec) — realized here by
    replicating the tied params over `pipe` and psum-ing their grads, which is
    what the reference's tied-weight allreduce does (`pipe/engine.py:266`)."""

    def __init__(self, key, init_fn, apply_fn, name="tied"):
        super().__init__(init_fn, apply_fn, name)
        self.key = key


def partition_layers(n_layers, n_stages, method="uniform", costs=None, names=None):
    """Layer → stage assignment (reference `PipelineModule` partition methods
    `module.py:370-386`): 'uniform' (equal counts), 'parameters' (balance by
    per-layer cost), or 'type:regex' (balance the count of layers whose name
    matches the regex; non-matching layers ride along with their stage —
    reference `module.py:385`)."""
    if method.startswith("type:"):
        import re
        if names is None:
            raise ValueError(
                "type: regex partitioning needs layer names — pass names=[...] "
                "(the reference matches layer class names, pipe/module.py:385)")
        pattern = re.compile(method[len("type:"):])
        weights = [1.0 if pattern.search(str(n)) else 0.0 for n in names]
        if sum(weights) == 0:
            raise ValueError(f"no layer name matches {method!r}: {names}")
        return partition_layers(n_layers, n_stages, "parameters", costs=weights)
    if method == "parameters" and costs is not None:
        costs = np.asarray(costs, dtype=np.float64)
        target = costs.sum() / n_stages
        bounds = [0]
        acc = 0.0
        for i, c in enumerate(costs):
            acc += c
            if acc >= target * len(bounds) and len(bounds) < n_stages:
                bounds.append(i + 1)
        while len(bounds) < n_stages:
            bounds.append(n_layers)
        bounds.append(n_layers)
        return [(bounds[i], bounds[i + 1]) for i in range(n_stages)]
    per = n_layers // n_stages
    rem = n_layers % n_stages
    out, start = [], 0
    for s in range(n_stages):
        n = per + (1 if s < rem else 0)
        out.append((start, start + n))
        start += n
    return out


def bubble_fraction(num_stages, num_microbatches, schedule="1f1b"):
    """Idle-tick fraction of the pipeline schedule.

    Both loops here run `n_ticks` scan iterations while only M of them do
    useful work per stage, so the bubble is (n_ticks - M) / n_ticks:

      gpipe (fill-drain forward): n_ticks = M + PP - 1  → (PP-1)/(M+PP-1)
      1f1b  (TrainSchedule):      n_ticks = M + 2PP - 1 → (2PP-1)/(M+2PP-1)

    (The 1F1B loop interleaves one forward AND one backward micro-step per
    tick, so its tick count — and bubble — spans the combined fwd+bwd
    schedule; the classic (PP-1)/M figure is this same quantity for the
    fwd-only fill-drain loop at large M.)
    """
    PP, M = int(num_stages), int(num_microbatches)
    if PP < 1 or M < 1:
        raise ValueError(f"num_stages={PP} and num_microbatches={M} must be >= 1")
    schedule = schedule.lower()
    if schedule == "1f1b":
        n_ticks = M + 2 * PP - 1
    elif schedule == "gpipe":
        n_ticks = M + PP - 1
    else:
        raise ValueError(f"unknown pipeline schedule {schedule!r}; "
                         "expected '1f1b' or 'gpipe'")
    return float(n_ticks - M) / float(n_ticks)


# ----------------------------------------------------------------------
# the looped pipeline program
# ----------------------------------------------------------------------


def _block_specs(params, block_tp_specs=None):
    """blocks-leaf PartitionSpecs: leading dim on `pipe`, optional TP tails
    (one composition point for the outer param specs AND shard_map in_specs —
    they must never disagree or every step pays a reshard)."""
    if block_tp_specs is None:
        return jax.tree_util.tree_map(
            lambda l: P(*([PIPE_AXIS] + [None] * (l.ndim - 1))), params["blocks"])
    return jax.tree_util.tree_map(
        lambda l, s: P(*([PIPE_AXIS] + list(tuple(s)))),
        params["blocks"], block_tp_specs)


def _pipe_inner_specs(params, block_tp_specs=None):
    """shard_map in_specs for the pipeline param layout (embed/head replicated,
    blocks leading-dim sharded on pipe) — one source of truth for both the
    training (1F1B) and inference schedules.

    `block_tp_specs`: optional tree matching params["blocks"] whose leaves are
    PartitionSpecs WITHOUT the leading layer dim (Megatron TP tails, e.g.
    P(None, "tensor") for a column-parallel [D, F] weight) — composed as
    P(pipe, *tail) for 3D pp x tp (x dp/zero outside)."""
    return {
        "embed": jax.tree_util.tree_map(lambda _: P(), params["embed"]),
        "blocks": _block_specs(params, block_tp_specs),
        "head": jax.tree_util.tree_map(lambda _: P(), params["head"]),
    }


# ----------------------------------------------------------------------
# Megatron-style tensor parallelism INSIDE the pipeline stage
# ----------------------------------------------------------------------


@jax.custom_vjp
def _tp_copy(x):
    """Megatron's `f` operator at a TP branch input: identity forward,
    all-reduce (psum over `tensor`) backward — the branch's column-parallel
    consumers each see the full activation, and its cotangent re-assembles
    the full gradient before flowing into the replicated region (reference
    equivalent: megatron's copy_to_tensor_model_parallel_region; the row
    outputs' forward psum plays `g`, whose transpose is identity)."""
    return x


def _tp_copy_fwd(x):
    return x, None


def _tp_copy_bwd(_, g):
    return (jax.lax.psum(g, TENSOR_AXIS),)


_tp_copy.defvjp(_tp_copy_fwd, _tp_copy_bwd)


@jax.custom_vjp
def _tp_reduce(x):
    """Megatron's `g` operator at a TP row-parallel output: psum forward,
    IDENTITY backward. Must be a custom_vjp: under shard_map(check_vma=False)
    a raw `lax.psum` transposes to psum again (the unchecked-replication
    transpose rule), which double-counts every TP cotangent by a factor of
    tp — measured as exactly-2x weight grads at tp=2 before this wrapper."""
    return jax.lax.psum(x, TENSOR_AXIS)


def _tp_reduce_fwd(x):
    return jax.lax.psum(x, TENSOR_AXIS), None


def _tp_reduce_bwd(_, g):
    return (g,)


_tp_reduce.defvjp(_tp_reduce_fwd, _tp_reduce_bwd)


def make_tp_block_fn(cfg, tp):
    """Transformer block over TENSOR-SHARDED leaves inside a fully-manual
    shard_map (pipeline stages): column-parallel q/k/v/up (separate leaves —
    a fused qkv dim cannot be evenly chunked into per-rank q|k|v runs), heads
    computed locally, row-parallel out/down followed by an explicit psum over
    `tensor`. LayerNorms run replicated; `_tp_copy` at each branch input
    makes their backward exact. Activation layout between blocks: replicated
    over `tensor` (classic Megatron; sequence-parallel LN sharding composes
    via the `sequence` axis outside).

    Supported config subset under TP is asserted in `split_block_params`."""
    from deepspeed_tpu.models.gpt import _attention, _norm, _rope, _act

    Hl = cfg.n_head // tp
    Hkvl = cfg.n_kv_head // tp
    hd = cfg.head_dim
    lcfg = dataclasses.replace(cfg, use_flash_attention=False)

    def block_fn(p, x, rng):
        B, T, D = x.shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

        h = _norm(x, p["ln1_scale"], p.get("ln1_bias"), cfg.use_rmsnorm,
                  cfg.norm_eps)
        h = _tp_copy(h)
        q = (h @ p["attn_q_w"] + p["attn_q_b"]).reshape(B, T, Hl, hd)
        k = (h @ p["attn_k_w"] + p["attn_k_b"]).reshape(B, T, Hkvl, hd)
        v = (h @ p["attn_v_w"] + p["attn_v_b"]).reshape(B, T, Hkvl, hd)
        if cfg.use_rotary:
            rd = int(cfg.rotary_pct * hd) // 2 * 2
            q = _rope(q, positions, rd, cfg.rope_theta)
            k = _rope(k, positions, rd, cfg.rope_theta)
        causal = jnp.tril(jnp.ones((T, T), bool))[None, None]
        attn = _attention(q, k, v, causal, lcfg)           # local heads
        attn_o = attn.reshape(B, T, Hl * hd) @ p["attn_out_w"]  # row parallel
        attn_o = _tp_reduce(attn_o) + p["attn_out_b"]

        use_rms = cfg.use_rmsnorm
        if cfg.parallel_residual:
            h2 = _norm(x, p["ln2_scale"], p.get("ln2_bias"), use_rms, cfg.norm_eps)
        else:
            x = x + attn_o
            h2 = _norm(x, p["ln2_scale"], p.get("ln2_bias"), use_rms, cfg.norm_eps)
        h2 = _tp_copy(h2)
        if cfg.use_swiglu:
            up = jax.nn.silu(h2 @ p["mlp_gate_w"]) * (h2 @ p["mlp_up_w"])
        else:
            up = _act(h2 @ p["mlp_up_w"] + p["mlp_up_b"], cfg)
        down = _tp_reduce(up @ p["mlp_down_w"]) + p["mlp_out_b"]
        if cfg.parallel_residual:
            return x + attn_o + down
        return x + down

    return block_fn


def split_block_params(cfg, blocks):
    """Fused-qkv stacked block params → the TP layout (separate q/k/v leaves).

    The fused [L, D, (H+2Hkv)*hd] weight cannot shard its output dim over
    `tensor`: equal chunks straddle the q|k|v boundaries. Splitting restores
    clean per-leaf column sharding; `checkpoint/universal.py` already
    converts fused↔split qkv orderings for resharding."""
    assert not cfg.use_alibi, "alibi slopes need global head indices under TP"
    assert cfg.attn_layer_types is None and not cfg.sliding_window, \
        "per-layer local attention is not wired for the TP pipeline block yet"
    H, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    out = dict(blocks)
    qkv_w = out.pop("attn_qkv_w")
    qkv_b = out.pop("attn_qkv_b")
    q_end, k_end = H * hd, (H + Hkv) * hd
    out["attn_q_w"], out["attn_k_w"], out["attn_v_w"] = (
        qkv_w[..., :q_end], qkv_w[..., q_end:k_end], qkv_w[..., k_end:])
    out["attn_q_b"], out["attn_k_b"], out["attn_v_b"] = (
        qkv_b[..., :q_end], qkv_b[..., q_end:k_end], qkv_b[..., k_end:])
    return out


def tp_block_specs(cfg, blocks_split):
    """PartitionSpec tails (no layer dim) for the split TP block layout."""
    t = TENSOR_AXIS
    col_w, col_b = P(None, t), P(t)
    row_w, rep_v, rep_b = P(t, None), P(None), P(None)
    specs = {
        "ln1_scale": rep_v, "ln2_scale": rep_v,
        "attn_q_w": col_w, "attn_k_w": col_w, "attn_v_w": col_w,
        "attn_q_b": col_b, "attn_k_b": col_b, "attn_v_b": col_b,
        "attn_out_w": row_w, "attn_out_b": rep_b, "mlp_out_b": rep_b,
    }
    if not cfg.use_rmsnorm:
        specs["ln1_bias"] = rep_v
        specs["ln2_bias"] = rep_v
    if cfg.use_swiglu:
        specs["mlp_gate_w"] = col_w
        specs["mlp_up_w"] = col_w
        specs["mlp_down_w"] = row_w
    else:
        specs["mlp_up_w"] = col_w
        specs["mlp_up_b"] = col_b
        specs["mlp_down_w"] = row_w
    assert set(specs) == set(blocks_split), (
        sorted(set(blocks_split) ^ set(specs)))
    return specs


def make_ulysses_block_fn(cfg, sp):
    """Transformer block with DeepSpeed-Ulysses sequence parallelism INSIDE the
    pipeline stage: activations arrive sequence-sharded [B, T/sp, D]; q/k/v are
    computed locally, the Ulysses all-to-all sandwich (reference
    `sequence/layer.py:15` `_SeqAllToAll`) trades the sequence shard for a head
    shard, attention runs over the FULL sequence with H/sp local heads, and the
    output trades back. RoPE is applied BEFORE the all-to-all using global
    positions (axis_index(sequence) * T_local offset), so rotary phases match
    the unsharded model exactly.

    Composes pipe × data × sequence: the `pipe` axis is handled by the outer
    schedule, `sequence` by this block. Mutually exclusive with in-stage TP
    (asserted by the caller): both re-shard heads and would fight over them."""
    from deepspeed_tpu.models.gpt import _attention, _norm, _rope, _act
    from deepspeed_tpu.parallel.ulysses import seq_all_to_all

    assert not cfg.use_alibi, "alibi slopes need global head indices under Ulysses"
    assert cfg.attn_layer_types is None and not cfg.sliding_window, \
        "per-layer local attention is not wired for the Ulysses pipeline block yet"
    H, Hkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    lcfg = dataclasses.replace(cfg, use_flash_attention=False)

    def block_fn(p, x, rng):
        B, Tl, D = x.shape
        t0 = jax.lax.axis_index(SEQ_AXIS) * Tl
        positions = jnp.broadcast_to(
            t0 + jnp.arange(Tl, dtype=jnp.int32)[None], (B, Tl))

        h = _norm(x, p["ln1_scale"], p.get("ln1_bias"), cfg.use_rmsnorm,
                  cfg.norm_eps)
        qkv = h @ p["attn_qkv_w"] + p["attn_qkv_b"]
        q, k, v = jnp.split(qkv, [H * hd, (H + Hkv) * hd], axis=-1)
        q = q.reshape(B, Tl, H, hd)
        k = k.reshape(B, Tl, Hkv, hd)
        v = v.reshape(B, Tl, Hkv, hd)
        if cfg.use_rotary:
            rd = int(cfg.rotary_pct * hd) // 2 * 2
            q = _rope(q, positions, rd, cfg.rope_theta)
            k = _rope(k, positions, rd, cfg.rope_theta)
        # sequence→head re-shard: [B, T/sp, H, hd] → [B, T, H/sp, hd]
        q = seq_all_to_all(q, scatter_axis=2, gather_axis=1)
        k = seq_all_to_all(k, scatter_axis=2, gather_axis=1)
        v = seq_all_to_all(v, scatter_axis=2, gather_axis=1)
        T = Tl * sp
        causal = jnp.tril(jnp.ones((T, T), bool))[None, None]
        attn = _attention(q, k, v, causal, lcfg)      # full seq, local heads
        # head→sequence re-shard back: [B, T, H/sp, hd] → [B, T/sp, H, hd]
        attn = seq_all_to_all(attn, scatter_axis=1, gather_axis=2)
        attn_o = attn.reshape(B, Tl, H * hd) @ p["attn_out_w"] + p["attn_out_b"]

        use_rms = cfg.use_rmsnorm
        if cfg.parallel_residual:
            h2 = _norm(x, p["ln2_scale"], p.get("ln2_bias"), use_rms, cfg.norm_eps)
        else:
            x = x + attn_o
            h2 = _norm(x, p["ln2_scale"], p.get("ln2_bias"), use_rms, cfg.norm_eps)
        if cfg.use_swiglu:
            up = jax.nn.silu(h2 @ p["mlp_gate_w"]) * (h2 @ p["mlp_up_w"])
        else:
            up = _act(h2 @ p["mlp_up_w"] + p["mlp_up_b"], cfg)
        down = up @ p["mlp_down_w"] + p["mlp_out_b"]
        if cfg.parallel_residual:
            return x + attn_o + down
        return x + down

    return block_fn


def _batch_specs(batch, seq_sharded=False):
    """shard_map in_specs for the batch: leading dim over the data domain,
    and — for sequence-parallel pipelines — dim 1 (time) over `sequence`."""
    def leaf(a):
        if seq_sharded and a.ndim >= 2:
            return P(BATCH_AXES, SEQ_AXIS)
        return P(BATCH_AXES)
    return jax.tree_util.tree_map(leaf, batch)


def _mb_view(batch, i, M):
    """Microbatch i of a microbatch-major local batch."""
    def slice_leaf(a):
        if a.shape[0] % M != 0:
            raise ValueError(
                f"pipeline batch leading dim {a.shape[0]} is not divisible by "
                f"num_microbatches={M}; trailing samples would be silently "
                f"dropped from the loss")
        return jax.lax.dynamic_slice_in_dim(a, i * (a.shape[0] // M),
                                            a.shape[0] // M, axis=0)
    return jax.tree_util.tree_map(slice_leaf, batch)


def _make_stage_apply(block_fn, blocks):
    """Apply this stage's stacked layers (scan over the local block slice)."""
    def stage_apply(x, rng):
        def layer_body(h, lp):
            return block_fn(lp, h, rng), None
        out, _ = jax.lax.scan(layer_body, x, blocks)
        return out
    return stage_apply


def pipeline_loss_fn(embed_fn, block_fn, head_loss_fn, num_stages,
                     num_microbatches, remat_blocks=True, block_tp_specs=None,
                     remat_prevent_cse=False, seq_sharded=False):
    """Builds loss_fn(params, batch, rng) running the pipelined schedule.

    params = {"embed": <replicated>, "blocks": <stacked [PP*Lp, ...] leaves,
    sharded on pipe via leading dim>, "head": <replicated>}

    * embed_fn(embed_params, micro_batch, rng) -> activation [mb, ...]
    * block_fn(layer_params, activation, rng) -> activation  (applied per layer)
    * head_loss_fn(full_params, activation, micro_batch, rng) -> scalar loss
      (gets the FULL params dict so tied embeddings read the single "embed" leaf —
      reference TiedLayerSpec semantics with one parameter instead of a
      replicate+allreduce pair)
    batch: pytree with leading dim M*mb (microbatch-major).
    """
    PP = num_stages
    M = num_microbatches
    if remat_blocks:
        # default False: block_fn runs inside the schedule scan, the
        # safe+faster placement (see GPTConfig.remat_prevent_cse)
        block_fn = jax.checkpoint(block_fn, prevent_cse=remat_prevent_cse)

    def local(params, batch, rng):
        # inside shard_map over ('pipe',): blocks leaf leading dim = layers/stage
        p_idx = jax.lax.axis_index(PIPE_AXIS)
        stage_apply = _make_stage_apply(block_fn, params["blocks"])

        def mb_view(i):
            return _mb_view(batch, i, M)

        mb0 = mb_view(0)
        act_shape = jax.eval_shape(embed_fn, params["embed"], mb0, rng)
        zeros_act = jnp.zeros(act_shape.shape, act_shape.dtype)

        n_ticks = M + PP - 1
        perm_fwd = [(j, j + 1) for j in range(PP - 1)]

        def tick(carry, t):
            buf, loss_sum, n_done = carry
            mb_idx = t - p_idx
            active = (mb_idx >= 0) & (mb_idx < M)
            # Stage 0 reads its microbatch; others read the handed-off
            # activation. Embed and head run under lax.cond so only the owning
            # stage pays their flops — safe because any collective inside a
            # branch (the sequence-parallel loss psum) runs over an axis whose
            # ranks all share the branch predicate; pipe ppermute/psum stay at
            # tick top level.
            mb_i = jnp.clip(t, 0, M - 1)
            x_in = jax.lax.cond(
                p_idx == 0,
                lambda: embed_fn(params["embed"], mb_view(mb_i), rng),
                lambda: buf)
            y = stage_apply(x_in, rng)
            y = jnp.where(active, y, zeros_act)
            # last stage: loss of its active microbatch (owner-only compute —
            # the [mb,T,d]x[d,V] head matmul is a large fraction of stage flops)
            out_idx = jnp.clip(t - (PP - 1), 0, M - 1)
            take = active & (p_idx == PP - 1)
            mb_loss = jax.lax.cond(
                take,
                lambda: head_loss_fn(params, y, mb_view(out_idx), rng).astype(
                    jnp.float32),
                lambda: jnp.asarray(0.0, jnp.float32))
            loss_sum = loss_sum + mb_loss
            n_done = n_done + jnp.where(take, 1, 0)
            buf = coll.ppermute(y, PIPE_AXIS, perm_fwd, repeats=n_ticks)
            return (buf, loss_sum, n_done), None

        (buf, loss_sum, n_done), _ = jax.lax.scan(
            tick, (zeros_act, jnp.asarray(0.0, jnp.float32), jnp.asarray(0, jnp.int32)),
            jnp.arange(n_ticks))
        # broadcast the mean loss to every pipe rank (reference _aggregate_total_loss)
        total = coll.psum(loss_sum, PIPE_AXIS)
        count = coll.psum(n_done, PIPE_AXIS)
        loss = total / jnp.maximum(count, 1)
        # mean over the data domain so grads of pipe-replicated leaves come out as
        # global-batch means
        return coll.pmean(loss, (DATA_AXIS, ZERO_INNER_AXIS, SEQ_AXIS))

    def loss_fn(params, batch, rng):
        mesh = mesh_mod.get_mesh()
        # batch stays data-sharded on its leading dim (composes PP × DP);
        # sequence-parallel models also shard the time dim over `sequence`
        batch_spec = _batch_specs(batch, seq_sharded)
        with mesh_mod.constraints_disabled():
            fn = shard_map(local, mesh=mesh,
                           in_specs=(_pipe_inner_specs(params, block_tp_specs),
                                     batch_spec, P()),
                           out_specs=P(), check_vma=False)
            return fn(params, batch, rng)

    return loss_fn


def pipeline_grad_fn(embed_fn, block_fn, head_loss_fn, num_stages,
                     num_microbatches, remat_blocks=True, block_tp_specs=None,
                     remat_prevent_cse=False, seq_sharded=False,
                     grad_reduce_transform="none"):
    """1F1B-structured pipelined (loss, grads) — reference `TrainSchedule`
    (`runtime/pipe/schedule.py:189`).

    One `lax.scan` interleaves a forward micro-step and a delayed backward
    micro-step per tick. Stage INPUTS are stashed in a ring buffer of 2*PP
    slots; the backward recomputes the stage forward inside `jax.vjp`, so live
    activation memory is O(PP) — independent of the microbatch count M.
    (GPipe/fill-drain autodiff through the scan keeps O(M) activations; this
    is the 1F1B memory bound the reference schedule exists for.)

    Schedule (stage s, microbatch i, PP stages):
      forward  of (i, s) at tick t = i + s
      backward of (i, s) at tick t = i + 2*PP - 1 - s
    Loss + head vjp run fused in the last stage's backward; cotangents hop
    stage s -> s-1 via reverse ppermute. Total ticks: M + 2*PP - 1; per tick
    each rank does one stage forward + one stage backward — the steady-state
    1F1B pattern. Embed/head/loss run under `lax.cond` so only the owning
    stage pays their flops (branches are collective-free).

    Returns grad_fn(params, batch, rng) -> (mean_loss, grads), grads in the
    pipeline layout (blocks pipe-sharded, embed/head replicated with tied
    contributions psummed over pipe — the reference's tied-weight allreduce),
    averaged over the data domain.
    """
    PP = num_stages
    M = num_microbatches
    R = 2 * PP  # ring slots; a stash entry lives 2*(PP-s)-1 < R ticks
    if grad_reduce_transform not in ("none", "int8"):
        raise ValueError(
            f"pipeline grad_reduce_transform must be one of ('none', 'int8'); "
            f"got {grad_reduce_transform!r} ('onebit' needs the persistent "
            f"error-feedback state the engine's onebit_gradients path carries)")
    if remat_blocks:
        # default False: block_fn runs inside the schedule scan, the
        # safe+faster placement (see GPTConfig.remat_prevent_cse)
        block_fn = jax.checkpoint(block_fn, prevent_cse=remat_prevent_cse)

    def local(params, batch, rng):
        p_idx = jax.lax.axis_index(PIPE_AXIS)
        blocks = params["blocks"]
        he = {"embed": params["embed"], "head": params["head"]}

        def stage_apply_with(blk, x):
            def layer_body(h, lp):
                return block_fn(lp, h, rng), None
            out, _ = jax.lax.scan(layer_body, x, blk)
            return out

        def mb_view(i):
            return _mb_view(batch, i, M)

        mb0 = mb_view(0)
        act_shape = jax.eval_shape(embed_fn, params["embed"], mb0, rng)
        zeros_act = jnp.zeros(act_shape.shape, act_shape.dtype)

        def zeros32(tree):
            return jax.tree_util.tree_map(
                lambda l: jnp.zeros(l.shape, jnp.float32), tree)

        carry0 = (
            zeros_act,                                   # fwd handoff buffer
            zeros_act,                                   # bwd cotangent buffer
            jnp.zeros((R,) + act_shape.shape, act_shape.dtype),  # input stash
            zeros32(blocks),                             # grad accum (blocks)
            zeros32(he),                                 # grad accum (embed/head)
            jnp.asarray(0.0, jnp.float32),               # loss sum
        )

        n_ticks = M + 2 * PP - 1
        perm_fwd = [(j, j + 1) for j in range(PP - 1)]
        perm_bwd = [(j, j - 1) for j in range(1, PP)]

        def tick(carry, t):
            fwd_buf, bwd_buf, xstash, gblocks, ghe, loss_sum = carry

            # ---- forward micro-step ------------------------------------
            f_idx = t - p_idx
            f_active = (f_idx >= 0) & (f_idx < M)
            mb_f = jnp.clip(f_idx, 0, M - 1)
            x_in = jax.lax.cond(
                p_idx == 0,
                lambda: embed_fn(params["embed"], mb_view(mb_f), rng),
                lambda: fwd_buf)
            y = stage_apply_with(blocks, x_in)
            y = jnp.where(f_active, y, zeros_act)
            f_slot = jnp.mod(f_idx, R)
            cur = jax.lax.dynamic_index_in_dim(xstash, f_slot, keepdims=False)
            xstash = jax.lax.dynamic_update_index_in_dim(
                xstash, jnp.where(f_active, x_in, cur), f_slot, 0)

            # ---- backward micro-step -----------------------------------
            b_idx = t - (2 * PP - 1 - p_idx)
            b_active = (b_idx >= 0) & (b_idx < M)
            mb_b = jnp.clip(b_idx, 0, M - 1)
            mbb = mb_view(mb_b)
            x_b = jax.lax.dynamic_index_in_dim(
                xstash, jnp.mod(b_idx, R), keepdims=False)

            def last_bwd():
                # loss + head vjp fused into the last stage's backward
                def f(blk, he_, x):
                    full = {"embed": he_["embed"], "blocks": blk,
                            "head": he_["head"]}
                    yy = stage_apply_with(blk, x)
                    return head_loss_fn(full, yy, mbb, rng).astype(jnp.float32)
                loss_i, vjp = jax.vjp(f, blocks, he, x_b)
                dblk, dhe, dx = vjp(jnp.asarray(1.0, jnp.float32))
                return loss_i, dblk, dhe, dx

            def mid_bwd():
                # cotangent for an invalid microbatch is always zero (zeros
                # propagate down from the last stage), so grads stay clean
                def f(blk, x):
                    return stage_apply_with(blk, x)
                _, vjp = jax.vjp(f, blocks, x_b)
                dblk, dx = vjp(bwd_buf)
                return (jnp.asarray(0.0, jnp.float32), dblk,
                        jax.tree_util.tree_map(jnp.zeros_like, he), dx)

            loss_i, dblk, dhe, dx = jax.lax.cond(
                b_active & (p_idx == PP - 1), last_bwd, mid_bwd)

            def emb_bwd():
                _, vjp = jax.vjp(lambda ep: embed_fn(ep, mbb, rng),
                                 params["embed"])
                (dep,) = vjp(dx)
                return dep

            dembed = jax.lax.cond(
                b_active & (p_idx == 0), emb_bwd,
                lambda: jax.tree_util.tree_map(jnp.zeros_like,
                                               params["embed"]))

            def add32(a, g):
                return a + g.astype(jnp.float32)

            gblocks = jax.tree_util.tree_map(add32, gblocks, dblk)
            ghe = jax.tree_util.tree_map(add32, ghe, dhe)
            ghe = {"embed": jax.tree_util.tree_map(add32, ghe["embed"], dembed),
                   "head": ghe["head"]}
            loss_sum = loss_sum + loss_i

            fwd_buf = coll.ppermute(y, PIPE_AXIS, perm_fwd, repeats=n_ticks)
            bwd_buf = coll.ppermute(dx, PIPE_AXIS, perm_bwd, repeats=n_ticks)
            return (fwd_buf, bwd_buf, xstash, gblocks, ghe, loss_sum), None

        (carry_out, _) = jax.lax.scan(tick, carry0, jnp.arange(n_ticks))
        _, _, _, gblocks, ghe, loss_sum = carry_out

        data_axes = (DATA_AXIS, ZERO_INNER_AXIS, SEQ_AXIS)
        inv_m = 1.0 / M

        def data_mean(g):
            # mean over the data domain. With a wire transform, the reduce is
            # hierarchical: plain psum rides the fast inner axes, the
            # compressed 2-hop wire rides the outermost (slow / DCN-tier)
            # data axis — the engine's explicit grad-reduce split
            # (zero.ZeroShardingPolicy.reduce_domain) applied to the
            # pipeline's post-schedule grad finish.
            if grad_reduce_transform == "none":
                return coll.pmean(g, data_axes)
            n_total, active = 1, []
            for a in data_axes:
                s = int(jax.lax.psum(1, a))
                n_total *= s
                if s > 1:
                    active.append(a)
            if not active:
                return g
            slow, fast = active[0], tuple(active[1:])
            if fast:
                g = coll.psum(g, fast)
            g = coll.compressed_all_reduce(g, slow, grad_reduce_transform)
            return g / n_total

        def finish_rep(g, p):  # replicated leaves: tied psum over pipe
            g = coll.psum(g * inv_m, PIPE_AXIS)
            return data_mean(g).astype(p.dtype)

        def finish_shard(g, p):  # pipe-sharded leaves stay per-stage
            return data_mean(g * inv_m).astype(p.dtype)

        grads = {
            "embed": jax.tree_util.tree_map(finish_rep, ghe["embed"],
                                            params["embed"]),
            "blocks": jax.tree_util.tree_map(finish_shard, gblocks, blocks),
            "head": jax.tree_util.tree_map(finish_rep, ghe["head"],
                                           params["head"]),
        }
        loss = coll.psum(loss_sum, PIPE_AXIS) * inv_m
        loss = coll.pmean(loss, data_axes)
        return loss, grads

    def grad_fn(params, batch, rng):
        mesh = mesh_mod.get_mesh()
        batch_spec = _batch_specs(batch, seq_sharded)
        specs = _pipe_inner_specs(params, block_tp_specs)
        with mesh_mod.constraints_disabled():
            fn = shard_map(local, mesh=mesh,
                           in_specs=(specs, batch_spec, P()),
                           out_specs=(P(), specs),
                           check_vma=False)
            return fn(params, batch, rng)

    return grad_fn


def pipeline_forward_fn(embed_fn, block_fn, head_fn, num_stages,
                        num_microbatches, block_tp_specs=None,
                        seq_sharded=False):
    """Pipelined forward-only schedule (reference `InferenceSchedule`,
    `runtime/pipe/schedule.py:135`): microbatches stream through the stages,
    the last stage applies `head_fn(params, act, micro_batch, rng) -> out
    [mb, ...]`, and the concatenated outputs are broadcast to every pipe rank
    (psum from the single contributing stage — the reference's result bcast).

    Returns forward(params, batch, rng) -> outputs with leading dim M*mb.
    """
    PP = num_stages
    M = num_microbatches

    def local(params, batch, rng):
        p_idx = jax.lax.axis_index(PIPE_AXIS)
        stage_apply = _make_stage_apply(block_fn, params["blocks"])

        def mb_view(i):
            return _mb_view(batch, i, M)

        mb0 = mb_view(0)
        act_shape = jax.eval_shape(embed_fn, params["embed"], mb0, rng)
        zeros_act = jnp.zeros(act_shape.shape, act_shape.dtype)
        out_shape = jax.eval_shape(head_fn, params, zeros_act, mb0, rng)
        out_buf0 = jnp.zeros((M * out_shape.shape[0],) + out_shape.shape[1:],
                             out_shape.dtype)

        n_ticks = M + PP - 1
        perm_fwd = [(j, j + 1) for j in range(PP - 1)]

        def tick(carry, t):
            buf, out_buf = carry
            mb_idx = t - p_idx
            active = (mb_idx >= 0) & (mb_idx < M)
            mb_i = jnp.clip(t, 0, M - 1)
            x_in = jax.lax.cond(
                p_idx == 0,
                lambda: embed_fn(params["embed"], mb_view(mb_i), rng),
                lambda: buf)
            y = stage_apply(x_in, rng)
            y = jnp.where(active, y, zeros_act)
            out_idx = jnp.clip(t - (PP - 1), 0, M - 1)
            take = active & (p_idx == PP - 1)
            out = jax.lax.cond(
                take,
                lambda: head_fn(params, y, mb_view(out_idx), rng),
                lambda: jnp.zeros(out_shape.shape, out_shape.dtype))
            start = out_idx * out.shape[0]
            cur = jax.lax.dynamic_slice_in_dim(out_buf, start, out.shape[0], axis=0)
            out_buf = jax.lax.dynamic_update_slice_in_dim(out_buf, cur + out,
                                                          start, axis=0)
            buf = coll.ppermute(y, PIPE_AXIS, perm_fwd, repeats=n_ticks)
            return (buf, out_buf), None

        (buf, out_buf), _ = jax.lax.scan(tick, (zeros_act, out_buf0),
                                         jnp.arange(n_ticks))
        # only the last stage wrote non-zeros; broadcast to all pipe ranks
        return coll.psum(out_buf, PIPE_AXIS)

    def forward(params, batch, rng=None):
        mesh = mesh_mod.get_mesh()
        shards = mesh_mod.axis_size(BATCH_AXES)
        lead = jax.tree_util.tree_leaves(batch)[0].shape[0]
        assert lead % (shards * M) == 0, (
            f"pipelined forward: batch dim {lead} must divide into "
            f"{shards} data shard(s) x {M} microbatches")
        batch_spec = _batch_specs(batch, seq_sharded)
        out_spec = P(BATCH_AXES, SEQ_AXIS) if seq_sharded else P(BATCH_AXES)
        with mesh_mod.constraints_disabled():
            fn = shard_map(local, mesh=mesh,
                           in_specs=(_pipe_inner_specs(params, block_tp_specs),
                                     batch_spec, P()),
                           out_specs=out_spec, check_vma=False)
            return fn(params, batch, rng)

    return forward


def pipeline_param_specs(params, block_tp_specs=None):
    """PartitionSpecs matching pipeline_loss_fn's layout (TP tails optional)."""
    blocks = _block_specs(params, block_tp_specs)
    return {
        "embed": jax.tree_util.tree_map(lambda l: P(*([None] * l.ndim)), params["embed"]),
        "blocks": blocks,
        "head": jax.tree_util.tree_map(lambda l: P(*([None] * l.ndim)), params["head"]),
    }


# ----------------------------------------------------------------------
# pipelined GPT (zoo integration)
# ----------------------------------------------------------------------


def make_gpt_pipeline_model(cfg=None, name="gpt2-pipe", num_stages=2,
                            num_microbatches=4, seed=0, schedule="1f1b",
                            tensor_parallel=None, sequence_parallel=None,
                            grad_reduce_transform="none"):
    """Pipeline-parallel GPT ModelSpec: blocks stacked [PP*Lp, ...] on `pipe`.

    schedule: "1f1b" (default — reference TrainSchedule memory bound) trains
    via `pipeline_grad_fn`; "gpipe" trains by autodiff through the fill-drain
    loss (O(M) activation memory, kept for comparison/debugging).

    tensor_parallel: Megatron TP degree INSIDE each stage (3D pp x tp x
    dp/zero — reference `runtime/pipe/topology.py:251`
    PipeModelDataParallelTopology). Default: the current mesh's `tensor`
    axis size. With tp > 1, block weights use the split-qkv TP layout and the
    stage body runs `make_tp_block_fn` (explicit psum collectives); embed and
    head stay tensor-replicated (their flops run once per tp rank — vocab
    parallelism is a future optimization).

    sequence_parallel: Ulysses degree INSIDE each stage (pipe × data ×
    sequence composition). Default: the current mesh's `sequence` axis size.
    With sp > 1, the batch arrives time-sharded, the stage body runs
    `make_ulysses_block_fn` (all-to-all head↔sequence re-sharding), and the
    batch MUST carry explicit "labels" (the next-token shift crosses shard
    boundaries). Mutually exclusive with tensor_parallel > 1.

    grad_reduce_transform: "none" | "int8" — wire encoding for the
    data-domain grad reduce in the 1F1B finish (qgZ over the outermost data
    axis; the engine's `explicit_grad_reduce` equivalent for models that
    bring their own grad_fn)."""
    from deepspeed_tpu.models.gpt import (GPTConfig, GPT2_CONFIGS, init_gpt_params,
                                          _block, _norm)
    from deepspeed_tpu.runtime.engine import ModelSpec

    cfg = cfg or GPT2_CONFIGS.get(name) or GPTConfig()
    assert cfg.n_layer % num_stages == 0, \
        f"n_layer {cfg.n_layer} must divide evenly into {num_stages} stages"
    if tensor_parallel is None:
        tensor_parallel = (mesh_mod.axis_size(TENSOR_AXIS)
                           if mesh_mod.has_mesh() else 1)
    tp = int(tensor_parallel)
    if sequence_parallel is None:
        sequence_parallel = (mesh_mod.axis_size(SEQ_AXIS)
                             if mesh_mod.has_mesh() else 1)
    sp = int(sequence_parallel)
    if tp > 1 and sp > 1:
        raise ValueError(
            f"in-stage tensor_parallel={tp} and sequence_parallel={sp} are "
            "mutually exclusive: both re-shard attention heads. Put the "
            "degrees on one axis, or compose Ulysses with ring attention "
            "(parallel/ring.py) outside the pipeline instead")
    raw = init_gpt_params(cfg, seed=seed)

    blocks = raw["blocks"]
    block_tp_specs = None
    if tp > 1:
        assert cfg.n_head % tp == 0 and cfg.n_kv_head % tp == 0, \
            f"n_head {cfg.n_head}/n_kv_head {cfg.n_kv_head} must divide tp={tp}"
        blocks = split_block_params(cfg, blocks)
        block_tp_specs = tp_block_specs(cfg, blocks)
    if sp > 1:
        assert cfg.n_head % sp == 0 and cfg.n_kv_head % sp == 0, \
            f"n_head {cfg.n_head}/n_kv_head {cfg.n_kv_head} must divide sp={sp}"

    params = {
        "embed": {"wte": raw["wte"], **({"wpe": raw["wpe"]} if not cfg.use_rotary else {})},
        "blocks": blocks,
        "head": {"lnf_scale": raw["lnf_scale"],
                 **({"lnf_bias": raw["lnf_bias"]} if not cfg.use_rmsnorm else {})},
    }
    if not cfg.tie_embeddings:
        params["head"]["lm_head"] = raw["lm_head"]

    def _embed_tokens(ep, tokens):
        T = tokens.shape[1]
        x = jnp.take(ep["wte"], tokens, axis=0).astype(cfg.dtype)
        if not cfg.use_rotary:
            # sequence-parallel: tokens are the LOCAL time chunk — absolute
            # positions start at this rank's global offset
            t0 = jax.lax.axis_index(SEQ_AXIS) * T if sp > 1 else 0
            pos = t0 + jnp.arange(T, dtype=jnp.int32)[None]
            x = x + jnp.take(ep["wpe"], pos, axis=0).astype(cfg.dtype)
        return x

    def _head_logits(full_params, x):
        hp = full_params["head"]
        head_w = hp.get("lm_head", full_params["embed"]["wte"])  # tied by default
        x = _norm(x, hp["lnf_scale"], hp.get("lnf_bias"), cfg.use_rmsnorm)
        return jnp.einsum("btd,vd->btv", x, head_w.astype(x.dtype))

    def embed_fn(ep, micro_batch, rng):
        # gpt_loss contract: explicit "labels" → tokens are already the
        # (possibly curriculum-transformed) inputs; otherwise shift in-place.
        tokens = micro_batch.get("tokens", micro_batch.get("input_ids"))
        if sp > 1 and micro_batch.get("labels") is None:
            raise ValueError(
                "sequence-parallel pipeline needs explicit 'labels': tokens "
                "are sharded over the `sequence` axis, so the next-token "
                "shift cannot be derived locally (each shard's boundary "
                "label lives on the neighbor rank)")
        inputs = tokens if micro_batch.get("labels") is not None else tokens[:, :-1]
        return _embed_tokens(ep, inputs)

    if tp > 1:
        block_fn = make_tp_block_fn(cfg, tp)
    elif sp > 1:
        block_fn = make_ulysses_block_fn(cfg, sp)
    else:
        def block_fn(lp, x, rng):
            B, T, D = x.shape
            positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
            return _block(x, lp, cfg=cfg, positions=positions)

    def head_loss_fn(full_params, x, micro_batch, rng):
        labels = micro_batch.get("labels")
        if labels is None:
            tokens = micro_batch.get("tokens", micro_batch.get("input_ids"))
            labels = tokens[:, 1:]
        logits = _head_logits(full_params, x).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        safe = jnp.maximum(labels, 0)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        num = jnp.sum((logz - gold) * mask)
        den = jnp.sum(mask)
        if sp > 1:
            # token-weighted mean over the sequence shards (this rank holds
            # only T/sp time steps). RAW lax.psum is load-bearing here: under
            # check_vma=False its transpose is psum again, scaling every
            # downstream cotangent by sp — which the finish pmean over
            # data_axes (sequence included) divides back out, turning the
            # per-shard grads into the SUM over sequence ranks that the true
            # gradient requires. A custom-vjp identity-backward psum would
            # undercount by exactly sp. The psum pair runs inside the
            # last-stage lax.cond, which is safe: the predicate is uniform
            # across the `sequence` axis (it depends only on the pipe index).
            num = jax.lax.psum(num, SEQ_AXIS)
            den = jax.lax.psum(den, SEQ_AXIS)
        return num / jnp.maximum(den, 1.0)

    loss_fn = pipeline_loss_fn(embed_fn, block_fn, head_loss_fn,
                               num_stages=num_stages,
                               num_microbatches=num_microbatches,
                               remat_blocks=cfg.remat,
                               block_tp_specs=block_tp_specs,
                               remat_prevent_cse=cfg.remat_prevent_cse,
                               seq_sharded=sp > 1)
    # training backward: 1F1B schedule (O(PP) live activations); the
    # fill-drain loss_fn above stays as the cheaper eval/forward-only path
    schedule = schedule.lower()
    if schedule not in ("1f1b", "gpipe"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}; "
                         "expected '1f1b' or 'gpipe'")
    grad_fn = (pipeline_grad_fn(embed_fn, block_fn, head_loss_fn,
                                num_stages=num_stages,
                                num_microbatches=num_microbatches,
                                remat_blocks=cfg.remat,
                                block_tp_specs=block_tp_specs,
                                remat_prevent_cse=cfg.remat_prevent_cse,
                                seq_sharded=sp > 1,
                                grad_reduce_transform=grad_reduce_transform)
               if schedule == "1f1b" else None)
    if schedule == "gpipe" and grad_reduce_transform != "none":
        raise ValueError(
            "grad_reduce_transform requires the '1f1b' schedule (gpipe trains "
            "by autodiff through the fill-drain loss — no explicit grad finish "
            "to compress)")

    # pipelined inference forward (reference InferenceSchedule): full-sequence
    # logits, microbatches streamed through the stages
    def fwd_embed_fn(ep, micro_batch, rng):
        return _embed_tokens(ep, micro_batch["tokens"])

    def fwd_head_fn(full_params, x, micro_batch, rng):
        return _head_logits(full_params, x)

    pipelined_fwd = pipeline_forward_fn(fwd_embed_fn, block_fn, fwd_head_fn,
                                        num_stages=num_stages,
                                        num_microbatches=num_microbatches,
                                        block_tp_specs=block_tp_specs,
                                        seq_sharded=sp > 1)

    def apply_fn(params, tokens, rng=None):
        # uniform ModelSpec.apply_fn contract: raw [B, T] token array
        # (models/gpt.py gpt_forward signature); dict batches also accepted
        batch = tokens if isinstance(tokens, dict) else {"tokens": tokens}
        return pipelined_fwd(params, batch, rng)

    pipeline_info = {
        "num_stages": int(num_stages),
        "num_microbatches": int(num_microbatches),
        "schedule": schedule,
        "tensor_parallel": tp,
        "sequence_parallel": sp,
        "grad_reduce_transform": grad_reduce_transform,
        "bubble_fraction": bubble_fraction(num_stages, num_microbatches,
                                           schedule),
    }
    return ModelSpec(loss_fn=loss_fn, params=params, apply_fn=apply_fn,
                     grad_fn=grad_fn,
                     param_specs=pipeline_param_specs(params, block_tp_specs),
                     pipeline_info=pipeline_info,
                     name=name)
