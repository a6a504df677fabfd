"""Sequence-spanning serving — one monster-context request across chips.

The serving tier's paged pool (`inference/kv_cache.py`) caps a request's
context at what ONE chip's HBM holds. This module removes that wall for the
128k+ tier: the pool's physical-block axis is sharded over the `sequence`
mesh axis, a request's block table is SPLIT into per-shard tables (shard s
owns the contiguous logical-block range [s·nb_s, (s+1)·nb_s) — i.e. the
contiguous token range [s·nb_s·bs, (s+1)·nb_s·bs), ring order), and the
attention of every serving step runs as a shard_map over the sequence axis:

  * WRITE — chunked prefill "walks the ring": each incoming chunk's tokens
    scatter into the shard that owns their positions (non-owned positions
    land in that shard's trash block), so the prefill cursor advances
    through shard 0's blocks, then shard 1's, ... exactly like the ring's
    token order;
  * READ — each shard gathers only ITS table's blocks ([B, Hkv, nb_s·bs,
    hd] — 1/sp of the context), computes an online-softmax PARTIAL
    (m, l, o) against absolute positions, and the partials merge across
    the axis with the same (m, l) combination the ring kernel uses
    (pmax + weighted psum), leaving every chip with the full output.

Per-chip KV residency is therefore ~1/sp of the request's total KV bytes —
`memscope.plan_serving(..., sequence_parallel=sp)` prices exactly this, and
`SpanKVPool.per_chip_bytes()` is the live-ledger view. Block accounting is
per shard: `span_blocks_needed` prices a request's occupancy on EACH shard
(shard 0 binds for long prompts), and `SpanKVPool` runs one `BlockAllocator`
per shard with all-or-nothing admission across all of them.

Trash-block convention: LOCAL physical block 0 of EVERY shard is that
shard's trash block (the global pool reserves sp blocks total) — table
entries and non-owned writes point there, so the fixed-shape span step
never branches on ownership.

Scope: bf16/fp32 pools, plain causal archs (no alibi/sliding-window — the
same contract as the paged Pallas kernel). The int8 pool composes naturally
(scales ride the same sharded block axis) but is not wired here yet.
"""

from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.comm.mesh import SEQ_AXIS
from deepspeed_tpu.inference.kv_cache import (BlockAllocator, blocks_needed,
                                              gather_block_kv)
from jax import shard_map

SPAN_TRASH = 0   # LOCAL physical block 0 of every shard: that shard's trash


# ----------------------------------------------------------------------
# per-shard block accounting (the planner/admission math)
# ----------------------------------------------------------------------


def span_table_width(max_context: int, block_size: int, sp: int) -> int:
    """Per-shard logical table width nb_s: the global table rounds up to
    sp equal shard ranges so every shard's table (and therefore the span
    step's shape) is identical."""
    nb = -(-int(max_context) // int(block_size))
    return -(-nb // int(sp))


def span_blocks_needed(prompt_len: int, padded_prompt: int, max_new: int,
                       block_size: int, sp: int, nb_s: int,
                       window: int = 1, spec_k: int = 0) -> List[int]:
    """Physical blocks a request occupies ON EACH SHARD for its lifetime.

    The blocks-from-write-extent math is the flat pool's single source of
    truth (`kv_cache.blocks_needed` over `max_written_pos`) — this only
    SPLITS it: the contiguous logical-block range [0, used) maps onto
    shard s as its slice of [s·nb_s, (s+1)·nb_s). Shard 0 is the binding
    shard for long prompts; later shards taper. A request whose extent
    overflows sp·nb_s can never be admitted — `SpanKVPool.admit` raises
    on it (the span analog of the scheduler's table-width check)."""
    used = blocks_needed(prompt_len, padded_prompt, max_new, block_size,
                         window=window, spec_k=spec_k)
    return [max(0, min(nb_s, used - s * nb_s)) for s in range(sp)]


# ----------------------------------------------------------------------
# the span attention step (inside shard_map over the sequence axis)
# ----------------------------------------------------------------------


def _span_partial_attend(q, k_ctx, v_ctx, q_pos, k_offset, scale):
    """One shard's unnormalized online-softmax partial against ABSOLUTE
    positions. q: [B, C, H, hd]; k_ctx/v_ctx: [B, Hkv, S, hd] (this shard's
    gathered blocks, S = nb_s·bs, key i sits at absolute position
    k_offset + i); q_pos: [B, C]. GQA contracts grouped, like
    `_paged_attend`. Returns (m [B,Hkv,G,C], l [B,Hkv,G,C],
    o [B,C,Hkv,G,hd]) — fp32."""
    B, C, H, hd = q.shape
    Hkv, S = k_ctx.shape[1], k_ctx.shape[2]
    G = H // Hkv
    k_pos = k_offset + jnp.arange(S, dtype=jnp.int32)
    valid = k_pos[None, None, :] <= q_pos[:, :, None]          # [B, C, S]
    qg = q.reshape(B, C, Hkv, G, hd)
    s = jnp.einsum("bckgd,bksd->bkgcs", qg.astype(jnp.float32),
                   k_ctx.astype(jnp.float32)) * scale
    s = jnp.where(valid[:, None, None, :, :], s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    # all-masked rows (a shard holding only FUTURE keys for this query):
    # m == the -1e30 mask sentinel (finite!), p == exp(0) == 1 everywhere —
    # zero the row so its (l, o) partial is empty rather than trash-block
    # mass. (The cross-shard merge would also kill it — exp(m - m_g)
    # underflows to exactly 0 — but partials should be sane on their own.)
    live = (m > -5e29)[..., None]
    p = jnp.where(live, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgcs,bksd->bckgd", p, v_ctx.astype(jnp.float32))
    return m, l, o


def _span_attn_local(q, k_new, v_new, pool_k, pool_v, tbl, positions, *,
                     axis_name, bs, scale):
    """Per-shard write + partial attend + cross-shard merge. Local shapes:
    q [B,C,H,hd]; k_new/v_new [B,C,Hkv,hd]; pool_k/v [N_s,Hkv,bs,hd] (this
    shard's physical blocks); tbl [B,1,nb_s] (this shard's table slice,
    LOCAL physical ids, 0 = local trash); positions [B,C] absolute."""
    B, C, H, hd = q.shape
    nb_s = tbl.shape[-1]
    s_idx = jax.lax.axis_index(axis_name)
    tbl = tbl[:, 0]

    # write: this shard owns logical blocks [s·nb_s, (s+1)·nb_s) — tokens
    # outside that range scatter into the LOCAL trash block, so the chunk
    # walk needs no ownership branch (the ring-walk write)
    lb = positions // bs
    own = (lb >= s_idx * nb_s) & (lb < (s_idx + 1) * nb_s)
    lb_local = jnp.clip(lb - s_idx * nb_s, 0, nb_s - 1)
    blk = jnp.where(own, jnp.take_along_axis(tbl, lb_local, axis=1),
                    SPAN_TRASH)
    off = positions % bs
    pool_k = pool_k.at[blk, :, off, :].set(k_new.astype(pool_k.dtype))
    pool_v = pool_v.at[blk, :, off, :].set(v_new.astype(pool_v.dtype))

    # read: gather ONLY this shard's blocks (1/sp of the context), partial
    # online-softmax at the shard's absolute key offset, merge over the axis
    k_ctx, v_ctx = gather_block_kv(pool_k, pool_v, tbl)
    m, l, o = _span_partial_attend(q, k_ctx, v_ctx, positions,
                                   s_idx * nb_s * bs, scale)
    m_g = jax.lax.pmax(m, axis_name)
    safe = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
    coef = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)  # [B,Hkv,G,C]
    l_g = jax.lax.psum(l * coef, axis_name)
    o_g = jax.lax.psum(o * coef.transpose(0, 3, 1, 2)[..., None], axis_name)
    out = o_g / jnp.maximum(l_g.transpose(0, 3, 1, 2)[..., None], 1e-30)
    return out.reshape(B, C, H * hd).astype(q.dtype), pool_k, pool_v


def make_span_gpt_fns(cfg, mesh=None, axis_name=SEQ_AXIS):
    """(prefill_chunk_fn, decode_fn) for a GPT config over a sequence-
    sharded paged pool — the span analogs of the serving engine's two
    programs, same shapes-never-change contract:

      prefill_chunk_fn(params, tokens [B,C], start_pos [B], pool,
                       span_tables [B,sp,nb_s]) -> (logits [B,C,V], pool)
      decode_fn(params, token [B], pos [B], pool, span_tables)
                       -> (logits [B,V], pool)

    `pool` is the `init_paged_kv_pool` tree with leaves placed
    P(None, `sequence`, ...) (the physical-block axis sharded — see
    `SpanKVPool`); `span_tables` hold LOCAL physical ids per shard. Layers
    scan exactly like `_scan_paged`, so depth stays out of compile time."""
    from deepspeed_tpu.models.gpt import (_decode_qkv, _embed, _gate_output,
                                          _lm_head, _residual_mlp,
                                          score_scale)
    mesh = mesh or mesh_mod.get_mesh()
    if cfg.use_alibi or cfg.sliding_window:
        raise ValueError(
            "sequence-spanning serving carries the plain-causal kernel "
            "contract: alibi / sliding-window archs are not supported")
    scale = score_scale(cfg, cfg.head_dim)

    rep = P(*([None] * 4))
    # one LAYER's pool slice [N, Hkv, block, hd]: block axis sharded
    pool_spec = P(axis_name, None, None, None)

    def _span_half(x, p, pool_l, positions, span_tables):
        bs = pool_l["k"].shape[2]
        q, k, v, gate = _decode_qkv(x, p, positions, cfg)
        fn = shard_map(
            partial(_span_attn_local, axis_name=axis_name, bs=bs,
                    scale=scale),
            mesh=mesh,
            in_specs=(rep, rep, rep, pool_spec, pool_spec,
                      P(None, axis_name, None), P(None, None)),
            out_specs=(P(None, None, None), pool_spec, pool_spec),
            check_vma=False)
        attn, pk, pv = fn(q, k, v, pool_l["k"], pool_l["v"], span_tables,
                          positions)
        pool_l = dict(pool_l, k=pk, v=pv)
        attn_out = _gate_output(attn, gate) @ p["attn_out_w"] \
            + p["attn_out_b"]
        return attn_out, pool_l

    def _scan_span(params, x, pool, span_tables, positions):
        def body(x, inputs):
            p, pool_l = inputs
            attn_out, pool_l = _span_half(x, p, pool_l, positions,
                                          span_tables)
            x = _residual_mlp(x, attn_out, p, cfg, constrain=False)
            return x, pool_l

        return jax.lax.scan(body, x, (params["blocks"], pool))

    def prefill_chunk_fn(params, tokens, start_pos, pool, span_tables):
        B, C = tokens.shape
        positions = start_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        x = _embed(params, tokens, positions, cfg)
        x, pool = _scan_span(params, x, pool, span_tables, positions)
        return _lm_head(params, x, cfg), pool

    def decode_fn(params, token, pos, pool, span_tables):
        x = _embed(params, token[:, None], pos[:, None], cfg)
        x, pool = _scan_span(params, x, pool, span_tables, pos[:, None])
        return _lm_head(params, x, cfg)[:, 0], pool

    return prefill_chunk_fn, decode_fn


# ----------------------------------------------------------------------
# the host-side span pool manager
# ----------------------------------------------------------------------


class SpanKVPool:
    """A paged KV pool whose physical-block axis spans the `sequence` mesh
    axis, plus the per-shard allocators and table builder.

    Allocation is per shard (one ref-counted `BlockAllocator` each, LOCAL
    block 0 reserved as that shard's trash) and ALL-OR-NOTHING across
    shards — a request either gets its priced occupancy on every shard
    (`span_blocks_needed`) or admits nothing, the flat pool's deadlock rule
    lifted to the span. Per-chip KV bytes are `per_chip_bytes()` —
    1/sp of the global pool, the number `plan_serving(...,
    sequence_parallel=sp)` predicts.

    Ledger contract: a serving engine built OVER a span pool mirrors
    `span_shards` (`serving.span_shards = pool.span_shards`) so
    `ServingMemScope` divides its `mem/kv_pool_per_chip_bytes` gauge —
    that attribute is the ONE wire between the span pool and the ledger
    (flat engines default to 1 and the gauge equals `mem/kv_pool_bytes`)."""

    def __init__(self, cfg, blocks_per_shard, block_size, mesh=None,
                 dtype=jnp.bfloat16, axis_name=SEQ_AXIS):
        from deepspeed_tpu.models.gpt import init_paged_kv_pool
        self.mesh = mesh or mesh_mod.get_mesh()
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        self.sp = sizes.get(axis_name, 1)
        self.blocks_per_shard = int(blocks_per_shard)
        self.block_size = int(block_size)
        if jnp.dtype(dtype) == jnp.int8:
            raise ValueError("SpanKVPool: the int8 quantized pool is not "
                             "wired through the span step yet")
        pool = init_paged_kv_pool(cfg, self.sp * self.blocks_per_shard,
                                  block_size, dtype)
        sharding = NamedSharding(self.mesh, P(None, axis_name, None, None,
                                              None))
        self.pool = jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, sharding), pool)
        self.allocators = [BlockAllocator(self.blocks_per_shard)
                           for _ in range(self.sp)]
        # the ledger wire (see class docstring): engines mirror this
        self.span_shards = self.sp

    def per_chip_bytes(self) -> int:
        """MEASURED addressable KV bytes per sequence shard — computed
        from each leaf's actual shard shape under its sharding (not
        total/sp arithmetic), so a silently-dropped placement would show
        up as full-pool residency here, not be papered over. This is the
        live number the planner's `sequence_parallel` pricing predicts."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(self.pool):
            shape = leaf.sharding.shard_shape(leaf.shape)
            total += int(np.prod(shape)) * jnp.dtype(leaf.dtype).itemsize
        return total

    def admit(self, prompt_len: int, max_new: int, nb_s: int,
              padded_prompt: Optional[int] = None,
              window: int = 1, spec_k: int = 0) -> Optional[np.ndarray]:
        """Allocate one request's span tables: [sp, nb_s] int32 LOCAL
        physical ids (trash-filled past each shard's occupancy). None —
        and no state change on ANY shard — when a shard cannot serve its
        slice RIGHT NOW (backpressure); raises ValueError when the
        request can NEVER fit — its write extent overflows the sp·nb_s
        table (the span analog of the scheduler's table-width check —
        without it, out-of-table positions would scatter into trash and
        decode would silently read truncated context), or a shard's need
        exceeds that shard's whole allocator capacity."""
        padded = int(padded_prompt) if padded_prompt else prompt_len
        used = blocks_needed(prompt_len, padded, max_new, self.block_size,
                             window=window, spec_k=spec_k)
        if used > self.sp * nb_s:
            raise ValueError(
                f"span request needs {used} logical blocks but the span "
                f"table holds {self.sp} x {nb_s} = {self.sp * nb_s} — "
                f"prompt {prompt_len} (+{max_new} new) exceeds the pool's "
                f"max context {self.sp * nb_s * self.block_size}; raise "
                f"nb_s / blocks_per_shard or the sequence-axis size")
        needs = span_blocks_needed(prompt_len, padded, max_new,
                                   self.block_size, self.sp, nb_s,
                                   window=window, spec_k=spec_k)
        for s, (alloc, need) in enumerate(zip(self.allocators, needs)):
            if need > alloc.capacity:
                # permanent, not backpressure: a retry loop treating None
                # as try-again would starve this request forever
                raise ValueError(
                    f"span request needs {need} blocks on shard {s} but "
                    f"the shard's allocator holds {alloc.capacity} usable "
                    f"blocks — it can never be admitted; raise "
                    f"blocks_per_shard")
        got, tables = [], np.full((self.sp, nb_s), SPAN_TRASH, np.int32)
        for s, (alloc, need) in enumerate(zip(self.allocators, needs)):
            blocks = alloc.alloc(need) if need else []
            if need and blocks is None:
                for a, b in zip(self.allocators, got):     # roll back
                    a.free(b)
                return None
            got.append(blocks)
            tables[s, :len(blocks)] = blocks
        return tables

    def free(self, tables: np.ndarray):
        """Retire a request: decref every real block on every shard."""
        for s, alloc in enumerate(self.allocators):
            real = [int(b) for b in tables[s] if b != SPAN_TRASH]
            if real:
                alloc.free(real)
