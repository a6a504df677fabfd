"""Single-token KV-cache attention (Pallas) — the decode hot op.

Analog of the reference's `softmax_context` CUDA kernel
(`csrc/transformer/inference/csrc/pt_binding.cpp`, softmax.cu — fused
KV-cache attention with alibi/rope handled upstream). Decode attention is
HBM-bandwidth bound: each step streams the live K/V prefix once.

The cache is BLOCKED: [B, Hkv, M, hd] with M a multiple of `block_m` (the
inference engine rounds `max_len` up — `TpuInferenceConfig.kv_block_size`),
addressed by the kernel in [num_blocks, block_m, hd] units. The grid walks
the block axis; Pallas's pipeline DMAs one double-buffered [block_m, hd]
K/V tile from HBM per step while the online-softmax accumulator lives in
VMEM scratch — the VMEM working set is O(block_m), so context length is
bounded by HBM, not the old whole-[M, hd]-slab VMEM cap (~14k tokens at
head_dim 128 bf16). Blocks past each row's live prefix are neither fetched
(the scalar-prefetched `pos` clamps the block index map, and Pallas elides
the DMA when consecutive block indices repeat) nor computed (`pl.when`),
so a step's HBM traffic is ceil((pos+1)/block_m) tiles — the valid prefix
only, PagedAttention-style, regardless of the cache's allocated M. GQA is
supported by attending one kv head's group of query heads per grid cell.

Layout: q [B, H, hd]; k/v cache [B, Hkv, M, hd]; pos [B] (current position,
inclusive — the new token's k/v must already be scattered at pos).

The online softmax has ONE definition, here: `_online_softmax_update`. Its
row statistics m, l (and alpha) are lane-replicated [R, 128] from scratch to
scratch, widened with `_widen` where they meet the scores and the
accumulator — never narrowed to a one-lane column and broadcast back. Its
callers: the decode kernels of this file (`_online_softmax_tile`: contiguous,
paged, int8-paged, and `mla_attention.py`'s `dstpu_mla_decode` through
`_paged_walk`), the chunk walks (`prefill_attention.py::_prefill_kernel`:
`dstpu_paged_prefill`, `dstpu_mla_prefill`) and the training forward
(`flash_attention.py::_fwd_kernel`).

The PAGED kernels (a pool of physical blocks and a table a row) go further:
their grid is a list of the live (row, block) pairs and nothing else, every
KV head of a block in one step (`_paged_walk`). Measured alone on a v5e (PR
28; 16 calls in one program, 512 x 128 bfloat16 blocks): 0.05 ms a call for
10 live rows of 32 (14 blocks of 8 heads, 29 MB), where the grid (B, Hkv, nb)
of one head a step took 1.52 ms whatever was live — 0.18 us a dead step; 2.1
ms for 746 blocks (1.56 GB: 91% of 819 GB/s).

Where a table is a few blocks long the walk moves its FRONTIER block — the one
that holds `pos`, half dead on average — by row tiles (`_frontier_rows`,
`_paged_cut_kernel`, PR 62): the kernel copies a pair's live tiles itself and
folds them in one update, so rows past `pos` are neither read nor multiplied.
Alone on a v5e at OLMoE's served shapes (64 slots, 16 KV heads, a 3-block
table, positions drawn from its traffic: 0.61 of the moved rows live) a call
takes 345.6 us where the whole blocks take 426.2; every block full, the same
359.8 against 359.7 (PERF.md section 6, PR 62).
"""

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.platform.device import pallas_interpret

NEG_INF = -1e30
_LANES = 128


def _widen(stat, n):
    """A lane-replicated [..., rows, 128] statistic as [..., rows, n], `n`
    under or over a lane tile."""
    return jnp.tile(stat, (1, pl.cdiv(n, _LANES)))[..., :n]


def _online_softmax_update(s, v, in_dtype, acc_ref, m_ref, l_ref):
    """Fold one tile of MASKED float32 scores into the online softmax — the
    single definition of that arithmetic for every streaming attention
    kernel: the decode kernels below through `_online_softmax_tile`,
    `prefill_attention.py`'s chunk kernel (and through it the latent chunk
    walk of `mla_attention.py`) directly, and the training forward
    (`flash_attention.py::_fwd_kernel`) on `.at[rows]` views of its scratch.

    s: [R, n] float32, masked entries at NEG_INF; v: [n, dv] in the compute
    dtype; scratch acc [R, dv] fp32, m/l [R, _LANES] fp32 carried across the
    tiles of a row's walk. The row statistics m, l (and alpha) stay
    LANE-REPLICATED [R, 128] from scratch to scratch: a row's one cross-lane
    reduction comes back 128 lanes wide and is repeated (`_widen`) across
    the keys and the accumulator's columns where they need it. Narrowing
    them to a one-lane column and broadcasting back every tile was half of
    a tile's time (PERF.md §6, PR 37 and PR 44). The probabilities narrow to
    `in_dtype` (the queries') for the p @ v dot, which accumulates in
    float32."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - _widen(m_new, s.shape[1]))
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * _widen(alpha, acc_ref.shape[-1]) \
        + jax.lax.dot_general(p.astype(in_dtype), v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)


def _start_softmax(acc_ref, m_ref, l_ref, sink=None):
    """The online softmax's state before its first tile. `sink` (a
    lane-replicated float32 tile of m's shape: a learned logit a row that
    joins the denominator and has no value) IS that state — m = sink, l = 1,
    acc = 0, exactly one more column whose value is zero — and None the
    plain softmax's (m = NEG_INF, l = 0)."""
    acc_ref[...] = jnp.zeros_like(acc_ref)
    if sink is None:
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
    else:
        m_ref[...] = sink
        l_ref[...] = jnp.ones_like(l_ref)


def _scores(q, k):
    """q [R, hd] against a key tile [n, hd] -> [R, n] float32; keys kept in
    several leaves (`kv_pool.py::kv_leaf_shapes`) come as a tuple of tiles,
    whose widths add up to q's, and are scored a leaf at a time."""
    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if not isinstance(k, tuple):
        return dot(q, k)
    s, at = None, 0
    for tile in k:
        part = dot(q[:, at:at + tile.shape[-1]], tile)
        s = part if s is None else s + part
        at += tile.shape[-1]
    return s


def _softmax_result(acc_ref, l_ref):
    """The walk's result from its scratch, acc / l, float32: what a kernel's
    `_finish` stores. A row nothing was folded into (l = 0) comes out 0."""
    l_safe = jnp.maximum(l_ref[...], 1e-30)
    return acc_ref[...] / _widen(l_safe, acc_ref.shape[-1])


def _online_softmax_tile(q, k, v, pos, j, acc_ref, m_ref, l_ref, *,
                         sm_scale, block_m, window=None, bias=None):
    """One streamed KV tile's online-softmax update — the SINGLE definition
    of the decode-attention math, shared by the contiguous, paged, and
    quantized-paged kernels (the dequantizing kernel hands in already-
    dequantized tiles; everything after the load is identical, so the
    variants cannot drift numerically).

    q: [G, hd]; k/v: [block_m, hd] in the compute dtype (k a tuple of tiles
    where the keys lie in several leaves, `_scores`); scratch as in
    `_online_softmax_update`, carried across the (sequential, innermost)
    block axis. `window` (static; None = none): keys more than `window - 1`
    positions behind `pos` are masked too — the mask inside the first live
    block of a windowed walk. `bias` [1, block_m] float32 (None = none): added
    to every row's scores — 0 at the positions a sparse layer's indexer
    selected for this slot and NEG_INF at the others
    (`sparse_index.py::sparse_select`).

    native-dtype dots (fp32 accumulate via preferred_element_type):
    pre-casting K/V blocks to fp32 doubles the VMEM working set and VPU
    traffic (same fix as flash_attention.py)."""
    G = q.shape[0]
    s = _scores(q, k) * sm_scale
    k_pos = j * block_m + jax.lax.broadcasted_iota(jnp.int32, (G, block_m), 1)
    seen = k_pos <= pos
    if window is not None:
        seen = jnp.logical_and(seen, k_pos > pos - window)
    s = jnp.where(seen, s, NEG_INF)
    if bias is not None:
        s = s + bias
    _online_softmax_update(s, v, q.dtype, acc_ref, m_ref, l_ref)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, sm_scale, block_m):
    # q_ref: [1, 1, G, hd]; k_ref/v_ref: [1, 1, block_m, hd] (one streamed
    # cache tile); pos_ref: SMEM [B]; scratch acc [G, hd] fp32, m/l
    # [G, _LANES] fp32. Grid (B, Hkv, num_blocks): the block axis is
    # innermost and sequential, scratch carries the online softmax across it.
    b = pl.program_id(0)
    j = pl.program_id(2)
    nm = pl.num_programs(2)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # only blocks intersecting [0, pos]; beyond them the clamped index map
    # re-serves the frontier tile and this predicate keeps it out of the math
    @pl.when(j * block_m <= pos)
    def _step():
        _online_softmax_tile(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], pos, j,
                             acc_ref, m_ref, l_ref,
                             sm_scale=sm_scale, block_m=block_m)

    @pl.when(j == nm - 1)
    def _finish():
        o_ref[0, 0] = _softmax_result(acc_ref, l_ref).astype(o_ref.dtype)


def decode_attention(q, k, v, pos, sm_scale=None, block_m=None, interpret=None):
    """q: [B, H, hd]; k,v: [B, Hkv, M, hd]; pos: [B] int32 → [B, H, hd].

    Attends each query head to cache positions 0..pos inclusive. GQA-aware:
    H must be a multiple of Hkv; the group of G=H//Hkv query heads rides one
    grid cell with its kv head. Streams the cache one [block_m, hd] tile at
    a time and touches only the live prefix — M is bounded by HBM, and a
    mostly-empty long cache costs what its prefix costs, not what its
    allocation costs (the XLA einsum path always reads all M).

    `block_m=None` auto-selects: decode is HBM-bandwidth-bound (each step
    must read the whole live KV prefix), and the inner-loop fixed overhead
    dominates at small blocks — measured on v5e at ctx 8192 / GQA 4 kv heads
    (median-of-6 interleaved marginal timings): 644 us/step at block 128 vs
    189 us at block 512, against a 164 us bandwidth floor and XLA's 174-204
    us. Large blocks put the kernel AT the floor; nothing can go below it.
    """
    if interpret is None:
        interpret = pallas_interpret()
    B, H, hd = q.shape
    _, Hkv, M, _ = k.shape
    assert H % Hkv == 0
    G = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if block_m is None:
        # largest measured-good block that tiles M exactly — a non-divisor
        # would force the whole-cache pad below
        block_m = 512 if M >= 1024 else 128
        while block_m > 128 and M % block_m != 0:
            block_m //= 2
    block_m = min(block_m, M)
    if M % block_m != 0:  # pad cache length to block multiple (masked anyway;
        # the engine's kv_block_size rounding keeps serving caches
        # block-tileable, so only direct odd-M callers pay this copy)
        pad = block_m - M % block_m
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        M += pad

    pos = pos.astype(jnp.int32)
    qg = q.reshape(B, Hkv, G, hd)

    def kv_index(b, h, j, pos_ref):
        # clamp past-prefix steps to the frontier block: consecutive equal
        # indices elide the DMA, so dead blocks cost no HBM traffic
        return (b, h, jnp.minimum(j, pos_ref[b] // block_m), 0)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale, block_m=block_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, M // block_m),
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, h, j, pos_ref: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_m, hd), kv_index),
                pl.BlockSpec((1, 1, block_m, hd), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda b, h, j, pos_ref: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, hd), jnp.float32),
                pltpu.VMEM((G, _LANES), jnp.float32),
                pltpu.VMEM((G, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        interpret=interpret,
    )(pos, qg, k, v)
    return out.reshape(B, H, hd)


# ----------------------------------------------------------------------
# the paged walk: one grid step per LIVE (slot, logical block) pair
# ----------------------------------------------------------------------

# What a step's K and V tiles (the int8 pool's scale tiles with them), double-
# buffered by the pipeline, may take of a kernel's scoped VMEM (16 MiB unless
# a kernel asks for more): half, which leaves the other half to q, the output,
# the softmax state and the dots' temporaries.
_WALK_TILE_BYTES = 8 * 2**20

PagedDecodeWork = collections.namedtuple("PagedDecodeWork", [
    "count",    # [1] int32: live (slot, logical block) pairs
    "slot",     # [B * nb] int32: the pairs' slots, slot-major; past `count`
    "block",    # [B * nb] int32: ... and logical blocks, ascending in a slot
                # (past `count` both are in range and mean nothing)
    "live",     # [B] bool: slots with any block that is not the trash block
])


def window_first_block(pos, block_m, window):
    """The first logical block a query at `pos` can see: 0 with no window,
    else the block of position `pos - window + 1` (the LOWER bound of a
    windowed walk; the decode kernel, the work list and the scheduler's
    host twin share this one definition). Works on Python ints, numpy and
    traced values alike."""
    if not window:
        return pos * 0
    first = pos - (window - 1)
    on_host = isinstance(first, (int, np.integer, np.ndarray))
    return (np.maximum(first, 0) if on_host
            else jnp.maximum(first, 0)) // block_m


def paged_decode_work(block_tables, pos, block_m, window=None):
    """The walk's work list, from the tables as the scheduler builds them
    (NOT offset to a layer's blocks: the list is the same for every layer of
    one KIND, so `models/gpt.py::scan_paged` builds it once a token, outside
    the layer loop). A slot is dead when its whole table row is the trash
    block; a live slot holds blocks 0 .. pos // block_m, of which a layer
    with a `window` visits those from `window_first_block` on. Everything
    here has the tables' size, nothing the pool's."""
    from deepspeed_tpu.inference.kv_cache import TRASH_BLOCK
    B, nb = block_tables.shape
    # the scope names these operations in a compiled program: the guard of
    # tests/test_steptrace.py finds them by it, outside the layer loop
    with jax.named_scope("paged_decode_work"):
        live = jnp.any(block_tables != TRASH_BLOCK, axis=1)
        pos = pos.astype(jnp.int32)
        if window is None:
            blocks = jnp.where(live, jnp.minimum(pos // block_m + 1, nb), 0)
        else:
            first = window_first_block(pos, block_m, window)
            blocks = jnp.where(live, jnp.maximum(
                jnp.minimum(pos // block_m + 1, nb) - first, 0), 0)
        ends = jnp.cumsum(blocks)
        i = jnp.arange(B * nb, dtype=jnp.int32)
        slot = jnp.minimum(
            jnp.searchsorted(ends, i, side="right", method="compare_all"),
            B - 1).astype(jnp.int32)
        block = i - (ends - blocks)[slot]
        if window is not None:
            block = block + first[slot]
        block = jnp.clip(block, 0, nb - 1).astype(jnp.int32)
        return PagedDecodeWork(ends[-1:].astype(jnp.int32), slot, block, live)


def paged_decode_walk_steps(live_blocks):
    """Host twin of the walk's grid bound: the block-axis steps a call with
    `live_blocks` live (slot, logical block) pairs is launched with (the
    scheduler's `StepRecord.decode_grid_steps`). A call with nothing live
    still takes one step, which computes nothing."""
    return max(int(live_blocks), 1)


def paged_decode_walk_counts(at, block_m, window=None, nb=None, widths=(),
                             selected=False):
    """Host twin of what the walk does in a CALL, a layer: `at` [tokens,
    slots] (numpy) the positions the call's tokens attend from, its live
    slots only. `live_blocks`: the (slot, logical block) pairs it visits,
    `pos // block_m + 1` a token a slot, and `grid_steps`: the block-axis
    steps it is launched with, summed over the call's tokens; with a
    `window`, the pairs from `window_first_block` on, of `table_blocks`: the
    pairs the same walk would visit with no window. `rows`: the rows of K
    (and of V) it moves for those pairs — whole blocks, but for the frontier
    block of a short table, which moves in tiles of `_frontier_rows` (`nb`
    the table's blocks, `widths` the pool leaves' and `selected` as the
    walk's; no `nb`: whole blocks)."""
    whole = at // block_m + 1
    if window:
        live = int((whole - window_first_block(at, block_m, window)).sum())
        return {"live_blocks": live, "table_blocks": int(whole.sum()),
                "rows": live * block_m}
    tr = block_m if nb is None \
        else _frontier_rows(nb, block_m, widths, selected=selected)
    return {"live_blocks": int(whole.sum()),
            "grid_steps": sum(paged_decode_walk_steps(n)
                              for n in whole.sum(axis=1)),
            "rows": int(((whole - 1) * block_m
                         + (at % block_m // tr + 1) * tr).sum())}


def _heads_per_step(Hkv, head_tile_bytes, share=1):
    """KV heads a grid step carries: the most (a divisor of Hkv) whose K and
    V tiles, double-buffered, fit `_WALK_TILE_BYTES`. All of them at the
    served widths (8 x 512 x 128 bfloat16: 4 MiB; 16 heads: 8 MiB), so a
    step moves ONE contiguous `[Hkv, block, hd]` run of the pool per leaf.
    `share`: the most KV heads that share a head of some leaf (2 where the
    keys' half tile pairs them): a step carries whole groups of them, or
    one head."""
    heads = Hkv
    while heads > 1 and (Hkv % heads or heads % share
                         or 2 * heads * head_tile_bytes > _WALK_TILE_BYTES):
        heads -= 1
    return heads


# The frontier block of a walk is half dead on average: 1 / (2 x table blocks)
# of what a full table moves. Tables of up to `_CUT_TABLE_BLOCKS` blocks (a
# sixteenth and more) move it in tiles of `_FRONTIER_ROWS` rows; longer ones
# keep the whole-block walk, which stands at 92-93% of its count there.
_CUT_TABLE_BLOCKS = 8
_FRONTIER_ROWS = 128


def _frontier_rows(nb, block_m, widths, window=None, selected=False):
    """The rows `tr` of the tiles the walk moves its FRONTIER block in, read
    from shapes alone: `block_m` (the whole block: `_paged_walk_kernel`, the
    walk of every table past `_CUT_TABLE_BLOCKS` blocks) unless the table is
    short, and then `_FRONTIER_ROWS` (`_paged_cut_kernel`) — whole tiles of
    every pool dtype (16 rows bfloat16, 32 int8). `widths`: the pool
    leaves' last dimensions, whole lane tiles each for the cut: Mosaic
    copies no row slice of a narrower leaf (an int8 pool's scale columns, a
    head of 64), so such a pool keeps the whole block. So does a walk with a
    `window` (its dead rows lie in its FIRST block) or a sparse layer's
    `selected` bias, whatever its table. The kernel and its host twin
    (`paged_decode_walk_counts`) share this one definition."""
    if (window or selected or nb > _CUT_TABLE_BLOCKS
            or block_m <= _FRONTIER_ROWS or block_m % _FRONTIER_ROWS
            or any(width % _LANES for width in widths)):
        return block_m
    return _FRONTIER_ROWS


def _leaf_heads(leaf_heads, Hkv, heads):
    """(heads of a leaf's step tile, what the step's head-group index `g`
    becomes on that leaf's head axis, in tiles) for a leaf of `leaf_heads`
    heads (all `Hkv`, or one a pair) in a walk of `heads` KV heads a step."""
    mine = heads * leaf_heads // Hkv
    if mine:
        return mine, lambda g: g
    return 1, lambda g: g * leaf_heads // Hkv      # one KV head a step


def _vmem_tile_bytes(rows, cols, dtype):
    """Bytes of a [rows, cols] tile in VMEM: the lane dimension pads to 128
    (a 1-wide scale column costs what a 128-wide one does)."""
    return rows * -(-cols // _LANES) * _LANES * jnp.dtype(dtype).itemsize


def _paged_walk_kernel(cnt_ref, slot_ref, blk_ref, pos_ref, bt_ref, q_ref,
                       *refs, load_head, sm_scale, block_m, last_block,
                       window=None, sink=False, selected=False):
    # grid (head groups, work items); a step holds every pool leaf's
    # [1, heads, block_m, ...] tile of ONE live (slot, logical block) pair,
    # resolved to its physical block by the index map (so bt_ref is unused
    # here). q_ref / o_ref: [1, heads, G, hd] of the pair's slot; scratch acc
    # [heads, G, hd] fp32, m/l [heads, G, _LANES] fp32 carry the online
    # softmax over a slot's pairs, which are consecutive and ascending.
    # `sink`: one more input after the pool's, [heads, G, _LANES] float32;
    # `selected`: one more after that, [1, 1, 1, block_m] float32, the pair's
    # bias (`_online_softmax_tile`).
    del bt_ref
    *pool_refs, o_ref, acc_ref, m_ref, l_ref = refs
    bias_ref = pool_refs.pop() if selected else None
    sink_ref = pool_refs.pop() if sink else None
    i = pl.program_id(1)
    b = slot_ref[i]
    j = blk_ref[i]
    pos = pos_ref[b]

    # a slot's first pair: block 0, or the block its window begins in
    @pl.when(j == (0 if window is None
                   else jnp.minimum(window_first_block(pos, block_m, window),
                                    last_block)))
    def _init():
        _start_softmax(acc_ref, m_ref, l_ref,
                       None if sink_ref is None else sink_ref[...])

    # false only in the one step of a call with nothing live
    @pl.when(i < cnt_ref[0])
    def _step():
        for h in range(q_ref.shape[1]):
            k, v = load_head(pool_refs, h, q_ref.dtype)
            _online_softmax_tile(q_ref[0, h], k, v, pos, j, acc_ref.at[h],
                                 m_ref.at[h], l_ref.at[h],
                                 sm_scale=sm_scale, block_m=block_m,
                                 window=window,
                                 **({} if bias_ref is None
                                    else dict(bias=bias_ref[0, 0])))

    @pl.when(j == jnp.minimum(pos // block_m, last_block))
    def _finish():
        o_ref[0] = _softmax_result(acc_ref, l_ref).astype(o_ref.dtype)


def _paged_cut_kernel(cnt_ref, slot_ref, blk_ref, pos_ref, bt_ref, q_ref,
                      *refs, load_head, sm_scale, block_m, tr, last_block,
                      leaf_groups, sink=False):
    # `_paged_walk_kernel`'s grid, steps and softmax state, for a short table
    # (`_frontier_rows`): the pool's leaves stay in HBM (`pl.ANY`) and a step
    # copies ITS pair's live row tiles of `tr` rows itself — every tile of a
    # block below the slot's frontier, those up to `pos` of the frontier
    # block — into one half of `bufs` ([2, heads, block_m, x] a leaf) while
    # the step before computes on the other. The live tiles are folded in ONE
    # update, its static row count a branch: an update's fixed part (two
    # cross-lane reductions, alpha, the accumulator's rescale: 1.4 us of 16
    # heads on a v5e) is paid a pair, not a tile. `leaf_groups`: a leaf's
    # `_leaf_heads` group function. No window, no selection (the rule keeps
    # those on the whole block).
    pool_hbm = refs[:len(leaf_groups)]
    rest = list(refs[len(leaf_groups):])
    sink_ref = rest.pop(0) if sink else None
    o_ref, acc_ref, m_ref, l_ref, *bufs, sem = rest
    g, i = pl.program_id(0), pl.program_id(1)
    groups, items = pl.num_programs(0), pl.num_programs(1)
    half = (g * items + i) % 2

    def pair(ii):
        """(slot, logical block, pos, live row tiles) of work item `ii`:
        no tile in the one step of a call with nothing live."""
        b = slot_ref[ii]
        j = blk_ref[ii]
        pos = pos_ref[b]
        rows = jnp.where(j == jnp.minimum(pos // block_m, last_block),
                         jnp.minimum(pos - j * block_m, block_m - 1) + 1,
                         block_m)
        return b, j, pos, jnp.where(ii < cnt_ref[0], pl.cdiv(rows, tr), 0)

    def each_copy(gg, ii, half, act):
        """`act` ("start" | "wait") on the copies of item `ii`'s live row
        tiles, head group `gg`, into half `half` of the buffers: a wait
        names the copies its start named."""
        b, j, _, tiles = pair(ii)
        block = bt_ref[b, j]
        for t in range(block_m // tr):
            @pl.when(t < tiles)
            def _():
                for hbm, buf, group in zip(pool_hbm, bufs, leaf_groups):
                    mine = buf.shape[1]
                    getattr(pltpu.make_async_copy(
                        hbm.at[block, pl.ds(group(gg) * mine, mine),
                               pl.ds(t * tr, tr)],
                        buf.at[half, :, pl.ds(t * tr, tr)],
                        sem.at[half]), act)()

    @pl.when(jnp.logical_and(g == 0, i == 0))
    def _first():
        each_copy(g, i, half, "start")

    # the next step's tiles, on their way while this step computes
    @pl.when(jnp.logical_or(i + 1 < items, g + 1 < groups))
    def _next():
        wraps = i + 1 == items
        each_copy(jnp.where(wraps, g + 1, g), jnp.where(wraps, 0, i + 1),
                  1 - half, "start")

    each_copy(g, i, half, "wait")
    b, j, pos, tiles = pair(i)

    @pl.when(j == 0)
    def _init():
        _start_softmax(acc_ref, m_ref, l_ref,
                       None if sink_ref is None else sink_ref[...])

    def fold(rows):
        # the block's first `rows` rows, as `_paged_walk_kernel` folds all
        # of them: the tile that holds `pos` keeps its mask. The heads are a
        # loop Mosaic unrolls whole, so a branch is TRACED once and not a
        # head: four branches of sixteen unrolled heads took a step program
        # 2-6 s more to trace on the serving host (PERF.md section 6, PR 62)
        live = [buf.at[pl.ds(half, 1), :, pl.ds(0, rows)] for buf in bufs]

        def head(h, _):
            k, v = load_head(live, h, q_ref.dtype)
            _online_softmax_tile(q_ref[0, h], k, v, pos - j * block_m, 0,
                                 acc_ref.at[h], m_ref.at[h], l_ref.at[h],
                                 sm_scale=sm_scale, block_m=rows)
        jax.lax.fori_loop(0, q_ref.shape[1], head, None, unroll=True)

    for n in range(1, block_m // tr + 1):
        pl.when(tiles == n)(functools.partial(fold, n * tr))

    @pl.when(j == jnp.minimum(pos // block_m, last_block))
    def _finish():
        o_ref[0] = _softmax_result(acc_ref, l_ref).astype(o_ref.dtype)


def _paged_walk(load_head, q, leaves, block_tables, pos, work, sm_scale,
                interpret, window=None, out_dim=None,
                name="dstpu_paged_decode", sink=None, selected=None):
    """THE walk over a paged pool, shared by the float and the int8 kernel:
    a 1-D list of the live (slot, logical block) pairs (`paged_decode_work`),
    its length the grid's DYNAMIC bound, so a dead slot and a block past a
    row's frontier make no step at all; a step carries `_heads_per_step` KV
    heads of its block — at the served widths all of them, one contiguous
    run of each pool leaf. `leaves`: the pool's arrays [N, Hkv, block, x],
    whole; `load_head(pool_refs, h, dtype)` hands head h's K and V tiles
    [block, hd] in the compute dtype to `_online_softmax_tile`. Rows of dead
    slots come back ZERO (they ride on through the MLP, and through a routed
    model's router and its counters). `out_dim`: the width of V's tiles and
    of the result where it is not q's (a latent pool's values are a slice of
    its keys' tile, `ops/pallas/mla_attention.py`; values narrower than the
    keys); `name`: the call's name in a compiled program. A leaf may have
    half the first leaf's heads (the keys' half tile, `kv_pool.py::
    kv_leaf_shapes`): its step tile is the heads its step's KV heads share.
    `sink` [H] float32: a learned logit a head, the INITIAL state of every
    row's online softmax (`_start_softmax`). `selected` [nb, B, 1, block]
    float32: a sparse layer's selection as a bias a (block, slot), 0 at the
    positions the slot's query attends and NEG_INF at the others; the walk
    still visits every live pair (the call is then named `<name>_sparse`).
    A short table's walk (`_frontier_rows` under the block) is the same
    grid on `_paged_cut_kernel`, which copies a pair's live row tiles
    itself; every other walk builds the `pallas_call` it built before that
    kernel was (`tests/step_program_hashes.json`, `long_table_walks`)."""
    if interpret is None:
        interpret = pallas_interpret()
    B, H, hd = q.shape
    _, Hkv, block_m, _ = leaves[0].shape
    nb = block_tables.shape[1]
    assert H % Hkv == 0
    G = H // Hkv
    out_dim = out_dim or hd
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if work is None:
        work = paged_decode_work(block_tables, pos, block_m, window)
    heads = _heads_per_step(
        Hkv, sum(_vmem_tile_bytes(block_m, x.shape[-1], x.dtype)
                 * x.shape[1] // Hkv for x in leaves),
        share=max(Hkv // x.shape[1] for x in leaves))

    def pair_spec(x):
        mine, group = _leaf_heads(x.shape[1], Hkv, heads)
        return pl.BlockSpec(
            (1, mine, block_m, x.shape[-1]),
            lambda g, i, cnt_ref, slot_ref, blk_ref, pos_ref, bt_ref:
            (bt_ref[slot_ref[i], blk_ref[i]], group(g), 0, 0))

    def slot_index(g, i, cnt_ref, slot_ref, blk_ref, pos_ref, bt_ref):
        return (slot_ref[i], g, 0, 0)

    sunk, sunk_specs = (), []
    if sink is not None:
        sunk = (jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(Hkv, G, 1), (Hkv, G, _LANES)),)
        sunk_specs = [pl.BlockSpec((heads, G, _LANES),
                                   lambda g, i, *_: (g, 0, 0))]
    static = dict(sink=True) if sunk else {}
    if selected is not None:
        static["selected"] = True
        name += "_sparse"
        sunk += (selected,)
        sunk_specs += [pl.BlockSpec(
            (1, 1, 1, block_m),
            lambda g, i, cnt_ref, slot_ref, blk_ref, pos_ref, bt_ref:
            (blk_ref[i], slot_ref[i], 0, 0))]

    tr = _frontier_rows(nb, block_m, [x.shape[-1] for x in leaves], window,
                        selected is not None)
    if tr == block_m:
        kernel = functools.partial(_paged_walk_kernel, window=window)
        pool_specs = [pair_spec(x) for x in leaves]
        copied = []
    else:
        geometry = [_leaf_heads(x.shape[1], Hkv, heads) for x in leaves]
        kernel = functools.partial(
            _paged_cut_kernel, tr=tr,
            leaf_groups=tuple(group for _, group in geometry))
        pool_specs = [pl.BlockSpec(memory_space=pl.ANY)] * len(leaves)
        # what the pipeline would hold of the pool, held by the kernel
        copied = [pltpu.VMEM((2, mine, block_m, x.shape[-1]), x.dtype)
                  for x, (mine, _) in zip(leaves, geometry)] \
            + [pltpu.SemaphoreType.DMA((2,))]

    out = pl.pallas_call(
        functools.partial(kernel, load_head=load_head, sm_scale=sm_scale,
                          block_m=block_m, last_block=nb - 1, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(Hkv // heads, jnp.maximum(work.count[0], 1)),
            in_specs=[pl.BlockSpec((1, heads, G, hd), slot_index)]
            + pool_specs + sunk_specs,
            out_specs=pl.BlockSpec((1, heads, G, out_dim), slot_index),
            scratch_shapes=[
                pltpu.VMEM((heads, G, out_dim), jnp.float32),
                pltpu.VMEM((heads, G, _LANES), jnp.float32),
                pltpu.VMEM((heads, G, _LANES), jnp.float32),
            ] + copied,
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, out_dim), q.dtype),
        interpret=interpret,
        name=name,
    )(work.count, work.slot, work.block, pos.astype(jnp.int32),
      block_tables.astype(jnp.int32), q.reshape(B, Hkv, G, hd), *leaves,
      *sunk)
    # a slot the walk never visits is memory nobody wrote
    return jnp.where(work.live[:, None, None], out.reshape(B, H, out_dim), 0)


def _load_float_head(pool_refs, h, dtype):
    k_ref, v_ref = pool_refs
    return k_ref[0, h], v_ref[0, h]


def _load_split_head(pool_refs, h, dtype):
    # keys in two leaves: head h's own tile and the lane tile it shares
    # with its pair (a step tile of one head where the step carries one)
    k_ref, kr_ref, v_ref = pool_refs
    pair = h // 2 if kr_ref.shape[1] > 1 else 0
    return (k_ref[0, h], kr_ref[0, pair]), v_ref[0, h]


def paged_decode_attention(q, k_pool, v_pool, block_tables, pos, sm_scale=None,
                           interpret=None, work=None, window=None,
                           kr_pool=None, sink=None, selected=None):
    """Decode attention over a PAGED KV pool (vLLM's PagedAttention layout).

    q: [B, H, hd]; k_pool/v_pool: [N, Hkv, block, hd] physical blocks shared
    by every sequence; block_tables: [B, nb] int32 mapping each row's logical
    block j to a physical pool block; pos: [B] int32 (current position,
    inclusive — the new token's k/v must already be scattered at pos).
    Returns [B, H, hd].

    The grid walks the live (slot, logical block) pairs and nothing else
    (`_paged_walk`); the index map resolves logical → physical through the
    scalar-prefetched table, so the kernel DMAs exactly the pool blocks
    covering the live prefixes, every KV head of a block in one tile — no
    [B, M] gather is ever materialized in HBM (the XLA fallback path pays
    that gather every step). Rows whose table entries all point at the
    reserved trash block (inactive slots) cost no step and come back zero.

    `work`: the `paged_decode_work` of the UN-offset tables, where the
    caller has it already (the layer scan builds it once a token; here
    `block_tables` may then be offset to one layer's blocks of a whole
    stack); None builds it from `block_tables`.

    `window` (static int, None = none): sliding-window attention — the walk
    starts at the block of position `pos - window + 1` instead of block 0
    and masks what lies before that position inside it. The table may then
    be a RING (`inference/kv_cache.py::ring_tables`: logical block j at
    physical `j mod R` of the slot's ring): the blocks the walk visits are
    distinct physical blocks as long as the ring covers the window.

    `v_pool`'s width may differ from the keys' (the result is
    [B, H, v width]). `kr_pool` [N, Hkv / 2, block, 128]: the keys' half
    tile where the pool keeps it apart (`kv_pool.py::kv_leaf_shapes`); q is
    then `kv_pool.split_query`'s and `sm_scale` the caller's. `sink` [H]:
    `_paged_walk`'s."""
    load, leaves = (_load_float_head, (k_pool, v_pool)) if kr_pool is None \
        else (_load_split_head, (k_pool, kr_pool, v_pool))
    return _paged_walk(load, q, leaves, block_tables, pos, work, sm_scale,
                       interpret, window or None, out_dim=v_pool.shape[-1],
                       sink=sink, selected=selected)


def _dequant_tile(q, scale, dtype):
    """`quantization.dequantize_kv` for one VMEM tile, in the only form
    Mosaic lowers for every group count: q [block_m, hd] int8, scale
    [block_m, g] f32. The jnp definition reshapes the lane dim to
    [g, hd // g], which Mosaic refuses for g > 1 ("infer-vector-layout:
    unsupported shape cast", tpu.reshape 512x128 -> 512x4x32, libtpu
    0.0.34); here each group's scale column is lane-broadcast and selected
    into place instead. Every element is the SAME f32 product
    int8 x scale narrowed last, so the tile stays bit-identical to the
    gather oracle's."""
    hd = q.shape[-1]
    g = scale.shape[-1]
    gs = hd // g
    lane = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1)
    s_full = jnp.zeros(q.shape, jnp.float32)
    for j in range(g):
        s_full = jnp.where((lane >= j * gs) & (lane < (j + 1) * gs),
                           scale[:, j:j + 1], s_full)
    return (q.astype(jnp.float32) * s_full).astype(dtype)


def _load_quant_head(pool_refs, h, dtype):
    # The int8-pool variant: k/v tiles arrive QUANTIZED (int8 payload +
    # [block_m, g] f32 group scales, all four resolved through the same
    # index map), are dequantized here in VMEM — fp K/V never exists in
    # HBM — and then run the shared online-softmax tile update. Dequant
    # ordering (int8 -> f32 x scale -> narrow to the compute dtype) is
    # `quantization.dequantize_kv`'s (see _dequant_tile), so this kernel and
    # the dequantizing gather oracle see bit-identical tiles.
    k_ref, v_ref, ks_ref, vs_ref = pool_refs
    return (_dequant_tile(k_ref[0, h], ks_ref[0, h], dtype),
            _dequant_tile(v_ref[0, h], vs_ref[0, h], dtype))


def paged_decode_attention_quant(q, k_pool, v_pool, k_scale, v_scale,
                                 block_tables, pos, sm_scale=None,
                                 interpret=None, work=None, window=None):
    """Decode attention over the INT8 paged pool: dequantize-inside-the-
    kernel PagedAttention.

    q: [B, H, hd]; k_pool/v_pool: [N, Hkv, block, hd] int8; k_scale/v_scale:
    [N, Hkv, block, hd//g] f32 (the `init_paged_kv_pool` quantized layout);
    block_tables: [B, nb]; pos: [B]. Returns [B, H, hd] in q's dtype.

    `paged_decode_attention`'s walk (`_paged_walk`) — the scale tiles ride
    the SAME index map as the payload and count in the step's VMEM budget,
    so a step's HBM traffic is its block's int8 bytes plus its scales
    (~half the bf16 pool's traffic at group >= 8): decode is HBM-bandwidth-
    bound, so the quantized pool buys tokens/s, not just capacity. fp K/V
    exists only tile-by-tile in VMEM."""
    return _paged_walk(_load_quant_head, q,
                       (k_pool, v_pool, k_scale, v_scale), block_tables, pos,
                       work, sm_scale, interpret, window or None)


def paged_decode_attention_quant_reference(q, pool_l, block_tables, pos,
                                           sm_scale=None):
    """jnp oracle for the quantized kernel: the dequantizing gather
    (`kv_cache.gather_block_kv_dequant` — the SAME definition the XLA
    fallback path runs, so the oracle cannot silently diverge from
    production) followed by the contiguous fp reference. `pool_l` is one
    layer's quantized pool slice (k/v int8 + k_scale/v_scale)."""
    from deepspeed_tpu.inference.kv_cache import gather_block_kv_dequant
    k, v = gather_block_kv_dequant(pool_l, block_tables, q.dtype)
    return decode_attention_reference(q, k, v, pos, sm_scale=sm_scale)


def paged_decode_attention_reference(q, k_pool, v_pool, block_tables, pos,
                                     sm_scale=None, window=None):
    """jnp oracle: gather each row's blocks into a contiguous cache (the
    SAME gather the XLA fallback path uses — one definition, so the oracle
    cannot silently diverge from production), then run the contiguous
    reference."""
    from deepspeed_tpu.inference.kv_cache import gather_block_kv
    k, v = gather_block_kv(k_pool, v_pool, block_tables)
    return decode_attention_reference(q, k, v, pos, sm_scale=sm_scale,
                                      window=window)


def decode_attention_reference(q, k, v, pos, sm_scale=None, window=None):
    """jnp reference (numerics oracle for tests)."""
    B, H, hd = q.shape
    _, Hkv, M, _ = k.shape
    G = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Hkv, G, hd)
    s = jnp.einsum("bkgd,bkmd->bkgm", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    k_pos = jnp.arange(M)[None, :]
    valid = k_pos <= pos[:, None]
    if window:
        valid = valid & (k_pos > pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgm,bkmd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(B, H, hd).astype(q.dtype)
