"""Chunked-prefill attention over the paged KV pool (Pallas) — the chunk's
twin of `decode_attention.py`'s paged walk.

A prefill chunk of C rows starting at position `start` can see positions
0 .. start + C - 1 and nothing else. The gather path copies the row's WHOLE
table out of the pool (`dstpu_kv_pool_gather`) and builds float32 scores over
all `nb * block` table positions before the causal mask throws most of them
away; at the served shapes (PERF.md §6, PR 30: a 512-row chunk, a table of 32
blocks of 512) that was half of the busy time of the long-prompt cell for
prompts that reach a quarter of the table.

This kernel is a forward-only flash attention whose KV axis is a walk over
the row's LIVE logical blocks, read where they lie:

- the pool leaves come in WHOLE (`[M, Hkv, block, hd]`, one layer's or the
  flat `[L*N, ...]` stack with the table already offset): a slice of the
  pool in front of a Mosaic call is a copy;
- the table and `start` are scalar-prefetched and the index map resolves
  logical -> physical, so the blocks under the frontier are the only part of
  the pool that is touched;
- the KV grid axis is bounded DYNAMICALLY by the furthest frontier of the
  call, `(max(start) + C - 1) // block + 1`, as the decode walk's is; a row
  (or a query tile) whose own frontier is nearer re-serves its frontier
  block, which fetches nothing, and computes nothing there;
- the G query heads of a KV head share its K/V tile, the softmax statistics
  and the accumulator are float32 scratch, the statistics lane-replicated
  [R, 128] and widened with `_widen` (`decode_attention.py::
  _online_softmax_update`, the ONE definition the decode kernels and the
  training forward share: every (head, key tile) update is a call of it),
  and the absolute-position causal mask is applied only in the tiles that
  overlap the chunk's own positions: a tile wholly below `start` needs none;
- q and the result keep the model's `[B, C, H*hd]` layout (a step's q tile
  is the lane-aligned `[tq, heads*G*hd]` slab of its KV heads), so nothing
  is transposed on the way in or out.

Rows of a final chunk past the prompt's end attend whatever their positions
hold, as on the gather path; nobody reads them.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.decode_attention import (NEG_INF, _LANES,
                                                       _leaf_heads,
                                                       _online_softmax_update,
                                                       _scores,
                                                       _softmax_result,
                                                       _start_softmax,
                                                       window_first_block)
from deepspeed_tpu.platform.device import pallas_interpret

# What a grid step may hold in VMEM (tiles double-buffered by the pipeline,
# the float32 scratch, the score tiles' temporaries), and what the call asks
# of the compiler for it (a v5e core has 128 MiB; the default scope is 16).
_STEP_VMEM_BYTES = 24 * 2**20
_VMEM_LIMIT_BYTES = 48 * 2**20
# Query rows a step carries for each of its heads and keys a softmax update
# takes (the largest of each that divides the chunk and the block), and the
# query heads a step unrolls. Measured alone on a v5e at Mistral's chunk
# (PERF.md §6, PR 30; ms a call at 8 live blocks): 512 rows x 256 keys x 8
# heads 0.56; 256 rows 0.65, 128 keys 1.16, 512 keys 0.56, 4 heads 0.73, 16
# heads 0.81.
_Q_TILES = (512, 256, 128)
_KV_TILES = (256, 128)
_MAX_Q_HEADS = 8


def _step_vmem_bytes(tq, tk, heads, G, block, hd, itemsize):
    rows = heads * G * tq
    tiles = 2 * (2 * rows * hd + 2 * heads * block * hd) * itemsize
    scratch = rows * (hd + 2 * _LANES) * 4
    scores = 4 * tq * tk * 4
    return tiles + scratch + scores


def _tiles(C, block, Hkv, G, hd, itemsize, share=1):
    """(query rows, keys, KV heads) a grid step carries, from the shapes: the
    largest 128-multiple tiles that divide the chunk and the block, then as
    many KV heads (a divisor of Hkv; whole groups of `share`, the KV heads
    that share a head of the keys' half-tile leaf, or one) as keep a step's
    query heads under `_MAX_Q_HEADS` and its VMEM under `_STEP_VMEM_BYTES`,
    halving the query tile when one head alone is over (G of 16 and
    more)."""
    tq = next((t for t in _Q_TILES if C % t == 0), C)
    tk = next((t for t in _KV_TILES if block % t == 0), block)

    def fits(tq, heads):
        return _step_vmem_bytes(tq, tk, heads, G, block, hd,
                                itemsize) <= _STEP_VMEM_BYTES

    while tq % 256 == 0 and not fits(tq, 1):
        tq //= 2
    heads = max([h for h in range(1, Hkv + 1)
                 if Hkv % h == 0 and h * G <= _MAX_Q_HEADS and fits(tq, h)
                 and (h == 1 or h % share == 0)],
                default=1)
    return tq, tk, heads


def _prefill_kernel(start_ref, bt_ref, q_ref, *refs, sm_scale, G, block, tk,
                    last_block, window=None, keys=1, values=True,
                    sink=False, block_length=1, selected=False):
    # grid (B, Hkv // heads, C // tq, live blocks); q_ref / o_ref:
    # [1, tq, heads*G*hd], the step's query heads side by side in the lanes;
    # the `keys` key leaves' refs and v_ref: [1, heads, block, hd], ONE
    # logical block of the row, resolved to its physical block by the index
    # map (so bt_ref is unused here; a key leaf of half the heads,
    # `kv_pool.py::kv_leaf_shapes`: the heads the step's KV heads share);
    # scratch acc [heads*G, tq, dv] fp32, m/l [heads*G, tq, _LANES] fp32
    # carry the online softmax over the row's blocks, innermost and
    # ascending. With a `window` the KV axis counts from the block the
    # tile's first row's window begins in (`window_first_block`), not from 0.
    # `values` False (a latent pool, `ops/pallas/mla_attention.py`): no
    # v_ref, a key tile's first `acc_ref.shape[-1]` columns are its values.
    # `sink`: one more input, [heads*G, 1, _LANES] float32, a learned logit a
    # query head: every row's INITIAL softmax state (`_start_softmax`).
    # `block_length` B > 1 (generation by diffusion over blocks): a row's
    # frontier is the END of its block of B positions, `pos | (B - 1)`; the
    # tile's first position is a multiple of B, so that is its row index's.
    # `selected`: one more input, [1, 1, tq, block] int8, a sparse layer's
    # selection for this (block, query tile): 1 at the positions a row
    # attends. Every live tile is then masked, below the diagonal too.
    *k_refs, o_ref, acc_ref, m_ref, l_ref = refs
    sel_ref = k_refs.pop() if selected else None
    sink_ref = k_refs.pop() if sink else None
    v_ref = k_refs.pop() if values else None
    assert len(k_refs) == keys
    k_ref = k_refs[0]
    del bt_ref
    b = pl.program_id(0)
    qi = pl.program_id(2)
    tq = q_ref.shape[1]
    heads = k_ref.shape[1]
    hd = sum(ref.shape[3] for ref in k_refs)    # a query head's columns
    dv = acc_ref.shape[-1]                  # the values' width: a result's
    q_lo = start_ref[b] + qi * tq           # this tile's first position
    q_hi = q_lo + tq - 1
    frontier = jnp.minimum(q_hi // block, last_block)
    j = pl.program_id(3)                    # the logical block of this step
    first_step = j == 0
    if window is not None:
        j = j + window_first_block(q_lo, block, window)

    @pl.when(first_step)
    def _init():
        _start_softmax(acc_ref, m_ref, l_ref,
                       None if sink_ref is None
                       else jnp.broadcast_to(sink_ref[...], m_ref.shape))

    def update(t, masked):
        rows = slice(t * tk, (t + 1) * tk)
        if masked:
            # key position - query position, the same for every head
            ahead = (j * block + t * tk - q_lo) \
                + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1) \
                - jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            if block_length > 1:
                # the row's index -> its block's last: `ahead` falls by what
                # that adds
                ahead = ahead - (block_length - 1 - (
                    jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
                    & (block_length - 1)))
        if sel_ref is not None:
            # the same rows for every head: made once a tile of keys
            chosen = sel_ref[0, 0, :, rows].astype(jnp.float32) > 0.5
            if masked:
                chosen = jnp.logical_and(chosen, ahead <= 0)

        # unrolled: the heads' updates are independent, and the compiler
        # overlaps one's matmuls with another's softmax (a `fori_loop` over
        # them halves the kernel's speed; measured, PERF.md §6, PR 30)
        for i in range(heads * G):
            q = q_ref[0, :, i * hd:(i + 1) * hd]
            k = k_ref[0, i // G, rows, :]
            v = k[:, :dv] if v_ref is None else v_ref[0, i // G, rows, :]
            if keys > 1:
                # a leaf of fewer heads: the one head i's KV head shares
                k = (k,) + tuple(
                    ref[0, (i // G) * ref.shape[1] // heads, rows, :]
                    for ref in k_refs[1:])
            s = _scores(q, k) * sm_scale
            if sel_ref is not None:
                s = jnp.where(chosen, s, NEG_INF)
            elif masked:
                seen = ahead <= 0
                if window is not None:
                    seen = jnp.logical_and(seen, ahead > -window)
                s = jnp.where(seen, s, NEG_INF)
            _online_softmax_update(s, v, q.dtype, acc_ref.at[i],
                                   m_ref.at[i], l_ref.at[i])

    # a tile of keys is live while any of this query tile's rows can see it
    # (past the frontier the index map re-serves the frontier block), and
    # needs the mask only where it reaches past the tile's FIRST row
    for t in range(block // tk):
        k_lo = j * block + t * tk
        live = jnp.logical_and(j <= frontier, k_lo <= q_hi)
        diagonal = k_lo + tk - 1 > q_lo
        if window is not None:
            # ... and while the tile's first row can still see its last key;
            # the mask is needed too where the tile's LAST row cannot see
            # its first key
            live = jnp.logical_and(live, k_lo + tk - 1 > q_lo - window)
            diagonal = jnp.logical_or(diagonal, k_lo <= q_hi - window)
        pl.when(jnp.logical_and(live, diagonal))(
            functools.partial(update, t, True))
        pl.when(jnp.logical_and(live, jnp.logical_not(diagonal)))(
            functools.partial(update, t, False))

    @pl.when(j == frontier)
    def _finish():
        for i in range(heads * G):
            o_ref[0, :, i * dv:(i + 1) * dv] = _softmax_result(
                acc_ref.at[i], l_ref.at[i]).astype(o_ref.dtype)


def paged_prefill_live_blocks(start, chunk, block, table_blocks, window=None):
    """Host twin of the walk's grid bound for ONE row: the logical blocks a
    chunk of `chunk` rows starting at `start` attends (the scheduler's
    `StepRecord.prefill_live_blocks`), of `table_blocks` in its table; with
    a `window`, those from the block the first row's window begins in."""
    return min((int(start) + int(chunk) - 1) // int(block) + 1,
               int(table_blocks)) \
        - int(window_first_block(int(start), int(block), window))


def paged_prefill_walk_counts(start, chunk, block, table_blocks, window=None):
    """Host twin of what the walk does for ONE chunk, a layer (the registered
    program's `work`, `ops/attention_dispatch.py`): `live_blocks`
    (`paged_prefill_live_blocks`), of `table_blocks` — the blocks in its
    table, what the gather path attends; with a `window`, what the same walk
    would visit with none — and `kept_pairs`, the (query, position) pairs
    the causal mask (and the window) keeps: what the chunk walks' roofline
    counts as work."""
    start, C = int(start), int(chunk)
    # rows that see fewer than `window` positions (every row, with none): all
    # they have
    short = C if not window else min(max(window - 1 - start, 0), C)
    return {"live_blocks": paged_prefill_live_blocks(
                start, C, block, table_blocks, window),
            "table_blocks": int(table_blocks) if not window
            else paged_prefill_live_blocks(start, C, block, table_blocks),
            "kept_pairs": short * start + short * (short + 1) // 2
            + (C - short) * (window or 0)}


def paged_prefill_attention(q, k_pool, v_pool, block_tables, start,
                            sm_scale=None, interpret=None, window=None,
                            kr_pool=None, sink=None, block_length=1,
                            selected=None):
    """Causal attention of a prefill chunk over a PAGED KV pool, the live
    blocks only.

    q: [B, C, H, hd], row (b, c) being position `start[b] + c`, whose K/V
    (and every earlier position's) must already be in the pool; k_pool /
    v_pool: [M, Hkv, block, hd] physical blocks, WHOLE (one layer's, or the
    flat `[L*N, ...]` stack); block_tables: [B, nb] int32 physical ids in
    the pool's numbering (already offset to the layer's blocks of a flat
    stack); start: [B] int32. Returns [B, C, H*hd] in q's dtype — what
    `models/gpt.py::_paged_attend` gives over the gathered table with
    neither alibi nor a window.

    Row b reads logical blocks 0 .. (start[b] + C - 1) // block of its table
    and no other: entries past them may hold anything (the trash block, a
    stale id).

    `window` (static int, None = none): sliding-window attention, `i - j <
    window` — what `_paged_attend` gives with `cfg.sliding_window`. A query
    tile's walk then starts at the block its first row's window begins in
    (the KV axis is a STATIC few blocks long), and the table may be a ring
    (`inference/kv_cache.py::ring_tables`) that covers window + chunk.

    `v_pool`'s width may differ from the keys' (the result is [B, C, H * v
    width]). `kr_pool` [M, Hkv / 2, block, 128]: the keys' half tile where
    the pool keeps it apart (`kv_pool.py::kv_leaf_shapes`); q is then
    `kv_pool.split_query`'s and `sm_scale` the caller's. `sink` [H]
    float32: a learned logit a head that joins every row's denominator —
    the INITIAL state of the online softmax. `block_length` B > 1 (a power
    of two that divides every `start`; no window): the block-causal mask of
    a diffusion generator — row i sees the keys up to the end of its block
    of B positions, `i | (B - 1)`, all of them in the pool already; 1 is
    the causal mask, the kernel's text as it was. `selected` [B, nb, C,
    block] int8 (no window): a sparse layer's selection, block-major as
    `sparse_index.py::sparse_select` leaves it — row c attends the
    positions s <= start + c with a 1 at [b, s // block, c, s % block] and
    no other; the walk still visits every block under the frontier (the
    call is then named `dstpu_paged_prefill_sparse`)."""
    if interpret is None:
        interpret = pallas_interpret()
    B, C, H, hd = q.shape
    _, Hkv, block, _ = k_pool.shape
    dv = v_pool.shape[-1]
    nb = block_tables.shape[1]
    assert H % Hkv == 0
    G = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    key_pools = (k_pool,) if kr_pool is None else (k_pool, kr_pool)
    tq, tk, heads = _tiles(C, block, Hkv, G, hd, q.dtype.itemsize,
                           share=len(key_pools))
    width = heads * G * hd

    window = window or None
    start = start.astype(jnp.int32)
    if window is None:
        # the furthest frontier of the call: the KV axis ends there
        live_blocks = jnp.minimum((jnp.max(start) + C - 1) // block + 1, nb)
    else:
        # the blocks a tile's rows can see between them: window + tile
        # positions, starting anywhere in a block
        live_blocks = min((window + tq - 2) // block + 2, nb)

    def q_index(b, g, qi, j, start_ref, bt_ref):
        return (b, qi, g)

    def kv_index(b, g, qi, j, start_ref, bt_ref):
        # the table is read in SMEM, where nothing checks the index
        frontier = jnp.minimum((start_ref[b] + (qi + 1) * tq - 1) // block,
                               nb - 1)
        if window is not None:
            j = j + window_first_block(start_ref[b] + qi * tq, block, window)
        return (bt_ref[b, jnp.minimum(j, frontier)], g, 0, 0)

    def pool_spec(x):
        mine, group = _leaf_heads(x.shape[1], Hkv, heads)
        return pl.BlockSpec(
            (1, mine, block, x.shape[-1]),
            lambda b, g, *rest: kv_index(b, group(g), *rest))

    static, sunk, sunk_specs = {}, (), []
    if block_length > 1:
        assert window is None and tq % block_length == 0
        static["block_length"] = block_length
    if kr_pool is not None:
        static["keys"] = 2
    if sink is not None:
        static["sink"] = True
        sunk = (jnp.broadcast_to(sink.astype(jnp.float32)[:, None, None],
                                 (H, 1, _LANES)),)
        sunk_specs = [pl.BlockSpec((heads * G, 1, _LANES),
                                   lambda b, g, qi, j, *_: (g, 0, 0))]
    name = "dstpu_paged_prefill"
    if selected is not None:
        assert window is None and block_length == 1
        static["selected"] = True
        name += "_sparse"
        sunk += (selected,)

        def sel_index(b, g, qi, j, start_ref, bt_ref):
            frontier = jnp.minimum(
                (start_ref[b] + (qi + 1) * tq - 1) // block, nb - 1)
            return (b, jnp.minimum(j, frontier), qi, 0)
        sunk_specs += [pl.BlockSpec((1, 1, tq, block), sel_index)]
    return pl.pallas_call(
        functools.partial(_prefill_kernel, sm_scale=sm_scale, G=G,
                          block=block, tk=tk, last_block=nb - 1,
                          window=window, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv // heads, C // tq, live_blocks),
            in_specs=[pl.BlockSpec((1, tq, width), q_index)]
            + [pool_spec(x) for x in (*key_pools, v_pool)] + sunk_specs,
            out_specs=pl.BlockSpec((1, tq, heads * G * dv), q_index),
            scratch_shapes=[
                pltpu.VMEM((heads * G, tq, dv), jnp.float32),
                pltpu.VMEM((heads * G, tq, _LANES), jnp.float32),
                pltpu.VMEM((heads * G, tq, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, C, H * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(start, block_tables.astype(jnp.int32), q.reshape(B, C, H * hd),
      *key_pools, v_pool, *sunk)
