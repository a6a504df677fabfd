"""The paged KV pool touched in place (Pallas) — the other half of
`decode_attention.py`: that kernel reads the pool through the block table
for one token; `kv_pool_write` WRITES a step's new K/V rows into the pool
where it lies, and `kv_pool_gather` copies a row's blocks out for the
programs that attend a whole table densely (spec-decode verify, and a prefill
chunk where `prefill_attention.py`'s walk does not apply).

Why kernels for a 32-row write and a block copy: what XLA makes of the same
operations on a pool that is carried through the layer scan (PERF.md §6,
PR 25; compiled for a v5e):

- the scatter `pool.at[blk, :, off, :].set(rows)` writes one `[Hkv, hd]`
  row at a `(block, offset)`, so layout assignment wants the pool with the
  heads next to `hd` (`{3,1,2,0}`) while the Mosaic decode kernel is pinned
  to the default `{3,2,1,0}`: the pool is re-laid-out — copied WHOLE —
  inside the loop;
- the gather `pool[block_tables]` of a few dozen 1 MB blocks is rewritten
  ("mini-gather") into slices of its operand along `block`: two halves of
  the WHOLE pool are materialised a layer, for K and for V.

A Mosaic call has the pool in its default layout by construction, reads
only the tiles its index map names, and with `input_output_aliases` updates
the pool where it lies.

How the writer moves the bytes: a grid step owns one `[Hkv, tile, hd]` TILE
of the pool (`tile` = the dtype's sublane packing, 16 rows for bfloat16, 8
for float32), reads it, replaces the rows the step writes, and the pipeline
writes it back. A row's C consecutive positions touch at most
`ceil((C-1)/tile) + 1` tiles, so rows that share a tile are merged in ONE
step and never race (a step per row would prefetch its tile before the
previous row's write-back lands). The new rows arrive already placed at
their sublane (`_place_rows`, a small XLA gather), so the kernel body is one
select on whole tiles — nothing Mosaic has to shuffle.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.platform.device import pallas_interpret

_LANES = 128


def pool_tile_rows(dtype) -> int:
    """Rows of the pool's `block` dimension in one native tile: 8 sublanes
    of 32 bits, so 8 float32 rows or 16 bfloat16 rows."""
    return 32 // jnp.dtype(dtype).itemsize


def pool_in_place_supported(dtype, block_size: int, head_dim: int) -> bool:
    """Shapes and dtypes these kernels can address: a float pool whose
    `(block, hd)` face is made of whole native tiles. (The int8 pool is out:
    its `[.., block, hd//g]` scale leaves have a lane dimension of a few
    elements.)"""
    dtype = jnp.dtype(dtype)
    return (jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize in (2, 4)
            and head_dim % _LANES == 0
            and block_size % pool_tile_rows(dtype) == 0)


# A head whose KEYS are a lane tile and a half wide (192 columns beside
# 128-wide values) would be stored padded to two tiles, a fifth more of every
# block and of every walk's bytes. Its first `_HALF` columns go to a leaf of
# their own instead, `kr`, TWO KV heads side by side in one lane tile (a
# reshape: the heads are adjacent), and the rest stay in `k`: every leaf is
# whole lane tiles, the writer and the gather apply to each as they are, and
# a position is stored at exactly the model's width. A walk scores head h
# against `[k[h] | kr[h // 2]]` with a query whose first `_HALF` columns lie
# in its own head's half of the pair and zeros in the neighbour's
# (`split_query`), so no kernel slices a lane tile.
_HALF = _LANES // 2


def kv_leaf_shapes(kv_heads: int, key_dim: int, value_dim: int) -> dict:
    """{leaf name as a paged half reads it: (heads, width)} of a cached
    position of a K/V kind — `k`/`v`, and the half tile of the keys apart
    (`kr`) where their width is whole tiles and a half and the heads pair."""
    if key_dim % _LANES == _HALF and key_dim > _LANES and kv_heads % 2 == 0:
        return {"k": (kv_heads, key_dim - _HALF),
                "kr": (kv_heads // 2, _LANES), "v": (kv_heads, value_dim)}
    return {"k": (kv_heads, key_dim), "v": (kv_heads, value_dim)}


def pool_rows(k, v, pool_l) -> dict:
    """New rows k [B, C, Hkv, hd] / v [B, C, Hkv, vd] as the leaves of
    `pool_l` hold them."""
    if "kr" not in pool_l:
        return {"k": k, "v": v}
    B, C, Hkv, _ = k.shape
    return {"k": k[..., _HALF:],
            "kr": k[..., :_HALF].reshape(B, C, Hkv // 2, _LANES), "v": v}


def merge_keys(ctx: dict):
    """The keys [B, Hkv, S, hd] of gathered key leaves (`k`, and `kr`
    [B, Hkv / 2, S, 128] where the pool splits them)."""
    if "kr" not in ctx:
        return ctx["k"]
    kr = ctx["kr"]
    B, pairs, S, _ = kr.shape
    kr = jnp.moveaxis(kr.reshape(B, pairs, S, 2, _HALF), 3, 2)
    return jnp.concatenate([kr.reshape(B, 2 * pairs, S, _HALF), ctx["k"]],
                           axis=-1)


def split_query(q, kv_heads: int):
    """q [..., H, hd] for a walk over split keys: `[q[_HALF:] | the first
    _HALF columns in this head's half of its KV pair's tile, zeros in the
    other]`, [..., H, hd + _HALF]."""
    H = q.shape[-2]
    odd = (jnp.arange(H) // (H // kv_heads)) % 2 == 1
    first, zeros = q[..., :_HALF], jnp.zeros_like(q[..., :_HALF])
    pair = jnp.where(odd[:, None],
                     jnp.concatenate([zeros, first], axis=-1),
                     jnp.concatenate([first, zeros], axis=-1))
    return jnp.concatenate([q[..., _HALF:], pair], axis=-1)


def _num_tiles(C: int, tile: int) -> int:
    # C consecutive rows starting anywhere in a tile
    return (C + tile - 2) // tile + 1


def _place_rows(rows, start, tile):
    """[B, C, Hkv, hd] -> [B, Hkv, nT*tile, hd] with row c of batch b at
    sublane `start[b] % tile + c`: the offset it has inside the run of
    tiles it is written to. Sublanes outside the run repeat an edge row;
    the kernel masks them."""
    B, C, Hkv, hd = rows.shape
    width = _num_tiles(C, tile) * tile
    rows = jnp.moveaxis(rows, 1, 2)                              # [B,Hkv,C,hd]
    if C == 1:
        return jnp.broadcast_to(rows, (B, Hkv, width, hd))
    src = jnp.arange(width, dtype=jnp.int32)[None] - (start % tile)[:, None]
    src = jnp.clip(src, 0, C - 1)
    return jnp.take_along_axis(rows, src[:, None, :, None], axis=2)


def _write_kernel(start_ref, bt_ref, new_ref, pool_ref, out_ref, *, C, tile):
    # new_ref / pool_ref / out_ref: [1, Hkv, tile, hd]; grid (B, nT). Step
    # (b, t) owns the t-th tile of row b's run; positions start..start+C-1
    # are written, the tile's other sublanes keep what the pool held.
    del bt_ref
    b = pl.program_id(0)
    t = pl.program_id(1)
    start = start_ref[b]
    first = start // tile
    last = (start + C - 1) // tile

    # past the run's last tile the index maps re-serve that tile (same block
    # index: no fetch, no write-back in between) and the step leaves the
    # output block as the run's last real step made it
    @pl.when(first + t <= last)
    def _write():
        old = pool_ref[0]
        new = new_ref[0]
        pos = (first + t) * tile + jax.lax.broadcasted_iota(
            jnp.int32, old.shape, 1)
        mine = (pos >= start) & (pos < start + C)
        if old.dtype.itemsize < 4:
            # select on 32-bit lanes: the mask is int32-shaped, and the
            # round trip through float32 is exact for a 16-bit float
            out = jnp.where(mine, new.astype(jnp.float32),
                            old.astype(jnp.float32)).astype(old.dtype)
        else:
            out = jnp.where(mine, new, old)
        out_ref[0] = out


def kv_pool_write(pool, rows, start, block_tables, interpret=None):
    """Write `rows` into the paged pool IN PLACE; returns the pool.

    pool: [M, Hkv, block, hd] physical blocks (one layer's, or the whole
    stack flattened to M = L*N); rows: [B, C, Hkv, hd], row (b, c) being
    position `start[b] + c` of sequence b; block_tables: [B, nb] int32
    physical block ids in `pool`'s numbering. Equal to
    `pool.at[blk, :, off, :].set(rows)` with `blk = tables[b, pos // block]`,
    `off = pos % block`, except where two sequences write one position
    (inactive slots in the trash block): there one of them wins per TILE,
    where the scatter's winner is unspecified per row.

    The pool operand is aliased to the result (`input_output_aliases`), so
    under `jit` with the pool donated, or carried through a loop, nothing of
    the pool's size is allocated or copied."""
    if interpret is None:
        interpret = pallas_interpret()
    M, Hkv, block, hd = pool.shape
    B, C = rows.shape[:2]
    nb = block_tables.shape[1]
    tile = pool_tile_rows(pool.dtype)
    if not pool_in_place_supported(pool.dtype, block, hd):
        raise ValueError(
            f"kv_pool_write: a {pool.dtype} pool with block {block} and "
            f"head_dim {hd} is not made of whole [{tile}, {_LANES}] tiles")
    nT = _num_tiles(C, tile)
    tiles_per_block = block // tile

    start = start.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)
    placed = _place_rows(rows.astype(pool.dtype), start, tile)

    def run_tile(b, t, start_ref):
        # the t-th tile of row b's run, in units of `tile` positions
        return jnp.minimum(start_ref[b] // tile + t,
                           (start_ref[b] + C - 1) // tile)

    def new_index(b, t, start_ref, bt_ref):
        return (b, 0, run_tile(b, t, start_ref) - start_ref[b] // tile, 0)

    def pool_index(b, t, start_ref, bt_ref):
        lt = run_tile(b, t, start_ref)
        # the table is read in SMEM, where nothing checks the index
        logical = jnp.minimum(lt // tiles_per_block, nb - 1)
        return (bt_ref[b, logical], 0, lt % tiles_per_block, 0)

    tile_shape = (1, Hkv, tile, hd)
    return pl.pallas_call(
        functools.partial(_write_kernel, C=C, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nT),
            in_specs=[pl.BlockSpec(tile_shape, new_index),
                      pl.BlockSpec(tile_shape, pool_index)],
            out_specs=pl.BlockSpec(tile_shape, pool_index),
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operands: start, block_tables, placed, pool
        input_output_aliases={3: 0},
        interpret=interpret,
        name="dstpu_kv_pool_write",
    )(start, block_tables, placed, pool)


def kv_pool_write_reference(pool, rows, start, block_tables):
    """The XLA scatter the kernel replaces, as `models/gpt.py` writes it
    where the kernel does not apply (numerics oracle for tests)."""
    block = pool.shape[2]
    C = rows.shape[1]
    positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    blk = jnp.take_along_axis(block_tables, positions // block, axis=1)
    return pool.at[blk, :, positions % block, :].set(rows.astype(pool.dtype))


# one gathered block in VMEM, in and out, double-buffered: 4x this
_GATHER_BLOCK_BYTES = 2 * 1024 * 1024


def _gather_kernel(bt_ref, pool_ref, out_ref):
    del bt_ref
    out_ref[...] = pool_ref[...]


def kv_pool_gather(pool, block_tables, interpret=None):
    """Each row's logical KV, contiguous: [B, Hkv, nb*block, hd] — what
    `kv_cache.gather_block_kv` returns for one leaf, element for element,
    read through the scalar-prefetched table by the pipeline's DMAs: the
    blocks the table names are the only part of the pool that is touched,
    and they land in position order with no transpose.

    pool: [M, Hkv, block, hd]; block_tables: [B, nb] int32 physical ids."""
    if interpret is None:
        interpret = pallas_interpret()
    M, Hkv, block, hd = pool.shape
    B, nb = block_tables.shape
    # as many heads a step as keep a block small in VMEM
    heads = Hkv
    while heads > 1 and (heads * block * hd * pool.dtype.itemsize
                         > _GATHER_BLOCK_BYTES or Hkv % heads):
        heads -= 1
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv // heads, nb),
            in_specs=[pl.BlockSpec((1, heads, block, hd),
                                   lambda b, h, j, bt_ref: (bt_ref[b, j], h,
                                                            0, 0))],
            out_specs=pl.BlockSpec((1, heads, block, hd),
                                   lambda b, h, j, bt_ref: (b, h, j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, nb * block, hd), pool.dtype),
        interpret=interpret,
        name="dstpu_kv_pool_gather",
    )(block_tables.astype(jnp.int32), pool)
