"""Latent attention (MLA) over the paged pool, in ABSORBED form (Pallas).

What a token leaves in a latent layer's pool is ONE entry for all heads:
`[c | k_r | 0]` — the normed latent `c` (`kv_lora_rank` columns), the
rotated shared key `k_r` (`qk_rope_head_dim`), and zeros up to a whole lane
tile (`latent_entry_width`). With the key up-projection folded into the
query (`q~_h = W_kb,h^K q_n,h`), a head's score against a cached position is
one dot of `[q~_h | q_r,h | 0]` with the entry, and its value is the entry's
first `rank` columns: attention with ONE KV head whose values are a slice of
its keys. So both kernels here are the walks the pool already has, handed a
pool of one leaf and told where the values are:

- `mla_decode_attention` (`dstpu_mla_decode`): `decode_attention._paged_walk`,
  the live (slot, block) pairs and nothing else, every head's row against a
  block's `[block, width]` tile — which is read ONCE and serves the scores
  and the values (a GQA walk reads a K and a V block apart);
- `mla_prefill_attention` (`dstpu_mla_prefill`): `prefill_attention.py`'s
  chunk kernel, the blocks under the chunk's frontier, with the query heads
  of the one KV head split over a grid axis (`_Q_HEADS` a step: twenty heads
  of 640 columns do not fit a step's VMEM).

Both return the probability-weighted LATENT `u_h = sum_j p_h(j) c(j)`
(`rank` columns a head); the caller un-absorbs it (`o_h = (W_kb,h^V)^T u_h`).
No per-head key or value of the cached context exists anywhere.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.decode_attention import (NEG_INF, _LANES,
                                                       _paged_walk)
from deepspeed_tpu.ops.pallas.prefill_attention import (
    _KV_TILES, _Q_TILES, _VMEM_LIMIT_BYTES, _prefill_kernel,
    paged_prefill_walk_counts)
from deepspeed_tpu.platform.device import pallas_interpret

# query heads a step of the chunk kernel carries (a divisor of the heads is
# taken): 4 x (a 512-row q tile of 640 columns + its 512-column result +
# float32 accumulator and statistics) is ~19 MiB of the 48 the call asks for
_Q_HEADS = 4


def latent_entry_width(rank: int, rope_dim: int) -> int:
    """Columns a latent entry is STORED in: `rank + rope_dim` rounded up to
    whole lane tiles (576 -> 640). XLA holds a bare `[.., block, 576]`
    bfloat16 array compact, but a Mosaic call takes its operands in the
    tiled layout, which pads the minor dimension to 640: compiled for a v5e
    (PERF.md section 6, PR 43), a 576-wide pool handed to `dstpu_kv_pool_
    write` was copied WHOLE into the padded form every call ("Unpadded
    9.89G, Padded 10.99G"). Stored as 640, the leaf has one layout, every
    kernel addresses whole tiles and the in-place writer (`kv_pool.py`)
    applies as it is; the price is an eleventh more bytes a position."""
    return -(-(rank + rope_dim) // _LANES) * _LANES


def _load_latent_head(rank, pool_refs, h, dtype):
    del dtype
    (c_ref,) = pool_refs
    tile = c_ref[0, h]
    return tile, tile[:, :rank]


def mla_decode_attention(q, pool, block_tables, pos, rank, sm_scale,
                         interpret=None, work=None):
    """Absorbed decode attention over a paged LATENT pool.

    q: [B, H, width] = `[q~ | q_r | 0]` a head; pool: [N, 1, block, width]
    physical blocks (`[c | k_r | 0]` a position), whole; block_tables:
    [B, nb] int32 in the pool's numbering; pos: [B] (inclusive: the new
    token's entry is already written). `sm_scale` is the MODEL's (1 / sqrt
    of the un-absorbed query-key width), never the entry's. Returns
    [B, H, rank]; dead rows (table all trash) come back zero. `work` as
    `paged_decode_attention`'s."""
    return _paged_walk(functools.partial(_load_latent_head, rank), q, (pool,),
                       block_tables, pos, work, sm_scale, interpret,
                       out_dim=rank, name="dstpu_mla_decode")


def _latent_prefill_kernel(start_ref, bt_ref, q_ref, c_ref, o_ref, *scratch,
                           **static):
    _prefill_kernel(start_ref, bt_ref, q_ref, c_ref, o_ref, *scratch,
                    values=False, **static)


def mla_prefill_attention(q, pool, block_tables, start, rank, sm_scale,
                          interpret=None):
    """Absorbed causal attention of a prefill chunk over a paged latent
    pool, the blocks under the chunk's frontier only.

    q: [B, C, H, width], row (b, c) being position `start[b] + c`, whose
    entry (and every earlier one) is already in the pool; pool, tables and
    `sm_scale` as `mla_decode_attention`'s; start: [B]. Returns
    [B, C, H * rank]."""
    if interpret is None:
        interpret = pallas_interpret()
    B, C, H, width = q.shape
    _, one, block, _ = pool.shape
    assert one == 1, "a latent pool has one entry a position"
    nb = block_tables.shape[1]
    tq = next((t for t in _Q_TILES if C % t == 0), C)
    tk = next((t for t in _KV_TILES if block % t == 0), block)
    heads = max(h for h in range(1, _Q_HEADS + 1) if H % h == 0)
    start = start.astype(jnp.int32)
    live_blocks = jnp.minimum((jnp.max(start) + C - 1) // block + 1, nb)

    def q_index(b, g, qi, j, start_ref, bt_ref):
        return (b, qi, g)

    def c_index(b, g, qi, j, start_ref, bt_ref):
        frontier = jnp.minimum((start_ref[b] + (qi + 1) * tq - 1) // block,
                               nb - 1)
        return (bt_ref[b, jnp.minimum(j, frontier)], 0, 0, 0)

    return pl.pallas_call(
        functools.partial(_latent_prefill_kernel, sm_scale=sm_scale, G=heads,
                          block=block, tk=tk, last_block=nb - 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // heads, C // tq, live_blocks),
            in_specs=[pl.BlockSpec((1, tq, heads * width), q_index),
                      pl.BlockSpec((1, 1, block, width), c_index)],
            out_specs=pl.BlockSpec((1, tq, heads * rank), q_index),
            scratch_shapes=[
                pltpu.VMEM((heads, tq, rank), jnp.float32),
                pltpu.VMEM((heads, tq, _LANES), jnp.float32),
                pltpu.VMEM((heads, tq, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, C, H * rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="dstpu_mla_prefill",
    )(start, block_tables.astype(jnp.int32), q.reshape(B, C, H * width),
      pool)


def mla_prefill_walk_counts(start, chunk, block, table_blocks, window=None):
    """The chunk walk's counts over a latent pool (`paged_prefill_walk_counts`)
    and `latent_positions`: the cached positions the chunk attends, a layer,
    `start + chunk` (a latent kind has no window)."""
    return dict(paged_prefill_walk_counts(start, chunk, block, table_blocks),
                latent_positions=int(start) + int(chunk))


def mla_attend_gathered(q, ctx, q_pos, rank, sm_scale):
    """The dense twin and oracle of both kernels: absorbed attention of
    q [B, C, H, width] at absolute positions `q_pos` [B, C] over each row's
    table-gathered entries `ctx` [B, S, width] in position order.
    Returns [B, C, H * rank]; float32 softmax."""
    B, C, H, _ = q.shape
    S = ctx.shape[1]
    s = jnp.einsum("bchw,bsw->bhcs", q, ctx).astype(jnp.float32) * sm_scale
    seen = jnp.arange(S, dtype=jnp.int32)[None, None] <= q_pos[:, :, None]
    s = jnp.where(seen[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    u = jnp.einsum("bhcs,bsr->bchr", p, ctx[..., :rank])
    return u.reshape(B, C, H * rank)


def gather_latent(pool, block_tables):
    """Each row's entries in position order: pool [N, 1, block, width],
    tables [B, nb] -> [B, nb * block, width] (an XLA gather: the form for a
    pool that no Mosaic call touches)."""
    B, nb = block_tables.shape
    return pool[block_tables][:, :, 0].reshape(B, nb * pool.shape[2], -1)
