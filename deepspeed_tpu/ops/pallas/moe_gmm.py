"""Grouped matmul over rows sorted by expert (Pallas) — the routed-expert
MLP's one kernel, `dstpu_moe_gmm`.

    out[r] = lhs[r] @ rhs[g(r)]      g(r) = the group whose run holds row r

`lhs` is `[M, K]` with the rows of group 0 first, then group 1, ...;
`group_sizes[e]` says how long each run is (they sum to M); `rhs` is
`[E, K, N]` — or a longer stack `[G, K, N]` of which group e uses matrix
`group_offset + e`: a model whose layers' experts are stacked `[L * E, K, N]`
hands the WHOLE stack and `layer * E`, so the layer loop never slices a
layer's experts out (a slice feeding a custom call is a copy of them). This is what a top-k router leaves after its N*k assignments
are sorted by expert: no capacity, no padding per expert, no `[N, E, C]`
tensor. Each row's result depends on that row and its expert alone (one
full-K dot, float32 accumulation), so any chunking or batching of the same
tokens gives the same numbers — the paged scheduler's parity invariant.

How the grid is laid out: a grid step is a (row tile, group) PAIR that has
rows. Rows are cut into tiles of `tm`; a group's run covers some consecutive
tiles, a tile may hold the ends of several runs. The pairs, in row order,
number at most `tiles + E - 1` (every group boundary inside a tile adds
one); the grid has that many steps along its inner axis, the pair tables
(`group of step s`, `tile of step s`) are computed from `group_sizes` by a
few XLA operations on `[E]`- and `[steps]`-sized arrays and scalar-
prefetched. Steps past the last real pair repeat it: same block indices, so
the pipeline fetches and writes nothing, and the body is skipped.

What that buys: an expert with no rows has no pair, so its weights are never
read — a decode step of 64 slots x top-8 touches the experts the router
chose and no others; a tile that straddles groups is visited once a group
with the rows of the other groups masked (the output tile stays in VMEM
across those visits), so nothing is padded to a per-expert capacity. The
outer grid axis walks `tn`-wide column tiles of `rhs`; each active expert's
`[K, tn]` panel is fetched once per column tile, which at decode sizes (a
few rows an expert) makes the call a read of the active experts' weights:
its roofline is HBM bandwidth.

Off the TPU the public entry runs `moe_gmm_reference`, a plain loop over
groups that is also the kernel's test oracle (`interpret=True` forces the
kernel through the Pallas interpreter for the tests).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.platform.device import pallas_interpret

KERNEL_NAME = "dstpu_moe_gmm"
_ROW_TILE = 128
_COL_TILE = 1024


def moe_gmm_reference(lhs, rhs, group_sizes, group_offset=0):
    """The oracle and the off-TPU path: one masked full matmul a group,
    float32 accumulation, result in `lhs.dtype`. Rows past
    `sum(group_sizes)` come back zero."""
    M = lhs.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    rows = jnp.arange(M, dtype=jnp.int32)[:, None]

    def one_group(e, out):
        y = jnp.dot(lhs, rhs[group_offset + e],
                    preferred_element_type=jnp.float32)
        mine = (rows >= starts[e]) & (rows < ends[e])
        return jnp.where(mine, y, out)

    out = jax.lax.fori_loop(0, group_sizes.shape[0], one_group,
                            jnp.zeros((M, rhs.shape[2]), jnp.float32))
    return out.astype(lhs.dtype)


def pair_tables(group_sizes, M, tm):
    """The (row tile, group) pairs that have rows, in row order.

    Returns (`group_of_step`, `tile_of_step`, both `[steps]` int32 with
    steps = ceil(M / tm) + E - 1; `starts`, `ends` `[E]`; `num_pairs` `[1]`).
    Steps at and past `num_pairs` repeat the last real pair."""
    E = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tiles_of_group = jnp.where(
        sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    pair_ends = jnp.cumsum(tiles_of_group)
    num_pairs = pair_ends[-1]
    steps = -(-M // tm) + E - 1
    s = jnp.minimum(jnp.arange(steps, dtype=jnp.int32),
                    jnp.maximum(num_pairs - 1, 0))
    group = jnp.minimum(jnp.searchsorted(pair_ends, s, side="right"),
                        E - 1).astype(jnp.int32)
    first_pair = pair_ends[group] - tiles_of_group[group]
    tile = (starts[group] // tm + (s - first_pair)).astype(jnp.int32)
    tile = jnp.clip(tile, 0, -(-M // tm) - 1)
    return group, tile, starts, ends, num_pairs[None]


def _gmm_kernel(group_ref, tile_ref, starts_ref, ends_ref, pairs_ref,
                offset_ref, lhs_ref, rhs_ref, out_ref, *, tm):
    # lhs_ref [tm, K], rhs_ref [1, K, tn], out_ref [tm, tn]; grid
    # (column tiles, pair steps). The output tile keeps its block index
    # while consecutive pairs share the row tile, so it stays in VMEM and
    # each pair fills in its own rows.
    del offset_ref                   # the index maps' business
    s = pl.program_id(1)

    @pl.when(s < pairs_ref[0])
    def _pair():
        g = group_ref[s]
        tile = tile_ref[s]
        y = jnp.dot(lhs_ref[...], rhs_ref[0],
                    preferred_element_type=jnp.float32)
        rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        mine = (rows >= starts_ref[g]) & (rows < ends_ref[g])
        first_visit = jnp.logical_or(s == 0,
                                     tile_ref[jnp.maximum(s - 1, 0)] != tile)

        # selects on float32 lanes; exact for a 16-bit float
        @pl.when(first_visit)
        def _first():
            out_ref[...] = jnp.where(mine, y, 0.0).astype(out_ref.dtype)

        @pl.when(jnp.logical_not(first_visit))
        def _later():
            out_ref[...] = jnp.where(
                mine, y, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _col_tile(N):
    for tn in (_COL_TILE, 512, 256):
        if N % tn == 0:
            return tn
    # an odd number of lane tiles (2688 = 21 x 128): the widest whole-tile
    # divisor (896), not 128 — a step's weight panel is then 7 tiles wide
    # and the row tile is fetched 3 times, not 21
    for tn in range(_COL_TILE - 128, 0, -128):
        if N % tn == 0:
            return tn
    return N


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gmm_call(lhs, rhs, group_sizes, group_offset, interpret):
    M, K = lhs.shape
    N = rhs.shape[2]
    tm = min(_ROW_TILE, M)
    tn = _col_tile(N)
    tables = pair_tables(group_sizes, M, tm) + (
        jnp.asarray(group_offset, jnp.int32).reshape(1),)
    steps = tables[0].shape[0]
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(N // tn, steps),
            in_specs=[
                pl.BlockSpec((tm, K), lambda n, s, g, t, *_: (t[s], 0)),
                pl.BlockSpec((1, K, tn), lambda n, s, g, t, st, en, pr, off:
                             (off[0] + g[s], 0, n)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n, s, g, t, *_: (t[s], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name=KERNEL_NAME,
    )(*tables, lhs, rhs)


def gmm_kernel_supported(M, K, N, dtype) -> bool:
    """Shapes the kernel's tiles can address: rows in whole sublane tiles
    (or one tile), lane dimensions in whole 128s."""
    sublanes = 32 // jnp.dtype(dtype).itemsize
    return (jnp.dtype(dtype).itemsize in (2, 4) and K % 128 == 0
            and N % 128 == 0 and M % sublanes == 0
            and (M <= _ROW_TILE or M % _ROW_TILE == 0))


def moe_gmm(lhs, rhs, group_sizes, group_offset=0, interpret=None):
    """`[M, K]` rows sorted by group x `[G, K, N]` -> `[M, N]`, each row
    against its group's matrix `rhs[group_offset + group]`; `group_sizes`
    `[E]` sums to M (rows past the sum are unspecified from the kernel, zero
    from the reference); `group_offset` may be traced.

    On a TPU, for shapes `gmm_kernel_supported` accepts: the Mosaic kernel.
    Everywhere else, and for other shapes: `moe_gmm_reference`.
    `interpret=True` runs the kernel in the Pallas interpreter (tests)."""
    M, K = lhs.shape
    N = rhs.shape[2]
    if interpret is None:
        if pallas_interpret() or not gmm_kernel_supported(M, K, N, lhs.dtype):
            return moe_gmm_reference(lhs, rhs, group_sizes, group_offset)
        interpret = False
    return _gmm_call(lhs, rhs, group_sizes, group_offset, interpret)
