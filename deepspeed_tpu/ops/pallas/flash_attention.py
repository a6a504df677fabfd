"""Flash attention (Pallas, TPU) — HBM-streaming K/V.

The training-attention hot op — replaces the reference's fused softmax CUDA
kernels (`csrc/transformer/softmax_kernels.cu`, sparse/triton attention
`ops/sparse_attention/matmul.py`) with the memory-optimal streaming formulation:
online softmax over KV blocks, O(T) memory, fp32 accumulation, causal masking,
custom VJP with the standard recomputation backward.

Layout: the kernels walk (batch x head, live tile) and address a head's
[block, D] tile by an INDEX MAP, in one of two arrays. q, k, dq and dk are
[BH, T, D], head-major: the zoo hands them over as [B, T, H, D] and the
wrapper's transpose rides in the fusion that made them (the rotation writes
that layout for nothing; turned the other way XLA runs the rotation's
convolution T-minor and pays a transposing copy on each side of it, PERF.md
section 7). v, o, dO and dv — the tensors between a projection and a kernel
with nothing in between — are the projections' own [B, T, H*D] where
`flash_heads_in_place(D)`, D % 128 == 0: head h of a row is column block h,
whole (16, 128) tiles, tile index (bh // H, tile, bh % H) in place of
(bh, tile, 0) (`_column_tiles`), and no copy on either side of either pass.
At any other head width (64, MLA's 192) and for "BHTD" callers (ring
attention through `flash_attention_with_lse`) all eight are [BH, T, D] and
the wrapper transposes from the zoo's [B, T, H, D]. The row statistics (lse,
delta) are [BH, T / block_q, 1, block_q] float32 either way. On the TPU
[B, T, H, D] <-> [B, T, H*D] is a relayout, not a reshape (the 3-D array
tiles (T, lanes), the 4-D one (H, D)): nothing on the [B, T, H*D] path is
ever given four dimensions — `delta`'s sum over a head's lanes is a product
with a 0/1 [H*D, H] matrix (`_row_dots`).

K/V STREAM from HBM: the grid is (batch x head, live tile) and Pallas's
pipeline DMAs one double-buffered [block_k, D] (resp. [block_q, D] in the
dk/dv pass) tile into VMEM per grid step while the previous tile computes.
The online-softmax state (acc/m/l) lives in VMEM scratch that persists
across the sequential steps of a q block, so the kernel's VMEM working set
is O(block), not O(T) — sequence length is bounded by HBM capacity
(`flash_max_seq`), not the old ~14k-token whole-slab VMEM cap.

What a tile costs OUTSIDE its matrix products decides the speed (PERF.md
§6, PR 37, measured on a v5e at [8, 16, 2048, 128] bf16):

- The walk visits LIVE tiles only: the (qi, ki) pairs that hold a visible
  pair are listed once (`_tile_tables`, scalar-prefetched) and the second
  grid axis runs over the list — a causal grid launches no step for the
  upper triangle (`flash_live_tiles` counts them).
- A tile is worked in sub-blocks of at most `_SUB` x `_SUB`, each behind
  its own predicate: a large tile (few grid steps, each ~0.2 us of
  bookkeeping) still skips the wholly hidden quarter on the diagonal.
- The forward's row statistics m, l (and alpha) stay LANE-REPLICATED
  [rows, 128] from scratch to scratch; the one cross-lane reduction a row
  is widened to 128 lanes once and repeated across the keys where `s - m`
  needs it. Narrowing them to a one-lane column and broadcasting back three
  times a tile was half of the forward's time. The update is `decode_
  attention.py::_online_softmax_update`, the one the serving walks call.
- The log-sum-exp leaves the forward through a transpose of the
  lane-replicated tile (rows to lanes in one XLU pass).
- The dk/dv kernel works on TRANSPOSED scores [keys, rows]: lse and delta
  lie along the lanes as stored, and p^T, ds^T feed their products as
  plain left operands (no [block, block] transposes).
- The causal mask is applied on every live sub-block: the compare and
  select ride in VALU slots that are free (masking only the diagonal
  tiles, in a second body, measured no gain at head widths 128 and 64).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.decode_attention import (NEG_INF, _LANES,
                                                       _online_softmax_update,
                                                       _widen)
from deepspeed_tpu.platform.device import pallas_interpret

# a tile is worked in sub-blocks of at most _SUB x _SUB (the size the MXU and
# the softmax between its two products overlap best at; 256 measured 1.6x
# slower, the whole 1024 x 1024 tile 1.16x)
_SUB = 512
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


# ----------------------------------------------------------------------
# the walk
# ----------------------------------------------------------------------


def _live_pairs(T, block_q, block_k, causal, k_major=False):
    """(qi, ki) of the tiles that hold a visible (query, key) pair, in the
    order a kernel walks them: q blocks outermost with keys ascending, or
    (`k_major`, the dk/dv pass) k blocks outermost with queries ascending."""
    nq, nk = T // block_q, T // block_k
    if k_major:
        ki, qi = np.divmod(np.arange(nq * nk), nq)
    else:
        qi, ki = np.divmod(np.arange(nq * nk), nk)
    if causal:
        live = ki * block_k <= (qi + 1) * block_q - 1
        qi, ki = qi[live], ki[live]
    return qi, ki


def flash_live_tiles(T, block_q, block_k, causal=True):
    """(diagonal, interior, dead) tiles of one (batch x head): a tile is
    DIAGONAL when the causal frontier passes through it (some of its pairs
    are hidden), INTERIOR when every pair is visible, DEAD when none is.
    The kernels walk diagonal + interior tiles and launch nothing for a dead
    one; with `_SUB`-sized blocks this is also the count of sub-blocks the
    default tiles compute. 4 / 6 / 6 at T 2048 in 512 x 512."""
    qi, ki = _live_pairs(T, block_q, block_k, causal)
    diagonal = int(np.sum((ki + 1) * block_k - 1 > qi * block_q)) if causal else 0
    return diagonal, len(qi) - diagonal, (T // block_q) * (T // block_k) - len(qi)


def _tile_tables(T, block_q, block_k, causal, k_major=False):
    """The live pairs as the two scalar-prefetched int32 tables the index
    maps and the kernels read a grid step's (qi, ki) from."""
    qi, ki = _live_pairs(T, block_q, block_k, causal, k_major)
    return jnp.asarray(qi, jnp.int32), jnp.asarray(ki, jnp.int32)


def _q_tile(bh, step, qi_ref, ki_ref):
    return (bh, qi_ref[step], 0)


def _k_tile(bh, step, qi_ref, ki_ref):
    return (bh, ki_ref[step], 0)


def _column_tiles(heads):
    """(`_q_tile`, `_k_tile`) for a `[B, T, H*D]` array: head h of a row is
    its column block h, whole lane tiles where `D % 128 == 0`, so the
    head-major layout is an index map and no copy."""

    def tile_from(table):           # 0: the step's q block, 1: its k block
        return lambda bh, step, *tables: (bh // heads, tables[table][step],
                                          bh % heads)

    return tile_from(0), tile_from(1)


def _value_side(v, heads):
    """(the array the kernels address, its q tile map, its k tile map) of a
    tensor that never passes the rotation (v, o, dO, dv): `[B, H, T, D]` as
    `[BH, T, D]` under `_q_tile` / `_k_tile` (`heads` None), or `[B, T, H*D]`
    as it is under `_column_tiles`."""
    if heads is None:
        B, H, T, D = v.shape
        return v.reshape(B * H, T, D), _q_tile, _k_tile
    return (v,) + _column_tiles(heads)


def _last_k_tile(causal, qi, block_q, block_k, nk):
    """The last k tile q block `qi` walks: the one its last row's own
    position falls in, or the sequence's last."""
    if not causal:
        return nk - 1
    return jnp.minimum(nk - 1, ((qi + 1) * block_q - 1) // block_k)


def _row_stat_spec(block_q):
    """BlockSpec of one q block's row statistics (lse, delta), addressed by
    the q tile's own index map. The arrays are [BH, Tb, 1, block_q]: rows of
    a q block along the LANES, one block per (bh, qi). The unit third dim is
    what makes the tile legal — Mosaic requires a block's last two dims to
    be (8, 128)-divisible or equal to the array's, and a (1, block_q) tile
    of a [BH, Tb, block_q] array is neither once Tb > 1 (it compiled under
    jax 0.4; jax 0.9 refuses it)."""
    return pl.BlockSpec((None, None, 1, block_q),
                        lambda *grid: _q_tile(*grid) + (0,))


def _sub_blocks(causal, qi, ki, block_q, block_k, update):
    """Run `update(rows, keys)` (static slices of the tile) on every
    sub-block of tile (qi, ki) that holds a visible pair, keys ascending
    within a band of rows."""
    for r0 in range(0, block_q, _SUB):
        rows = slice(r0, min(r0 + _SUB, block_q))
        for k0 in range(0, block_k, _SUB):
            keys = slice(k0, min(k0 + _SUB, block_k))
            if causal:
                # its first key is no later than its last query
                pl.when(ki * block_k + k0 <= qi * block_q + rows.stop - 1)(
                    functools.partial(update, rows, keys))
            else:
                update(rows, keys)


def _hide_future(s, q0, k0, transposed=False):
    """Scores of pairs whose key lies after the query -> NEG_INF. s is
    [rows, keys] from positions (q0, k0), or `transposed` [keys, rows]."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                          1 if transposed else 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                          0 if transposed else 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------


def _fwd_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                m_ref, l_ref, *, sm_scale, causal, block_k, nk):
    # q_ref/o_ref: [block_q, D]; k_ref/v_ref: [block_k, D] (one streamed KV
    # tile); lse_ref: [1, block_q]; scratch acc [block_q, D] fp32, m/l
    # [block_q, _LANES] fp32, every lane of a row the same value. Grid:
    # (BH, live tiles), a q block's tiles consecutive with keys ascending,
    # so scratch carries the online-softmax state across them.
    #
    # Dots run on NATIVE-dtype operands (bf16 in, fp32 out via
    # preferred_element_type): casting inputs to fp32 first forces the MXU's
    # fp32 path (~4x slower) and was measured to make the whole kernel lose
    # to XLA attention at seq 512. `p` narrows back to the input dtype for
    # the p@v dot — standard TPU flash practice; softmax stats stay fp32.
    step = pl.program_id(1)
    qi, ki = qi_ref[step], ki_ref[step]
    block_q, D = q_ref.shape
    last_ki = _last_k_tile(causal, qi, block_q, block_k, nk)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def update(rows, keys):
        q, k, v = q_ref[rows, :], k_ref[keys, :], v_ref[keys, :]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _hide_future(s, qi * block_q + rows.start,
                             ki * block_k + keys.start)
        _online_softmax_update(s, v, q.dtype, acc_ref.at[rows],
                               m_ref.at[rows], l_ref.at[rows])

    _sub_blocks(causal, qi, ki, block_q, block_k, update)

    @pl.when(ki == last_ki)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / _widen(l_safe, D)).astype(o_ref.dtype)
        # rows -> lanes: every row of the transposed tile is the lse
        lse_ref[...] = jnp.transpose(m_ref[...] + jnp.log(l_safe))[0:1, :]


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               heads=None):
    B, H, T, D = q.shape
    BH = B * H
    q2, k2 = (x.reshape(BH, T, D) for x in (q, k))
    v2, vq_tile, vk_tile = _value_side(v, heads)
    qi, ki = _tile_tables(T, block_q, block_k, causal)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_k=block_k, nk=T // block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, qi.shape[0]),
            in_specs=[
                pl.BlockSpec((None, block_q, D), _q_tile),
                pl.BlockSpec((None, block_k, D), _k_tile),
                pl.BlockSpec((None, block_k, D), vk_tile),
            ],
            out_specs=[
                pl.BlockSpec((None, block_q, D), vq_tile),
                _row_stat_spec(block_q),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(v2.shape, q.dtype),
            jax.ShapeDtypeStruct((BH, T // block_q, 1, block_q), jnp.float32),
        ],
        interpret=interpret,
        name="dstpu_flash_fwd",
    )(qi, ki, q2, k2, v2)
    return out.reshape(v.shape), lse


# ----------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------


def _bwd_dq_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_acc_ref,
                   *, sm_scale, causal, block_k, nk):
    # the forward's walk: k/v [block_k, D] tiles stream past a q block's
    # q/do/lse/delta; dq accumulates in scratch across them
    step = pl.program_id(1)
    qi, ki = qi_ref[step], ki_ref[step]
    block_q, D = q_ref.shape
    last_ki = _last_k_tile(causal, qi, block_q, block_k, nk)

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def update(rows, keys):
        q, do = q_ref[rows, :], do_ref[rows, :]
        k, v = k_ref[keys, :], v_ref[keys, :]
        lse = lse_ref[0, rows]
        delta = delta_ref[0, rows]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _hide_future(s, qi * block_q + rows.start,
                             ki * block_k + keys.start)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None])).astype(q.dtype)
        dq_acc_ref[rows, :] = dq_acc_ref[rows, :] + jax.lax.dot_general(
            ds, k, _NN, preferred_element_type=jnp.float32)

    _sub_blocks(causal, qi, ki, block_q, block_k, update)

    @pl.when(ki == last_ki)
    def _finish():
        dq_ref[...] = (dq_acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, sm_scale, causal, block_q, nq):
    # the mirrored walk: a k block's live q tiles consecutive, queries
    # ascending; q/do/lse/delta tiles stream past a resident [block_k, D]
    # k/v tile; dk/dv accumulate in scratch. The scores are TRANSPOSED,
    # [keys, rows]: lse_ref/delta_ref [1, block_q] lie along the lanes as
    # stored, and p^T / ds^T are the LEFT operands of their products.
    step = pl.program_id(1)
    qi, ki = qi_ref[step], ki_ref[step]
    block_k, D = k_ref.shape
    first_qi = (ki * block_k) // block_q if causal else 0

    @pl.when(qi == first_qi)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def update(rows, keys):
        q, do = q_ref[rows, :], do_ref[rows, :]
        k, v = k_ref[keys, :], v_ref[keys, :]
        lse = lse_ref[:, rows]                                    # [1, rows]
        delta = delta_ref[:, rows]
        st = jax.lax.dot_general(k, q, _NT,
                                 preferred_element_type=jnp.float32) * sm_scale
        if causal:
            st = _hide_future(st, qi * block_q + rows.start,
                              ki * block_k + keys.start, transposed=True)
        pt = jnp.exp(st - lse)                                # [keys, rows]
        dv_acc_ref[keys, :] = dv_acc_ref[keys, :] + jax.lax.dot_general(
            pt.astype(q.dtype), do, _NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta)).astype(q.dtype)
        dk_acc_ref[keys, :] = dk_acc_ref[keys, :] + jax.lax.dot_general(
            dst, q, _NN, preferred_element_type=jnp.float32)

    _sub_blocks(causal, qi, ki, block_q, block_k, update)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[...] = (dk_acc_ref[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)


def _row_dots(do, o, heads):
    """delta, each row's `sum(dO * o)` over its head's columns, float32
    `[B, H, T]`: of `[B, H, T, D]` operands (`heads` None), or of
    `[B, T, H*D]` ones, where the sum over a head's lanes is a product with a
    constant 0/1 `[H*D, H]` matrix at float32 precision — a reshape to
    `[.., H, D]` would bring back the relayout of dO and o the index maps
    spare (PERF.md section 7). Reading dO and o once is its whole cost on
    the chip."""
    prod = do.astype(jnp.float32) * o.astype(jnp.float32)
    if heads is None:
        return jnp.sum(prod, axis=-1)
    head_of = np.arange(prod.shape[-1]) // (prod.shape[-1] // heads)
    pick = jnp.asarray(head_of[:, None] == np.arange(heads), jnp.float32)
    return jnp.swapaxes(
        jnp.matmul(prod, pick, precision=jax.lax.Precision.HIGHEST), 1, 2)


def _flash_bwd(res, g, sm_scale, causal, block_q, block_k, interpret,
               delta_adjust=None, heads=None):
    q, k, v, o, lse = res
    do = g
    B, H, T, D = q.shape
    BH = B * H
    delta = _row_dots(do, o, heads)                               # [B,H,T]
    if delta_adjust is not None:
        # lse cotangent: d lse/d s = p, so ds = p*(dp - delta + dlse) — i.e.
        # the existing kernels run unchanged with delta' = delta - dlse
        delta = delta - delta_adjust

    q2, k2 = (x.reshape(BH, T, D) for x in (q, k))
    v2, vq_tile, vk_tile = _value_side(v, heads)
    do2 = _value_side(do, heads)[0]
    Tb = T // block_q
    delta2 = delta.reshape(BH, Tb, 1, block_q)          # lse: [BH, Tb, 1, block_q]
    operands = (q2, k2, v2, do2, lse, delta2)
    in_specs = [
        pl.BlockSpec((None, block_q, D), _q_tile),
        pl.BlockSpec((None, block_k, D), _k_tile),
        pl.BlockSpec((None, block_k, D), vk_tile),
        pl.BlockSpec((None, block_q, D), vq_tile),
        _row_stat_spec(block_q),
        _row_stat_spec(block_q),
    ]

    qi, ki = _tile_tables(T, block_q, block_k, causal)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_k=block_k, nk=T // block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, qi.shape[0]),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, block_q, D), _q_tile),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        interpret=interpret,
        name="dstpu_flash_dq",
    )(qi, ki, *operands)

    qi, ki = _tile_tables(T, block_q, block_k, causal, k_major=True)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, nq=Tb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, qi.shape[0]),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((None, block_k, D), _k_tile),
                pl.BlockSpec((None, block_k, D), vk_tile),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct(v2.shape, q.dtype),
        ],
        interpret=interpret,
        name="dstpu_flash_dkv",
    )(qi, ki, *operands)

    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


# ----------------------------------------------------------------------
# public op
# ----------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret, heads):
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                        heads)
    return out


# The name of the two residuals the backward kernels read of the forward
# one: under a `jax.checkpoint` whose policy holds this name the backward
# never runs `dstpu_flash_fwd` again (`models/gpt.py::held_candidates`).
FLASH_RESIDUALS = "flash_residuals"


def _flash_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                   heads):
    out, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                          heads)
    out = checkpoint_name(out, FLASH_RESIDUALS)
    lse = checkpoint_name(lse, FLASH_RESIDUALS)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, interpret, heads, res,
                   g):
    return _flash_bwd(res, g, sm_scale, causal, block_q, block_k, interpret,
                      heads=heads)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_seq_tileable(T):
    """True when the kernel's 128-lane tiling divides T — the shard-shape
    contract ring attention (`parallel/ring.py`) checks before forcing the
    kernel on a per-rank T/sp shard, and the zoo's dispatch layer checks
    for the whole-sequence path. One definition, next to the lane width it
    encodes."""
    return T % _LANES == 0


def flash_max_seq(d_head, itemsize=2, hbm_budget=12 * 2**30):
    """Largest single-device T the STREAMING kernel can serve. K/V tiles are
    DMA'd from HBM per grid step, so VMEM no longer bounds the sequence —
    the bound is HBM holding the op's own operands through fwd+bwd: per
    (batch x head), ~8 [T, D] slabs (q/k/v/o + do/dq/dk/dv) plus two fp32
    [T] rows (lse, delta). The historical whole-slab VMEM cap this replaces
    was (14 MiB)/(4*D*itemsize) ~ 14k tokens at head_dim 128 bf16; the
    streaming bound at the same shape is ~6M tokens on a 16 GiB chip
    (12 GiB budgeted — activations elsewhere claim HBM first, so treat
    this as advisory, not a hard wall)."""
    return int(hbm_budget) // (8 * d_head * itemsize + 8)


def _default_blocks(T, head_bytes, block_q, block_k):
    """The tile of all three kernels, from T and the bytes of one head's row
    (`head_dim * itemsize`): the largest power-of-two divisor of T from 128
    up to 1024 — 512 where a row is wider than 512 bytes (at [1024, 256]
    float32 tiles the dk/dv kernel passes the 16 MiB of scoped VMEM). A
    tile of 1024 is still WORKED in `_SUB` = 512 sub-blocks, so it costs a
    causal grid no more products than 512 x 512 tiles and a third of their
    grid steps (v5e, [8, 16, 2048, 128] bf16, fwd / dq / dkv ms a call:
    512 tiles 1.43 / 1.78 / 2.28, 1024 tiles 1.32 / 1.61 / 2.08, one
    2048 tile 1.29 / 1.56 / 2.03 at four times the code; T 512 in 128
    tiles 2.71 / 2.66 / 2.93, as one tile 0.67 / 0.72 / 0.93).
    Explicit sizes pass through."""
    largest = 1024 if head_bytes <= 512 else 512

    def tile(block):
        if block is None:
            block = largest
            while block > _LANES and T % block != 0:
                block //= 2
        return min(block, T)

    block_q, block_k = tile(block_q), tile(block_k)
    assert T % block_q == 0 and T % block_k == 0, (T, block_q, block_k)
    return block_q, block_k


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    """(o, lse) variant for composition (ring attention): lse
    [BH, Tb, 1, bq] participates in autodiff — its cotangent folds into the
    backward as a delta adjustment (see _flash_bwd)."""
    return _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)


def _flash_lse_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_vjp_bwd(sm_scale, causal, block_q, block_k, interpret, res, g):
    do, dlse = g
    q = res[0]
    B, H, T, D = q.shape
    # ds = p*(dp - delta + dlse) = p*(dp - (delta - dlse)) → delta' = delta - dlse
    dlse_rows = dlse.astype(jnp.float32).reshape(B, H, T)
    return _flash_bwd(res, do, sm_scale, causal, block_q, block_k, interpret,
                      delta_adjust=dlse_rows)


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def flash_attention_with_lse(q, k, v, causal=True, sm_scale=None, block_q=None,
                             block_k=None, interpret=None):
    """Differentiable (output, lse) flash attention, [B, H, T, D] layout.

    lse is returned as [B, H, T] (row log-sum-exp, fp32) — the combination
    statistic ring attention needs to merge per-shard partials
    (parallel/ring.py): out = Σ_i o_i · exp(lse_i − logsumexp_i lse_i)."""
    if interpret is None:
        interpret = pallas_interpret()
    B, H, T, D = q.shape
    block_q, block_k = _default_blocks(T, D * q.dtype.itemsize, block_q,
                                       block_k)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    out, lse = _flash_lse(q, k, v, float(sm_scale), bool(causal), int(block_q),
                          int(block_k), bool(interpret))
    # blocked [BH, Tb, 1, bq] rows concatenate in order → [B, H, T]
    return out, lse.reshape(B, H, T)


def flash_heads_in_place(d_head):
    """True when a head of a `[B, T, H*D]` array is a column block of whole
    lane tiles, so the kernels address it there by an index map
    (`_column_tiles`) and the values, the output and their gradients cross
    the kernels' boundary without a copy."""
    return d_head % _LANES == 0


def flash_attention(q, k, v, causal=True, sm_scale=None, block_q=None,
                    block_k=None, layout="BTHD", interpret=None):
    """Flash attention. q,k,v: [B,T,H,D] ("BTHD", zoo layout) or [B,H,T,D].
    Under "BTHD" with `flash_heads_in_place(D)` v may also come with its
    heads merged, [B,T,H*D] (what the projection's columns are); the result
    has v's form.

    Sequence length must be a multiple of the block size (the zoo pads to 128
    multiples; MXU-friendly anyway) and is otherwise bounded only by HBM
    (`flash_max_seq`) — K/V stream through VMEM one [block_k, D] tile at a
    time. The tiles follow from T and the head's width (`_default_blocks`:
    1024 / 1024 where T allows, worked in 512 / 512 sub-blocks). Measured on
    a v5e (PERF.md §6, PR 37), fwd / dq / dkv ms a call at [8, 16, 2048, 128]
    bf16 causal: 1.32 / 1.61 / 2.08 — 53 / 65 / 67% of the least time the
    MXU allows for the lower triangle's products.
    """
    if interpret is None:
        interpret = pallas_interpret()
    heads, form = None, v.shape
    if layout == "BTHD":
        q, k = (jnp.swapaxes(x, 1, 2) for x in (q, k))
        if flash_heads_in_place(q.shape[-1]):
            heads = q.shape[1]
            v = v.reshape(v.shape[:2] + (-1,))
        elif v.ndim == 4:
            v = jnp.swapaxes(v, 1, 2)
        else:
            raise ValueError(
                f"v with merged heads {v.shape} needs a head width of whole "
                f"lane tiles (`flash_heads_in_place`), not {q.shape[-1]}")
    B, H, T, D = q.shape
    block_q, block_k = _default_blocks(T, D * q.dtype.itemsize, block_q,
                                       block_k)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    out = _flash(q, k, v, float(sm_scale), bool(causal), int(block_q), int(block_k),
                 bool(interpret), heads)
    if heads is not None:
        return out.reshape(form)
    if layout == "BTHD":
        out = jnp.swapaxes(out, 1, 2)
    return out
