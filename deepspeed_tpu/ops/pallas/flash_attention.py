"""Flash attention (Pallas, TPU) — HBM-streaming K/V.

The training-attention hot op — replaces the reference's fused softmax CUDA
kernels (`csrc/transformer/softmax_kernels.cu`, sparse/triton attention
`ops/sparse_attention/matmul.py`) with the memory-optimal streaming formulation:
online softmax over KV blocks, O(T) memory, fp32 accumulation, causal masking,
custom VJP with the standard recomputation backward.

Layout: [B, H, T, D] (wrapper transposes from the zoo's [B, T, H, D]).

K/V STREAM from HBM: the grid carries a KV-block dimension and Pallas's
pipeline DMAs one double-buffered [block_k, D] (resp. [block_q, D] in the
dk/dv pass) tile into VMEM per grid step while the previous tile computes.
The online-softmax state (acc/m/l) lives in VMEM scratch that persists
across the sequential KV grid steps, so the kernel's VMEM working set is
O(block), not O(T) — sequence length is bounded by HBM capacity
(`flash_max_seq`), not the old ~14k-token whole-slab VMEM cap. Causal
grids skip fully-masked tiles entirely: compute and output writes are
predicated off (`pl.when`), and the block index maps clamp to the diagonal
frontier so the dead steps' DMAs are elided too (repeated consecutive
block indices fetch nothing — same trick as the decode kernel's prefix
clamp).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.platform.device import pallas_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
# VPU lane width: m/l scratch rows are replicated across one lane tile so the
# scratch stays 2D and tile-aligned regardless of block_q
_LANES = 128


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, sm_scale, causal, block_k):
    # q_ref/o_ref: [block_q, D]; k_ref/v_ref: [block_k, D] (one streamed KV
    # tile); lse_ref: [1, block_q]; scratch acc [block_q, D] fp32, m/l
    # [block_q, _LANES] fp32 (row stats replicated across lanes — TPU scratch
    # wants a 128-lane trailing dim). Grid: (BH, nq, nk), nk innermost and
    # sequential, so scratch carries the online-softmax state across KV tiles.
    #
    # Dots run on NATIVE-dtype operands (bf16 in, fp32 out via
    # preferred_element_type): casting inputs to fp32 first forces the MXU's
    # fp32 path (~4x slower) and was measured to make the whole kernel lose
    # to XLA attention at seq 512. `p` narrows back to the input dtype for
    # the p@v dot — standard TPU flash practice; softmax stats stay fp32.
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    block_q, D = q_ref.shape
    in_dtype = q_ref.dtype

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if causal:
        # any (q_pos >= k_pos) pair in this tile? max q_pos = (qi+1)*bq - 1
        run = ki * block_k < (qi + 1) * block_q
        last_ki = jnp.minimum(nk - 1, ((qi + 1) * block_q - 1) // block_k)
    else:
        run = ki >= 0          # traced always-true (Mosaic-friendly pl.when)
        last_ki = nk - 1

    @pl.when(run)
    def _step():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(in_dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == last_ki)
    def _finish():
        m = m_ref[:, 0]
        l_safe = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[...] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, :] = (m + jnp.log(l_safe)).astype(jnp.float32)


def _kv_index_map(causal, block_q, block_k):
    """KV-tile index for the (BH, nq, nk) grids. Causal grids clamp ki to the
    q row's diagonal frontier: fully-masked tiles re-serve the frontier block,
    and Pallas elides the DMA when consecutive block indices repeat — dead
    grid steps cost neither MXU (pl.when) nor HBM traffic (same trick as the
    decode kernel's prefix clamp)."""
    if not causal:
        return lambda bh, qi, ki: (bh, ki, 0)

    def index(bh, qi, ki):
        frontier = ((qi + 1) * block_q - 1) // block_k
        return (bh, jnp.minimum(ki, frontier), 0)

    return index


def _row_stat_spec(block_q, q_index):
    """BlockSpec of one q block's row statistics (lse, delta), addressed by
    the q tile's own index map. The arrays are [BH, Tb, 1, block_q]: rows of
    a q block along the LANES, one block per (bh, qi). The unit third dim is
    what makes the tile legal — Mosaic requires a block's last two dims to
    be (8, 128)-divisible or equal to the array's, and a (1, block_q) tile
    of a [BH, Tb, block_q] array is neither once Tb > 1 (it compiled under
    jax 0.4; jax 0.9 refuses it)."""
    return pl.BlockSpec((None, None, 1, block_q),
                        lambda *grid: q_index(*grid) + (0,))


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    B, H, T, D = q.shape
    BH = B * H
    q2 = q.reshape(BH, T, D)
    k2 = k.reshape(BH, T, D)
    v2 = v.reshape(BH, T, D)
    Tb = T // block_q
    grid = (BH, Tb, T // block_k)
    kv_index = _kv_index_map(causal, block_q, block_k)
    q_index = lambda bh, qi, ki: (bh, qi, 0)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, D), q_index),
            pl.BlockSpec((None, block_k, D), kv_index),
            pl.BlockSpec((None, block_k, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), q_index),
            _row_stat_spec(block_q, q_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Tb, 1, block_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="dstpu_flash_fwd",
    )(q2, k2, v2)
    return out.reshape(B, H, T, D), lse


# ----------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc_ref, *, sm_scale, causal, block_k):
    # streamed tiles: k/v [block_k, D] walk the KV grid dim; q/do/lse/delta
    # ride the q block; dq accumulates in scratch across the KV walk
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    block_q, D = q_ref.shape
    in_dtype = q_ref.dtype

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    if causal:
        run = ki * block_k < (qi + 1) * block_q
        last_ki = jnp.minimum(nk - 1, ((qi + 1) * block_q - 1) // block_k)
    else:
        run = ki >= 0          # traced always-true (Mosaic-friendly pl.when)
        last_ki = nk - 1

    @pl.when(run)
    def _step():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[0, :]
        delta = delta_ref[0, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None])).astype(in_dtype)
        dq_acc_ref[...] = dq_acc_ref[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == last_ki)
    def _finish():
        dq_ref[...] = (dq_acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, sm_scale, causal, block_q):
    # grid (BH, nk, nq), nq innermost: q/do/lse/delta tiles stream past a
    # resident [block_k, D] k/v tile; dk/dv accumulate in scratch
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    block_k, D = k_ref.shape
    in_dtype = k_ref.dtype

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    # causal: q blocks strictly before the diagonal see no (q_pos >= k_pos)
    run = (qi + 1) * block_q > ki * block_k if causal else qi >= 0

    @pl.when(run)
    def _step():
        k = k_ref[...]
        v = v_ref[...]
        q = q_ref[...]
        do = do_ref[...]
        lse = lse_ref[0, :]
        delta = delta_ref[0, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                                 # [bq, bk]
        dv_acc_ref[...] = dv_acc_ref[...] + jax.lax.dot_general(
            p.astype(in_dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None])).astype(in_dtype)
        dk_acc_ref[...] = dk_acc_ref[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[...] = (dk_acc_ref[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd(res, g, sm_scale, causal, block_q, block_k, interpret,
               delta_adjust=None):
    q, k, v, o, lse = res
    do = g
    B, H, T, D = q.shape
    BH = B * H
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [B,H,T]
    if delta_adjust is not None:
        # lse cotangent: d lse/d s = p, so ds = p*(dp - delta + dlse) — i.e.
        # the existing kernels run unchanged with delta' = delta - dlse
        delta = delta - delta_adjust

    q2, k2, v2 = (x.reshape(BH, T, D) for x in (q, k, v))
    do2 = do.reshape(BH, T, D)
    Tb = T // block_q
    lse2 = lse                                   # [BH, Tb, 1, block_q]
    delta2 = delta.reshape(BH, Tb, 1, block_q)

    kv_index = _kv_index_map(causal, block_q, block_k)
    q_tile = lambda bh, qi, ki: (bh, qi, 0)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_k=block_k),
        grid=(BH, Tb, T // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, D), q_tile),
            pl.BlockSpec((None, block_k, D), kv_index),
            pl.BlockSpec((None, block_k, D), kv_index),
            pl.BlockSpec((None, block_q, D), q_tile),
            _row_stat_spec(block_q, q_tile),
            _row_stat_spec(block_q, q_tile),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), q_tile),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="dstpu_flash_dq",
    )(q2, k2, v2, do2, lse2, delta2)

    if causal:
        # mirror of _kv_index_map for the transposed (BH, nk, nq) grid:
        # pre-diagonal q tiles re-serve the diagonal block (DMA elided)
        def q_index(bh, ki, qi):
            first = (ki * block_k) // block_q
            return (bh, jnp.maximum(qi, first), 0)
    else:
        q_index = lambda bh, ki, qi: (bh, qi, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q),
        grid=(BH, T // block_k, Tb),
        in_specs=[
            pl.BlockSpec((None, block_q, D), q_index),
            pl.BlockSpec((None, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((None, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((None, block_q, D), q_index),
            _row_stat_spec(block_q, q_index),
            _row_stat_spec(block_q, q_index),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((None, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="dstpu_flash_dkv",
    )(q2, k2, v2, do2, lse2, delta2)

    return (dq.reshape(B, H, T, D), dk.reshape(B, H, T, D), dv.reshape(B, H, T, D))


# ----------------------------------------------------------------------
# public op
# ----------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return out


def _flash_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, interpret, res, g):
    return _flash_bwd(res, g, sm_scale, causal, block_q, block_k, interpret)


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


def _default_blocks(T, block_q, block_k):
    """Measured-crossover default tiles (512/512 from T >= 1024 — see
    flash_attention docstring), shrunk to the largest power-of-two divisor
    of T >= the 128 lane width; explicit sizes pass through."""
    if block_q is None:
        block_q = 512 if T >= 1024 else DEFAULT_BLOCK_Q
        while block_q > DEFAULT_BLOCK_Q and T % block_q != 0:
            block_q //= 2
    if block_k is None:
        block_k = 512 if T >= 1024 else DEFAULT_BLOCK_K
        while block_k > DEFAULT_BLOCK_K and T % block_k != 0:
            block_k //= 2
    block_q = min(block_q, T)
    block_k = min(block_k, T)
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
    block_q, block_k = _default_blocks(T, block_q, block_k)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    out, lse = _flash_lse(q, k, v, float(sm_scale), bool(causal), int(block_q),
                          int(block_k), bool(interpret))
    # blocked [BH, Tb, 1, bq] rows concatenate in order → [B, H, T]
    return out, lse.reshape(B, H, T)


def flash_attention(q, k, v, causal=True, sm_scale=None, block_q=None,
                    block_k=None, layout="BTHD", interpret=None):
    """Flash attention. q,k,v: [B,T,H,D] ("BTHD", zoo layout) or [B,H,T,D].

    Sequence length must be a multiple of the block size (the zoo pads to 128
    multiples; MXU-friendly anyway) and is otherwise bounded only by HBM
    (`flash_max_seq`) — K/V stream through VMEM one [block_k, D] tile at a
    time. Default blocks scale with T: 512/512 tiles from T >= 1024
    (measured r4 with native-dtype dots, fwd+bwd vs materialized XLA
    attention: 1.6x at 1k, 2.3x at 2k, 3.4x at 4k; 512/512 edged out
    512/1024 at both 2k and 4k); short sequences keep 128/128.
    """
    if interpret is None:
        interpret = pallas_interpret()
    if layout == "BTHD":
        q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    B, H, T, D = q.shape
    block_q, block_k = _default_blocks(T, block_q, block_k)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    out = _flash(q, k, v, float(sm_scale), bool(causal), int(block_q), int(block_k),
                 bool(interpret))
    if layout == "BTHD":
        out = jnp.swapaxes(out, 1, 2)
    return out
