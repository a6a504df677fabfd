"""Block-sparse flash attention (Pallas, TPU).

Real-kernel analog of the reference's Triton SDD/DSD block-sparse matmuls
(`ops/sparse_attention/matmul.py:17`): the `[H, n, n]` block layout from the
sparsity configs (`ops/sparse_attention.py`) folds into the flash kernel's KV
loop as a **visit list** — for every (head, q-tile) row the kernel iterates
ONLY the k-blocks with any live fine-granular cell, so compute and HBM
traffic scale with layout density, not T^2.

Mechanics:
  * host side: the fine layout (granularity `config.block`, normalized to 16)
    is coarsened to (block_q x 128) kernel granularity; per (h, qi) rows of
    visited k-block indices + counts are precomputed (static per layout+T)
    and passed as scalar-prefetch operands (SMEM — the splash-attention
    pattern; the TPU lowering requires SMEM for scalar/loop-bound data);
  * kernel side: `fori_loop` over the visit count with `pl.multiple_of`-
    aligned dynamic loads of the listed k-blocks; the fine 16-granular mask
    tile is picked out with a one-hot selection matmul and expanded to
    [block_q, 128] with two 0/1 expansion matmuls (all MXU-friendly — Mosaic
    cannot prove alignment of dynamic lane/sublane slices, so no slicing);
  * block_q defaults to 512 at long T: grid-step fixed overhead measured
    ~20us/step on v5e dominates at 128 (5.3ms of a 5.6ms pass at T=8k/5%),
    so fewer, fatter q tiles buy ~4x;
  * backward: same structure — dq iterates the q-row visit lists, dk/dv
    iterate the TRANSPOSED lists, matching the forward's visited set
    exactly, with the standard recomputation flash backward.

Numerics match the dense masked fp32 path (`SparseSelfAttention`'s fallback)
to fp32 tolerance on CPU (interpret) and to the MXU default-precision band on
hardware. Fully-dead query rows are rejected at build time (softmax over an
empty visit set is undefined; no shipped config produces them).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.platform.device import pallas_interpret

NEG_INF = -1e30
BLOCK_K = 128
FINE = 16                      # internal mask granularity
FPK_K = BLOCK_K // FINE        # fine cells per k block (8 — tiling-legal)


class BiasVmemBudgetError(ValueError):
    """The bias-streaming path cannot fit its VMEM slabs at this shape.

    A dedicated type so callers (SparseSelfAttention) can fall back to the
    dense path on exactly this condition without swallowing unrelated
    ValueErrors from inside the kernel."""


def _visit_lists(coarse):
    """coarse: [H, nq, nk] bool -> (counts [H,nq], idx [H,nq,max_visits]).
    idx rows are the visited k-block indices, padded with 0 (never read past
    counts)."""
    H, nq, nk = coarse.shape
    counts = coarse.sum(-1).astype(np.int32)
    maxv = max(1, int(counts.max()))
    idx = np.zeros((H, nq, maxv), np.int32)
    for h in range(H):
        for i in range(nq):
            cols = np.nonzero(coarse[h, i])[0]
            idx[h, i, :len(cols)] = cols
    return counts, idx


def _expander(fine_rows, width):
    """[fine_rows, width] 0/1 matrix E with E[a, i] = (i // FINE == a);
    fine_tile -> (E_q.T @ tile) @ E_k expands a 16-granular mask tile to
    kernel granularity using two small matmuls."""
    a = jax.lax.broadcasted_iota(jnp.int32, (fine_rows, width), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (fine_rows, width), 1)
    return (i // FINE == a).astype(jnp.float32)


def _expand_mask(tile, width_q, width_k):
    """tile: [fq, fk] f32 -> [width_q, width_k] f32 (0/1)."""
    Eq = _expander(tile.shape[0], width_q)
    Ek = _expander(tile.shape[1], width_k)
    return jax.lax.dot_general(
        jax.lax.dot_general(Eq, tile, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32),
        Ek, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _select_cols(layout_row, j, width):
    """layout_row: [fq, n16]; select columns j*width..+width via a one-hot
    selection matmul (Mosaic cannot prove alignment of dynamic lane slices;
    a matmul against an iota-built selector is always legal)."""
    n16 = layout_row.shape[1]
    c = jax.lax.broadcasted_iota(jnp.int32, (n16, width), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (n16, width), 1)
    S = (c == j * width + b).astype(jnp.float32)
    return jax.lax.dot_general(layout_row, S, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _select_row(mat, i):
    """mat: [n_rows, W]; pick row i as [W] via one-hot matmul (dynamic
    sublane slicing has the same Mosaic alignment restriction)."""
    n_rows = mat.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (1, n_rows), 1)
    onehot = (r == i).astype(jnp.float32)
    row = jax.lax.dot_general(onehot, mat, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return row.reshape((mat.shape[1],))


def _causal_tile(qi, block_q, j, transpose=False):
    """[block_q, BLOCK_K] bool (or its transpose): token-granular q >= k for
    q-tile qi vs k-block j — the layout's unidirectional tril is only
    block-granular, so diagonal blocks need this intra-block mask."""
    shape = (BLOCK_K, block_q) if transpose else (block_q, BLOCK_K)
    qdim, kdim = (1, 0) if transpose else (0, 1)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, qdim)
    k_pos = j * BLOCK_K + jax.lax.broadcasted_iota(jnp.int32, shape, kdim)
    return q_pos >= k_pos


def _fwd_kernel(counts_ref, idx_ref, layout_ref, *rest, causal, has_bias,
                has_kpm):
    # counts_ref: [H, nbq] SMEM; idx_ref: [H, nbq, maxv] SMEM;
    # layout_ref: [fq, n16] f32 (this q-tile's fine mask rows);
    # optional bias_ref: [nbk, block_q, BLOCK_K] (this (h, qi)'s additive-bias
    # tiles — dynamic leading-index load per visited k-block);
    # optional kvb_ref: [nbk, BLOCK_K] (this batch's key-padding additive row);
    # q_ref: [block_q, D]; k/v_ref: [T, D]; lse_ref: [nbq, block_q] whole
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    kvb_ref = rest.pop(0) if has_kpm else None
    q_ref, k_ref, v_ref, o_ref, lse_ref = rest
    h, qi = pl.program_id(1), pl.program_id(2)
    block_q, D = q_ref.shape
    # dots run on native-dtype operands (bf16 in, fp32 accumulate) — casting
    # inputs to fp32 first forces the MXU's ~4x-slower fp32 path (same fix as
    # flash_attention.py); p/ds narrow back to the input dtype for the second
    # dot of each pair, softmax stats stay fp32
    in_dtype = q_ref.dtype
    q = q_ref[:, :]
    n_visit = counts_ref[h, qi]

    def body(t, carry):
        acc, m_prev, l_prev = carry
        j = idx_ref[h, qi, t]
        start = pl.multiple_of(j * BLOCK_K, BLOCK_K)
        k = k_ref[pl.ds(start, BLOCK_K), :]
        v = v_ref[pl.ds(start, BLOCK_K), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if has_bias:
            s = s + bias_ref[j]
        if has_kpm:
            s = s + _select_row(kvb_ref[:, :], j)[None, :]
        tile = _select_cols(layout_ref[:, :], j, FPK_K)
        s = jnp.where(_expand_mask(tile, block_q, BLOCK_K) > 0, s, NEG_INF)
        if causal:
            s = jnp.where(_causal_tile(qi, block_q, j), s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(in_dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, D), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_visit, body, (acc0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[:, :] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[qi, :] = (m + jnp.log(l_safe)).astype(jnp.float32)


def _bwd_dq_kernel(counts_ref, idx_ref, layout_ref, *rest, causal, has_bias,
                   has_kpm, want_dbias, swapped_grid):
    # swapped_grid (learned bias with a single shared-head slab): grid is
    # (b, qi, h) so the head-broadcast dbias block's revisits across h are
    # CONSECUTIVE — Pallas only guarantees output-block accumulation across
    # back-to-back grid steps (a revisit after the block was swapped out
    # loses the writes). want_dbias is False for non-learned masks: the bias
    # still masks s, but no dense [T, T] gradient output is materialized.
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    kvb_ref = rest.pop(0) if has_kpm else None
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref = rest[:7]
    dbias_ref = rest[7] if want_dbias else None
    if swapped_grid:
        qi, h = pl.program_id(1), pl.program_id(2)
    else:
        h, qi = pl.program_id(1), pl.program_id(2)
    block_q, D = q_ref.shape
    in_dtype = q_ref.dtype
    q = q_ref[:, :]
    do = do_ref[:, :]
    lse = lse_ref[qi, :]
    delta = delta_ref[qi, :]
    n_visit = counts_ref[h, qi]

    if want_dbias:
        # zero the dbias block on first visit: every program owns its block
        # when the bias is per-head; the shared-slab case revisits across h
        # (consecutive under swapped_grid) and zeroes only at h == 0
        @pl.when(pl.program_id(2) == 0 if swapped_grid else True)
        def _zero():
            dbias_ref[...] = jnp.zeros(dbias_ref.shape, dbias_ref.dtype)

    def body(t, dq):
        j = idx_ref[h, qi, t]
        start = pl.multiple_of(j * BLOCK_K, BLOCK_K)
        k = k_ref[pl.ds(start, BLOCK_K), :]
        v = v_ref[pl.ds(start, BLOCK_K), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if has_bias:
            s = s + bias_ref[j]
        if has_kpm:
            s = s + _select_row(kvb_ref[:, :], j)[None, :]
        tile = _select_cols(layout_ref[:, :], j, FPK_K)
        s = jnp.where(_expand_mask(tile, block_q, BLOCK_K) > 0, s, NEG_INF)
        if causal:
            s = jnp.where(_causal_tile(qi, block_q, j), s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds_f32 = p * (dp - delta[:, None])
        if want_dbias:
            # dL/dbias for this tile: the bias enters s additively AFTER the
            # q-side sm_scale folding, so dbias == ds (accumulated over batch
            # outside, and over heads here when the slab is head-shared)
            dbias_ref[j] = dbias_ref[j] + ds_f32
        ds = ds_f32.astype(in_dtype)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, n_visit, body, jnp.zeros((block_q, D), jnp.float32))
    dq_ref[:, :] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(counts_ref, idx_ref, layout_ref, *rest, block_q, causal,
                    has_bias, has_kpm):
    # transposed visit lists: for THIS k-block, which q-tiles touch it.
    # layout_ref is this k-row of layout^T: [FPK_K, n16].
    # optional bias_ref: [nbq, block_q, BLOCK_K] (this (h, ki)'s column of
    # the blocked bias in the S orientation — each picked tile is transposed
    # in-register, saving a dense-T^2 HBM copy); optional kvbT_ref:
    # [BLOCK_K, 1] (this (b, ki)'s key-padding additive column).
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    kvbT_ref = rest.pop(0) if has_kpm else None
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref = rest
    h, ki = pl.program_id(1), pl.program_id(2)
    block_k, D = dk_ref.shape
    in_dtype = k_ref.dtype
    k = k_ref[:, :]
    v = v_ref[:, :]
    n_visit = counts_ref[h, ki]
    fq = block_q // FINE

    def body(t, carry):
        dk, dv = carry
        i = idx_ref[h, ki, t]
        start = pl.multiple_of(i * block_q, block_q)
        q = q_ref[pl.ds(start, block_q), :]
        do = do_ref[pl.ds(start, block_q), :]
        lse = _select_row(lse_ref[:, :], i)
        delta = _select_row(delta_ref[:, :], i)
        sT = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [bk, bq]
        if has_bias:
            sT = sT + bias_ref[i].T                                   # -> [bk, bq]
        if has_kpm:
            sT = sT + kvbT_ref[:, :]                                  # [bk, 1]
        tileT = _select_cols(layout_ref[:, :], i, fq)                 # [FPK_K, fq]
        sT = jnp.where(_expand_mask(tileT, BLOCK_K, block_q) > 0, sT, NEG_INF)
        if causal:
            sT = jnp.where(_causal_tile(i, block_q, ki, transpose=True),
                           sT, NEG_INF)
        pT = jnp.exp(sT - lse[None, :])
        dv = dv + jax.lax.dot_general(pT.astype(in_dtype), do, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dpT = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # [bk, bq]
        dsT = (pT * (dpT - delta[None, :])).astype(in_dtype)
        dk = dk + jax.lax.dot_general(dsT, q, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((block_k, D), jnp.float32)
    dv0 = jnp.zeros((block_k, D), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, n_visit, body, (dk0, dv0))
    dk_ref[:, :] = dk.astype(dk_ref.dtype)
    dv_ref[:, :] = dv.astype(dv_ref.dtype)


def _normalize_16(layout, block):
    """Re-express a [H, T/block, T/block] layout at the internal 16
    granularity (expand coarse blocks; group finer ones by any())."""
    layout = np.asarray(layout, bool)
    if block == FINE:
        return layout
    H, n, _ = layout.shape
    if block > FINE:
        assert block % FINE == 0, f"layout block {block} must be a multiple of {FINE}"
        r = block // FINE
        return np.kron(layout, np.ones((r, r), bool))
    r = FINE // block
    assert r * block == FINE, f"layout block {block} must divide {FINE}"
    n16 = n // r
    return layout.reshape(H, n16, r, n16, r).any((2, 4))


def _build(layout, T, block, block_q, causal=False):
    """Host-side static prep: 16-granular fine masks (f32, both orientations)
    + visit lists at (block_q x BLOCK_K) granularity, all numpy."""
    fine = _normalize_16(layout, block)                # [H, n16, n16]
    H, n16, _ = fine.shape
    assert n16 * FINE == T, (n16, T)
    assert T % block_q == 0 and T % BLOCK_K == 0, (T, block_q)
    nbq, nbk = T // block_q, T // BLOCK_K
    fq = block_q // FINE
    coarse = fine.reshape(H, nbq, fq, nbk, FPK_K).any((2, 4))
    assert coarse.any(-1).all(), \
        "sparsity layout has a fully-masked query row (undefined softmax)"
    if causal:
        # the intersection with the token-granular causal mask must also keep
        # >=1 key per query row (else m stays -inf and the kernel emits a
        # spurious mean-of-V with bogus grads): a fine row survives iff some
        # visited fine tile lies on or below the diagonal — a strictly-upper
        # layout row dies even though the layout-only check above passes
        assert np.tril(np.ones((n16, n16), bool))[None].__and__(fine).any(-1).all(), \
            "causal=True: some query row's visited blocks are entirely in " \
            "the future (fully masked after the causal intersection)"
    counts, idx = _visit_lists(coarse)
    countsT, idxT = _visit_lists(coarse.transpose(0, 2, 1))
    fineT = fine.transpose(0, 2, 1)
    return (counts, idx, fine.astype(np.float32), countsT, idxT,
            fineT.astype(np.float32), nbq, nbk)


def block_sparse_attention(q, k, v, layout, block=16, sm_scale=None,
                           block_q=None, causal=False, interpret=None,
                           bias=None, key_padding_mask=None,
                           bias_needs_grad=None):
    """q,k,v: [B, H, T, D]; layout: [H, T//block, T//block] bool (numpy,
    static). Differentiable; compute scales with layout density. The softmax
    scale is folded into q once up front (not per-block).

    `causal=True` adds TOKEN-granular q>=k masking inside visited blocks —
    the unidirectional layouts' tril is block-granular only (a diagonal
    block is fully open, leaking up to block-1 future tokens), so causal
    LMs must set this.

    `bias`: optional additive score bias [T, T] or [Hb, T, T] with Hb in
    {1, H} — the reference's rpe / additive attn_mask, streamed IN-KERNEL
    (reference `ops/sparse_attention/softmax.py` streams these through its
    Triton kernel the same way). Differentiable (rpe may be learned): the
    backward accumulates dbias inside the dq kernel over the visited blocks
    only. `bias_needs_grad` (default: True when bias is given): pass False
    for NON-learned masks — the dbias accumulation materializes a dense
    [B, Hb, T, T] fp32 output, which is pure waste when the gradient is
    discarded (256 MB x B at T=8k). `key_padding_mask`: optional [B, T]
    bool, True = attend — masked keys get -1e30 added before the online
    softmax, matching the dense path's where(). Batched [B, T, T] masks
    don't fit the per-head slab streaming; `SparseSelfAttention` falls back
    to dense (with a warning) for those."""
    if interpret is None:
        interpret = pallas_interpret()
    B, H, T, D = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    if block_q is None:
        block_q = 512 if T >= 2048 else 128
        while block_q > 128 and T % block_q != 0:
            block_q //= 2
        if bias is not None:
            # the bias slab a q-tile program holds in VMEM is [nbk, block_q,
            # BLOCK_K] f32 = T*block_q*4 bytes; cap it at ~2 MiB (next to
            # k/v/q tiles) by shrinking the AUTO-chosen q tile (an explicitly
            # passed block_q is respected)
            while block_q > 128 and T * block_q * 4 > 2 * 2**20:
                block_q //= 2
    if bias_needs_grad is None:
        bias_needs_grad = bias is not None
    if bias is not None:
        # fail loudly where the bias streaming cannot fit VMEM: per-program
        # resident slabs are the bias tile stack (T*block_q*4), the dbias
        # output block (same size, learned bias only), and the [T, D] k/v/q
        # slabs — Mosaic's allocation failure at compile time is far less
        # actionable than this message
        itemsize = jnp.dtype(q.dtype).itemsize
        est = (T * block_q * 4 * (2 if bias_needs_grad else 1)
               + 4 * T * D * itemsize)
        if est > 12 * 2**20:
            raise BiasVmemBudgetError(
                f"block-sparse bias streaming at T={T}, block_q={block_q}, "
                f"D={D} needs ~{est / 2**20:.0f} MiB of VMEM-resident slabs "
                "(>12 MiB budget): pass a smaller block_q, drop the bias "
                "(mask via the layout), or use bias_needs_grad=False for "
                "non-learned masks")
    layout = np.asarray(layout, bool)
    if layout.shape[0] == 1 and H > 1:
        # head-broadcast layout (the configs allow num_heads=1 shared layouts)
        layout = np.broadcast_to(layout, (H,) + layout.shape[1:])
    assert layout.shape[0] == H, (layout.shape, H)
    args = _build_cached(layout, T, block, block_q, bool(causal))
    nbq, nbk = T // block_q, T // BLOCK_K
    bias_q = None
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32)
        if bias.ndim == 2:
            bias = bias[None]
        assert bias.shape in ((1, T, T), (H, T, T)), (bias.shape, H, T)
        # blocked per (q-tile, k-block): [Hb, nbq, nbk, block_q, BLOCK_K]
        bias_q = bias.reshape(bias.shape[0], nbq, block_q, nbk, BLOCK_K) \
                     .transpose(0, 1, 3, 2, 4)
    kvb = None
    if key_padding_mask is not None:
        kpm = jnp.asarray(key_padding_mask)
        assert kpm.shape == (B, T), (kpm.shape, B, T)
        kvb = jnp.where(kpm, 0.0, NEG_INF).astype(jnp.float32) \
                 .reshape(B, nbk, BLOCK_K)
    return _sparse(q, k, v, *args, bias_q, kvb, float(sm_scale), int(block_q),
                   bool(causal), bool(interpret), bool(bias_needs_grad))


_BUILD_CACHE = {}


def _build_cached(layout, T, block, block_q, causal=False):
    """Memoize _build's host-side visit-list loops — eager per-token callers
    would otherwise redo O(H*nq*nk) Python work every call. Cached values are
    HOST numpy, converted per call site: caching jnp arrays would capture
    tracers when the first call happens under a jit trace and leak them into
    later traces (observed UnexpectedTracerError)."""
    # key on the bytes themselves, not hash(): a 64-bit collision between two
    # same-shape layouts would silently serve the wrong sparsity pattern
    key = (layout.tobytes(), layout.shape, T, block, block_q, causal)
    if key not in _BUILD_CACHE:
        (counts, idx, fine, countsT, idxT, fineT, _, _) = \
            _build(layout, T, block, block_q, causal)
        _BUILD_CACHE[key] = (counts, idx, fine, countsT, idxT, fineT)
        if len(_BUILD_CACHE) > 32:  # bound resident mask tables
            _BUILD_CACHE.pop(next(iter(_BUILD_CACHE)))
    return tuple(jnp.asarray(a) for a in _BUILD_CACHE[key])


@functools.partial(jax.custom_vjp, nondiff_argnums=(11, 12, 13, 14, 15))
def _sparse(q, k, v, counts, idx, fine, countsT, idxT, fineT, bias_q, kvb,
            sm_scale, block_q, causal, interpret, need_dbias):
    out, _ = _sparse_fwd_impl(q, k, v, counts, idx, fine, bias_q, kvb,
                              sm_scale, block_q, causal, interpret)
    return out


def _bias_specs(bias_q, kvb, index_b, index_hqi):
    """BlockSpecs for the optional bias/key-padding inputs of the fwd and dq
    kernels. index_b/index_hqi: pick (b,) / (h, qi) out of the grid args."""
    specs = []
    if bias_q is not None:
        Hb, nbq, nbk, bq, bk = bias_q.shape
        specs.append(pl.BlockSpec(
            (None, None, nbk, bq, bk),
            lambda *g, Hb=Hb: (index_hqi(*g)[0] if Hb > 1 else 0,
                               index_hqi(*g)[1], 0, 0, 0)))
    if kvb is not None:
        _, nbk, bk = kvb.shape
        specs.append(pl.BlockSpec((None, nbk, bk),
                                  lambda *g: (index_b(*g), 0, 0)))
    return specs


def _sparse_fwd_impl(q, k, v, counts, idx, fine, bias_q, kvb, sm_scale,
                     block_q, causal, interpret):
    B, H, T, D = q.shape
    nbq = T // block_q
    n16 = fine.shape[-1]
    fq = block_q // FINE
    qs = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
    extra_specs = _bias_specs(bias_q, kvb, lambda b, h, qi, *_: b,
                              lambda b, h, qi, *_: (h, qi))
    extra_args = [a for a in (bias_q, kvb) if a is not None]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nbq),
        in_specs=[
            pl.BlockSpec((None, None, fq, n16),
                         lambda b, h, qi, *_: (h, qi, 0, 0)),
            *extra_specs,
            pl.BlockSpec((None, None, block_q, D),
                         lambda b, h, qi, *_: (b, h, qi, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, h, qi, *_: (b, h, 0, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, h, qi, *_: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, D),
                         lambda b, h, qi, *_: (b, h, qi, 0)),
            pl.BlockSpec((None, None, nbq, block_q),
                         lambda b, h, qi, *_: (b, h, 0, 0)),
        ],
    )
    # fine mask rows regrouped per q-tile: [H, nbq, fq, n16] -> block (fq, n16)
    fine_q = fine.reshape(H, nbq, fq, n16)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal,
                          has_bias=bias_q is not None, has_kpm=kvb is not None),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, nbq, block_q), jnp.float32),
        ],
        interpret=interpret,
    )(counts, idx, fine_q, *extra_args, qs, k, v)
    return out, lse


def _sparse_vjp_fwd(q, k, v, counts, idx, fine, countsT, idxT, fineT, bias_q,
                    kvb, sm_scale, block_q, causal, interpret, need_dbias):
    out, lse = _sparse_fwd_impl(q, k, v, counts, idx, fine, bias_q, kvb,
                                sm_scale, block_q, causal, interpret)
    return out, (q, k, v, out, lse, counts, idx, fine, countsT, idxT, fineT,
                 bias_q, kvb)


def _sparse_vjp_bwd(sm_scale, block_q, causal, interpret, need_dbias, res, g):
    (q, k, v, out, lse, counts, idx, fine, countsT, idxT, fineT,
     bias_q, kvb) = res
    B, H, T, D = q.shape
    nbq, nbk = T // block_q, T // BLOCK_K
    n16 = fine.shape[-1]
    fq = block_q // FINE
    do = g
    has_bias, has_kpm = bias_q is not None, kvb is not None
    want_dbias = has_bias and need_dbias
    Hb = bias_q.shape[0] if has_bias else 0
    qs = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = delta.reshape(B, H, nbq, block_q)
    fine_q = fine.reshape(H, nbq, fq, n16)

    # head-shared LEARNED bias slab: dbias accumulates across h IN-kernel,
    # which needs the revisits consecutive -> grid (b, qi, h); per-head slabs
    # (and non-learned masks, which emit no dbias) keep the cache-friendly
    # (b, h, qi) order
    swapped = want_dbias and Hb == 1
    if swapped:
        grid = (B, nbq, H)
        gb, gh, gqi = (lambda b, qi, h, *_: b), (lambda b, qi, h, *_: h), \
                      (lambda b, qi, h, *_: qi)
    else:
        grid = (B, H, nbq)
        gb, gh, gqi = (lambda b, h, qi, *_: b), (lambda b, h, qi, *_: h), \
                      (lambda b, h, qi, *_: qi)
    extra_specs = _bias_specs(bias_q, kvb, gb,
                              lambda *a: (gh(*a), gqi(*a)))
    extra_args = [a for a in (bias_q, kvb) if a is not None]
    dq_out_specs = pl.BlockSpec((None, None, block_q, D),
                                lambda *a: (gb(*a), gh(*a), gqi(*a), 0))
    dq_out_shape = jax.ShapeDtypeStruct((B, H, T, D), q.dtype)
    if want_dbias:
        # dbias is per-batch (summed after): cross-b accumulation would need
        # b-innermost revisits, which would refetch the [T, D] k/v slabs every
        # program. [B, Hb, nbq, nbk, bq, bk] f32 — dense T^2; only emitted
        # for a LEARNED bias (need_dbias), never for plain masks.
        dq_out_specs = [dq_out_specs, pl.BlockSpec(
            (None, None, None, nbk, block_q, BLOCK_K),
            lambda *a: (gb(*a), gh(*a) if Hb > 1 else 0, gqi(*a), 0, 0, 0))]
        dq_out_shape = [dq_out_shape, jax.ShapeDtypeStruct(
            (B, Hb, nbq, nbk, block_q, BLOCK_K), jnp.float32)]
    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, fq, n16),
                         lambda *a: (gh(*a), gqi(*a), 0, 0)),
            *extra_specs,
            pl.BlockSpec((None, None, block_q, D),
                         lambda *a: (gb(*a), gh(*a), gqi(*a), 0)),
            pl.BlockSpec((None, None, T, D), lambda *a: (gb(*a), gh(*a), 0, 0)),
            pl.BlockSpec((None, None, T, D), lambda *a: (gb(*a), gh(*a), 0, 0)),
            pl.BlockSpec((None, None, block_q, D),
                         lambda *a: (gb(*a), gh(*a), gqi(*a), 0)),
            pl.BlockSpec((None, None, nbq, block_q),
                         lambda *a: (gb(*a), gh(*a), 0, 0)),
            pl.BlockSpec((None, None, nbq, block_q),
                         lambda *a: (gb(*a), gh(*a), 0, 0)),
        ],
        out_specs=dq_out_specs,
    )
    dq_res = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, has_bias=has_bias,
                          has_kpm=has_kpm, want_dbias=want_dbias,
                          swapped_grid=swapped),
        grid_spec=dq_spec, out_shape=dq_out_shape,
        interpret=interpret,
    )(counts, idx, fine_q, *extra_args, qs, k, v, do, lse, delta)
    dbias_q = None
    if want_dbias:
        dq, dbias_raw = dq_res
        dbias_q = dbias_raw.sum(axis=0)
    else:
        dq = dq_res
    dq = (dq.astype(jnp.float32) * sm_scale).astype(q.dtype)

    # fineT rows regrouped per k-block: [H, nbk, FPK_K, n16]
    fineT_k = fineT.reshape(H, nbk, FPK_K, n16)
    dkv_extra_specs = []
    dkv_extra_args = []
    if has_bias:
        # stream the SAME blocked bias_q — no transposed HBM copy (an extra
        # dense-T^2 tensor + full transpose per step): per (h, ki) the slab
        # is bias_q[h?, :, ki] = [nbq, block_q, BLOCK_K] and the kernel
        # transposes each picked tile to the sT orientation in-register
        dkv_extra_specs.append(pl.BlockSpec(
            (None, nbq, None, block_q, BLOCK_K),
            lambda b, h, ki, *_, Hb=Hb: (h if Hb > 1 else 0, 0, ki, 0, 0)))
        dkv_extra_args.append(bias_q)
    if has_kpm:
        kvbT = kvb[..., None]                       # [B, nbk, BLOCK_K, 1]
        dkv_extra_specs.append(pl.BlockSpec(
            (None, None, BLOCK_K, 1), lambda b, h, ki, *_: (b, ki, 0, 0)))
        dkv_extra_args.append(kvbT)
    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nbk),
        in_specs=[
            pl.BlockSpec((None, None, FPK_K, n16),
                         lambda b, h, ki, *_: (h, ki, 0, 0)),
            *dkv_extra_specs,
            pl.BlockSpec((None, None, T, D), lambda b, h, ki, *_: (b, h, 0, 0)),
            pl.BlockSpec((None, None, BLOCK_K, D),
                         lambda b, h, ki, *_: (b, h, ki, 0)),
            pl.BlockSpec((None, None, BLOCK_K, D),
                         lambda b, h, ki, *_: (b, h, ki, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, h, ki, *_: (b, h, 0, 0)),
            pl.BlockSpec((None, None, nbq, block_q),
                         lambda b, h, ki, *_: (b, h, 0, 0)),
            pl.BlockSpec((None, None, nbq, block_q),
                         lambda b, h, ki, *_: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, BLOCK_K, D),
                         lambda b, h, ki, *_: (b, h, ki, 0)),
            pl.BlockSpec((None, None, BLOCK_K, D),
                         lambda b, h, ki, *_: (b, h, ki, 0)),
        ],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, causal=causal,
                          has_bias=has_bias, has_kpm=has_kpm),
        grid_spec=dkv_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        ],
        interpret=interpret,
    )(countsT, idxT, fineT_k, *dkv_extra_args, qs, k, v, do, lse, delta)
    # dk needs no extra sm_scale: the kernel contracts ds^T against the
    # PRE-SCALED q, which already carries the factor (dq does need it — its
    # contraction is against the unscaled k)

    return (dq, dk, dv, None, None, None, None, None, None, dbias_q, None)


_sparse.defvjp(_sparse_vjp_fwd, _sparse_vjp_bwd)
