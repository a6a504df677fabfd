"""A learned sparse-attention indexer over the paged pool (Pallas): the score
walk over a sequence's cached INDEX KEYS and the EXACT selection of the
`topk` best-scored positions a query — what stands between the pool write
and the attention walk of a layer of the sparse kind
(`models/sparse_attn.py`; DeepSeek-V3.2's "lightning indexer").

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])     (float32)
    S_t     = the `topk` positions s <= t with the largest I[t, s],
              ties to the EARLIER position; all of them while t < topk

The index key of a position is the third leaf of the full kind's entry, `ik`
`[M, 1, block, 128]`: its 64 values in the first half of a lane tile, zeros
in the other (`kv_pool.py`'s rule: a leaf's last dimension is whole lane
tiles), so a query head padded the same way scores a whole tile with one
product and nothing slices a tile.

Three kernels, and their `jax.numpy` twins (what runs off the TPU, and the
oracles the tests hold the kernels to):

- `paged_index_scores` (`dstpu_sparse_index_scores`): a chunk's rows against
  the blocks under its frontier, the chunk walk's grid
  (`prefill_attention.py`): `[B, nb, C, block]` float32, block-major, so the
  selection reads a row tile's blocks by a LEADING index and the attention
  walk reads the selection a (block, query tile) at a time.
- `paged_index_scores_decode` (`dstpu_sparse_index_scores_decode`): a row a
  slot over the decode walk's work list (`decode_attention.py::
  paged_decode_work`): `[B, nb, 1, block]`.
- `sparse_select` (`dstpu_sparse_select`): the k-th largest score of a row by
  COUNTING — 32 passes of compare-and-sum over the float's bit pattern (a
  monotone int32 key), most significant bit first — then the tie rule by a
  second search over the position's bits: exact, no sort (what `lax.top_k`
  lowers to on this chip is a sort of the whole row). A row tile's keys stay
  in VMEM for all the passes; only the blocks under the tile's frontier are
  counted (a dynamic loop bound). The result is the walk's mask: int8 0 / 1
  for a chunk, a float32 bias 0 / NEG_INF for the slots' rows.

Blocks past a row's frontier hold whatever the memory held, in the scores
and in the selection alike: nobody reads them (the walks clamp to the
frontier as these kernels do).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.decode_attention import (NEG_INF, _LANES,
                                                       _widen,
                                                       paged_decode_work)
from deepspeed_tpu.platform.device import pallas_interpret

INT_MIN = -2**31
_VMEM_LIMIT_BYTES = 96 * 2**20
# query rows a score step carries (the largest that divides the chunk)
_SCORE_Q_TILES = (256, 128)
# rows a selection step carries: an int8 result tile is 32 sublanes
_SELECT_ROWS = 32


def pad_lanes(x):
    """[..., d] -> [..., 128]: zeros beside the values, a whole lane tile."""
    d = x.shape[-1]
    assert d <= _LANES
    if d == _LANES:
        return x
    return jnp.concatenate(
        [x, jnp.zeros(x.shape[:-1] + (_LANES - d,), x.dtype)], axis=-1)


def sortable_keys(scores):
    """float32 -> int32, monotone: a < b as floats iff key(a) < key(b) as
    signed integers (-0.0 counts as 0.0: both are replaced by +0.0 first — by
    a select, which no compiler folds away as it may `x + 0.0`)."""
    scores = scores.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0.0, 0.0, scores), jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


# ----------------------------------------------------------------------
# the `jax.numpy` twins
# ----------------------------------------------------------------------


def index_scores(qi, w, ik):
    """qi [B, C, Hi, d], w [B, C, Hi] float32, ik [B, S, d] -> I [B, C, S]
    float32: the products take the operands as they are (bfloat16 in the
    served model) and accumulate in float32."""
    s = jnp.einsum("bchd,bsd->bchs", qi, ik,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(s, 0.0) * w[..., None].astype(jnp.float32),
                   axis=2)


def select_topk(scores, limit, topk):
    """The exact selection: scores [..., S] float32, `limit` [...] (a row's
    valid positions are s < limit) -> bool [..., S], true at the `topk`
    largest valid scores, ties to the earlier position; at every valid
    position where limit <= topk. The kernel's algorithm in `jax.numpy`: the
    k-th largest key by 32 counting passes, then the tie rule by rank."""
    S = scores.shape[-1]
    valid = jnp.arange(S, dtype=jnp.int32) < limit[..., None]
    key = jnp.where(valid, sortable_keys(scores), INT_MIN)

    def bit(t, cu):
        cand = cu | jnp.left_shift(jnp.int32(1), 31 - t)
        n = jnp.sum(key >= (cand ^ INT_MIN)[..., None], axis=-1)
        return jnp.where(n >= topk, cand, cu)

    cu = jax.lax.fori_loop(0, 32, bit,
                           jnp.zeros(scores.shape[:-1], jnp.int32))
    tau = (cu ^ INT_MIN)[..., None]
    above, tie = key > tau, key == tau
    need = topk - jnp.sum(above, axis=-1, keepdims=True)
    return valid & (above | (tie & (jnp.cumsum(tie, axis=-1) <= need)))


# ----------------------------------------------------------------------
# the score walks
# ----------------------------------------------------------------------


def _chunk_scores_kernel(start_ref, bt_ref, q_ref, w_ref, k_ref, o_ref, *,
                         heads, block, last_block):
    # grid (B, C // tq, live blocks); q_ref [1, tq, heads * 128] (a head's
    # values in the first half of its lane tile), w_ref [1, tq, 128] float32
    # (head j's weight in lane j), k_ref [1, 1, block, 128] ONE logical block
    # of the row's index keys, o_ref [1, 1, tq, block] float32. Past the
    # tile's frontier the index maps re-serve the frontier's blocks and the
    # step leaves the result as the frontier's step made it.
    del bt_ref
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)
    tq = q_ref.shape[1]
    frontier = jnp.minimum((start_ref[b] + (qi + 1) * tq - 1) // block,
                           last_block)

    @pl.when(j <= frontier)
    def _score():
        k = k_ref[0, 0]
        w = w_ref[0]
        acc = jnp.zeros((tq, block), jnp.float32)
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[0, :, h * _LANES:(h + 1) * _LANES], k,
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w[:, h:h + 1]
        o_ref[0, 0] = acc


def paged_index_scores(qi, w, ik_pool, block_tables, start, interpret=None):
    """A chunk's index scores over the blocks under its frontier.

    qi [B, C, Hi, d] (d <= 128), w [B, C, Hi] float32, ik_pool [M, 1, block,
    128] WHOLE (the flat stack with the tables already offset), block_tables
    [B, nb], start [B] -> [B, nb, C, block] float32: I[t, s] of row c =
    `start + c` at [b, s // block, c, s % block] for every block up to the
    query tile's frontier; past it, nothing anybody reads."""
    if interpret is None:
        interpret = pallas_interpret()
    B, C, Hi, _ = qi.shape
    _, _, block, _ = ik_pool.shape
    nb = block_tables.shape[1]
    tq = next((t for t in _SCORE_Q_TILES if C % t == 0), C)
    start = start.astype(jnp.int32)
    live_blocks = jnp.minimum((jnp.max(start) + C - 1) // block + 1, nb)

    def frontier(b, qi_, start_ref):
        return jnp.minimum((start_ref[b] + (qi_ + 1) * tq - 1) // block,
                           nb - 1)

    def k_index(b, qi_, j, start_ref, bt_ref):
        return (bt_ref[b, jnp.minimum(j, frontier(b, qi_, start_ref))],
                0, 0, 0)

    def o_index(b, qi_, j, start_ref, bt_ref):
        return (b, jnp.minimum(j, frontier(b, qi_, start_ref)), qi_, 0)

    def q_index(b, qi_, j, start_ref, bt_ref):
        return (b, qi_, 0)

    return pl.pallas_call(
        functools.partial(_chunk_scores_kernel, heads=Hi, block=block,
                          last_block=nb - 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, C // tq, live_blocks),
            in_specs=[pl.BlockSpec((1, tq, Hi * _LANES), q_index),
                      pl.BlockSpec((1, tq, _LANES), q_index),
                      pl.BlockSpec((1, 1, block, _LANES), k_index)],
            out_specs=pl.BlockSpec((1, 1, tq, block), o_index),
        ),
        out_shape=jax.ShapeDtypeStruct((B, nb, C, block), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="dstpu_sparse_index_scores",
    )(start, block_tables.astype(jnp.int32),
      pad_lanes(qi).reshape(B, C, Hi * _LANES),
      pad_lanes(w.astype(jnp.float32)), ik_pool)


def _decode_scores_kernel(cnt_ref, slot_ref, blk_ref, bt_ref, q_ref, w_ref,
                          k_ref, o_ref):
    # grid (live pairs,); q_ref [1, heads, 128] the pair's slot's query
    # heads, w_ref [1, heads, 128] float32 lane-replicated, k_ref
    # [1, 1, block, 128], o_ref [1, 1, 1, block]
    del slot_ref, blk_ref, bt_ref

    # false only in the one step of a call with nothing live
    @pl.when(pl.program_id(0) < cnt_ref[0])
    def _score():
        s = jax.lax.dot_general(q_ref[0], k_ref[0, 0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * _widen(w_ref[0], s.shape[1])
        o_ref[0, 0] = jnp.sum(s, axis=0, keepdims=True)


def paged_index_scores_decode(qi, w, ik_pool, block_tables, pos, work=None,
                              interpret=None):
    """The slots' index scores over their live blocks: qi [B, Hi, d], w
    [B, Hi] float32, ik_pool [M, 1, block, 128] whole, block_tables [B, nb]
    (offset to the layer's blocks where `work` is given: it is the list of
    the UN-offset tables, `paged_decode_work`), pos [B] -> [B, nb, 1, block]
    float32, written at the live (slot, block) pairs only."""
    if interpret is None:
        interpret = pallas_interpret()
    B, Hi, _ = qi.shape
    _, _, block, _ = ik_pool.shape
    nb = block_tables.shape[1]
    if work is None:
        work = paged_decode_work(block_tables, pos, block)

    def slot_index(i, cnt_ref, slot_ref, blk_ref, bt_ref):
        return (slot_ref[i], 0, 0)

    return pl.pallas_call(
        _decode_scores_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(jnp.maximum(work.count[0], 1),),
            in_specs=[
                pl.BlockSpec((1, Hi, _LANES), slot_index),
                pl.BlockSpec((1, Hi, _LANES), slot_index),
                pl.BlockSpec(
                    (1, 1, block, _LANES),
                    lambda i, cnt_ref, slot_ref, blk_ref, bt_ref:
                    (bt_ref[slot_ref[i], blk_ref[i]], 0, 0, 0))],
            out_specs=pl.BlockSpec(
                (1, 1, 1, block),
                lambda i, cnt_ref, slot_ref, blk_ref, bt_ref:
                (slot_ref[i], blk_ref[i], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, nb, 1, block), jnp.float32),
        interpret=interpret,
        name="dstpu_sparse_index_scores_decode",
    )(work.count, work.slot, work.block, block_tables.astype(jnp.int32),
      pad_lanes(qi),
      jnp.broadcast_to(w.astype(jnp.float32)[..., None], (B, Hi, _LANES)),
      ik_pool)


# ----------------------------------------------------------------------
# the selection
# ----------------------------------------------------------------------


def _select_kernel(live_ref, s_ref, lim_ref, o_ref, key_ref, *, topk, block,
                   pos_bits, bias):
    # grid (B, R // tr); s_ref [1, nb, tr, block] float32 the row tile's
    # scores, every block of the table (only `live_ref[b, i]` of them are
    # read); lim_ref [1, tr, 128] int32 lane-replicated: a row's valid
    # positions are s < lim; o_ref [1, nb, tr, block]; key_ref [nb, tr,
    # block] int32 scratch: the monotone keys, INT_MIN at invalid positions.
    n = live_ref[pl.program_id(0), pl.program_id(1)]
    tr = lim_ref.shape[1]
    lim = _widen(lim_ref[0], block)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tr, block), 1)

    def make_keys(j, carry):
        s = s_ref[0, j]
        bits = jax.lax.bitcast_convert_type(jnp.where(s == 0.0, 0.0, s),
                                            jnp.int32)     # -0.0 is 0.0
        key = bits ^ ((bits >> 31) & 0x7FFFFFFF)
        key_ref[j] = jnp.where(j * block + lane < lim, key, INT_MIN)
        return carry

    jax.lax.fori_loop(0, n, make_keys, 0)

    def count(hit):
        """hit(key [tr, block], first position) -> bool; the count a row,
        float32 (exact: a row has under 2**24 positions), lane-replicated
        [tr, 128]. A block's lane tiles are added as they are; ONE
        cross-lane sum a pass."""
        def body(j, c):
            one = jnp.where(hit(key_ref[j], j * block), 1.0, 0.0)
            for g in range(block // _LANES):
                c = c + one[:, g * _LANES:(g + 1) * _LANES]
            return c
        c = jax.lax.fori_loop(0, n, body,
                              jnp.zeros((tr, _LANES), jnp.float32))
        return jnp.broadcast_to(jnp.sum(c, axis=-1, keepdims=True),
                                (tr, _LANES))

    # the k-th largest key, most significant bit first, in the order of the
    # keys' bit patterns with the sign bit turned (`^ INT_MIN`)
    def key_bit(t, cu):
        cand = cu | jnp.left_shift(jnp.int32(1), 31 - t)
        at = _widen(cand ^ INT_MIN, block)
        return jnp.where(count(lambda key, _: key >= at) >= topk, cand, cu)

    cu = jax.lax.fori_loop(0, 32, key_bit,
                           jnp.zeros((tr, _LANES), jnp.int32))
    tau = _widen(cu ^ INT_MIN, block)
    need = topk - count(lambda key, _: key > tau)
    surplus = count(lambda key, _: key == tau) - need

    # the tie rule: the position of the `need`-th key equal to tau is the
    # largest p with fewer than `need` of them before it. Searched only where
    # some row of the tile HAS more keys at tau than it needs (with real
    # scores: hardly ever; a row of fewer than `topk` valid positions has
    # its invalid ones at tau = INT_MIN, and they are masked below anyway)
    def pos_bit(t, p):
        cand = p | jnp.left_shift(jnp.int32(1), pos_bits - 1 - t)
        at = _widen(cand, block)
        before = count(lambda key, first:
                       (key == tau) & (first + lane < at))
        return jnp.where(before < need, cand, p)

    everything = jnp.full((tr, _LANES), (1 << pos_bits) - 1, jnp.int32)
    p = _widen(jax.lax.cond(
        jnp.max(jnp.where(cu == 0, 0.0, surplus)) > 0.0,
        lambda: jax.lax.fori_loop(0, pos_bits, pos_bit,
                                  jnp.zeros((tr, _LANES), jnp.int32)),
        lambda: everything), block)

    def write(j, carry):
        key = key_ref[j]
        at = j * block + lane
        chosen = ((key > tau) | ((key == tau) & (at <= p))) & (at < lim)
        if bias:
            o_ref[0, j] = jnp.where(chosen, 0.0, NEG_INF)
        else:
            o_ref[0, j] = jnp.where(chosen, 1.0, 0.0).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n, write, 0)


def sparse_select(scores, limit, topk, bias=False, interpret=None):
    """The walk's mask from a call's index scores, block-major as the score
    walks leave them: scores [B, nb, R, block] float32, limit [B, R] int32 (a
    row's valid positions are s < limit) -> [B, nb, R, block], int8 1 at the
    selected positions and 0 elsewhere, or with `bias` float32 0 and NEG_INF
    — `select_topk`'s set, for every block up to the row tile's frontier."""
    if interpret is None:
        interpret = pallas_interpret()
    B, nb, R, block = scores.shape
    tr = _SELECT_ROWS if R % _SELECT_ROWS == 0 else R
    limit = limit.astype(jnp.int32)
    live = jnp.minimum(
        (jnp.max(limit.reshape(B, R // tr, tr), axis=-1) - 1) // block + 1,
        nb).astype(jnp.int32)
    tile = (1, nb, tr, block)
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, block=block,
                          pos_bits=max((nb * block - 1).bit_length(), 1),
                          bias=bias),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, R // tr),
            in_specs=[pl.BlockSpec(tile, lambda b, i, live_ref: (b, 0, i, 0)),
                      pl.BlockSpec((1, tr, _LANES),
                                   lambda b, i, live_ref: (b, i, 0))],
            out_specs=pl.BlockSpec(tile, lambda b, i, live_ref: (b, 0, i, 0)),
            scratch_shapes=[pltpu.VMEM((nb, tr, block), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct(
            scores.shape, jnp.float32 if bias else jnp.int8),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="dstpu_sparse_select",
    )(live, scores,
      jnp.broadcast_to(limit[..., None], (B, R, _LANES)))
