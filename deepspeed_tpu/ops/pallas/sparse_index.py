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
- `sparse_select` (`dstpu_sparse_select`): the walk needs the SET, not the
  k-th score — any x with `count(key >= x) == topk` selects it — so the
  kernel finds such an x from a verified bracket, by COUNTING (no sort: what
  `lax.top_k` lowers to on this chip is a sort of the whole row). A row
  tile's keys (the float's bit pattern as a monotone int32) stay in VMEM; a
  SAMPLE of them (one key in `_sample_stride x lane tiles`, spread over every
  block) is searched bit by bit for two order statistics around the rank the
  k-th key would have in it; ONE sweep over the tile's keys counts both and
  proves `count(>= lo) >= topk > count(> hi)` a row — a row it refutes falls
  back to the key's whole range on the side the sweep proved — and the
  integer interval is bisected until every row's `lo` selects exactly `topk`
  keys or its interval is one key wide (then, only where a row has more keys
  AT the k-th than it needs, the tie rule by a second search over the
  position's bits). ~23 sweeps a tile of 64 rows for the 36 a tile of 32 of a
  search over the key's 32 bits (PR 61; `SELECT_COUNTERS` count them: a
  sweep's tail of cross-lane sums and exit test costs ~0.25 us whatever the
  rows that share it), and a tile no row of which
  has more than `topk` valid positions makes one. The kernel copies its own
  operands: only the blocks under the tile's frontier move (the scores in,
  the next tile's while this one searches; the result out), where a
  `BlockSpec` would move the whole table's slab a tile. The result is the
  walk's mask: int8 0 / 1 for a chunk, a float32 bias 0 / NEG_INF for the
  slots' rows — `select_topk`'s set, bit for bit.

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
# rows a selection step carries (the largest that divides the call's): an
# int8 result tile is 32 sublanes, and a sweep's cross-lane sums and exit
# test cost ~0.25 us whatever the rows that share them
_SELECT_ROWS = (64, 32)


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
    position where limit <= topk. The plain search in `jax.numpy` — the
    k-th largest key by 32 counting passes over the key's bits, then the tie
    rule by rank: the oracle the kernel's bracketed search is held to."""
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


# the sampled order statistic the bracket is built around: the sample's share
# is the largest power of two that leaves about this many of a row's `topk`
# best keys in it (a 32-bit search of the sample then costs 1/16 - 1/64 of a
# sweep a bit)
_SAMPLE_RANK = 64
# the bracket's two sample ranks stand this many standard deviations off the
# k-th key's (`_bracket_ranks`)
_BRACKET_SIGMAS = 6.0
# blocks a copy moves between HBM and the kernel's buffers
_COPY_BLOCKS = 8
# the sample's search stops at the bit where the bracket's ends are this many
# steps apart
_BRACKET_STEPS = 8


def _sample_stride(topk, groups):
    """Blocks a sample slot takes its 128 lanes from (a power of two): the
    sample's share of a row's keys is 1 / (stride * groups), `groups` the
    lane tiles of a block."""
    want = max(topk // (_SAMPLE_RANK * groups), 1)
    return max(min(1 << (want.bit_length() - 1), _LANES // groups), 1)


def _select_kernel(live_ref, reads_ref, s_hbm, row_ref, o_hbm, stat_ref,
                   key_ref, smp_ref, s_ref, o_ref, sems, *, topk, block,
                   pos_bits, bias, stride):
    # grid (B, R // tr), in order; s_hbm [B, nb, R, block] float32 and o_hbm
    # (the result, as wide) stay in HBM: the kernel copies a row tile's slab
    # of the `live_ref[b, i]` blocks under its frontier and of no other — in
    # through s_ref [nb, tr, block], the NEXT tile's while this one searches
    # (`reads_ref[b, i]` blocks: none for a tile that reads no score), out of
    # o_ref [nb, tr, block] while the next one searches. row_ref [1, tr, 128]
    # int32: a row's facts in its first lanes — its valid positions are s <
    # lane 0, lanes 1 and 2 the bracket's two sample ranks
    # (`_bracket_ranks`); stat_ref int32 SMEM [2 * tiles]: the tile's
    # (sweeps, bracket refuted); key_ref [nb + 1, tr, block] int32 scratch:
    # the monotone keys, INT_MIN at invalid positions; smp_ref [cdiv(nb,
    # stride) + 1, tr, 128] int32 scratch: the sample, lane l of slot i the
    # key of block `i * stride + (l % span) // groups`, lane tile `l %
    # groups`, lane l (span = stride * groups): a sample spread over every
    # block, a vector register a slot and eight rows.
    b, i = pl.program_id(0), pl.program_id(1)
    tiles = pl.num_programs(1)
    tile = b * tiles + i
    n = live_ref[b, i]
    tr = row_ref.shape[1]

    # a copy moves a row tile's slab of `_COPY_BLOCKS` blocks (a descriptor
    # costs ~30 scalar bundles to issue): the last one of a tile starts
    # where it still ends inside the table, so a tile moves up to that many
    # blocks past its frontier — scores nobody reads, a result nobody reads
    nb = s_ref.shape[0]
    step = min(_COPY_BLOCKS, nb)

    def each_copy(blocks, tile_b, tile_i, copy, act):
        """`act` ("start" | "wait") on each `copy(b, i, first block)` that
        moves the first `blocks` blocks of tile (tile_b, tile_i)."""
        def body(c, carry):
            getattr(copy(tile_b, tile_i, jnp.minimum(c * step, nb - step)),
                    act)()
            return carry
        jax.lax.fori_loop(0, (blocks + step - 1) // step, body, 0)

    def slab(hbm, b, i, j):
        return hbm.at[b, pl.ds(j, step), pl.ds(i * tr, tr), :]

    def fetch(b, i, j):         # the tile's scores -> s_ref
        return pltpu.make_async_copy(
            slab(s_hbm, b, i, j), s_ref.at[pl.ds(j, step)], sems.at[0])

    def hand_back(b, i, j):     # o_ref -> the tile's result
        return pltpu.make_async_copy(
            o_ref.at[pl.ds(j, step)], slab(o_hbm, b, i, j), sems.at[1])

    def neighbour(d):           # the tile d = -1 | +1 steps from this one
        wraps = i + d == (tiles if d > 0 else -1)
        return (jnp.where(wraps, b + d, b),
                jnp.where(wraps, 0 if d > 0 else tiles - 1, i + d))

    last_tile = pl.num_programs(0) * tiles - 1

    @pl.when(tile == 0)
    def _first():
        each_copy(reads_ref[0, 0], 0, 0, fetch, "start")

    def result_is_free():
        @pl.when(tile > 0)
        def _():    # the tile before has handed its result back
            before = neighbour(-1)
            each_copy(live_ref[before], *before, hand_back, "wait")

    def result_goes():
        each_copy(n, b, i, hand_back, "start")

        @pl.when(tile == last_tile)
        def _():
            each_copy(n, b, i, hand_back, "wait")

    def next_scores():
        @pl.when(tile < last_tile)
        def _():
            after = neighbour(1)
            each_copy(reads_ref[after], *after, fetch, "start")

    groups = block // _LANES

    def fact(c):            # a row's c-th fact, lane-replicated
        return jnp.broadcast_to(row_ref[0, :, c:c + 1], (tr, _LANES))

    lim = fact(0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tr, _LANES), 1)
    # a row of `topk` valid positions or fewer keeps every one of them
    searching = lim > topk

    def lanes(x, g):
        return x[:, g * _LANES:(g + 1) * _LANES]

    def count(ref, slots, width, *hits):
        """hits: (key [tr, 128], first position) -> bool, each counted over
        the first `slots` entries of `ref` [., tr, width] in ONE sweep ->
        a count a hit, float32 (exact: a row has under 2**24 positions),
        lane-replicated [tr, 128]. A block's lane tiles are summed as a tree
        before the one add into the accumulator, two blocks a turn."""
        def block_of(j, acc):
            key = ref[j]
            out = []
            for hit, c in zip(hits, acc):
                one = [jnp.where(hit(lanes(key, g), j * width + g * _LANES),
                                 1.0, 0.0) for g in range(width // _LANES)]
                while len(one) > 1:
                    one = [a + b for a, b in zip(one[::2], one[1::2])] \
                        + one[len(one) & ~1:]
                out.append(c + one[0])
            return tuple(out)

        zero = tuple(jnp.zeros((tr, _LANES), jnp.float32) for _ in hits)
        # (an odd count's last turn reads the entry past it: INT_MIN)
        acc = jax.lax.fori_loop(
            0, (slots + 1) // 2,
            lambda i, acc: block_of(2 * i + 1, block_of(2 * i, acc)), zero)
        return [jnp.broadcast_to(jnp.sum(c, axis=-1, keepdims=True),
                                 (tr, _LANES)) for c in acc]

    def at_least(x):
        return lambda key, _: key >= x

    def any_row(flag):
        # (a float32 reduction: an int32 one is two of them on this chip)
        return jnp.max(jnp.where(flag, 1.0, 0.0)) > 0.0

    def write(chosen_at):
        """chosen_at(block j, lane tile g, positions [tr, 128]) -> bool: the
        tile's result, a block after another, and on its way out."""
        def body(j, carry):
            for g in range(groups):
                at = j * block + g * _LANES + lane
                chosen = chosen_at(j, g, at) & (at < lim)
                where = (j, slice(None), pl.ds(g * _LANES, _LANES))
                if bias:
                    o_ref[where] = jnp.where(chosen, 0.0, NEG_INF)
                else:
                    o_ref[where] = jnp.where(chosen, 1.0, 0.0).astype(
                        o_ref.dtype)
            return carry
        result_is_free()
        jax.lax.fori_loop(0, n, body, 0)
        result_goes()

    lowest = jnp.full((tr, _LANES), INT_MIN, jnp.int32)
    everything = jnp.full((tr, _LANES), (1 << pos_bits) - 1, jnp.int32)
    any_searching = reads_ref[b, i] > 0

    @pl.when(jnp.logical_not(any_searching))
    def _all_valid():
        # every position the keys would call valid is kept: no score is
        # read, no key made (tau is under every key and no tie is cut)
        next_scores()
        write(lambda j, g, at: at >= 0)
        stat_ref[2 * tile] = 1
        stat_ref[2 * tile + 1] = 0

    @pl.when(any_searching)
    def _search():
        span = stride * groups
        slots = (n + stride - 1) // stride
        source = lane % span

        def clear(i, carry):
            smp_ref[i] = lowest
            return carry
        jax.lax.fori_loop(0, slots + 1, clear, 0)

        each_copy(n, b, i, fetch, "wait")

        def make_keys(j, carry):
            s = s_ref[j]
            bits = jax.lax.bitcast_convert_type(jnp.where(s == 0.0, 0.0, s),
                                                jnp.int32)     # -0.0 is 0.0
            key = bits ^ ((bits >> 31) & 0x7FFFFFFF)
            sample = smp_ref[j // stride]
            for g in range(groups):
                k = jnp.where(j * block + g * _LANES + lane < lim,
                              lanes(key, g), INT_MIN)
                key_ref[j, :, pl.ds(g * _LANES, _LANES)] = k
                sample = jnp.where(source == (j % stride) * groups + g, k,
                                   sample)
            smp_ref[j // stride] = sample
            return carry
        jax.lax.fori_loop(0, n, make_keys, 0)
        # (the entry behind the last is no row's key: a sweep goes two
        # entries a turn; the sample's was cleared above)
        key_ref[n] = jnp.full(key_ref.shape[1:], INT_MIN, jnp.int32)
        next_scores()

        # the bracket: two order statistics of the sample, around the rank
        # the k-th key would have in it
        rank_lo = fact(1).astype(jnp.float32)
        rank_hi = fact(2).astype(jnp.float32)      # a LARGER key: `hi`

        def sample_bit(t, cu):
            bit = jnp.left_shift(jnp.int32(1), 31 - t)
            cand = [c | bit for c in cu]
            got = count(smp_ref, slots, _LANES,
                        *(at_least(c ^ INT_MIN) for c in cand))
            return tuple(jnp.where(g >= r, c, u) for g, r, c, u
                         in zip(got, (rank_lo, rank_hi), cand, cu))

        # the two statistics' leading bits, four at a time, until they stand
        # `_BRACKET_STEPS` steps of the bits left apart in every row (the
        # bits below then widen the bracket by under a quarter; rows whose
        # sample ties at both ranks run all 32 and end on the tie)
        def coarse(state):
            t, u_lo, u_hi = state
            apart = jax.lax.shift_right_logical(u_hi - u_lo, 32 - t)
            return (t < 32) & any_row(searching & (apart < _BRACKET_STEPS))

        def four_more(state):
            t, u_lo, u_hi = state
            return (t + 4,) + jax.lax.fori_loop(t, t + 4, sample_bit,
                                                (u_lo, u_hi))

        zero = jnp.zeros((tr, _LANES), jnp.int32)
        bits, u_lo, u_hi = jax.lax.while_loop(
            coarse, four_more, (jnp.int32(8),) + jax.lax.fori_loop(
                0, 8, sample_bit, (zero, zero)))
        below = jax.lax.shift_right_logical(jnp.int32(-1), bits)
        below = jnp.where(bits == 32, 0, below)
        s_lo = u_lo ^ INT_MIN
        s_hi = jnp.where(rank_hi < 1.0, jnp.int32(2**31 - 1),
                         (u_hi | below) ^ INT_MIN)

        # ONE sweep proves the bracket a row: count(key >= lo) >= topk >
        # count(key > hi) from here on (where many keys tie AT the k-th the
        # two ends meet on it). A row it refutes keeps what the sweep did
        # prove: the k-th key lies on the bracket's other side
        c_lo, c_hi = count(key_ref, n, block, at_least(s_lo),
                           lambda key, _: key > s_hi)
        under, over = c_hi >= topk, c_lo < topk
        lo = jnp.where(under, s_hi + 1, jnp.where(over, INT_MIN, s_lo))
        hi = jnp.where(under, jnp.int32(2**31 - 1),
                       jnp.where(over, s_lo - 1, s_hi))
        c_at = jnp.where(under, c_hi, jnp.where(
            over, (n * block).astype(jnp.float32), c_lo))
        refuted = any_row(searching & (under | over))

        # bisect the integer interval; a row is decided when `lo` selects
        # exactly `topk` keys (no tie can matter) or the interval is one key
        def open_rows(lo, hi, c_at):
            return searching & (lo < hi) & (c_at != topk)

        def middle(a, b):       # the upper middle of [a, b], a where a == b
            return (a | b) - ((a ^ b) >> 1)

        def narrow(state):
            lo, hi, c_at, sweeps = state
            is_open = open_rows(lo, hi, c_at)
            mid = middle(lo, hi)
            c_mid, = count(key_ref, n, block, at_least(mid))
            up = is_open & (c_mid >= topk)
            return (jnp.where(up, mid, lo),
                    jnp.where(is_open & ~up, mid - 1, hi),
                    jnp.where(up, c_mid, c_at), sweeps + 1)

        lo, hi, c_at, sweeps = jax.lax.while_loop(
            lambda s: any_row(open_rows(*s[:3])),
            narrow, (lo, hi, c_at, jnp.int32(0)))

        # `lo` is the k-th key now, or above every key the set leaves out
        tau = jnp.where(searching, lo, INT_MIN)
        # the tie rule, where some row has more keys at tau than it needs
        # (with real scores: hardly ever): the position of the `need`-th key
        # equal to tau is the largest p with fewer than `need` before it
        tied = searching & (c_at > topk)

        def tie_rule():
            need = topk - count(key_ref, n, block,
                                lambda key, _: key > tau)[0]

            def pos_bit(t, p):
                cand = p | jnp.left_shift(jnp.int32(1), pos_bits - 1 - t)
                before, = count(
                    key_ref, n, block, lambda key, first:
                    (key == tau) & (first + lane < cand))
                return jnp.where(before < need, cand, p)

            p = jax.lax.fori_loop(0, pos_bits, pos_bit, zero)
            return jnp.where(tied, p, everything), jnp.int32(1 + pos_bits)

        p, tie_sweeps = jax.lax.cond(
            any_row(tied), tie_rule,
            lambda: (everything, jnp.int32(0)))

        def chosen_at(j, g, at):
            key = key_ref[j, :, pl.ds(g * _LANES, _LANES)]
            return (key > tau) | ((key == tau) & (at <= p))
        write(chosen_at)
        # the keys, the proof, the write; the sample's bits at its share
        stat_ref[2 * tile] = sweeps + tie_sweeps + 3 \
            + (2 * bits + span - 1) // span
        stat_ref[2 * tile + 1] = refuted.astype(jnp.int32)


SELECT_COUNTERS = ("sparse_select_tiles", "sparse_select_sweeps",
                   "sparse_select_fallback_tiles")


def _bracket_ranks(limit, topk, span):
    """limit [...] int32 -> (rank_lo, rank_hi) int32: the two ranks in a
    row's sample (one key in `span`: limit / span = M keys of it) whose keys
    bracket the row's k-th largest. The sample holds X of the row's `topk`
    best keys, hypergeometric around k = topk / span, and the r-th largest
    sampled key is one of them iff X >= r; asin(sqrt(X / M)) is normal with
    deviation 1 / (2 sqrt(M)) (times the finite population's
    sqrt(1 - 1 / span)) whether a row keeps a ninth of its positions or
    nearly all, so the two ranks stand `_BRACKET_SIGMAS` of those either side
    of k, one rank of slack: at 6 a row in ~10**9 is refuted (the exact
    tails, 1e-9 to 3e-9 at contexts of 2.1k to 66k), with scores in any order
    that does not know the sample's layout."""
    m = jnp.maximum(limit, 1).astype(jnp.float32) / span
    centre = jnp.arcsin(jnp.sqrt(jnp.minimum(topk / span / m, 1.0)))
    half = _BRACKET_SIGMAS * 0.5 * (1.0 - 1.0 / span) ** 0.5 / jnp.sqrt(m)
    rank_hi = m * jnp.sin(jnp.maximum(centre - half, 0.0)) ** 2
    rank_lo = m * jnp.sin(jnp.minimum(centre + half, jnp.pi / 2)) ** 2
    return (jnp.ceil(rank_lo).astype(jnp.int32) + 1,
            jnp.floor(rank_hi).astype(jnp.int32) - 1)


def select_rows(rows):
    """Rows of a call's `rows` that one step of the selection carries."""
    return next((t for t in _SELECT_ROWS if rows % t == 0), rows)


def sparse_select(scores, limit, topk, bias=False, interpret=None):
    """The walk's mask from a call's index scores, block-major as the score
    walks leave them: scores [B, nb, R, block] float32, limit [B, R] int32 (a
    row's valid positions are s < limit) -> ([B, nb, R, block], int8 1 at the
    selected positions and 0 elsewhere, or with `bias` float32 0 and NEG_INF
    — `select_topk`'s set, for every block up to the row tile's frontier;
    int32 [3] in `SELECT_COUNTERS` order: the call's row tiles, the sweeps
    over a tile's keys they made, the tiles with a row whose bracket the
    proving sweep refuted)."""
    if interpret is None:
        interpret = pallas_interpret()
    B, nb, R, block = scores.shape
    tr = select_rows(R)
    tiles = R // tr
    limit = limit.astype(jnp.int32)
    live = jnp.minimum(
        (jnp.max(limit.reshape(B, tiles, tr), axis=-1) - 1) // block + 1,
        nb).astype(jnp.int32)
    # the blocks whose scores a tile reads: none where no row has more
    # valid positions than `topk`
    reads = jnp.where(jnp.max(limit.reshape(B, tiles, tr), axis=-1) > topk,
                      live, 0)
    kind = jnp.float32 if bias else jnp.int8
    stride = _sample_stride(topk, block // _LANES)
    chosen, stats = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, block=block,
                          pos_bits=max((nb * block - 1).bit_length(), 1),
                          bias=bias, stride=stride),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, tiles),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((1, tr, _LANES),
                                   lambda b, i, live_ref, reads_ref:
                                   (b, i, 0))],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.VMEM((nb + 1, tr, block), jnp.int32),
                            pltpu.VMEM((pl.cdiv(nb, stride) + 1, tr, _LANES),
                                       jnp.int32),
                            pltpu.VMEM((nb, tr, block), jnp.float32),
                            pltpu.VMEM((nb, tr, block), kind),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct(scores.shape, kind),
                   jax.ShapeDtypeStruct((2 * B * tiles,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="dstpu_sparse_select",
    )(live, reads, scores, pad_lanes(jnp.stack(
        [limit, *_bracket_ranks(limit, topk, stride * (block // _LANES))],
        axis=-1)))
    stats = jnp.sum(stats.reshape(B * tiles, 2), axis=0)
    return chosen, jnp.concatenate(
        [jnp.full((1,), B * tiles, jnp.int32), stats])
