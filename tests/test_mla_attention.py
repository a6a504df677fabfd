"""The absorbed latent-attention walks (`ops/pallas/mla_attention.py`) in the
Pallas interpreter against their dense `jax.numpy` twin: the decode walk
`dstpu_mla_decode` over the live (slot, block) pairs and the chunk's walk
`dstpu_mla_prefill` under its frontier, on a pool of one leaf whose values
are a slice of its keys. Sizes are tiny because the interpreter walks every
grid step; what Mosaic makes of the served shapes is
`tests/test_steptrace.py`'s (compiled for a described v5e)."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import mla_attention as ma
from deepspeed_tpu.ops.pallas.decode_attention import paged_decode_work

pytestmark = pytest.mark.serving

H, RANK, ROPE, BLOCK = 4, 128, 64, 128
WIDTH = ma.latent_entry_width(RANK, ROPE)
SCALE = 0.1


def _pool(blocks, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    pool = rng.normal(size=(blocks, 1, BLOCK, WIDTH))
    pool[..., RANK + ROPE:] = 0         # the stored width's tail
    return jnp.asarray(pool, dtype)


def _queries(shape, seed=1, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


def test_an_entry_is_stored_in_whole_lane_tiles():
    assert ma.latent_entry_width(512, 64) == 640
    assert WIDTH == 256 and ma.latent_entry_width(32, 8) == 128


# name -> (tables, positions): a dead slot (all trash block), a partly
# filled last block, a table longer than the context, a full last block
DECODE_CASES = {
    "dead_slot_and_partial_block": ([[3, 5, 0], [0, 0, 0], [7, 2, 4]],
                                    [130, 0, 300]),
    "table_longer_than_the_context": ([[6, 0, 0, 0], [1, 2, 8, 8]],
                                      [5, 200]),
    "last_position_of_a_block": ([[4, 3], [2, 1]], [127, 255]),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_walk_is_the_dense_absorbed_attention(case):
    tables, pos = (jnp.asarray(x, jnp.int32) for x in DECODE_CASES[case])
    pool = _pool(9)
    q = _queries((tables.shape[0], H, WIDTH))
    got = ma.mla_decode_attention(q, pool, tables, pos, RANK, SCALE,
                                  interpret=True)
    want = ma.mla_attend_gathered(
        q[:, None], ma.gather_latent(pool, tables), pos[:, None], RANK,
        SCALE).reshape(-1, H, RANK)
    live = np.asarray((tables != 0).any(axis=1))
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(got)[~live].any()     # a dead row comes back zero


def test_decode_walk_takes_a_work_list_built_from_unoffset_tables():
    """The layer scan builds the list once a token from the tables as the
    scheduler has them and hands each layer tables offset to its blocks."""
    tables = jnp.asarray([[1, 2], [0, 0], [3, 0]], jnp.int32)
    pos = jnp.asarray([140, 0, 17], jnp.int32)
    pool = _pool(8, seed=3)
    q = _queries((3, H, WIDTH), seed=4)
    work = paged_decode_work(tables, pos, BLOCK)
    base = 4                                    # "layer 1" of two, 4 blocks
    got = ma.mla_decode_attention(q, pool, tables + base, pos, RANK, SCALE,
                                  interpret=True, work=work)
    want = ma.mla_attend_gathered(
        q[:, None], ma.gather_latent(pool, tables + base), pos[:, None],
        RANK, SCALE).reshape(3, H, RANK)
    np.testing.assert_allclose(np.asarray(got)[[0, 2]],
                               np.asarray(want)[[0, 2]], rtol=2e-5, atol=2e-5)
    assert not np.asarray(got)[1].any()


@pytest.mark.parametrize("start", [0, 128, 200],
                         ids=["from-0", "block-boundary", "mid-block"])
def test_chunk_walk_is_the_dense_absorbed_attention(start):
    chunk = 128
    tables = jnp.asarray([[7, 2, 4]], jnp.int32)
    pool = _pool(9, seed=5)
    q = _queries((1, chunk, H, WIDTH), seed=6)
    got = ma.mla_prefill_attention(q, pool, tables,
                                   jnp.asarray([start], jnp.int32), RANK,
                                   SCALE, interpret=True)
    want = ma.mla_attend_gathered(
        q, ma.gather_latent(pool, tables),
        start + jnp.arange(chunk, dtype=jnp.int32)[None], RANK, SCALE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_chunk_walk_splits_the_heads_over_the_grid():
    """Six heads, four a step at most: the walk takes the largest divisor
    (three), two head groups, each re-reading the block."""
    heads, chunk = 6, 128
    tables = jnp.asarray([[1, 2]], jnp.int32)
    pool = _pool(3, seed=7)
    q = _queries((1, chunk, heads, WIDTH), seed=8)
    got = ma.mla_prefill_attention(q, pool, tables,
                                   jnp.asarray([64], jnp.int32), RANK, SCALE,
                                   interpret=True)
    want = ma.mla_attend_gathered(
        q, ma.gather_latent(pool, tables),
        64 + jnp.arange(chunk, dtype=jnp.int32)[None], RANK, SCALE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_bfloat16_walks_stay_in_the_dense_twins_band():
    tables = jnp.asarray([[3, 5, 0], [7, 2, 4]], jnp.int32)
    pos = jnp.asarray([130, 300], jnp.int32)
    pool = _pool(9, dtype=jnp.bfloat16)
    q = _queries((2, H, WIDTH), dtype=jnp.bfloat16)
    got = ma.mla_decode_attention(q, pool, tables, pos, RANK, SCALE,
                                  interpret=True)
    want = ma.mla_attend_gathered(
        q[:, None].astype(jnp.float32),
        ma.gather_latent(pool, tables).astype(jnp.float32), pos[:, None],
        RANK, SCALE).reshape(2, H, RANK)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=0.05, atol=0.02)


# (width, rank, rope, heads): the file's small entry, and GLM-4.7-Flash's
# served one — twenty query rows a slot against 640-wide keys whose first
# 512 columns are the values (scores and accumulator both over a lane tile)
_COLUMN_FORM_ENTRIES = {"small": (RANK, ROPE, H), "glm": (512, 64, 20)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("walk", ["decode", "chunk"])
@pytest.mark.parametrize("entry", sorted(_COLUMN_FORM_ENTRIES))
def test_the_latent_walks_give_the_column_forms_bits(entry, walk, dtype,
                                                     monkeypatch):
    """The lane-replicated update (PR 44) against the frozen column form:
    the same bits out of `dstpu_mla_decode` and `dstpu_mla_prefill`."""
    from tests.softmax_oracle import assert_same_bits_as_the_column_form
    rank, rope, heads = _COLUMN_FORM_ENTRIES[entry]
    width = ma.latent_entry_width(rank, rope)
    rng = np.random.default_rng(23)
    pool = rng.normal(size=(6, 1, BLOCK, width))
    pool[..., rank + rope:] = 0
    pool = jnp.asarray(pool, dtype)
    if walk == "decode":
        tables = jnp.asarray([[3, 5, 0], [0, 0, 0], [1, 2, 4]], jnp.int32)
        pos = jnp.asarray([130, 0, 300], jnp.int32)
        q = jnp.asarray(rng.normal(size=(3, heads, width)), dtype)
        got = assert_same_bits_as_the_column_form(
            monkeypatch,
            lambda *a: ma.mla_decode_attention(*a, rank, SCALE,
                                               interpret=True),
            q, pool, tables, pos)
        assert got.shape == (3, heads, rank)
    else:
        tables = jnp.asarray([[3, 5, 2]], jnp.int32)
        start = jnp.asarray([200], jnp.int32)
        q = jnp.asarray(rng.normal(size=(1, 128, heads, width)), dtype)
        got = assert_same_bits_as_the_column_form(
            monkeypatch,
            lambda *a: ma.mla_prefill_attention(*a, rank, SCALE,
                                                interpret=True),
            q, pool, tables, start)
        assert got.shape == (1, 128, heads * rank)
