"""The chunked-prefill kernel over the paged pool
(`ops/pallas/prefill_attention.py::paged_prefill_attention`,
`dstpu_paged_prefill`) against the path it replaces — `dstpu_kv_pool_gather`
of the row's whole table and `models/gpt.py::_paged_attend` over it — and the
scheduler's count of what the walk attends.

On the CPU the kernel runs in the Pallas interpreter, at the served tile
widths (block 512, head 128) with few heads; what Mosaic makes of the real
shapes is held by `tests/test_steptrace.py` (compiled for a described v5e)
and `tests/test_tpu_kernels.py` (on the chip).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.engine import init_inference
from deepspeed_tpu.inference.kv_cache import TRASH_BLOCK
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt import (GPTConfig, _paged_attend,
                                      make_gpt_decode_model)
from deepspeed_tpu.ops import attention_dispatch as attn_dispatch
from deepspeed_tpu.ops.pallas.kv_pool import kv_pool_gather
from deepspeed_tpu.ops.pallas.prefill_attention import (
    _tiles, paged_prefill_attention, paged_prefill_live_blocks)

pytestmark = pytest.mark.serving

BLOCK, HD = 512, 128


def _case(starts, chunk, heads, nb=5, base=0, seed=3):
    """q, a pool of shuffled physical blocks (`base` blocks of another
    layer's in front of them: the flat `[L*N, ...]` form), tables that name
    the blocks under each row's frontier and the trash block past it."""
    Hkv, G = heads
    rng = np.random.default_rng(seed)
    B = len(starts)
    N = base + 1 + B * nb
    k = jnp.asarray(rng.normal(size=(N, Hkv, BLOCK, HD)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(N, Hkv, BLOCK, HD)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, chunk, Hkv * G, HD)), jnp.float32)
    tables = np.full((B, nb), TRASH_BLOCK, np.int32)
    physical = iter(rng.permutation(np.arange(1, 1 + B * nb)))
    for b, start in enumerate(starts):
        for j in range(paged_prefill_live_blocks(start, chunk, BLOCK, nb)):
            tables[b, j] = next(physical)
    return q, k, v, jnp.asarray(tables + base), jnp.asarray(starts, jnp.int32)


def _oracle(q, k, v, tables, start):
    B, C, H, hd = q.shape
    Hkv = k.shape[1]
    cfg = GPTConfig(vocab_size=64, n_layer=1, n_head=H, n_kv_head=Hkv,
                    d_model=H * hd, d_ff=64,
                    max_seq_len=tables.shape[1] * BLOCK)
    positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    return _paged_attend(q, kv_pool_gather(k, tables),
                         kv_pool_gather(v, tables), positions, cfg)


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# (chunk, start): Mistral's chunk is a whole block, OLMoE's half of one, so
# its second chunk starts inside a block
STARTS = {"first_chunk": 0, "second_chunk": 1, "many_blocks_in": 4}


@pytest.mark.parametrize("heads", [(2, 1), (1, 4)], ids=["mha", "gqa4"])
@pytest.mark.parametrize("chunk", [256, 512])
@pytest.mark.parametrize("where", sorted(STARTS))
def test_kernel_matches_gather_and_dense_attend(where, chunk, heads):
    start = STARTS[where] * chunk if where != "many_blocks_in" \
        else 3 * BLOCK + (BLOCK - chunk)
    args = _case([start], chunk, heads)
    _close(paged_prefill_attention(*args), _oracle(*args))


def test_rows_with_different_frontiers_share_a_call():
    """B > 1: the KV axis ends at the furthest frontier; a nearer row
    re-serves its frontier block and computes nothing past it."""
    args = _case([3 * BLOCK, 0, BLOCK + 256], 256, (1, 4))
    _close(paged_prefill_attention(*args), _oracle(*args))


def test_a_final_chunk_that_ends_mid_chunk():
    """The prompt ends 100 rows into its last chunk: the rows before the end
    neither see the padded rows' K/V nor depend on them."""
    q, k, v, tables, start = _case([BLOCK], 512, (1, 4))
    got = paged_prefill_attention(q, k, v, tables, start)
    _close(got, _oracle(q, k, v, tables, start))
    frontier = int(tables[0, 1])
    k2 = k.at[frontier, :, 100:].set(1e4)
    v2 = v.at[frontier, :, 100:].set(-1e4)
    again = paged_prefill_attention(q, k2, v2, tables, start)
    np.testing.assert_array_equal(np.asarray(got[:, :100]),
                                  np.asarray(again[:, :100]))


def test_table_entries_past_the_frontier_are_never_read():
    """A table wider than the frontier holds the trash block and stale ids
    there: whatever those blocks hold (NaN here), the result is the
    oracle's over a clean pool."""
    q, k, v, tables, start = _case([BLOCK], 512, (2, 1), nb=6)
    want = _oracle(q, k, v, tables, start)
    used = set(np.asarray(tables[0, :2]).tolist())
    dead = [n for n in range(k.shape[0]) if n not in used]
    k = k.at[jnp.asarray(dead)].set(jnp.nan)
    v = v.at[jnp.asarray(dead)].set(jnp.nan)
    tables = tables.at[0, 2:].set(jnp.asarray(
        [TRASH_BLOCK, dead[-1], dead[1], TRASH_BLOCK], jnp.int32))
    got = paged_prefill_attention(q, k, v, tables, start)
    assert np.isfinite(np.asarray(got)).all()
    _close(got, want)


def test_a_layer_of_the_flat_pool_is_addressed_by_its_offset():
    """`block_base` != 0: the tables arrive offset to the layer's blocks of
    the whole `[L*N, ...]` stack, which the call takes unsliced."""
    base = 11
    q, k, v, tables, start = _case([BLOCK + 256], 256, (2, 1), base=base)
    assert int(jnp.min(tables)) >= base
    _close(paged_prefill_attention(q, k, v, tables, start),
           _oracle(q, k[base:], v[base:], tables - base, start))


@pytest.mark.parametrize("shape,want", [
    # C, block, Hkv, G, hd, itemsize -> (query rows, keys, KV heads) a step
    ((512, 512, 8, 4, 128, 2), (512, 256, 2)),        # Mistral
    ((256, 512, 16, 1, 128, 2), (256, 256, 8)),       # OLMoE
    ((128, 128, 1, 32, 128, 4), (128, 128, 1)),       # MQA of 32, float32
    ((1024, 512, 4, 2, 256, 2), (512, 256, 4)),
], ids=["mistral", "olmoe", "mqa32_f32", "hd256"])
def test_tiles_come_from_the_shapes(shape, want):
    assert _tiles(*shape) == want


def test_the_scheduler_counts_the_blocks_each_chunk_walks(monkeypatch):
    """A 200-token prompt in chunks of 128 over blocks of 128, a table of 3:
    chunk 0 walks block 0, chunk 1 blocks 0-1 — `prefill_live_blocks` 1 then
    2 of `prefill_table_blocks` 3 and 3, a chunk a step — with the kernel run
    by the scheduler (the in-place form, steered on: the CPU's own rule
    declines it) and the tokens those of `generate`. Where the program built
    is the gather, both stay zero."""
    def serve(in_place):
        mesh_mod.clear_mesh()
        mesh_mod.init_mesh(MeshConfig(data=1, tensor=1, sequence=1, expert=1,
                                      pipe=1))
        if in_place:
            monkeypatch.setattr(
                attn_dispatch, "kv_pool_writer",
                lambda pool: attn_dispatch.KV_POOL_WRITE_KERNEL)
        cfg = GPTConfig(n_layer=2, n_head=2, n_kv_head=1, d_model=256,
                        d_ff=128, max_seq_len=384, vocab_size=256,
                        use_rotary=True, dtype=jnp.float32, remat=False)
        engine = init_inference(
            model=make_gpt_decode_model(cfg=cfg, name="tiny"), config={
                "dtype": "float32", "kv_cache_dtype": "float32",
                "greedy": True, "kv_block_size": 128, "max_out_tokens": 8})
        serving = engine.serving(max_slots=2, max_context=384,
                                 prefill_chunk=128)
        prompt = np.random.default_rng(2).integers(0, 256, (200,)) \
            .astype(np.int32)
        done = serving.run([Request(uid=0, tokens=prompt, max_new_tokens=4,
                                    stop_on_eos=False)])
        return serving, done[0].tokens, \
            np.asarray(engine.generate(prompt[None], max_new_tokens=4))[0]

    serving, tokens, want = serve(in_place=True)
    assert serving.stats()["attention_program"] == {
        "prefill_step": "paged_prefill_kernel", "decode_step": "paged_gather"}
    np.testing.assert_array_equal(np.asarray(tokens), want)
    chunks = [r for r in serving.steptrace.records() if r.prefill_chunks]
    assert [(r.prefill_live_blocks, r.prefill_table_blocks)
            for r in chunks] == [(1, 3), (2, 3)]
    assert all(r.prefill_live_blocks == r.prefill_table_blocks == 0
               for r in serving.steptrace.records() if not r.prefill_chunks)

    monkeypatch.undo()
    serving, tokens, want = serve(in_place=False)
    assert serving.stats()["attention_program"]["prefill_step"] \
        == "paged_gather"
    np.testing.assert_array_equal(np.asarray(tokens), want)
    assert all(r.prefill_live_blocks == r.prefill_table_blocks == 0
               for r in serving.steptrace.records())


# (head width, Hkv, G, window): the widths and group sizes the served models
# bring the chunk walk (OLMoE G 1, Mistral 4, K-EXAONE 8 and its window
# layers), and a head narrower than a lane tile
_COLUMN_FORM_CASES = {
    "hd64-mha": (64, 2, 1, None),
    "hd64-gqa8": (64, 1, 8, None),
    "hd128-mha": (128, 2, 1, None),
    "hd128-gqa4": (128, 2, 4, None),
    "hd128-gqa8": (128, 1, 8, None),
    "hd128-gqa8-window": (128, 1, 8, 128),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_COLUMN_FORM_CASES))
def test_the_chunk_walk_gives_the_column_forms_bits(case, dtype, monkeypatch):
    """The lane-replicated update (PR 44) against the frozen column form:
    the same bits out of `dstpu_paged_prefill`, over a walk with a tile
    under the chunk (no mask), diagonal tiles and — with a window — tiles
    its first rows no longer see."""
    from tests.softmax_oracle import assert_same_bits_as_the_column_form
    hd, Hkv, G, window = _COLUMN_FORM_CASES[case]
    block = chunk = 128
    rng = np.random.default_rng(17)
    k, v = (jnp.asarray(rng.normal(size=(5, Hkv, block, hd)), dtype)
            for _ in range(2))
    q = jnp.asarray(rng.normal(size=(2, chunk, Hkv * G, hd)), dtype)
    tables = jnp.asarray([[3, 1, 4], [2, 4, TRASH_BLOCK]], jnp.int32)
    start = jnp.asarray([block + 64, 32], jnp.int32)
    got = assert_same_bits_as_the_column_form(
        monkeypatch,
        lambda *a: paged_prefill_attention(*a, interpret=True, window=window),
        q, k, v, tables, start)
    assert got.shape == (2, chunk, Hkv * G * hd)
