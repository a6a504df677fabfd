"""The in-place paged-pool writer and the block gather
(`ops/pallas/kv_pool.py`), the carried-pool form of the GPT paged programs
that uses them, and the rule that
chooses between it and the XLA scatter
(`ops/attention_dispatch.py::kv_pool_writer`).

On the CPU the rule declines, so these tests steer it themselves and the
kernel runs in the Pallas interpreter; sizes are tiny because the
interpreter walks every grid step. What Mosaic and XLA make of the real
shapes is held by `tests/test_steptrace.py` (compiled for a described v5e).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.engine import init_inference
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model
from deepspeed_tpu.ops import attention_dispatch as attn_dispatch
from deepspeed_tpu.ops.pallas.kv_pool import (kv_pool_gather, kv_pool_write,
                                              kv_pool_write_reference)
from deepspeed_tpu.platform import device

pytestmark = pytest.mark.serving

BLOCK = 32          # two bfloat16 tiles, four float32 tiles

# name -> (starts, tables): row b writes positions starts[b]..+C-1 through
# tables[b]; a row whose table is all block 0 is an inactive slot
WRITES = {
    # C = 1: two live rows in different blocks, three colliding in the trash
    "decode_with_trash": (1, [5, 33, 0, 0, 0],
                          [[1, 2], [3, 4], [0, 0], [0, 0], [0, 0]]),
    "chunk_inside_one_tile": (5, [18], [[1, 2]]),
    "chunk_off_a_tile_boundary": (20, [5], [[3, 2]]),
    "chunk_across_two_blocks": (24, [20, 3], [[1, 2], [5, 4]]),
    # a block generator's fused forward: two blocks of 4 rows a slot as one
    # run, which straddles a pool block where the first is the block's last
    "two_row_blocks_across_a_pool_block": (8, [28, 12, 0],
                                           [[1, 2], [3, 4], [0, 0]]),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(WRITES))
def test_writer_matches_the_scatter_bit_for_bit(case, dtype):
    C, starts, tables = WRITES[case]
    rng = np.random.default_rng(len(case))
    pool = jnp.asarray(rng.standard_normal((6, 2, BLOCK, 128)), dtype)
    rows = jnp.asarray(rng.standard_normal((len(starts), C, 2, 128)), dtype)
    start = jnp.asarray(starts, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    want = np.asarray(kv_pool_write_reference(pool, rows, start, tables),
                      np.float32)
    got = np.array(jax.jit(kv_pool_write, donate_argnums=(0,))(
        pool, rows, start, tables), np.float32)
    # where inactive slots collide (block 0, position 0) either writer may
    # keep any one of them; everything else is the scatter's, exactly
    trash = got[0, :, 0].copy()
    got[0, :, 0] = want[0, :, 0]
    np.testing.assert_array_equal(got, want)
    inactive = [b for b, t in enumerate(np.asarray(tables)) if not t.any()]
    if inactive:
        assert any(np.array_equal(trash, np.asarray(rows[b, 0], np.float32))
                   for b in inactive)


def test_writer_refuses_a_pool_it_cannot_tile():
    with pytest.raises(ValueError, match="whole"):
        kv_pool_write(jnp.zeros((4, 2, BLOCK, 64), jnp.bfloat16),
                      jnp.zeros((1, 1, 2, 64), jnp.bfloat16),
                      jnp.zeros((1,), jnp.int32), jnp.zeros((1, 2), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_gather_kernel_equals_the_xla_gather(dtype, monkeypatch):
    from deepspeed_tpu.inference.kv_cache import gather_block_kv
    from deepspeed_tpu.ops.pallas import kv_pool
    pool = jnp.asarray(np.random.default_rng(1).standard_normal(
        (6, 4, BLOCK, 128)), dtype)
    tables = jnp.asarray([[5, 1, 3], [0, 0, 0], [2, 2, 4]], jnp.int32)
    want, _ = gather_block_kv(pool, pool, tables)
    np.testing.assert_array_equal(
        np.asarray(kv_pool_gather(pool, tables), np.float32),
        np.asarray(want, np.float32))
    # a block of all heads too large for VMEM: the heads are split
    monkeypatch.setattr(kv_pool, "_GATHER_BLOCK_BYTES",
                        2 * BLOCK * 128 * pool.dtype.itemsize)
    np.testing.assert_array_equal(
        np.asarray(kv_pool_gather(pool, tables), np.float32),
        np.asarray(want, np.float32))


# ----------------------------------------------------------------------
# the rule
# ----------------------------------------------------------------------


def _pool(dtype=jnp.bfloat16, block=512, hd=128, **extra):
    shape = (2, 4, 8, block, hd)
    leaf = jax.ShapeDtypeStruct(shape, dtype)
    return {"k": leaf, "v": leaf, **extra}


def test_rule_reads_platform_dtype_shape_and_mesh(monkeypatch):
    mesh_mod.clear_mesh()
    kernel, scatter = (attn_dispatch.KV_POOL_WRITE_KERNEL,
                       attn_dispatch.KV_POOL_WRITE_SCATTER)
    assert attn_dispatch.kv_pool_writer(_pool()) == scatter        # the CPU
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    assert attn_dispatch.kv_pool_writer(_pool()) == kernel
    assert attn_dispatch.kv_pool_writer(_pool(jnp.float32, 16)) == kernel
    scales = jax.ShapeDtypeStruct((2, 4, 8, 512, 1), jnp.float32)
    assert attn_dispatch.kv_pool_writer(
        _pool(jnp.int8, k_scale=scales, v_scale=scales)) == scatter
    assert attn_dispatch.kv_pool_writer(_pool(hd=64)) == scatter   # GPT-2
    assert attn_dispatch.kv_pool_writer(_pool(block=8)) == scatter
    # a bare Mosaic call cannot sit in a program partitioned over a mesh
    mesh_mod.init_mesh(MeshConfig(data=2, tensor=1, sequence=1, expert=1,
                                  pipe=1))
    try:
        assert attn_dispatch.kv_pool_writer(_pool()) == scatter
    finally:
        mesh_mod.clear_mesh()


# ----------------------------------------------------------------------
# the carried, flat pool against today's xs/ys scan
# ----------------------------------------------------------------------


def _paged_run(in_place, monkeypatch, dtype, layer_types):
    """A prefill chunk (off a tile boundary, across two blocks), three
    decode steps with one inactive slot, and a verify chunk; returns every
    program's logits, the final pool and the writers the spec recorded."""
    if in_place:
        monkeypatch.setattr(attn_dispatch, "kv_pool_writer",
                            lambda pool: attn_dispatch.KV_POOL_WRITE_KERNEL)
    cfg = GPTConfig(vocab_size=64, n_layer=2, n_head=2, n_kv_head=1,
                    d_model=256, d_ff=128, max_seq_len=128, use_rotary=True,
                    dtype=dtype, remat=False,
                    sliding_window=24 if layer_types else None,
                    attn_layer_types=layer_types)
    spec = make_gpt_decode_model(cfg, name="tiny", seed=0)
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), spec.params)
    pool = spec.init_paged_pool(6, BLOCK, dtype)
    tables = jnp.asarray([[2, 4], [0, 0]], jnp.int32)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (1, 24)),
                       jnp.int32)
    logits, pool = jax.jit(spec.prefill_paged_fn)(
        params, toks, jnp.asarray([20], jnp.int32),
        jnp.asarray([23], jnp.int32), pool, tables[:1])
    outs = [logits]
    tok = jnp.asarray([3, 5], jnp.int32)
    pos = jnp.asarray([44, 0], jnp.int32)
    decode = jax.jit(spec.decode_paged_fn)
    for _ in range(3):
        logits, pool = decode(params, tok, pos, pool, tables)
        outs.append(logits[:1])             # slot 1 is inactive: garbage
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = pos + jnp.asarray([1, 0], jnp.int32)
    logits, pool = jax.jit(spec.verify_paged_fn)(
        params, jnp.tile(toks[:, :4], (2, 1)), jnp.asarray([47, 0], jnp.int32),
        pool, tables)
    outs.append(logits[:1])
    return outs, pool, dict(spec.kv_pool_writers)


@pytest.mark.parametrize("dtype,layer_types", [
    (jnp.float32, None), (jnp.bfloat16, None),
    (jnp.float32, ("local", "global"))], ids=["f32", "bf16", "f32_windowed"])
def test_in_place_programs_equal_the_scatter_programs(monkeypatch, dtype,
                                                      layer_types):
    mesh_mod.clear_mesh()
    want, want_pool, writers = _paged_run(False, monkeypatch, dtype,
                                          layer_types)
    assert set(writers.values()) == {attn_dispatch.KV_POOL_WRITE_SCATTER}
    got, got_pool, writers = _paged_run(True, monkeypatch, dtype, layer_types)
    assert writers == {phase: attn_dispatch.KV_POOL_WRITE_KERNEL
                       for phase in ("prefill_chunk", "paged_decode",
                                     "verify")}
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    for leaf in want_pool:
        # block 0 is the trash block: the inactive slot's rows land there
        assert got_pool[leaf].shape == want_pool[leaf].shape
        np.testing.assert_array_equal(
            np.asarray(got_pool[leaf][:, 1:], np.float32),
            np.asarray(want_pool[leaf][:, 1:], np.float32))


def test_stats_say_which_writer_each_program_was_built_with():
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1, tensor=1, sequence=1, expert=1,
                                  pipe=1))
    cfg = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=256,
                    vocab_size=256, dtype=jnp.float32, remat=False)
    engine = init_inference(
        model=make_gpt_decode_model(cfg=cfg, name="tiny"), config={
            "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
            "kv_block_size": 16, "max_out_tokens": 64})
    serving = engine.serving(max_slots=2, max_context=64, prefill_chunk=16)
    assert serving.stats()["kv_pool_writer"] == {}      # nothing traced yet
    serving.run([Request(uid=0, tokens=np.arange(5, dtype=np.int32),
                         max_new_tokens=3)])
    assert serving.stats()["kv_pool_writer"] == {
        "decode_step": attn_dispatch.KV_POOL_WRITE_SCATTER,
        "prefill_step": attn_dispatch.KV_POOL_WRITE_SCATTER}
