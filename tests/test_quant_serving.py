"""Quantized serving end-to-end: int8 KV-cache pool + weight-only int8/int4
(inference/quantization.py, the quantized paged kernel, the planner's
capacity math, and every serving subsystem composed over the int8 pool).

Everything here rides the `quant` marker (tier-1; run alone with
`pytest -m quant`).
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.engine import init_inference
from deepspeed_tpu.inference.quantization import (dequantize_kv,
                                                  dequantize_tensor,
                                                  quantize_kv,
                                                  quantize_tensor)
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt import (GPTConfig, init_paged_kv_pool,
                                      make_gpt_decode_model)
from tests.paged_cases import (assert_one_compile_each,
                               PAGED_KERNEL_HEADS, PAGED_KERNEL_ROWS,
                               paged_kernel_case)

pytestmark = pytest.mark.quant

TINY = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=512,
                 vocab_size=256, dtype=jnp.float32, remat=False)
INT8_KV = {"kv_cache_dtype": "int8"}


def _mk_mesh():
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    return mesh_mod.init_mesh(MeshConfig(data=1, tensor=1, sequence=1,
                                         expert=1, pipe=1))


def _mk_engine(cfg=TINY, **cfg_over):
    _mk_mesh()
    spec = make_gpt_decode_model(cfg=cfg, name="tiny")
    return init_inference(model=spec, config={
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": 16, "max_out_tokens": 64, **cfg_over})


def _ragged_requests(rng, lens, max_new=6):
    return [Request(uid=i,
                    tokens=rng.integers(0, TINY.vocab_size, (L,)).astype(
                        np.int32),
                    max_new_tokens=max_new, stop_on_eos=False)
            for i, L in enumerate(lens)]


# ----------------------------------------------------------------------
# quantize_tensor geometry validation (satellite: clear errors, no asserts)
# ----------------------------------------------------------------------


def test_quantize_tensor_rejects_non_tiling_group():
    x = jnp.ones((4, 100), jnp.float32)
    with pytest.raises(ValueError, match="does not tile into groups"):
        quantize_tensor(x, bits=8, group_size=64)
    with pytest.raises(ValueError, match="two values per byte"):
        quantize_tensor(jnp.ones((4, 7), jnp.float32), bits=4, group_size=7)
    with pytest.raises(ValueError, match="bits must be 4 or 8"):
        quantize_tensor(x, bits=2, group_size=4)
    # the admissible case still round-trips
    t = quantize_tensor(jnp.ones((4, 128), jnp.float32), bits=8,
                        group_size=64)
    np.testing.assert_allclose(np.asarray(dequantize_tensor(t)),
                               np.ones((4, 128)), rtol=1e-2)


def test_quantize_kv_rejects_non_tiling_group():
    with pytest.raises(ValueError, match="does not tile"):
        quantize_kv(jnp.ones((2, 3, 16), jnp.float32), 5)


# ----------------------------------------------------------------------
# Pallas quant kernels vs the pure-jnp scheme (the two cannot drift)
# ----------------------------------------------------------------------


def test_pallas_int8_parity_with_jnp_scheme():
    from deepspeed_tpu.ops.pallas.quant import dequantize_int8, quantize_int8
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 256)), jnp.float32)
    qp, sp = quantize_int8(x, 64)
    qj, sj = quantize_kv(x, 64)
    t = quantize_tensor(x, bits=8, group_size=64)
    # identical clip/round semantics: the int payloads are EXACTLY equal
    # across all three spellings; scales agree to fp rounding (XLA may
    # fuse the /127 differently inside the pallas interpret path)
    np.testing.assert_array_equal(np.asarray(qp), np.asarray(qj))
    np.testing.assert_array_equal(np.asarray(qp), np.asarray(t.q))
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sj), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(t.scale),
                               rtol=1e-6)
    d_pal = dequantize_int8(qp, sp, jnp.float32, 64)
    d_jnp = dequantize_kv(qp, sp, jnp.float32)       # same payload+scales
    np.testing.assert_allclose(np.asarray(d_pal), np.asarray(d_jnp),
                               rtol=1e-6, atol=1e-7)


def test_pallas_int4_packed_parity_with_jnp_scheme():
    from deepspeed_tpu.ops.pallas.quant import dequantize_int4, quantize_int4
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
    qp, sp = quantize_int4(x, 64)
    t = quantize_tensor(x, bits=4, group_size=64)
    assert qp.shape == (4, 64)                       # two per byte
    # packed BYTES are identical: same nibble bias, same lo/hi layout
    np.testing.assert_array_equal(np.asarray(qp), np.asarray(t.q))
    np.testing.assert_allclose(np.asarray(sp), np.asarray(t.scale),
                               rtol=1e-6)
    d_pal = np.asarray(dequantize_int4(qp, sp, jnp.float32, 64))
    d_jnp = np.asarray(dequantize_tensor(t).astype(jnp.float32))
    np.testing.assert_allclose(d_pal, d_jnp, rtol=1e-6, atol=1e-7)
    # int4 at group 64 reconstructs to ~15% worst-case of a unit normal
    assert np.abs(d_pal - np.asarray(x)).max() < 0.5


# ----------------------------------------------------------------------
# the quantized paged kernel vs the dequantizing gather oracle
# ----------------------------------------------------------------------


@pytest.mark.parametrize("rows", PAGED_KERNEL_ROWS)
@pytest.mark.parametrize("heads", PAGED_KERNEL_HEADS, ids=str)
def test_quant_paged_kernel_matches_dequant_gather_oracle(heads, rows):
    """The float kernel's cases (tests/test_serving.py) on the int8 pool, at
    4 scale groups a vector: live rows match the dequantizing gather oracle,
    dead rows are exactly zero."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention_quant, paged_decode_attention_quant_reference)
    q, k, v, bt, pos, live = paged_kernel_case(heads, rows)
    kq, ks = quantize_kv(k, 8)
    vq, vs = quantize_kv(v, 8)
    out = np.asarray(paged_decode_attention_quant(q, kq, vq, ks, vs, bt, pos))
    ref = np.asarray(paged_decode_attention_quant_reference(
        q, {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}, bt, pos))
    np.testing.assert_allclose(out[live], ref[live], rtol=2e-5, atol=2e-5)
    assert not out[~live].any()


def test_int8_pool_layout_and_zero_init():
    pool = init_paged_kv_pool(TINY, 9, 16, jnp.int8)
    assert pool["k"].dtype == jnp.int8
    assert pool["k_scale"].shape == (2, 9, 4, 16, 1)     # g = head_dim
    assert pool["k_scale"].dtype == jnp.float32
    pool8 = init_paged_kv_pool(TINY, 9, 16, jnp.int8, kv_group_size=8)
    assert pool8["v_scale"].shape == (2, 9, 4, 16, 2)
    with pytest.raises(ValueError, match="does not tile head_dim"):
        init_paged_kv_pool(TINY, 9, 16, jnp.int8, kv_group_size=5)
    # zero scales dequantize to exact zeros (trash-block reads are benign)
    k, v = np.asarray(pool["k"]), np.asarray(pool["k_scale"])
    assert not k.any() and not v.any()


# ----------------------------------------------------------------------
# greedy generation on the int8 pool: kernel path == dequantizing fp path
# ----------------------------------------------------------------------


def test_int8_kv_kernel_engine_token_identical_to_dequant_reference():
    """THE acceptance path: greedy generation on an int8-KV engine whose
    decode rides the dequantizing Pallas kernel is token-identical to a
    reference engine that dequantizes the SAME int8 pool content through
    the gather path and runs fp attention (the two read paths share one
    write path and one dequant definition — only the attention walk
    differs)."""
    rng = np.random.default_rng(2)
    reqs = _ragged_requests(rng, (20, 7, 33))
    kcfg = dataclasses.replace(TINY, use_flash_attention=True)  # force kernel
    ek = _mk_engine(kcfg, kv_block_size=128)
    sk = ek.serving(max_slots=2, max_context=256, prefill_chunk=128,
                    quantization=INT8_KV)
    res_kernel = sk.run(reqs)
    eg = _mk_engine(TINY, kv_block_size=128)    # auto: gather+dequant path
    sg = eg.serving(max_slots=2, max_context=256, prefill_chunk=128,
                    quantization=INT8_KV)
    res_gather = sg.run(reqs)
    for i in range(len(reqs)):
        np.testing.assert_array_equal(res_kernel[i].tokens,
                                      res_gather[i].tokens)
    # the serving compile contract survives quantization: one compile per
    # persistent program, watchdog silent
    assert_one_compile_each(sk)
    assert_one_compile_each(sg)


def test_int8_kv_close_to_fp_pool_on_tiny_model():
    """int8 KV is lossy vs the fp pool, but per-vector scales keep a tiny
    fp32 model's greedy rollout identical on short horizons — a drift here
    means the quantizer regressed, not that the bound is tight."""
    rng = np.random.default_rng(3)
    reqs = _ragged_requests(rng, (5, 11, 3, 8, 14, 31), max_new=5)
    e8 = _mk_engine()
    r8 = e8.serving(max_slots=3, max_context=64, prefill_chunk=16,
                    quantization=INT8_KV).run(reqs)
    ef = _mk_engine()
    rf = ef.serving(max_slots=3, max_context=64, prefill_chunk=16).run(reqs)
    same = sum(np.array_equal(r8[i].tokens, rf[i].tokens)
               for i in range(len(reqs)))
    assert same == len(reqs)


# ----------------------------------------------------------------------
# composition: prefix cache, spec decode, handoff — all over the int8 pool
# ----------------------------------------------------------------------


def test_prefix_cache_hit_on_int8_pool_token_identical(tmp_path):
    engine = _mk_engine()
    serving = engine.serving(max_slots=2, max_context=128, prefill_chunk=16,
                             enable_prefix_caching=True,
                             quantization=INT8_KV)
    rng = np.random.default_rng(4)
    sysp = rng.integers(0, 256, (48,)).astype(np.int32)
    tail = np.asarray([1, 2, 3], np.int32)
    prompt = np.concatenate([sysp, tail])
    cold = serving.run([Request(uid="c", tokens=prompt, max_new_tokens=4,
                                stop_on_eos=False)])
    chunks_cold = serving.prefill_chunks
    warm = serving.run([Request(uid="w", tokens=prompt, max_new_tokens=4,
                                stop_on_eos=False)])
    chunks_warm = serving.prefill_chunks - chunks_cold
    # a hit on the int8 pool maps int8 blocks + their scales: the warm
    # request is token-identical to its own cold prefill AND strictly
    # cheaper (the shared blocks' chunks are skipped)
    np.testing.assert_array_equal(cold["c"].tokens, warm["w"].tokens)
    assert warm["w"].cached_prefix_tokens == 48
    assert chunks_warm < chunks_cold
    assert_one_compile_each(serving)
    assert serving.close().ok                       # clean invariant audit


def test_spec_decode_verify_over_int8_pool_parity():
    rep = np.tile(np.asarray([7, 8, 9], np.int32), 8)
    run = lambda **kw: _mk_engine().serving(
        max_slots=2, max_context=128, prefill_chunk=16,
        quantization=INT8_KV, **kw).run(
            [Request(uid=0, tokens=rep, max_new_tokens=10,
                     stop_on_eos=False)])
    plain = run()
    engine = _mk_engine()
    spec = engine.serving(max_slots=2, max_context=128, prefill_chunk=16,
                          quantization=INT8_KV,
                          spec_decode={"drafter": "ngram", "draft_k": 3})
    drafted = spec.run([Request(uid=0, tokens=rep, max_new_tokens=10,
                                stop_on_eos=False)])
    # the paged verify path dequantizes the same pool the decode path
    # writes: greedy output is token-identical, and the repetitive prompt
    # actually exercises acceptance (a 0-acceptance run proves nothing)
    np.testing.assert_array_equal(plain[0].tokens, drafted[0].tokens)
    assert spec.stats()["spec_decode"]["accepted_tokens"] > 0
    assert spec.close().ok


def test_handoff_transplant_carries_scales_both_pools_clean():
    src_e, dst_e = _mk_engine(), _mk_engine()
    src = src_e.serving(max_slots=2, max_context=128, prefill_chunk=16,
                        quantization=INT8_KV)
    dst = dst_e.serving(max_slots=2, max_context=128, prefill_chunk=16,
                        quantization=INT8_KV)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 256, (20,)).astype(np.int32)
    req = Request(uid="h", tokens=prompt, max_new_tokens=6,
                  stop_on_eos=False)
    src.submit(req, prefill_only=True)
    while not src.handoff_ready():
        src.step()
    state = src.export_handoff("h")
    assert dst.adopt_handoff(state, src.pool)
    # scales traveled with their blocks: the transplanted physical blocks'
    # scale content on the destination equals the source's, and is real
    # (nonzero) data, not init zeros
    dst_slot = next(s for s in dst.slots if s.uid == "h")
    src_b, dst_b = state["blocks"], dst_slot.blocks[:len(state["blocks"])]
    for leaf in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(
            np.asarray(src.pool[leaf])[:, src_b],
            np.asarray(dst.pool[leaf])[:, dst_b])
    assert np.asarray(src.pool["k_scale"])[:, src_b].any()
    src.release_handoff("h")
    done = {}
    while dst.num_active:
        for d in dst.step():
            done[d.uid] = d
    ref = _mk_engine().serving(max_slots=2, max_context=128,
                               prefill_chunk=16,
                               quantization=INT8_KV).run([req])
    np.testing.assert_array_equal(done["h"].tokens, ref["h"].tokens)
    assert src.close().ok and dst.close().ok


# ----------------------------------------------------------------------
# weight-only int8/int4 through the serving programs
# ----------------------------------------------------------------------


@pytest.mark.parametrize("weights", ["int8", "int4"])
def test_weight_only_serving_matches_generate(weights):
    engine = _mk_engine()
    serving = engine.serving(
        max_slots=3, max_context=64, prefill_chunk=16,
        quantization={"weights": weights, "weight_group_size": 16})
    assert serving.weight_quant_stats["quantized"] > 0
    # the dense tree is gone: the engine's resident params are the packed
    # pytree, and generate() serves it through the same dequant view — so
    # serving output == static generate output, both on quantized weights
    assert serving.weight_quant_stats["ratio"] > (2.0 if weights == "int8"
                                                  else 3.0)
    rng = np.random.default_rng(6)
    reqs = _ragged_requests(rng, (5, 11, 3, 8), max_new=4)
    res = serving.run(reqs)
    for r in reqs:
        ref = engine.generate(np.asarray(r.tokens)[None, :],
                              max_new_tokens=r.max_new_tokens,
                              stop_on_eos=False)
        np.testing.assert_array_equal(res[r.uid].tokens, ref[0])
    assert_one_compile_each(serving)


def test_weight_quant_conflict_and_idempotence():
    engine = _mk_engine(quant={"enabled": True, "bits": 8, "group_size": 16})
    assert engine.quant_stats is not None
    # matching serving request is a no-op; conflicting bits refuse loudly
    serving = engine.serving(max_slots=2, max_context=64,
                             quantization={"weights": "int8",
                                           "weight_group_size": 16})
    assert serving.weight_quant_stats == engine.quant_stats
    with pytest.raises(ValueError, match="already quantized"):
        engine.serving(max_slots=2, max_context=64,
                       quantization={"weights": "int4",
                                     "weight_group_size": 16})
    with pytest.raises(ValueError, match="unknown serving.quantization"):
        _mk_engine().serving(max_slots=2, max_context=64,
                             quantization={"weights": "int2"})


def test_router_refuses_quant_divergent_replicas():
    # pool compatibility is a BUILD-time property: an int8 replica next to
    # a bf16 one (or mismatched scale groups) must refuse at construction,
    # not fail mid-request at the first handoff's transplant
    from deepspeed_tpu.serving import ServingRouter
    engine = _mk_engine()
    sv_q = engine.serving(max_slots=2, max_context=64, quantization=INT8_KV)
    sv_f = engine.serving(max_slots=2, max_context=64)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        ServingRouter(replicas=[sv_q, sv_f])
    sv_g8 = engine.serving(max_slots=2, max_context=64,
                           quantization={"kv_cache_dtype": "int8",
                                         "kv_group_size": 8})
    with pytest.raises(ValueError, match="kv_group_size"):
        ServingRouter(replicas=[sv_q, sv_g8])
    # matching quantized replicas are fine
    sv_q2 = engine.serving(max_slots=2, max_context=64, quantization=INT8_KV)
    ServingRouter(replicas=[sv_q, sv_q2])


def test_non_int8_integer_kv_dtype_refused():
    # int8 is the one quantized layout; any other integer dtype would
    # silently truncate float K/V through the fp write path's cast
    for bad in ("int16", "uint8", "int4"):
        with pytest.raises((ValueError, TypeError),
                           match="KV-cache dtype|data type"):
            _mk_engine().serving(max_slots=2, max_context=64,
                                 quantization={"kv_cache_dtype": bad})


def test_int8_contiguous_generate_cache_refused():
    engine = _mk_engine(kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="paged-pool serving feature"):
        engine.generate(np.asarray([[1, 2, 3]], np.int32), max_new_tokens=2)


# ----------------------------------------------------------------------
# quantization + everything: int8 KV + int4 weights + prefix cache + spec
# ----------------------------------------------------------------------


def test_fully_quantized_engine_end_to_end():
    engine = _mk_engine()
    serving = engine.serving(
        max_slots=2, max_context=128, prefill_chunk=16,
        enable_prefix_caching=True,
        spec_decode={"drafter": "ngram", "draft_k": 3},
        quantization={"kv_cache_dtype": "int8", "weights": "int4",
                      "weight_group_size": 16})
    rep = np.tile(np.asarray([5, 6], np.int32), 12)
    res = serving.run([Request(uid=i, tokens=rep, max_new_tokens=8,
                               stop_on_eos=False) for i in range(3)])
    # all three requests identical (same prompt, greedy), pool clean, one
    # compile per program incl. the verify step
    np.testing.assert_array_equal(res[0].tokens, res[1].tokens)
    np.testing.assert_array_equal(res[0].tokens, res[2].tokens)
    stats = serving.stats()
    assert stats["quantization"]["kv_cache_dtype"] == "int8"
    assert stats["quantization"]["weights"] == "int4"
    compiles = serving.compile_stats()
    assert compiles["decode_step"] <= 1 and compiles["prefill_step"] == 1 \
        and compiles["verify_step"] == 1
    assert serving.close().ok
