"""Continuous-batching serving engine + paged KV-cache pool
(inference/scheduler.py, inference/kv_cache.py, the paged decode kernel).

Everything here rides the `serving` marker (tier-1; run alone with
`pytest -m serving`).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.engine import init_inference
from deepspeed_tpu.inference.kv_cache import (BlockAllocator, TRASH_BLOCK,
                                              blocks_needed)
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model
from tests.paged_cases import (assert_one_compile_each,
                               PAGED_KERNEL_HEADS, PAGED_KERNEL_ROWS,
                               paged_kernel_case)

pytestmark = pytest.mark.serving

TINY = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=256,
                 vocab_size=256, dtype=jnp.float32, remat=False)


def _mk_mesh(**axes):
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    return mesh_mod.init_mesh(MeshConfig(**{**dict(data=1, tensor=1, sequence=1,
                                                   expert=1, pipe=1), **axes}))


def _mk_engine(cfg=TINY, **cfg_over):
    _mk_mesh(data=1)
    spec = make_gpt_decode_model(cfg=cfg, name="tiny")
    return init_inference(model=spec, config={
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": 16, "max_out_tokens": 64, **cfg_over})


def _ragged_prompts(rng, lens, vocab=TINY.vocab_size):
    return [rng.integers(0, vocab, (L,)).astype(np.int32) for L in lens]


# ----------------------------------------------------------------------
# allocator + sizing math
# ----------------------------------------------------------------------


def test_block_allocator_free_list():
    alloc = BlockAllocator(8)            # block 0 reserved
    assert alloc.capacity == 7
    a = alloc.alloc(3)
    b = alloc.alloc(4)
    assert a is not None and b is not None
    assert TRASH_BLOCK not in a + b and len(set(a + b)) == 7
    assert alloc.alloc(1) is None        # exhausted: all-or-nothing, no change
    alloc.free(a)
    assert alloc.num_free == 3
    c = alloc.alloc(3)
    assert sorted(c) == sorted(a)        # freed blocks get reused
    with pytest.raises(AssertionError):
        alloc.free([b[0], b[0]])         # double free


def test_blocks_needed_math():
    # prompt 5 padded to 16, 4 new tokens, block 16: prefill writes 0..15,
    # decode writes positions 5..7 -> 1 block
    assert blocks_needed(5, 16, 4, 16) == 1
    # decode crosses into a second block: prompt 14, +6 new writes up to 18
    assert blocks_needed(14, 16, 6, 16) == 2
    # max_new=1: the single token is sampled from prefill logits, never
    # written -> padded prompt alone decides
    assert blocks_needed(16, 16, 1, 16) == 1
    # decode window: max_new-1=5 decode writes round up to 8 (one 8-window
    # tail is written blindly) -> prompt 14 writes up to position 21
    assert blocks_needed(14, 16, 6, 16, window=8) == 2
    assert blocks_needed(14, 16, 12, 16, window=8) == 2   # 11 -> 16 writes, pos 29
    assert blocks_needed(14, 16, 20, 16, window=8) == 3   # 19 -> 24 writes, pos 37


# ----------------------------------------------------------------------
# paged decode kernel vs gather oracle (interpret mode on the CPU harness)
# ----------------------------------------------------------------------


@pytest.mark.parametrize("rows", PAGED_KERNEL_ROWS)
@pytest.mark.parametrize("heads", PAGED_KERNEL_HEADS, ids=str)
def test_paged_decode_kernel_matches_gather_oracle(heads, rows):
    """Live rows match the gather oracle; a dead row (the oracle attends
    the trash block there) comes back exactly zero."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention, paged_decode_attention_reference)
    q, kp, vp, bt, pos, live = paged_kernel_case(heads, rows)
    out = np.asarray(paged_decode_attention(q, kp, vp, bt, pos))
    ref = np.asarray(paged_decode_attention_reference(q, kp, vp, bt, pos))
    np.testing.assert_allclose(out[live], ref[live], rtol=2e-5, atol=2e-5)
    assert not out[~live].any()


def test_paged_decode_work_lists_the_live_pairs_in_order():
    """The walk's work list: one entry a live (slot, logical block) pair,
    slot-major and ascending, `count` of them; the entries past `count` stay
    in range. An offset table (a layer's blocks of a flat stack) reads as
    the same list when the caller hands the list of the plain one."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention, paged_decode_walk_steps, paged_decode_work)
    q, kp, vp, bt, pos, live = paged_kernel_case((2, 2), "mixed")
    work = paged_decode_work(bt, pos, 512)
    want = [(b, j) for b in range(len(live)) if live[b]
            for j in range(int(pos[b]) // 512 + 1)]
    n = int(work.count[0])
    assert n == len(want) == 1 + 1 + 2 + 2 + 3
    assert list(zip(np.asarray(work.slot)[:n].tolist(),
                    np.asarray(work.block)[:n].tolist())) == want
    assert np.asarray(work.live).tolist() == live.tolist()
    assert work.slot.shape == work.block.shape == (bt.size,)
    assert 0 <= int(work.slot.min()) and int(work.slot.max()) < bt.shape[0]
    assert 0 <= int(work.block.min()) and int(work.block.max()) < bt.shape[1]
    assert paged_decode_walk_steps(n) == n and paged_decode_walk_steps(0) == 1
    # layer 1 of a two-layer flat pool: tables offset by N, the list handed in
    N = kp.shape[0]
    flat_k = jnp.concatenate([jnp.zeros_like(kp), kp])
    flat_v = jnp.concatenate([jnp.zeros_like(vp), vp])
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention(q, flat_k, flat_v, bt + N, pos,
                                          work=work)),
        np.asarray(paged_decode_attention(q, kp, vp, bt, pos)))


# ----------------------------------------------------------------------
# serving engine: correctness, retirement, backpressure, compile accounting
# ----------------------------------------------------------------------


def test_serving_matches_static_generate_on_ragged_trace():
    """Block-table correctness end to end: a mixed-length trace through the
    continuous-batching engine must emit EXACTLY the tokens each prompt gets
    from static-batch generate() (same greedy math, chunked prefill +
    paged decode vs whole-prompt prefill + contiguous cache)."""
    engine = _mk_engine()
    rng = np.random.default_rng(1)
    prompts = _ragged_prompts(rng, (5, 11, 3, 8, 14, 2, 31, 17))
    serving = engine.serving(max_slots=3, max_context=64, prefill_chunk=16)
    reqs = [Request(uid=i, tokens=p, max_new_tokens=3 + i % 5,
                    stop_on_eos=False)
            for i, p in enumerate(prompts)]
    res = serving.run(reqs)
    assert sorted(res) == list(range(len(prompts)))
    for i, p in enumerate(prompts):
        ref = engine.generate(p[None, :], max_new_tokens=3 + i % 5,
                              stop_on_eos=False)
        np.testing.assert_array_equal(res[i].tokens, ref[0])
        assert res[i].finish_reason == "length"


def test_serving_single_compile_per_program_across_mixed_trace():
    """THE recompile-tax guarantee: one decode program and one prefill-chunk
    program for the engine's lifetime, across arbitrary prompt lengths,
    max_new values, and admission orders."""
    engine = _mk_engine()
    rng = np.random.default_rng(2)
    serving = engine.serving(max_slots=2, max_context=64, prefill_chunk=16)
    for wave in ((4, 9), (21, 2, 33), (15,)):
        reqs = [Request(uid=f"{wave}-{i}", tokens=p,
                        max_new_tokens=2 + i * 3, stop_on_eos=False)
                for i, p in enumerate(_ragged_prompts(rng, wave))]
        serving.run(reqs)
    assert_one_compile_each(serving)


def test_eos_retirement_frees_slot_and_blocks_immediately():
    """A sequence retires the step it emits EOS: its blocks return to the
    pool, its slot admits the next queued request, and the emitted output
    keeps the EOS token (generate()'s contract)."""
    engine = _mk_engine()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, TINY.vocab_size, (6,)).astype(np.int32)
    # free-run to discover what greedy emits, then use token 2 as "EOS"
    free = engine.generate(prompt[None], max_new_tokens=8, stop_on_eos=False)[0]
    eos = int(free[2])
    serving = engine.serving(max_slots=1, max_context=64, prefill_chunk=16)
    free_blocks0 = serving.allocator.num_free
    res = serving.run([Request(uid="a", tokens=prompt, max_new_tokens=8,
                               eos_token_id=eos)])
    out = res["a"].tokens
    assert res["a"].finish_reason == "eos"
    assert out[-1] == eos and len(out) <= 3 + 1
    np.testing.assert_array_equal(out, free[:len(out)])
    assert serving.allocator.num_free == free_blocks0, "blocks leaked"
    # slot is reusable: a second request runs through the same slot
    res2 = serving.run([Request(uid="b", tokens=prompt, max_new_tokens=4,
                                stop_on_eos=False)])
    np.testing.assert_array_equal(res2["b"].tokens, free[:4])
    assert_one_compile_each(serving)


def test_pool_exhaustion_backpressure():
    """A pool sized for ~one request at a time: excess requests WAIT in the
    queue (no crash, no over-allocation) and complete as blocks free up."""
    engine = _mk_engine()
    rng = np.random.default_rng(4)
    prompts = _ragged_prompts(rng, (17, 20, 18))
    # each request: padded prompt 32 -> 2 blocks of 16; 3 usable blocks fit
    # one request at a time, never two
    serving = engine.serving(max_slots=3, max_context=48, prefill_chunk=16,
                             num_kv_blocks=4)
    reqs = [Request(uid=i, tokens=p, max_new_tokens=6, stop_on_eos=False)
            for i, p in enumerate(prompts)]
    res = serving.run(reqs)
    assert sorted(res) == [0, 1, 2]
    assert serving.peak_active == 1, \
        "backpressure failed: two requests shared a 1-request pool"
    for i, p in enumerate(prompts):
        ref = engine.generate(p[None, :], max_new_tokens=6, stop_on_eos=False)
        np.testing.assert_array_equal(res[i].tokens, ref[0])
    assert serving.allocator.num_free == serving.allocator.capacity


def test_submit_rejects_impossible_requests():
    engine = _mk_engine()
    serving = engine.serving(max_slots=2, max_context=32, prefill_chunk=16)
    with pytest.raises(ValueError, match="max_context"):
        serving.submit(Request(uid=0, tokens=list(range(30)),
                               max_new_tokens=16))
    with pytest.raises(ValueError, match="empty prompt"):
        serving.submit(Request(uid=1, tokens=[], max_new_tokens=4))
    small = engine.serving(max_slots=1, max_context=64, prefill_chunk=16,
                           num_kv_blocks=2)
    with pytest.raises(ValueError, match="KV blocks"):
        small.submit(Request(uid=2, tokens=list(range(40)), max_new_tokens=8))


def test_serving_interleaves_prefill_with_decode():
    """A long prompt arriving mid-flight must not stall the running batch:
    with prefill_chunks_per_step=1 the already-decoding request keeps
    emitting a token every step while the newcomer prefills chunk by chunk."""
    engine = _mk_engine()
    rng = np.random.default_rng(5)
    short, long = _ragged_prompts(rng, (4, 60))
    serving = engine.serving(max_slots=2, max_context=96, prefill_chunk=16,
                             prefill_chunks_per_step=1)
    serving.submit(Request(uid="short", tokens=short, max_new_tokens=12,
                           stop_on_eos=False))
    # warm the short request into decode
    serving.step()
    emitted_before = serving.slots and max(
        len(s.emitted) for s in serving.slots if s.uid == "short")
    serving.submit(Request(uid="long", tokens=long, max_new_tokens=2,
                           stop_on_eos=False))
    done = {}
    for _ in range(4):           # long needs 4 chunks of 16 to finish prefill
        for f in serving.step():
            done[f.uid] = f
    short_slot = [s for s in serving.slots if s.uid == "short"]
    assert short_slot, "short request should still be decoding"
    # the short request advanced EVERY step while the long one prefilled
    assert len(short_slot[0].emitted) == emitted_before + 4
    while serving.num_active or serving.queue:
        for f in serving.step():
            done[f.uid] = f
    ref_s = engine.generate(short[None], max_new_tokens=12, stop_on_eos=False)
    ref_l = engine.generate(long[None], max_new_tokens=2, stop_on_eos=False)
    np.testing.assert_array_equal(done["short"].tokens, ref_s[0])
    np.testing.assert_array_equal(done["long"].tokens, ref_l[0])


def test_decode_window_matches_per_step_and_generate():
    """decode_steps_per_sync > 1 (multi-step scheduling: a whole window of
    tokens per jitted call) must emit the same tokens as window=1 and as
    static generate(), including EOS truncation mid-window."""
    engine = _mk_engine()
    rng = np.random.default_rng(12)
    prompts = _ragged_prompts(rng, (5, 11, 3, 22))
    news = [9, 4, 13, 6]
    ref = {i: engine.generate(p[None], max_new_tokens=n, stop_on_eos=False)[0]
           for i, (p, n) in enumerate(zip(prompts, news))}
    for window in (4, 8):
        serving = engine.serving(max_slots=2, max_context=96, prefill_chunk=16,
                                 decode_steps_per_sync=window)
        res = serving.run([Request(uid=i, tokens=p, max_new_tokens=n,
                                   stop_on_eos=False)
                           for i, (p, n) in enumerate(zip(prompts, news))])
        for i in ref:
            np.testing.assert_array_equal(res[i].tokens, ref[i]), (window, i)
        assert_one_compile_each(serving)
    # EOS mid-window: discover a token greedy emits, stop on it, and check
    # the output truncates exactly there (the window tail is discarded)
    eos = int(ref[0][3])
    serving = engine.serving(max_slots=1, max_context=96, prefill_chunk=16,
                             decode_steps_per_sync=4)
    out = serving.run([Request(uid="e", tokens=prompts[0], max_new_tokens=9,
                               eos_token_id=eos)])["e"]
    hits = np.flatnonzero(ref[0] == eos)
    np.testing.assert_array_equal(out.tokens, ref[0][:hits[0] + 1])
    assert out.finish_reason == "eos"
    assert serving.allocator.num_free == serving.allocator.capacity


def test_serving_arch_flags_parity():
    """Paged prefill/decode honor the arch flags (rotary+GQA+swiglu+rmsnorm,
    alibi, sliding window) — same tokens as static generate per arch."""
    archs = {
        "llama-style": dict(use_rotary=True, use_rmsnorm=True, use_swiglu=True,
                            n_kv_head=2),
        "bloom-style": dict(use_alibi=True, use_emb_ln=True),
        "mistral-style": dict(use_rotary=True, n_kv_head=2, sliding_window=6),
    }
    rng = np.random.default_rng(6)
    for name, flags in archs.items():
        cfg = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=256,
                        vocab_size=128, dtype=jnp.float32, remat=False, **flags)
        engine = _mk_engine(cfg=cfg)
        prompts = _ragged_prompts(rng, (5, 9, 3), vocab=cfg.vocab_size)
        serving = engine.serving(max_slots=2, max_context=48, prefill_chunk=16)
        res = serving.run([Request(uid=i, tokens=p, max_new_tokens=4,
                                   stop_on_eos=False)
                           for i, p in enumerate(prompts)])
        for i, p in enumerate(prompts):
            ref = engine.generate(p[None], max_new_tokens=4, stop_on_eos=False)
            np.testing.assert_array_equal(res[i].tokens, ref[0]), (name, i)


def test_serving_forced_paged_kernel_matches_gather_path():
    """use_flash_attention=True forces the paged Pallas kernel into the
    decode step (block 128 for lane alignment); tokens must match the
    default XLA gather path exactly."""
    rng = np.random.default_rng(7)
    prompts = _ragged_prompts(rng, (5, 150, 40))
    outs = {}
    for flag in (False, True):
        cfg = dataclasses.replace(TINY, use_flash_attention=flag)
        engine = _mk_engine(cfg=cfg, kv_block_size=128)
        serving = engine.serving(max_slots=3, max_context=256,
                                 prefill_chunk=128)
        res = serving.run([Request(uid=i, tokens=p, max_new_tokens=5,
                                   stop_on_eos=False)
                           for i, p in enumerate(prompts)])
        outs[flag] = [res[i].tokens for i in range(len(prompts))]
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b)


def test_serving_under_tensor_parallel_mesh():
    """The serving engine composes with TP sharding: params sharded over the
    tensor axis, pool replicated, same tokens as the single-device run."""
    rng = np.random.default_rng(8)
    prompts = _ragged_prompts(rng, (5, 9))

    engine1 = _mk_engine()
    ref = engine1.serving(max_slots=2, max_context=64, prefill_chunk=16).run(
        [Request(uid=i, tokens=p, max_new_tokens=4, stop_on_eos=False)
         for i, p in enumerate(prompts)])

    _mk_mesh(tensor=4)
    from deepspeed_tpu.models.gpt import gpt_param_specs
    spec = make_gpt_decode_model(cfg=TINY, name="tiny")
    spec.param_specs = gpt_param_specs(TINY)
    engine = init_inference(model=spec, config={
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": 16, "max_out_tokens": 64})
    serving = engine.serving(max_slots=2, max_context=64, prefill_chunk=16)
    res = serving.run([Request(uid=i, tokens=p, max_new_tokens=4,
                               stop_on_eos=False)
                       for i, p in enumerate(prompts)])
    for i in range(len(prompts)):
        np.testing.assert_array_equal(res[i].tokens, ref[i].tokens)


# ----------------------------------------------------------------------
# satellite regressions: generate() bucketing + engine-owned cache reuse
# ----------------------------------------------------------------------


def test_generate_max_new_bucketing_single_compile():
    """max_new_tokens is a static argnum: 5/6/7/8 must share ONE pow2-bucket
    compile, and the trimmed outputs must be prefixes of each other."""
    engine = _mk_engine()
    toks = np.random.default_rng(9).integers(
        0, TINY.vocab_size, (2, 6)).astype(np.int32)
    outs = {n: engine.generate(toks, max_new_tokens=n, stop_on_eos=False)
            for n in (5, 6, 7, 8)}
    assert engine._generate_jit._cache_size() == 1, \
        "max_new 5..8 must share the bucket-8 compile"
    for n in (5, 6, 7, 8):
        assert outs[n].shape == (2, n)
        np.testing.assert_array_equal(outs[n], outs[8][:, :n])
    engine.generate(toks, max_new_tokens=9, stop_on_eos=False)  # next bucket
    assert engine._generate_jit._cache_size() == 2


def test_engine_reuses_kv_cache_across_calls():
    """Shape-matching forward()/generate() calls reuse the engine-owned
    cache instead of re-allocating (satellite: stop re-tracing init_cache)."""
    engine = _mk_engine()
    toks = np.random.default_rng(10).integers(
        0, TINY.vocab_size, (2, 8)).astype(np.int32)
    engine.generate(toks, max_new_tokens=4, stop_on_eos=False)
    hits0 = engine._cache_hits
    out2 = engine.generate(toks, max_new_tokens=4, stop_on_eos=False)
    assert engine._cache_hits == hits0 + 1
    # reuse must not change results (the template is never mutated)
    np.testing.assert_array_equal(
        out2, engine.generate(toks, max_new_tokens=4, stop_on_eos=False))
    engine.forward(toks)
    h = engine._cache_hits
    engine.forward(toks)
    assert engine._cache_hits == h + 1


# ----------------------------------------------------------------------
# the loop runs one call deep: step k dispatches call k, THEN reads call
# k-1 back — token for token the contiguous generate() all the same
# ----------------------------------------------------------------------


def _greedy(engine, prompt, n):
    return np.asarray(engine.generate(prompt[None, :], max_new_tokens=n,
                                      stop_on_eos=False)[0])


def _mk_talkative_engine():
    """TINY with its matrices scaled up: the plain random model repeats its
    last token for ever, and an EOS in mid-stream needs a stream."""
    from deepspeed_tpu.models.gpt import gpt_init_fn
    _mk_mesh(data=1)
    params = jax.tree_util.tree_map(
        lambda a: a * 3.0 if a.ndim >= 2 else a,
        gpt_init_fn(TINY, dtype=jnp.float32)(jax.random.PRNGKey(7)))
    return init_inference(
        model=make_gpt_decode_model(cfg=TINY, name="tiny", params=params),
        config={"dtype": "float32", "kv_cache_dtype": "float32",
                "greedy": True, "kv_block_size": 16, "max_out_tokens": 64})


def _ends_at(engine, length, n, where, seed):
    """A prompt whose greedy continuation of `n` tokens has, at an index
    `where` accepts, a token that appears nowhere before it: an EOS of that
    token ends the request exactly there. Returns (prompt, reference, k)."""
    for s in range(seed, seed + 64):
        prompt = _ragged_prompts(np.random.default_rng(s), (length,))[0]
        ref = _greedy(engine, prompt, n)
        for k in range(len(ref)):
            if where(k) and ref[k] not in ref[:k]:
                return prompt, ref, k
    raise AssertionError("no seed gives a fresh token there")


def _steps(serving, done, cond=None, limit=400):
    """Step until `cond()` holds (or the engine is empty); completions go
    to `done`."""
    for _ in range(limit):
        if cond() if cond else not (serving.queue or serving.num_active):
            return
        done.update({d.uid: d for d in serving.step()})
    raise AssertionError("condition never held")


def _slot_uids(serving):
    return [s.uid for s in serving.slots if s.uid is not None]


def _all_free(serving):
    assert serving._pending is None and serving.num_active == 0
    assert serving.audit().ok
    assert serving.allocator.available == serving.allocator.capacity


def _case_count_end_readmits_the_slot_in_the_next_call():
    """An end the host can count is decided at dispatch: the request gives
    up its one slot while its last tokens are in flight, and the step that
    returns it is the step whose call already carries the next request."""
    engine = _mk_engine()
    a, b = _ragged_prompts(np.random.default_rng(11), (5, 9))
    serving = engine.serving(max_slots=1, max_context=64, prefill_chunk=16)
    serving.submit(Request(uid="a", tokens=a, max_new_tokens=4,
                           stop_on_eos=False))
    serving.submit(Request(uid="b", tokens=b, max_new_tokens=3,
                           stop_on_eos=False))
    done = {}
    _steps(serving, done, lambda: "a" in serving.active_uids()
           and "a" not in _slot_uids(serving))
    # it left at dispatch: still active, its tokens undelivered, slot free
    assert not done and serving.active_uids() == ["a"]
    assert serving.num_active == 1 and serving.has_free_slot
    assert len(serving._live()[0].emitted) < 4
    finished = serving.step()
    rec = serving.steptrace.records()[-1]
    assert [d.uid for d in finished] == ["a"] and rec.admitted == 1
    assert _slot_uids(serving) == ["b"]
    done["a"] = finished[0]
    _steps(serving, done)
    for uid, prompt, n in (("a", a, 4), ("b", b, 3)):
        np.testing.assert_array_equal(done[uid].tokens,
                                      _greedy(engine, prompt, n))
        assert done[uid].finish_reason == "length"
    st = serving.stats()
    assert 0 < st["overlapped_calls"] < st["device_calls"]
    _all_free(serving)
    assert_one_compile_each(serving)


def _case_eos_mid_window_drops_one_speculative_window():
    """An end only the token decides: the slot rides one more call, whose
    tokens for it are dropped; slot and blocks are released once."""
    engine = _mk_talkative_engine()
    b, c = _ragged_prompts(np.random.default_rng(12), (11, 4))
    # token 0 is the prompt's, then windows of 4: inside the second or third
    a, ref, k = _ends_at(engine, 6, 14, lambda k: k >= 5 and (k - 1) % 4 < 3,
                         seed=120)
    serving = engine.serving(max_slots=2, max_context=64, prefill_chunk=16,
                             decode_steps_per_sync=4)
    serving.submit(Request(uid="a", tokens=a, max_new_tokens=14,
                           eos_token_id=int(ref[k])))
    serving.submit(Request(uid="b", tokens=b, max_new_tokens=30,
                           stop_on_eos=False))
    done = {}
    _steps(serving, done, lambda: "a" in done)
    # the call dispatched before the EOS was read still holds a row for it
    assert serving._pending is not None and any(
        r.uid == "a" for r in serving._pending.rows)
    assert "a" not in serving.active_uids()
    serving.submit(Request(uid="c", tokens=c, max_new_tokens=5,
                           stop_on_eos=False))      # into the freed slot
    _steps(serving, done)
    np.testing.assert_array_equal(done["a"].tokens, ref[:k + 1])
    assert done["a"].finish_reason == "eos"
    np.testing.assert_array_equal(done["b"].tokens, _greedy(engine, b, 30))
    np.testing.assert_array_equal(done["c"].tokens, _greedy(engine, c, 5))
    _all_free(serving)
    assert_one_compile_each(serving)


def _case_a_prompts_last_chunk_rides_and_its_first_token_stays_on_device():
    engine = _mk_engine()
    a, b = _ragged_prompts(np.random.default_rng(13), (5, 40))
    serving = engine.serving(max_slots=2, max_context=64, prefill_chunk=16)
    done = {}
    serving.submit(Request(uid="a", tokens=a, max_new_tokens=12,
                           stop_on_eos=False))
    _steps(serving, done, lambda: serving.decode_steps >= 1)
    serving.submit(Request(uid="b", tokens=b, max_new_tokens=6,
                           stop_on_eos=False))
    _steps(serving, done, lambda: serving.fused_chunks == 3)
    slot = next(s for s in serving.slots if s.uid == "b")
    # its first token is call k's `first[0]`: call k+1 takes it from there
    assert slot.flying == 1 and not slot.emitted
    assert slot.feed == (serving._pending.id, 2)
    _steps(serving, done)
    np.testing.assert_array_equal(done["a"].tokens, _greedy(engine, a, 12))
    np.testing.assert_array_equal(done["b"].tokens, _greedy(engine, b, 6))
    _all_free(serving)
    assert_one_compile_each(serving)


def _case_cancel_with_a_call_in_flight_reads_it_first():
    engine = _mk_engine()
    a, b, c = _ragged_prompts(np.random.default_rng(14), (7, 12, 3))
    serving = engine.serving(max_slots=3, max_context=64, prefill_chunk=16)
    for uid, p, n in (("a", a, 12), ("b", b, 12), ("c", c, 4)):
        serving.submit(Request(uid=uid, tokens=p, max_new_tokens=n,
                               stop_on_eos=False))
    done = {}
    # c has left its slot: its last token is in the call in flight
    _steps(serving, done, lambda: "c" not in _slot_uids(serving)
           and "c" in serving.active_uids())
    gone = serving.cancel("c")
    assert serving._pending is None     # the call was read first ...
    assert gone.finish_reason == "length"       # ... and c had ended in it
    np.testing.assert_array_equal(gone.tokens, _greedy(engine, c, 4))
    serving.step()
    assert serving._pending is not None
    gone = serving.cancel("a")
    assert serving._pending is None and gone.finish_reason == "cancelled"
    assert 0 < len(gone.tokens) < 12
    np.testing.assert_array_equal(gone.tokens,
                                  _greedy(engine, a, 12)[:len(gone.tokens)])
    assert serving.cancel("a") is None and serving.cancelled == 1
    _steps(serving, done)
    np.testing.assert_array_equal(done["b"].tokens, _greedy(engine, b, 12))
    assert "a" not in done and "c" not in done
    _all_free(serving)


def _case_a_hard_deadline_with_a_call_in_flight():
    engine = _mk_engine()
    a, b = _ragged_prompts(np.random.default_rng(15), (7, 12))
    t = {"now": 0.0}
    serving = engine.serving(max_slots=2, max_context=64, prefill_chunk=16,
                             clock=lambda: t["now"])
    serving.submit(Request(uid="a", tokens=a, max_new_tokens=20,
                           stop_on_eos=False, deadline_ms=4500.0))
    serving.submit(Request(uid="b", tokens=b, max_new_tokens=12,
                           stop_on_eos=False))
    done = {}
    while serving.queue or serving.num_active:
        t["now"] += 1.0
        in_flight = serving._pending is not None
        for d in serving.step():
            done[d.uid] = d
            if d.uid == "a":
                assert in_flight and serving._pending is not None
    assert done["a"].finish_reason == "deadline"
    n = len(done["a"].tokens)
    assert 0 < n < 20
    np.testing.assert_array_equal(done["a"].tokens,
                                  _greedy(engine, a, 20)[:n])
    np.testing.assert_array_equal(done["b"].tokens, _greedy(engine, b, 12))
    assert serving.deadline_cancelled == 1
    _all_free(serving)


def _case_spec_decode_never_leaves_a_call_in_flight():
    """Acceptance decides the next input: `_verify_decode` steps stay
    synchronous, through the same loop."""
    engine = _mk_engine()
    prompts = _ragged_prompts(np.random.default_rng(16), (5, 11, 3, 20))
    serving = engine.serving(max_slots=2, max_context=64, prefill_chunk=16,
                             spec_decode={"drafter": "ngram", "draft_k": 2})
    for i, p in enumerate(prompts):
        serving.submit(Request(uid=i, tokens=p, max_new_tokens=4 + i,
                               stop_on_eos=False))
    done = {}
    while serving.queue or serving.num_active:
        done.update({d.uid: d for d in serving.step()})
        assert serving._pending is None
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(done[i].tokens,
                                      _greedy(engine, p, 4 + i))
    st = serving.stats()
    assert st["overlapped_calls"] == 0 and st["device_calls"] > 0
    assert st["spec_decode"]["verify_steps"] > 0
    _all_free(serving)


def _case_the_pressure_ladder_off_rest_reads_the_call_first():
    engine = _mk_engine()
    prompts = _ragged_prompts(np.random.default_rng(17),
                              (5, 11, 3, 20, 9, 14, 6, 2))
    serving = engine.serving(
        max_slots=2, max_context=64, prefill_chunk=16,
        decode_steps_per_sync=2, degradation={
            "enabled": True, "eval_interval": 1, "queue_high": 2,
            "queue_low": 1, "hold_steps": 2})
    for i, p in enumerate(prompts):
        serving.submit(Request(uid=i, tokens=p, max_new_tokens=3 + i,
                               stop_on_eos=False))
    done, levels, left_in_flight = {}, [], []
    while serving.queue or serving.num_active:
        levels.append(serving.pressure.level)
        done.update({d.uid: d for d in serving.step()})
        left_in_flight.append(serving._pending is not None)
    # it went up the ladder (the 1-step window and beyond), and a step left
    # a call in flight only while it was at rest
    assert levels[0] == 0 and max(levels) >= 3
    assert any(left_in_flight)
    assert not any(f for lvl, f in zip(levels, left_in_flight) if lvl)
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(done[i].tokens,
                                      _greedy(engine, p, 3 + i))
    _all_free(serving)


def _case_run_ends_with_nothing_in_flight():
    """Every row of the last call ended while it ran (an EOS read a step
    late): nobody waits for it, and it is read all the same."""
    engine = _mk_talkative_engine()
    a, ref, k = _ends_at(engine, 6, 10, lambda k: 3 <= k < 9, seed=180)
    serving = engine.serving(max_slots=1, max_context=64, prefill_chunk=16)
    res = serving.run([Request(uid="a", tokens=a, max_new_tokens=10,
                               eos_token_id=int(ref[k]))])
    np.testing.assert_array_equal(res["a"].tokens, ref[:k + 1])
    assert res["a"].finish_reason == "eos"
    assert serving.steptrace._inflight_since is None
    st = serving.stats()
    # one call more than the tokens it delivered: the speculative one
    assert st["device_calls"] == 1 + k + 1 and st["decode_steps"] == k + 1
    _all_free(serving)
    assert serving.close().ok


ONE_CALL_DEEP = (
    _case_count_end_readmits_the_slot_in_the_next_call,
    _case_eos_mid_window_drops_one_speculative_window,
    _case_a_prompts_last_chunk_rides_and_its_first_token_stays_on_device,
    _case_cancel_with_a_call_in_flight_reads_it_first,
    _case_a_hard_deadline_with_a_call_in_flight,
    _case_spec_decode_never_leaves_a_call_in_flight,
    _case_the_pressure_ladder_off_rest_reads_the_call_first,
    _case_run_ends_with_nothing_in_flight,
)


@pytest.mark.parametrize("case", ONE_CALL_DEEP,
                         ids=lambda f: f.__name__[len("_case_"):])
def test_one_call_deep_emits_what_generate_emits(case):
    case()
