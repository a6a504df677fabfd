"""Automatic prefix caching: ref-counted KV block reuse across serving
requests (inference/prefix_cache.py + the allocator refcount/reclaim
machinery in inference/kv_cache.py + the scheduler's admission match).

Everything here rides the `prefix_cache` marker (tier-1; run alone with
`pytest -m prefix_cache`).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.engine import init_inference
from deepspeed_tpu.inference.kv_cache import BlockAllocator, TRASH_BLOCK
from deepspeed_tpu.inference.prefix_cache import PrefixCache
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model
from tests.paged_cases import assert_one_compile_each

pytestmark = pytest.mark.prefix_cache

TINY = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=256,
                 vocab_size=256, dtype=jnp.float32, remat=False)
BS = 16  # kv_block_size == prefill_chunk for every engine below


def _mk_engine(cfg=TINY, **cfg_over):
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    mesh_mod.init_mesh(MeshConfig(data=1, tensor=1, sequence=1, expert=1,
                                  pipe=1))
    spec = make_gpt_decode_model(cfg=cfg, name="tiny")
    return init_inference(model=spec, config={
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": BS, "max_out_tokens": 64, **cfg_over})


def _prompts_with_shared_prefix(rng, prefix_len, tail_lens, vocab=256):
    prefix = rng.integers(0, vocab, (prefix_len,)).astype(np.int32)
    return [np.concatenate([prefix, rng.integers(0, vocab, (t,))
                            .astype(np.int32)]) for t in tail_lens]


# ----------------------------------------------------------------------
# allocator: refcounts, reclaim list, eviction, O(1) free
# ----------------------------------------------------------------------


def test_allocator_refcount_and_reclaim_lifecycle():
    cached = set()
    evicted = []
    alloc = BlockAllocator(6)
    alloc.is_cached = cached.__contains__
    alloc.on_evict = evicted.append
    a = alloc.alloc(3)
    assert [alloc.refcount(b) for b in a] == [1, 1, 1]
    alloc.incref(a[0])                       # a second reader (cache hit)
    assert alloc.refcount(a[0]) == 2
    cached.update(a[:2])
    alloc.free(a)                            # decref all three
    # a[0] still has a reader; a[1] cached -> reclaimable; a[2] -> free
    assert alloc.refcount(a[0]) == 1 and a[0] not in alloc._free_set
    assert alloc.num_reclaimable == 1 and alloc.num_free == 3
    assert alloc.available == 4
    alloc.free([a[0]])                       # last reader retires
    assert alloc.num_reclaimable == 2
    # resurrect a reclaimable block: leaves the LRU, refcount 1 again
    alloc.incref(a[1])
    assert alloc.num_reclaimable == 1 and alloc.refcount(a[1]) == 1
    alloc.free([a[1]])
    # demand eviction: 5 usable blocks, 3 free + 2 reclaimable; asking for
    # 5 must evict both (oldest first) and notify on_evict for each
    got = alloc.alloc(5)
    assert got is not None and len(got) == 5
    assert alloc.evictions == 2 and sorted(evicted) == sorted(a[:2])
    assert alloc.alloc(1) is None            # truly exhausted now


def test_allocator_eviction_is_lru_oldest_first():
    cached = {1, 2, 3}
    evicted = []
    alloc = BlockAllocator(5)
    alloc.is_cached = cached.__contains__
    alloc.on_evict = evicted.append
    blocks = alloc.alloc(4)                  # 1, 2, 3, 4
    alloc.free([2])                          # parked first -> evicted first
    alloc.free([3])
    alloc.free([1])
    alloc.free([4])                          # uncached: straight to free
    alloc.alloc(2)                           # needs 1 eviction past block 4
    assert evicted == [2]
    alloc.alloc(2)                           # two more evictions, in order
    assert evicted == [2, 3, 1]
    assert blocks == [1, 2, 3, 4]


def test_allocator_policy_none_frees_and_unregisters_immediately():
    cached = {1}
    evicted = []
    alloc = BlockAllocator(4, policy="none")
    alloc.is_cached = cached.__contains__
    alloc.on_evict = evicted.append
    alloc.alloc(1)
    alloc.free([1])
    assert alloc.num_reclaimable == 0 and 1 in alloc._free_set
    # unregistered on the spot, but routine retirement is NOT an eviction:
    # the counter means demand-driven reclaim (pool pressure) only
    assert evicted == [1] and alloc.evictions == 0
    with pytest.raises(AssertionError):
        BlockAllocator(4, policy="mru")


def test_allocator_free_is_set_backed_o1():
    """Satellite: the double-free guard must be an O(1) set probe, not an
    O(n) list scan — at serving scale (thousands of blocks, every
    retirement frees dozens) the scan was quadratic in pool size."""
    n = 4097
    alloc = BlockAllocator(n)
    assert alloc._free_set == set(alloc._free)       # shadow set exists
    got = alloc.alloc(n - 1)
    assert alloc._free_set == set()
    # deterministic order contract: pop() yields low ids first
    assert got[:4] == [1, 2, 3, 4]
    alloc.free(got)                                  # 4096 O(1) frees
    assert alloc._free_set == set(alloc._free)
    with pytest.raises(AssertionError):
        alloc.free([got[0]])                         # double free still caught
    with pytest.raises(AssertionError):
        alloc.free([TRASH_BLOCK])
    # freed blocks recycle in a deterministic order: pop() returns the
    # most recently freed block first after a full drain/refill
    assert alloc.alloc(4) == [got[-1], got[-2], got[-3], got[-4]]


# ----------------------------------------------------------------------
# hash chain + map
# ----------------------------------------------------------------------


def test_hash_chain_is_prefix_sensitive_and_fingerprinted():
    alloc = BlockAllocator(8)
    cache = PrefixCache(alloc, block_size=4, fingerprint="model-a")
    toks = np.arange(13, dtype=np.int32)             # 3 full blocks + tail
    h = cache.hash_chain(toks)
    assert len(h) == 3
    # chained: changing an EARLY block changes every later hash
    toks2 = toks.copy()
    toks2[0] += 1
    h2 = cache.hash_chain(toks2)
    assert h2[0] != h[0] and h2[1] != h[1] and h2[2] != h[2]
    # changing only the tail (not a full block) changes nothing
    assert cache.hash_chain(np.concatenate([toks, [99]]))[:3] == h
    # a different model identity produces disjoint hashes for the same tokens
    other = PrefixCache(BlockAllocator(8), block_size=4,
                        fingerprint="model-b")
    assert other.hash_chain(toks)[0] != h[0]
    # longest-prefix match stops at the first unregistered hash
    cache.register(h[0], 1)
    cache.register(h[2], 3)                          # gap at h[1]
    assert cache.match(h) == [1]
    cache.register(h[1], 2)
    assert cache.match(h) == [1, 2, 3]
    # first writer wins: re-registering a taken hash or block is a no-op
    assert not cache.register(h[0], 5)
    assert not cache.register(b"other", 1)
    assert cache.num_cached == 3


# ----------------------------------------------------------------------
# serving engine end to end
# ----------------------------------------------------------------------


def test_greedy_parity_and_fewer_prefill_chunks_zero_new_compiles():
    """THE acceptance criterion: on a shared-system-prompt trace the
    cache-enabled engine emits token-identical greedy output to the
    cache-disabled engine, executes strictly fewer prefill chunks, and
    compiles zero additional programs."""
    rng = np.random.default_rng(21)
    prompts = _prompts_with_shared_prefix(rng, 40, (7, 13, 3, 20, 11))
    reqs = lambda: [Request(uid=i, tokens=p, max_new_tokens=4 + i % 3,
                            stop_on_eos=False) for i, p in enumerate(prompts)]

    off = _mk_engine().serving(max_slots=2, max_context=96, prefill_chunk=BS)
    res_off = off.run(reqs())
    on_engine = _mk_engine()
    on = on_engine.serving(max_slots=2, max_context=96, prefill_chunk=BS,
                           enable_prefix_caching=True)
    res_on = on.run(reqs())

    for i in range(len(prompts)):
        np.testing.assert_array_equal(res_on[i].tokens, res_off[i].tokens)
    assert on.prefill_chunks < off.prefill_chunks, \
        (on.prefill_chunks, off.prefill_chunks)
    assert on.prefill_chunks + on.prefill_chunks_skipped == off.prefill_chunks
    assert_one_compile_each(on)
    st = on.stats()["prefix_cache"]
    assert st["hit_tokens"] == st["hit_blocks"] * BS > 0
    assert st["prefill_chunks_skipped"] == on.prefill_chunks_skipped


def test_refcounts_under_interleaved_admit_retire():
    """Shared blocks live until the LAST reader retires; a full drain parks
    registered blocks on the reclaimable list with the whole pool still
    available."""
    rng = np.random.default_rng(22)
    pa, pb = _prompts_with_shared_prefix(rng, 32, (5, 9))   # 2 shared blocks
    engine = _mk_engine()
    serving = engine.serving(max_slots=3, max_context=96, prefill_chunk=BS,
                             enable_prefix_caching=True)
    serving.submit(Request(uid="a", tokens=pa, max_new_tokens=12,
                           stop_on_eos=False))
    for _ in range(4):                       # a prefills (3 chunks) + decodes
        serving.step()
    serving.submit(Request(uid="b", tokens=pb, max_new_tokens=4,
                           stop_on_eos=False))
    serving.step()
    slot_a = next(s for s in serving.slots if s.uid == "a")
    slot_b = next(s for s in serving.slots if s.uid == "b")
    shared = slot_b.blocks[:2]
    assert shared == slot_a.blocks[:2], "hit must map a's physical blocks"
    assert slot_b.cached == 2 and slot_b.cursor >= 2 * BS
    assert all(serving.allocator.refcount(b) == 2 for b in shared)

    done = {}
    while any(s.uid == "b" for s in serving.slots):
        for f in serving.step():
            done[f.uid] = f
    # b retired first: shared blocks still owned by a, NOT freed
    assert all(serving.allocator.refcount(b) == 1 for b in shared)
    assert all(b not in serving.allocator._free_set for b in shared)
    while serving.num_active:
        for f in serving.step():
            done[f.uid] = f
    # full drain: refcount 0, parked reclaimable, capacity fully available
    assert all(serving.allocator.refcount(b) == 0 for b in shared)
    assert serving.allocator.num_reclaimable >= 2
    assert serving.allocator.available == serving.allocator.capacity
    assert done["b"].cached_prefix_tokens == 2 * BS
    # parity for both against static generate
    for uid, p, n in (("a", pa, 12), ("b", pb, 4)):
        ref = engine.generate(p[None], max_new_tokens=n, stop_on_eos=False)
        np.testing.assert_array_equal(done[uid].tokens, ref[0])


def test_eviction_under_pressure_still_admits():
    """An oversubscribed pool: cached refcount-0 blocks must be reclaimed
    (hash unregistered, LRU first) the moment a fresh allocation would
    otherwise fail — caching never reduces usable capacity."""
    rng = np.random.default_rng(23)
    p1 = rng.integers(0, 256, (40,)).astype(np.int32)
    p2 = rng.integers(0, 256, (40,)).astype(np.int32)
    engine = _mk_engine()
    # 3 usable blocks; each request needs 3 (padded prompt 48) -> the second
    # request can only be admitted by evicting the first one's cached blocks
    serving = engine.serving(max_slots=1, max_context=48, prefill_chunk=BS,
                             num_kv_blocks=4, enable_prefix_caching=True)
    r1 = serving.run([Request(uid=1, tokens=p1, max_new_tokens=4,
                              stop_on_eos=False)])
    assert serving.allocator.num_reclaimable == 2     # 2 registered blocks
    r2 = serving.run([Request(uid=2, tokens=p2, max_new_tokens=4,
                              stop_on_eos=False)])
    assert serving.allocator.evictions == 2
    assert serving.stats()["prefix_cache"]["evictions"] == 2
    # p1's cache is gone (evicted): re-running it misses but still works
    r1b = serving.run([Request(uid=3, tokens=p1, max_new_tokens=4,
                               stop_on_eos=False)])
    for uid, res, p in ((1, r1, p1), (2, r2, p2)):
        ref = engine.generate(p[None], max_new_tokens=4, stop_on_eos=False)
        np.testing.assert_array_equal(res[uid].tokens, ref[0])
    np.testing.assert_array_equal(r1b[3].tokens, r1[1].tokens)
    assert_one_compile_each(serving)


def test_prompt_len_exactly_on_block_edge():
    """Boundary case: prompt_len == k * block_size. All k blocks register
    (every token sits strictly below prompt_len), but an identical re-prompt
    may hit at most k-1 — the final token must prefill so its logits can
    seed sampling. A LONGER prompt sharing the prefix hits all k."""
    rng = np.random.default_rng(24)
    edge = rng.integers(0, 256, (2 * BS,)).astype(np.int32)   # exactly 2 blocks
    longer = np.concatenate([edge, rng.integers(0, 256, (10,)).astype(np.int32)])
    engine = _mk_engine()
    serving = engine.serving(max_slots=1, max_context=96, prefill_chunk=BS,
                             enable_prefix_caching=True)
    runs = {}
    for uid, p in ((1, edge), (2, edge), (3, longer)):
        runs[uid] = serving.run([Request(uid=uid, tokens=p, max_new_tokens=4,
                                         stop_on_eos=False)])[uid]
    assert runs[1].cached_prefix_tokens == 0
    assert runs[2].cached_prefix_tokens == (2 - 1) * BS       # k-1 hit
    assert runs[3].cached_prefix_tokens == 2 * BS             # k hit
    np.testing.assert_array_equal(runs[1].tokens, runs[2].tokens)
    for uid, p in ((1, edge), (3, longer)):
        ref = engine.generate(p[None], max_new_tokens=4, stop_on_eos=False)
        np.testing.assert_array_equal(runs[uid].tokens, ref[0])
    assert_one_compile_each(serving)


def test_hit_truncated_to_chunk_grid_when_chunk_exceeds_block():
    """prefill_chunk > kv_block_size: the hit truncates to whole-chunk
    coverage, so the counters report only tokens whose prefill was ACTUALLY
    skipped (regression: a partial-chunk hit once counted as cached while
    its chunk re-ran in full) and no chunk ever rewrites a shared block."""
    rng = np.random.default_rng(26)
    prompt = rng.integers(0, 256, (58,)).astype(np.int32)   # 3 full 16-blocks
    engine = _mk_engine()
    serving = engine.serving(max_slots=1, max_context=96, prefill_chunk=32,
                             enable_prefix_caching=True)
    r1 = serving.run([Request(uid=1, tokens=prompt, max_new_tokens=4,
                              stop_on_eos=False)])[1]
    chunks_cold = serving.prefill_chunks                    # padded 64 -> 2
    r2 = serving.run([Request(uid=2, tokens=prompt, max_new_tokens=4,
                              stop_on_eos=False)])[2]
    # the match finds 3 registered blocks; only 2 (32 tokens) cover a whole
    # 32-token chunk, so exactly those count as cached and 1 chunk is saved
    assert r2.cached_prefix_tokens == 32
    assert serving.prefill_chunks - chunks_cold == chunks_cold - 1
    assert serving.prefill_chunks_skipped == 1
    assert serving.stats()["prefix_cache"]["hit_tokens"] == 32
    np.testing.assert_array_equal(r2.tokens, r1.tokens)
    ref = engine.generate(prompt[None], max_new_tokens=4, stop_on_eos=False)
    np.testing.assert_array_equal(r1.tokens, ref[0])


def test_arch_fingerprints_disjoint():
    """Two archs never share a hash chain even on identical token streams."""
    from deepspeed_tpu.models.gpt import gpt_cache_identity
    import dataclasses
    rot = dataclasses.replace(TINY, use_rotary=True)
    assert gpt_cache_identity(TINY, "a") != gpt_cache_identity(rot, "a")
    assert gpt_cache_identity(TINY, "a") != gpt_cache_identity(TINY, "b")
    spec = make_gpt_decode_model(cfg=TINY, name="tiny")
    assert spec.cache_fingerprint == gpt_cache_identity(TINY, "tiny")


def test_monitor_events_emitted_and_guarded():
    class _Capture:
        enabled = True

        def __init__(self):
            self.events = []

        def write_events(self, ev):
            self.events.extend(ev)

    rng = np.random.default_rng(25)
    prompts = _prompts_with_shared_prefix(rng, 32, (5, 7))
    # max_slots=1 serializes the two requests so the second one's admission
    # sees the first one's registered blocks (a same-step sibling would not)
    serving = _mk_engine().serving(max_slots=1, max_context=96,
                                   prefill_chunk=BS,
                                   enable_prefix_caching=True)
    serving.run([Request(uid=i, tokens=p, max_new_tokens=3,
                         stop_on_eos=False) for i, p in enumerate(prompts)])
    mon = _Capture()
    serving.write_monitor_events(mon)
    tags = {t for t, _, _ in mon.events}
    assert tags == {"Serving/prefix_hit_tokens", "Serving/prefix_evictions",
                    "Serving/pool_free_blocks"}
    hit = next(v for t, v, _ in mon.events if t == "Serving/prefix_hit_tokens")
    assert hit == serving.prefix_hit_tokens > 0
    free = next(v for t, v, _ in mon.events
                if t == "Serving/pool_free_blocks")
    assert free == serving.allocator.available
    # never-die contract: a missing or broken monitor must not raise
    serving.write_monitor_events(None)

    class _Broken:
        enabled = True

        def write_events(self, ev):
            raise RuntimeError("boom")

    serving.write_monitor_events(_Broken())
