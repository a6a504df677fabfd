"""Paged KV-cache pool — the serving engine's memory system.

vLLM's PagedAttention insight mapped onto the existing blocked cache layout
(`TpuInferenceConfig.kv_block_size`): instead of one contiguous
[B, Hkv, M, hd] slab per generate() call, the engine owns a SINGLE pool of
physical [block, hd] KV blocks allocated once at init —
``k/v: [L, num_blocks, Hkv, block, hd]`` — and each serving slot holds a
block TABLE mapping its logical blocks to physical pool blocks. The decode
kernel (`ops/pallas/decode_attention.paged_decode_attention`) walks a row's
logical blocks and resolves them through the scalar-prefetched table, so:

  * no per-request cache allocation, ever — admission is a free-list pop;
  * a sequence's memory is freed the step it emits EOS (continuous batching
    can admit a queued request into the freed blocks immediately);
  * fragmentation is bounded to < one block per sequence.

Block 0 is RESERVED as the trash block: inactive slots point every table
entry at it, so the fixed-shape decode step can run over all slots — the
writes of dead slots land in the trash block and their reads produce garbage
the scheduler never looks at. This is what keeps the decode program's shape
(and therefore its compile) constant for the lifetime of the engine.

The allocator is deliberately host-side and stdlib-only: block alloc/free
happens at request admission/retirement (a few times per second), not in the
per-token hot loop, which stays a single fixed-shape jitted call.

Prefix caching (`inference/prefix_cache.py`) layers on the allocator's
REFERENCE COUNTS: a physical block shared by several sequences (same prompt
prefix) is freed only when its last reader retires, and a refcount-0 block
whose content is still registered in the prefix cache parks on a
"reclaimable" LRU list instead of the free list — its KV stays resurrectable
for future hits, but `alloc()` treats it as available and evicts it (via the
`on_evict` hook, which unregisters the hash) the moment a fresh allocation
would otherwise fail. Caching therefore never reduces usable capacity.

A model whose layers are of two KINDS (`CacheKind`: full attention beside
sliding-window attention) gets a pool of two kinds. The full kind is the pool
above, its blocks the allocator's. The window kind never needs more of a
sequence than its window and the chunk being written, so each slot owns a
fixed RING of `ring_blocks(...)` physical blocks a window layer, addressed
`logical block mod ring` (`ring_tables`: the same `[slots, table]` form the
kernels already take, so a walk needs a lower bound and nothing else), never
allocated and never freed; its block 0 is a trash block too.

A layer with RECURRENT state (a state-space layer) keeps a third kind
(`CacheKind(state=True)`): one state a slot whatever the context's length,
beside the blocks of the model's attention layers — see `CacheKind`.
"""

import collections
import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

TRASH_BLOCK = 0  # physical block 0: write sink for inactive slots


@dataclasses.dataclass(frozen=True)
class CacheKind:
    """What one kind of layer keeps of a sequence: `layers` layers, each
    holding either the whole context in allocator blocks (`window` 0; `block`
    is then the engine's `kv_block_size`) or the last `window` positions in
    a per-slot ring of `block`-token blocks; `leaves` names its leaves in
    the pool pytree (K and V, each with the kind's own heads and width; the
    keys in two leaves where `ops/pallas/kv_pool.py::kv_leaf_shapes` splits
    them; a latent kind's one) and `entry_values` counts the values the
    MODEL's entry has a position a layer (what the leaves store may pad it;
    0: not said). `state`: the kind keeps no token at all but
    one recurrent STATE a slot (`block` and `window` 0; `leaves` its state
    leaves, `[layers, 1 + slots, ...]` with row 0 the trash row): nothing is
    allocated, freed or walked, a program finds a slot's row by `state_rows`,
    zeroes it where a prompt begins (`start_pos == 0`) and hands it from one
    call to the next. `index_topk` > 0: a position's entry has an INDEX KEY
    beside K and V (the leaf `ik`: a learned sparse-attention indexer,
    `models/sparse_attn.py`), and a query attends the `index_topk` cached
    positions the indexer scores highest and no other."""
    name: str
    layers: int
    block: int
    window: int = 0
    leaves: tuple = ("k", "v")
    state: bool = False
    entry_values: int = 0
    index_topk: int = 0


def state_rows(slots: int) -> np.ndarray:
    """The state kind's "tables", fixed for an engine's lifetime: slot s's
    state is row `1 + s` of each of its layers (0 is the trash row).
    `[slots, 1]` int32."""
    return ring_tables(slots, 1, 1)


def ring_blocks(window: int, block: int, chunk: int, decode_steps: int) -> int:
    """Blocks in a slot's ring of one window layer: enough that the positions
    a call WRITES (a prefill chunk, or a decode window's steps) never alias a
    position one of its queries can still see — window + the longest write,
    starting anywhere in a block."""
    return -(-(window + max(chunk, decode_steps)) // block) + 1


def ring_tables(slots: int, table_blocks: int, ring: int) -> np.ndarray:
    """The window kind's block tables, fixed for an engine's lifetime: slot
    s's logical block j lives at physical `1 + s * ring + j % ring` (0 is
    the kind's trash block). `[slots, table_blocks]` int32."""
    j = np.arange(table_blocks, dtype=np.int32) % ring
    return 1 + np.arange(slots, dtype=np.int32)[:, None] * ring + j[None]


class BlockAllocator:
    """Ref-counted free-list over the physical blocks of a paged KV pool.

    Block 0 (TRASH_BLOCK) is never handed out. alloc() is all-or-nothing:
    a request either gets every block it needs or stays queued — partial
    allocation would deadlock two half-admitted requests against each other.

    Every allocated block carries a refcount (1 at alloc). `incref` adds a
    reader (a prefix-cache hit mapping the block into another slot's table);
    `free` is a DECREF — the block returns to circulation only at zero. A
    zero-refcount block that `is_cached` claims (its content hash is still
    registered) moves to the reclaimable LRU instead of the free list; it is
    recycled lazily, oldest first, only when alloc() finds the free list
    short, calling `on_evict(block)` so the cache unregisters the hash
    before the block's KV can be overwritten.

    The free list is a list (deterministic pop order: low ids first) + a
    shadow set, so the double-free guard is O(1) per freed block instead of
    an O(n) list scan.
    """

    def __init__(self, num_blocks: int, policy: str = "lru"):
        assert num_blocks >= 2, "pool needs >= 1 usable block past the trash block"
        assert policy in ("lru", "none"), \
            f"unknown reclaim policy {policy!r} (expected 'lru' or 'none')"
        self.num_blocks = num_blocks
        self.policy = policy
        self._free = list(range(num_blocks - 1, 0, -1))  # pop() yields low ids first
        self._free_set = set(self._free)
        self._refs = {}                     # block -> refcount (0 = reclaimable)
        self._reclaimable = collections.OrderedDict()  # LRU: oldest first
        self.is_cached = None               # hook: block -> bool (prefix cache)
        self.on_evict = None                # hook: block evicted -> unregister
        self.evictions = 0

    @property
    def capacity(self) -> int:
        """Usable blocks (the trash block is not allocatable)."""
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_reclaimable(self) -> int:
        return len(self._reclaimable)

    @property
    def available(self) -> int:
        """Blocks an alloc() can actually obtain: free + reclaimable. This,
        not num_free, is the admission-backpressure quantity — cached
        refcount-0 blocks are usable capacity, merely lazily recycled."""
        return len(self._free) + len(self._reclaimable)

    def refcount(self, b: int) -> int:
        return self._refs.get(b, 0)

    def _push_free(self, b: int):
        self._free.append(b)
        self._free_set.add(b)

    def _evict_one(self):
        """Recycle the least-recently-parked reclaimable block: unregister
        its cached content (on_evict) and hand it to the free list."""
        b, _ = self._reclaimable.popitem(last=False)
        del self._refs[b]
        self.evictions += 1
        if self.on_evict is not None:
            self.on_evict(b)
        self._push_free(b)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop n blocks, or None (and no state change) if fewer are
        available. Reclaimable cached blocks are evicted LRU-first, but only
        as many as the free list is short — eviction never runs ahead of
        demand."""
        if n > self.available:
            return None
        while len(self._free) < n:
            self._evict_one()
        got = []
        for _ in range(n):
            b = self._free.pop()
            self._free_set.discard(b)
            self._refs[b] = 1
            got.append(b)
        return got

    def incref(self, b: int) -> int:
        """Add a reader to an allocated or reclaimable block (prefix-cache
        hit). A reclaimable block is resurrected: it leaves the LRU and its
        KV content becomes live again without a copy."""
        assert b != TRASH_BLOCK, "incref of the trash block"
        assert b in self._refs and b not in self._free_set, \
            f"incref of unallocated block {b}"
        self._refs[b] += 1
        if b in self._reclaimable:
            del self._reclaimable[b]
        return self._refs[b]

    def flush_reclaimable(self, keep: int = 0) -> int:
        """Demand-independent reclaim (the degradation ladder's "aggressive
        prefix-cache reclaim" rung): evict parked refcount-0 cached blocks
        NOW — oldest first, down to `keep` survivors — instead of lazily at
        the next failing alloc. Trades future prefix-cache hits for
        immediately-free blocks under pool pressure. Returns the number of
        blocks evicted."""
        n = 0
        while len(self._reclaimable) > max(0, int(keep)):
            self._evict_one()
            n += 1
        return n

    def free(self, blocks: List[int]):
        """Decref each block. At zero: cached blocks (per `is_cached`) park
        on the reclaimable LRU (policy 'lru'); everything else — and all
        blocks under policy 'none' — returns to the free list, cached
        content unregistered on the spot."""
        for b in blocks:
            assert b != TRASH_BLOCK, "freeing the trash block"
            assert b not in self._free_set, f"double free of block {b}"
            assert self._refs.get(b, 0) > 0, f"free of unallocated block {b}"
            self._refs[b] -= 1
            if self._refs[b] > 0:
                continue
            cached = self.is_cached is not None and self.is_cached(b)
            if cached and self.policy == "lru":
                self._reclaimable[b] = None     # most-recently-parked end
            else:
                # policy "none" unregisters on the spot but does NOT count
                # as an eviction: `evictions` means demand-driven reclaim
                # (pool pressure), not routine retirement
                if cached and self.on_evict is not None:
                    self.on_evict(b)
                del self._refs[b]
                self._push_free(b)


def max_written_pos(prompt_len: int, padded_prompt: int, max_new: int,
                    window: int = 1, spec_k: int = 0) -> int:
    """Highest cache position a request ever WRITES — the single source of
    truth for pool sizing (blocks_needed) AND admission validation (the
    scheduler's table-width check); two copies of this math drifting apart
    would let a request scribble past its allocated blocks.

    Chunked prefill writes the padded prompt's tail (masked garbage,
    overwritten by decode as it advances), and decode writes token i's k/v
    at prompt_len + i for i in [0, max_new-1) — the final sampled token is
    emitted without a decode step, so it never lands in the cache. With a
    decode window (`decode_steps_per_sync` > 1) the device runs whole
    windows blindly, so the max_new-1 decode writes round UP to a window
    multiple (the tail of the last window is garbage the scheduler
    discards — but it was written).

    Speculative decoding (`spec_k` > 0 draft tokens per verify step —
    replaces the decode window): every verify call writes the k/v of its
    input token AND all k drafts, positions pos..pos+k, and a slot still
    verifies while one token short of its budget, so the write extent grows
    by the k-token draft overhang past the last real decode write. A max_new=1
    request never verifies (its only token comes from prefill logits), so
    the overhang only applies when there are decode writes at all.
    """
    decode_writes = max_new - 1
    if spec_k > 0 and decode_writes > 0:
        decode_writes += spec_k
    elif window > 1 and decode_writes > 0:
        decode_writes = -(-decode_writes // window) * window
    return max(padded_prompt - 1, prompt_len - 1 + decode_writes)


def blocks_needed(prompt_len: int, padded_prompt: int, max_new: int,
                  block_size: int, window: int = 1, spec_k: int = 0) -> int:
    """Physical blocks a request occupies for its whole lifetime (see
    max_written_pos for the write-extent reasoning)."""
    return max_written_pos(prompt_len, padded_prompt, max_new,
                           window, spec_k) // block_size + 1


def _transplant_jit(src_pool, src_idx, dst_pool, dst_idx):
    def copy_leaf(dst_leaf, src_leaf):
        return dst_leaf.at[:, dst_idx].set(
            jnp.take(src_leaf, src_idx, axis=1))
    return jax.tree_util.tree_map(copy_leaf, dst_pool, src_pool)


# destination donated: XLA aliases the scatter in place instead of copying
# the whole (potentially multi-GB) pool per handoff; the caller re-binds
# `engine.pool` to the result, exactly like the serving step programs
_transplant_jit = jax.jit(_transplant_jit, donate_argnums=(2,))


def transplant_blocks(src_pool, src_blocks, dst_pool, dst_blocks,
                      pad_to: Optional[int] = None):
    """Copy physical KV blocks across two pools — the prefill->decode
    handoff primitive (`deepspeed_tpu/serving/`): a slot prefilled on one
    engine replica moves into another replica's pool by copying just its
    blocks and rebuilding the block table there.

    The paged layout makes this a block-indexed gather: every pool leaf is
    ``[L, num_blocks, ...]`` (axis 1 is the physical-block axis — the
    `init_paged_kv_pool` contract), so the copy is one `take` along axis 1
    per leaf scattered into the destination's block slots, jitted with the
    destination DONATED so the update aliases in place. `pad_to` pins the
    index width (pad entries copy trash->trash, whose content is garbage
    by contract): pass the destination's table width so every handoff
    shares ONE compiled copy program instead of one per block count.

    Returns the updated destination pool (the caller re-binds
    `engine.pool`; the old buffer is donated/dead). Both pools must share
    leaf structure, block size, and dtype; the trash block is never a
    legal source or destination for REAL entries.
    """
    assert len(src_blocks) == len(dst_blocks), \
        f"transplant width mismatch: {len(src_blocks)} vs {len(dst_blocks)}"
    assert TRASH_BLOCK not in src_blocks and TRASH_BLOCK not in dst_blocks, \
        "transplant of the trash block"
    for d, s in zip(jax.tree_util.tree_leaves(dst_pool),
                    jax.tree_util.tree_leaves(src_pool)):
        if d.dtype != s.dtype:
            raise ValueError(f"pool dtype mismatch: {d.dtype} vs {s.dtype}")
    if not src_blocks:
        return dst_pool
    src_blocks, dst_blocks = list(src_blocks), list(dst_blocks)
    if pad_to is not None and pad_to > len(src_blocks):
        pad = pad_to - len(src_blocks)
        src_blocks += [TRASH_BLOCK] * pad
        dst_blocks += [TRASH_BLOCK] * pad
    return _transplant_jit(src_pool, jnp.asarray(src_blocks, jnp.int32),
                           dst_pool, jnp.asarray(dst_blocks, jnp.int32))


def gather_block_kv(pool_k_l, pool_v_l, block_tables):
    """Materialize each row's logical KV as contiguous [B, Hkv, nb*block, hd].

    The XLA fallback path for paged attention (short contexts / CPU harness /
    alibi + sliding-window archs): one gather per layer per step. The Pallas
    kernel exists precisely to NOT pay this — it resolves the table inside
    the block index map — but the gathered form keeps a dense oracle for
    numerics and covers every arch flag.

    pool_[kv]_l: [N, Hkv, block, hd] (one layer's pool; each leaf has its
    own heads and width); block_tables: [B, nb].
    """
    return (gather_block_leaf(pool_k_l, block_tables),
            gather_block_leaf(pool_v_l, block_tables))


def gather_block_leaf(pool_l, block_tables):
    """`gather_block_kv` for one leaf [N, heads, block, width] ->
    [B, heads, nb*block, width]."""
    B, nb = block_tables.shape
    _, heads, bm, width = pool_l.shape
    return jnp.moveaxis(pool_l[block_tables], 2, 1).reshape(
        B, heads, nb * bm, width)


def gather_block_kv_dequant(pool_l, block_tables, dtype):
    """Dequantizing gather for an INT8 paged pool layer — the quantized
    path's XLA fallback AND the quantized kernel's parity oracle, in one
    definition (the same role `gather_block_kv` plays for the fp pool).

    `pool_l` is one layer's quantized pool slice: ``k``/``v`` int8
    [N, Hkv, block, hd] plus ``k_scale``/``v_scale`` f32
    [N, Hkv, block, hd//g] (the `init_paged_kv_pool` int8 layout — scales
    ride the SAME physical-block axis as the payload, which is what lets
    `transplant_blocks` move a block's scales with its bytes for free).
    Gathers payload and scales through the table with the ordinary block
    gather, then dequantizes via `quantization.dequantize_kv` — int8 × f32
    scale, narrowed to `dtype` last, exactly the in-kernel ordering."""
    from deepspeed_tpu.inference.quantization import dequantize_kv
    k, v = gather_block_kv(pool_l["k"], pool_l["v"], block_tables)
    ks, vs = gather_block_kv(pool_l["k_scale"], pool_l["v_scale"],
                             block_tables)
    return dequantize_kv(k, ks, dtype), dequantize_kv(v, vs, dtype)
