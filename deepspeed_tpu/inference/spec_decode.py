"""Speculative decoding on the paged KV pool — drafters + the verify math.

Decode is dispatch-latency- and HBM-bound at small batch: every model step
reads the whole weight set and the live KV prefix to emit ONE token per
slot. Speculative decoding (Leviathan et al., "Fast Inference from
Transformers via Speculative Decoding") turns that step into k+1 tokens'
worth of work whose *acceptance* decides the payout: a cheap DRAFTER
proposes k tokens per slot, one fixed-shape jitted VERIFY call scores all
of them for every slot at once (the chunked-prefill machinery at positions
pos..pos+k — `_paged_attend` already builds causal masks from absolute
positions), and the scheduler accepts the longest agreeing prefix plus one
bonus token from the first disagreeing logit row. Greedy output is
token-identical to non-speculative serving by construction: a draft is
accepted only when it equals the target model's own (greedy) choice.

The paged layout is what makes rejection FREE: a rejected draft just
doesn't advance the slot's length cursor. Its k/v was written past the
cursor, later steps overwrite those positions, and the causal mask (k_pos
<= q_pos) guarantees nothing ever attends beyond the cursor — no cache
copy, no block free/realloc, block table untouched. That O(1) rollback is
the invariant tests/test_spec_decode.py pins.

Two drafters, one interface (`Drafter`):

  * `NgramDrafter` — model-free prompt lookup: match the newest generated
    tokens against the slot's OWN prompt+output history and propose the
    continuation. Zero extra device work; shines exactly on the
    cache-heavy, template/shared-prefix workloads the prefix cache serves
    (summarize/extract/multi-turn — output copies input).
  * `DraftModelDrafter` — a second, smaller `DecodeModelSpec` (the paged
    contract required) runs k greedy decode steps per verify inside one
    jitted lax.scan. Its pool mirrors the target's block geometry and is
    indexed by the SAME block tables, so slot lifecycle, prefix sharing
    and the cursor-rewind rollback all transfer verbatim; its prefill
    shadows the target's chunked prefill chunk for chunk.

Acceptance is greedy exact-match against the verify step's sampled row
(under greedy sampling, the argmax). For stochastic sampling the same
exact-match rule is the conservative "sample-and-match" scheme — the
emitted token at each position is always the target model's own sample, so
the output distribution is preserved; upgrading the acceptance test to
true rejection sampling (accept with prob p_target/p_draft) only needs the
verify step to return probabilities instead of samples, which is the one
documented extension point (`ServingEngine._build_verify_fn`).
"""

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Drafter:
    """Drafter interface the serving scheduler drives.

    `propose` is the only required method: given the active decode slots
    and the fixed-shape step arrays the scheduler already built (last
    emitted token, cursor position and block table per slot row), return
    `(drafts [max_slots, k] int32, lens [max_slots] int32)` — `lens[i]`
    counts the REAL proposals in row i (the rest is padding the verify
    step scores but acceptance ignores; proposing fewer than k costs
    nothing but the padded compute). `prefill_chunk` lets a stateful
    drafter shadow the target's chunked prefill; `retire` announces a
    slot recycle."""

    name = "none"

    def prefill_chunk(self, slot, chunk, start, last_idx, table):
        pass

    def propose(self, dec_slots, tok0, pos, tables
                ) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError

    def retire(self, slot):
        pass

    def compile_stats(self):
        return {}


# ----------------------------------------------------------------------
# n-gram / prompt-lookup drafter
# ----------------------------------------------------------------------


def ngram_propose(history: np.ndarray, k: int, max_n: int = 4,
                  min_n: int = 1) -> np.ndarray:
    """Prompt-lookup proposal (Saxena's prompt-lookup decoding, the
    model-free n-gram drafter): find the MOST RECENT earlier occurrence of
    the history's trailing n-gram (longest n first) and propose the up-to-k
    tokens that followed it. Returns [<=k] int32 — empty when no n-gram of
    any tried length recurs.

    Host-side and allocation-light: one sliding-window equality per tried
    n over an int32 history that is at most max_context long."""
    L = int(history.shape[0])
    for n in range(min(max_n, L - 1), max(min_n, 1) - 1, -1):
        pat = history[L - n:]
        # windows[i] == history[i:i+n]; candidates exclude the pattern's
        # own position (i == L - n)
        windows = np.lib.stride_tricks.sliding_window_view(history, n)
        hits = np.nonzero((windows == pat).all(axis=1))[0]
        hits = hits[hits < L - n]
        if hits.size:
            # most recent occurrence wins — but prefer one with a FULL
            # k-token continuation: the hit nearest the end of history is
            # usually the freshest context, yet a hit whose continuation
            # runs off the end can propose almost nothing (on a cycling
            # history the latest hit is only `period` tokens from the
            # end — a structurally short draft every single step)
            full = hits[hits + n + k <= L]
            start = int(full[-1] if full.size else hits[-1]) + n
            cont = history[start:start + k]
            if cont.size:
                return cont.astype(np.int32)
    return np.zeros((0,), np.int32)


class NgramDrafter(Drafter):
    """Model-free drafter: each slot's own prompt+output history is the
    draft model. No device state, no extra compiles — `propose` is pure
    host work against arrays the scheduler already holds."""

    name = "ngram"

    def __init__(self, draft_k: int, max_n: int = 4, min_n: int = 1):
        self.k = int(draft_k)
        self.max_n = int(max_n)
        self.min_n = int(min_n)

    def propose(self, dec_slots, tok0, pos, tables):
        S = tok0.shape[0]
        drafts = np.zeros((S, self.k), np.int32)
        lens = np.zeros((S,), np.int32)
        for s in dec_slots:
            # history ends at the slot's last emitted token — the verify
            # input — so the proposal is its continuation
            hist = np.concatenate(
                [s.prompt, np.asarray(s.emitted, np.int32)])
            cont = ngram_propose(hist, self.k, self.max_n, self.min_n)
            drafts[s.idx, :cont.shape[0]] = cont
            lens[s.idx] = cont.shape[0]
        return drafts, lens


# ----------------------------------------------------------------------
# draft-model drafter
# ----------------------------------------------------------------------


def build_draft_program(decode_paged_fn, draft_k: int):
    """K-step greedy draft loop as ONE jitted program (the draft-model
    analog of the scheduler's decode window): feed each slot's last token,
    scan `draft_k` paged decode steps with argmax feedback, return the
    drafts [S, k] and the (donated) draft pool. Factored out of
    `DraftModelDrafter` so other draft-model consumers — the RLHF rollout
    in `runtime/hybrid_engine.py` is the natural one — can reuse the exact
    program instead of growing a second drafting loop."""

    def draft_steps(params, tok, pos, pool, tables):
        def body(carry, _):
            tok, pos, pool = carry
            # [:2]: a counted model (`step_counters`) returns its counts too
            logits, pool = decode_paged_fn(params, tok, pos, pool, tables)[:2]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, pos + 1, pool), nxt

        (_, _, pool), toks = jax.lax.scan(
            body, (tok, pos, pool), None, length=draft_k)
        return jnp.moveaxis(toks, 0, 1), pool

    return jax.jit(draft_steps, donate_argnums=(3,))


class DraftModelDrafter(Drafter):
    """Drafter driven by a second, smaller `DecodeModelSpec`.

    The draft model owns a paged pool with the TARGET's block geometry
    (same num_blocks, same block_size, its own layer/head shapes) indexed
    by the scheduler's own block tables — physical block b holds the
    target's KV for some token span in the target pool and the draft
    model's KV for the SAME span in the draft pool. Admission, retirement,
    prefix sharing and cursor-rewind rollback therefore need no drafter
    bookkeeping at all: the tables are the bookkeeping. Drafting runs k
    greedy decode steps for ALL slots in one jitted scan; prefill shadows
    the target's chunked prefill chunk-for-chunk (same [1, chunk] slices,
    same tables), so the draft cache is warm the moment a slot starts
    decoding. Cost per verify: k draft-model steps — size the draft model
    so that is small next to one target step.

    Caveat (documented, correctness-neutral): a slot ADOPTED via the
    disaggregated prefill/decode handoff transplants only the target
    pool's blocks, so the draft pool has no KV for its prompt — drafts for
    such a slot are garbage until enough accepted tokens rebuild context,
    and the verify step simply rejects them (output stays exact)."""

    name = "model"

    def __init__(self, serving, draft_spec, draft_k: int):
        from jax.sharding import NamedSharding, PartitionSpec
        from deepspeed_tpu.utils.tree import tree_cast

        missing = [n for n in ("decode_paged_fn", "prefill_paged_fn",
                               "init_paged_pool")
                   if getattr(draft_spec, n, None) is None]
        if missing:
            raise ValueError(
                f"draft model spec '{getattr(draft_spec, 'name', '?')}' has "
                f"no paged serving contract (missing {missing}); build it "
                f"with make_gpt_decode_model")
        self.spec = draft_spec
        self.k = int(draft_k)
        engine = serving.engine
        sharding = NamedSharding(engine.mesh, PartitionSpec())
        self.params = jax.device_put(
            tree_cast(draft_spec.params, engine.dtype), sharding)
        # mirror the target pool's placement story (scheduler __init__):
        # committed sharding up front so the first call of each program has
        # the same arg signature as every later call — no phantom compile.
        # The mirror takes the serving engine's EFFECTIVE kv dtype (the
        # quantization block may have picked int8 over the engine config),
        # so a quantized target gets an equally-quantized draft mirror —
        # the draft model's resident bytes halve along with the target's
        if serving.kv_quant:
            # same contract story as the scheduler's own pool build: a
            # legacy 3-arg draft init_paged_pool (or one that returns a
            # scale-less tree) gets the quantized-pool-contract pointer
            # instead of a bare arity/shape error
            try:
                pool = draft_spec.init_paged_pool(
                    serving.allocator.num_blocks, serving.block_size,
                    jnp.int8, serving.kv_group_size)
            except TypeError as e:
                raise ValueError(
                    f"draft model spec '{getattr(draft_spec, 'name', '?')}'"
                    f" init_paged_pool does not accept the 4-arg quantized "
                    f"form (num_blocks, block_size, dtype, kv_group_size) "
                    f"— it does not implement the quantized-pool contract "
                    f"(init_paged_kv_pool in models/gpt.py is the "
                    f"reference): {e}") from e
            if not (isinstance(pool, dict) and "k_scale" in pool):
                raise ValueError(
                    f"draft model spec '{getattr(draft_spec, 'name', '?')}'"
                    f" init_paged_pool returned no k_scale/v_scale leaves "
                    f"for dtype int8 — it does not implement the "
                    f"quantized-pool contract")
        else:
            pool = draft_spec.init_paged_pool(
                serving.allocator.num_blocks, serving.block_size,
                jnp.dtype(serving.kv_cache_dtype))
        self.pool = jax.device_put(pool, sharding)
        self._draft_steps = build_draft_program(draft_spec.decode_paged_fn,
                                                self.k)

        def prefill(params, toks, start, last_idx, pool, table):
            return draft_spec.prefill_paged_fn(params, toks, start, last_idx,
                                               pool, table)[1]

        self._prefill = jax.jit(prefill, donate_argnums=(4,))

    def prefill_chunk(self, slot, chunk, start, last_idx, table):
        # shadow the target's chunk: same tokens, same cursor, same table —
        # the draft logits are discarded (the TARGET's prefill logits seed
        # the first token; the draft model only ever needs its cache warm)
        self.pool = self._prefill(self.params, chunk, start, last_idx,
                                  self.pool, table)

    def propose(self, dec_slots, tok0, pos, tables):
        drafts, self.pool = self._draft_steps(self.params, jnp.asarray(tok0),
                                              jnp.asarray(pos), self.pool,
                                              jnp.asarray(tables))
        # dstpu: ignore[DT001]: drafts are consumed host-side by accept_greedy — one readback per verify, amortized over k drafts x all slots
        drafts = np.asarray(jax.device_get(drafts))
        lens = np.zeros((tok0.shape[0],), np.int32)
        for s in dec_slots:
            lens[s.idx] = self.k
        return drafts, lens

    def compile_stats(self):
        return {"draft_prefill": int(self._prefill._cache_size()),
                "draft_steps": int(self._draft_steps._cache_size())}


def make_drafter(serving, cfg, draft_spec=None) -> Optional[Drafter]:
    """Build the configured drafter for a ServingEngine (None = spec decode
    off). `cfg` is the `ServingConfig.spec_decode` block."""
    kind = str(cfg.drafter or "off")
    if kind == "off":
        return None
    if int(cfg.draft_k) < 1:
        raise ValueError(f"spec_decode.draft_k must be >= 1 when the "
                         f"drafter is on (got {cfg.draft_k})")
    if kind == "ngram":
        return NgramDrafter(cfg.draft_k, max_n=cfg.ngram_max,
                            min_n=cfg.ngram_min)
    if kind == "model":
        if draft_spec is None:
            raise ValueError(
                "spec_decode.drafter='model' needs a draft DecodeModelSpec: "
                "engine.serving(draft_spec=make_gpt_decode_model(...))")
        return DraftModelDrafter(serving, draft_spec, cfg.draft_k)
    raise ValueError(f"unknown spec_decode.drafter {kind!r} "
                     f"(expected 'off', 'ngram' or 'model')")


def accept_greedy(draft_row: np.ndarray, target_row: np.ndarray,
                  draft_len: int) -> Tuple[int, List[int]]:
    """Longest-agreeing-prefix acceptance for one slot.

    `draft_row` [k]: the proposed tokens; `target_row` [k+1]: the verify
    step's sampled token per position (row i is the target's choice AFTER
    draft i — under greedy sampling, the argmax); `draft_len`: how many
    proposals are real. Returns `(n_accepted, emitted)` where emitted =
    the accepted drafts plus the bonus token from the first disagreeing
    row — always 1..k+1 tokens, so even a zero-length draft degrades to
    exactly the plain decode step (one target-sampled token)."""
    n = 0
    while n < draft_len and int(draft_row[n]) == int(target_row[n]):
        n += 1
    return n, [int(t) for t in draft_row[:n]] + [int(target_row[n])]
