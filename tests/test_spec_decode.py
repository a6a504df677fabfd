"""Speculative decoding on the paged KV pool (inference/spec_decode.py +
the ServingEngine verify step).

Everything here rides the `spec_decode` marker (tier-1; run alone with
`pytest -m spec_decode`). The correctness story is in three layers:

  * greedy PARITY: with any drafter — even one proposing garbage — the
    speculative engine must emit token-for-token what the plain serving
    engine emits (a draft is only accepted when it equals the target's own
    greedy choice, and the bonus token IS the target's choice);
  * O(1) ROLLBACK: rejection never moves a slot's blocks or table row —
    only the length cursor advances (by accepted+1), and rejected tokens'
    k/v is simply overwritten by later writes;
  * fixed shapes: one compile for the verify program across a whole ragged
    trace, exactly like the decode/prefill programs.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.engine import init_inference
from deepspeed_tpu.inference.kv_cache import blocks_needed, max_written_pos
from deepspeed_tpu.inference.scheduler import Request, _DECODE
from deepspeed_tpu.inference.spec_decode import (Drafter, accept_greedy,
                                                 ngram_propose)
from deepspeed_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                      make_gpt_decode_model)

pytestmark = pytest.mark.spec_decode

TINY = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=256,
                 vocab_size=256, dtype=jnp.float32, remat=False)
DRAFT = GPTConfig(n_layer=1, n_head=2, d_model=32, max_seq_len=256,
                  vocab_size=256, dtype=jnp.float32, remat=False)


def _mk_mesh():
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    return mesh_mod.init_mesh(MeshConfig(data=1, tensor=1, sequence=1,
                                         expert=1, pipe=1))


def _mk_engine(cfg=TINY, spec=None, **cfg_over):
    _mk_mesh()
    spec = spec or make_gpt_decode_model(cfg=cfg, name="tiny")
    return init_inference(model=spec, config={
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": 16, "max_out_tokens": 64, **cfg_over})


def _counting_model_spec(seed=0):
    """A model whose greedy decode COUNTS: argmax(t) = t+1 mod V. Blocks
    zeroed like the copy model, but the (untied) LM head is the embedding
    table rolled by one row — LN(wte[t]) has its biggest dot with
    lm_head[t+1] = wte[t]. Gives deterministic, all-distinct outputs for
    the EOS-position tests."""
    import dataclasses as dc
    cfg = dc.replace(TINY, tie_embeddings=False)
    params = init_gpt_params(cfg, seed=seed)
    params["blocks"]["attn_out_w"] = params["blocks"]["attn_out_w"] * 0.0
    params["blocks"]["mlp_down_w"] = params["blocks"]["mlp_down_w"] * 0.0
    params["lm_head"] = jnp.roll(params["wte"], 1, axis=0)
    return make_gpt_decode_model(cfg=cfg, name="count", params=params)


def _copy_model_spec(cfg=TINY, seed=0):
    """A model whose greedy decode COPIES its last token forever: block
    output projections zeroed, so the residual stream is just the token
    embedding (+ tiny positional noise) and the tied LM head's argmax is
    the input token itself. The deterministic high-acceptance regime the
    prompt-lookup drafter targets (real models do this on repetitive /
    extractive text; this one does it always)."""
    params = init_gpt_params(cfg, seed=seed)
    params["blocks"]["attn_out_w"] = params["blocks"]["attn_out_w"] * 0.0
    params["blocks"]["mlp_down_w"] = params["blocks"]["mlp_down_w"] * 0.0
    return make_gpt_decode_model(cfg=cfg, name="copy", params=params)


def _ragged_requests(rng, lens, max_new=12, **kw):
    return [Request(uid=i,
                    tokens=rng.integers(0, TINY.vocab_size, (L,))
                    .astype(np.int32),
                    max_new_tokens=max_new, stop_on_eos=False, **kw)
            for i, L in enumerate(lens)]


class JunkDrafter(Drafter):
    """Adversarial drafter: always proposes k uniform-random tokens —
    near-certain rejection. Parity and rollback must hold regardless."""

    name = "junk"

    def __init__(self, k, vocab, seed=0):
        self.k = int(k)
        self.vocab = int(vocab)
        self.rng = np.random.default_rng(seed)

    def propose(self, dec_slots, tok0, pos, tables):
        S = tok0.shape[0]
        drafts = self.rng.integers(0, self.vocab, (S, self.k)) \
            .astype(np.int32)
        lens = np.zeros((S,), np.int32)
        for s in dec_slots:
            lens[s.idx] = self.k
        return drafts, lens


# ----------------------------------------------------------------------
# unit layer: sizing math, n-gram proposals, acceptance rule
# ----------------------------------------------------------------------


def test_sizing_accounts_for_draft_overhang():
    # plain: prompt 14 padded 16, 6 new -> decode writes 5, top pos 18
    assert max_written_pos(14, 16, 6, 1) == 18
    # spec k=4: every verify writes its 4-draft overhang past the last
    # real decode write -> top pos 22, one more block
    assert max_written_pos(14, 16, 6, 1, spec_k=4) == 22
    assert blocks_needed(14, 16, 6, 16) == 2
    assert blocks_needed(14, 16, 6, 16, spec_k=4) == 2   # 22 // 16 + 1
    assert blocks_needed(14, 16, 6, 16, spec_k=14) == 3  # 32 // 16 + 1
    # max_new=1 never verifies: the overhang must NOT apply
    assert max_written_pos(16, 16, 1, 1, spec_k=8) == 15
    # spec replaces the window: window is ignored when spec_k > 0
    assert max_written_pos(14, 16, 6, 8, spec_k=4) == 22


def test_ngram_propose_prompt_lookup():
    hist = np.asarray([7, 1, 2, 3, 9, 9, 1, 2, 3], np.int32)
    # trailing [1,2,3] recurs at index 1 -> continuation [9, 9, 1, ...]
    np.testing.assert_array_equal(ngram_propose(hist, 3, max_n=4, min_n=1),
                                  [9, 9, 1])
    np.testing.assert_array_equal(ngram_propose(hist, 2, max_n=4, min_n=1),
                                  [9, 9])
    # most RECENT occurrence wins: trailing 5 matches index 3, not 0
    hist2 = np.asarray([5, 8, 8, 5, 6, 5], np.int32)
    np.testing.assert_array_equal(ngram_propose(hist2, 2, max_n=1, min_n=1),
                                  [6, 5])
    # no recurring n-gram of any length -> empty proposal
    assert ngram_propose(np.arange(8, dtype=np.int32), 4).size == 0
    # continuation clipped at history end
    hist3 = np.asarray([4, 4], np.int32)
    np.testing.assert_array_equal(ngram_propose(hist3, 4, max_n=2, min_n=1),
                                  [4])


def test_accept_greedy_rule():
    tgt = np.asarray([10, 11, 12, 13, 14], np.int32)   # k+1 target rows
    # full agreement: all 4 drafts + the bonus from the last row
    n, out = accept_greedy(np.asarray([10, 11, 12, 13]), tgt, 4)
    assert (n, out) == (4, [10, 11, 12, 13, 14])
    # first disagreement at i=2: keep 2, bonus = target row 2
    n, out = accept_greedy(np.asarray([10, 11, 99, 13]), tgt, 4)
    assert (n, out) == (2, [10, 11, 12])
    # zero-length draft degrades to exactly the plain decode step
    n, out = accept_greedy(np.asarray([10, 11, 12, 13]), tgt, 0)
    assert (n, out) == (0, [10])
    # padding past draft_len never accepted even if it matches
    n, out = accept_greedy(np.asarray([10, 11, 12, 13]), tgt, 2)
    assert (n, out) == (2, [10, 11, 12])


# ----------------------------------------------------------------------
# engine layer: parity, acceptance, rollback, compiles, EOS
# ----------------------------------------------------------------------


def _run_baseline(engine, reqs, **kw):
    serving = engine.serving(max_slots=3, max_context=64, prefill_chunk=16,
                             **kw)
    return serving.run([Request(uid=r.uid, tokens=r.tokens,
                                max_new_tokens=r.max_new_tokens,
                                eos_token_id=r.eos_token_id,
                                stop_on_eos=r.stop_on_eos) for r in reqs])


def test_greedy_parity_ngram_on_ragged_trace():
    """Speculative output must be token-identical to the PR 3 baseline on
    a mixed-length trace — and the verify program must compile once."""
    engine = _mk_engine()
    rng = np.random.default_rng(1)
    reqs = _ragged_requests(rng, (5, 11, 3, 8, 14, 2, 31, 17))
    base = _run_baseline(engine, reqs)
    serving = engine.serving(max_slots=3, max_context=64, prefill_chunk=16,
                             spec_decode={"drafter": "ngram", "draft_k": 4})
    out = serving.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(base[r.uid].tokens, out[r.uid].tokens)
    st = serving.stats()["spec_decode"]
    assert st["verify_steps"] > 0
    assert st["emitted_tokens"] == serving.tokens_generated - len(reqs)
    compiles = serving.compile_stats()
    assert compiles["verify_step"] == 1           # one compile, whole trace
    assert compiles["prefill_step"] == 1
    assert compiles["decode_step"] == 0           # verify REPLACED decode


def test_greedy_parity_model_drafter():
    """Draft-model drafter: an unrelated (different arch+seed) draft model
    must preserve parity; the target model drafting for ITSELF must hit
    100% acceptance — the strongest possible check that the draft pool's
    shadow prefill + shared block tables carry exactly the right KV."""
    engine = _mk_engine()
    rng = np.random.default_rng(2)
    reqs = _ragged_requests(rng, (5, 9, 17, 3, 12))
    base = _run_baseline(engine, reqs)

    draft = make_gpt_decode_model(cfg=DRAFT, name="tiny-draft", seed=7)
    serving = engine.serving(max_slots=3, max_context=64, prefill_chunk=16,
                             draft_spec=draft,
                             spec_decode={"drafter": "model", "draft_k": 3})
    out = serving.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(base[r.uid].tokens, out[r.uid].tokens)
    assert serving.compile_stats()["draft_steps"] == 1
    assert serving.compile_stats()["draft_prefill"] == 1

    self_draft = engine.serving(
        max_slots=3, max_context=64, prefill_chunk=16,
        draft_spec=engine.model_spec,
        spec_decode={"drafter": "model", "draft_k": 3})
    out2 = self_draft.run([Request(uid=r.uid, tokens=r.tokens,
                                   max_new_tokens=r.max_new_tokens,
                                   stop_on_eos=False) for r in reqs])
    for r in reqs:
        np.testing.assert_array_equal(base[r.uid].tokens, out2[r.uid].tokens)
    st = self_draft.stats()["spec_decode"]
    assert st["acceptance_rate"] == 1.0
    assert st["accepted_tokens_per_step"] > 1.0


def test_ngram_acceptance_on_repetitive_prompt():
    """The prompt-lookup regime: a copy-model (greedy output repeats) with
    a repetitive prompt must measure real acceptance — more than one token
    per sequence per model step — and expose it end to end through
    stats()["spec_decode"]."""
    engine = _mk_engine(spec=_copy_model_spec())
    pat = np.asarray([3, 1, 4, 1, 5], np.int32)
    prompt = np.tile(pat, 4)                       # repetitive history
    serving = engine.serving(max_slots=2, max_context=64, prefill_chunk=16,
                             spec_decode={"drafter": "ngram", "draft_k": 4})
    out = serving.run([Request(uid=0, tokens=prompt, max_new_tokens=16,
                               stop_on_eos=False)])
    st = serving.stats()["spec_decode"]
    assert st["acceptance_rate"] > 0
    assert st["accepted_tokens_per_step"] > 1.0
    assert len(out[0].tokens) == 16
    # fewer model steps than tokens: the whole point
    assert st["verify_steps"] < 16


def test_rollback_invariants_under_rejection():
    """Rejection is an O(1) cursor rewind: across every verify step the
    slot's block list and block-table row must be IDENTICAL, the cursor
    must advance by exactly the tokens emitted (1..k+1), and — with a
    drafter proposing pure junk — the output must still match baseline."""
    engine = _mk_engine()
    rng = np.random.default_rng(3)
    reqs = _ragged_requests(rng, (5, 11, 8), max_new=10)
    base = _run_baseline(engine, reqs)
    serving = engine.serving(max_slots=2, max_context=64, prefill_chunk=16,
                             spec_decode={"drafter": "ngram", "draft_k": 4})
    serving.drafter = JunkDrafter(4, TINY.vocab_size)   # force rejections
    for r in reqs:
        serving.submit(r)
    out = {}
    while serving.queue or serving.num_active:
        before = {s.idx: (s.uid, list(s.blocks), serving.tables[s.idx].copy(),
                          s.pos, len(s.emitted))
                  for s in serving.slots if s.state == _DECODE}
        for done in serving.step():
            out[done.uid] = done
        for idx, (uid, blocks, table, pos, n_emitted) in before.items():
            s = serving.slots[idx]
            if s.uid != uid:                        # retired this step
                continue
            assert s.blocks == blocks               # no realloc, ever
            np.testing.assert_array_equal(serving.tables[idx], table)
            advanced = s.pos - pos
            assert advanced == len(s.emitted) - n_emitted
            assert 1 <= advanced <= serving.draft_k + 1
    for r in reqs:
        np.testing.assert_array_equal(base[r.uid].tokens, out[r.uid].tokens)
    # junk acceptance is (essentially) zero -> one token per slot-step
    st = serving.stats()["spec_decode"]
    assert st["acceptance_rate"] < 0.2
    assert serving.compile_stats()["verify_step"] == 1


class OracleDrafter(Drafter):
    """Proposes the KNOWN true continuation (from a baseline run) — every
    draft is accepted, so a mid-draft event like EOS is deterministic."""

    name = "oracle"

    def __init__(self, k, continuation):
        self.k = int(k)
        self.cont = np.asarray(continuation, np.int32)

    def propose(self, dec_slots, tok0, pos, tables):
        S = tok0.shape[0]
        drafts = np.zeros((S, self.k), np.int32)
        lens = np.zeros((S,), np.int32)
        for s in dec_slots:
            nxt = self.cont[len(s.emitted):len(s.emitted) + self.k]
            drafts[s.idx, :nxt.shape[0]] = nxt
            lens[s.idx] = nxt.shape[0]
        return drafts, lens


def test_eos_inside_accepted_draft_retires_at_right_length():
    """An EOS landing INSIDE an accepted draft must retire the slot at the
    EOS position (accepted tail + bonus discarded), free its blocks, and
    report finish_reason='eos' — identical to the baseline's EOS cut. The
    oracle drafter pins the geometry: with draft_k=4, the baseline's token
    at index 2 is the SECOND accepted draft of the first verify step."""
    engine = _mk_engine(spec=_counting_model_spec())
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 128, (7,)).astype(np.int32)
    ref = _run_baseline(engine, [Request(uid=0, tokens=prompt,
                                         max_new_tokens=20,
                                         stop_on_eos=False)])[0].tokens
    # the counting model emits all-distinct tokens, so any position is a
    # legal first-occurrence EOS; pick one inside the first verify's draft
    assert len(set(int(t) for t in ref)) == len(ref)
    eos_pos = 2
    eos = int(ref[eos_pos])

    serving = engine.serving(max_slots=2, max_context=64, prefill_chunk=16,
                             spec_decode={"drafter": "ngram", "draft_k": 4})
    serving.drafter = OracleDrafter(4, ref)
    out = serving.run([Request(uid=0, tokens=prompt, max_new_tokens=20,
                               eos_token_id=eos)])[0]
    assert out.finish_reason == "eos"
    np.testing.assert_array_equal(out.tokens, ref[:eos_pos + 1])
    st = serving.stats()["spec_decode"]
    assert st["accepted_tokens"] > 0       # the EOS token WAS a draft
    # only whole-burst truncation explains fewer emitted than accepted+steps
    assert st["emitted_tokens"] == eos_pos + 1 - 1  # minus the prefill token
    # slot + every block back in circulation the same step
    assert serving.num_active == 0
    assert serving.allocator.num_free == serving.allocator.capacity


def test_spec_decode_requires_contract_and_draft_spec():
    engine = _mk_engine()
    import dataclasses as dc
    no_verify = dc.replace(engine.model_spec, verify_paged_fn=None)
    engine_nv = _mk_engine(spec=no_verify)
    with pytest.raises(ValueError, match="verify_paged_fn"):
        engine_nv.serving(max_slots=2, max_context=64,
                          spec_decode={"drafter": "ngram", "draft_k": 2})
    with pytest.raises(ValueError, match="draft_spec"):
        engine.serving(max_slots=2, max_context=64,
                       spec_decode={"drafter": "model", "draft_k": 2})
    with pytest.raises(ValueError, match="draft_k"):
        engine.serving(max_slots=2, max_context=64,
                       spec_decode={"drafter": "ngram", "draft_k": 0})
    # the symmetric mistake: a draft model passed but never consumed must
    # fail loudly, not silently serve non-speculatively
    draft = make_gpt_decode_model(cfg=DRAFT, name="d", seed=1)
    with pytest.raises(ValueError, match="draft_spec"):
        engine.serving(max_slots=2, max_context=64, draft_spec=draft)
    with pytest.raises(ValueError, match="draft_spec"):
        engine.serving(max_slots=2, max_context=64, draft_spec=draft,
                       spec_decode={"drafter": "ngram", "draft_k": 2})


def test_spec_decode_composes_with_prefix_caching():
    """A shared system prompt + spec decode: the second wave must hit the
    prefix cache (fewer prefill chunks) AND stay token-identical — cached
    blocks carry exactly the KV the verify step expects."""
    engine = _mk_engine()
    rng = np.random.default_rng(8)
    prefix = rng.integers(0, TINY.vocab_size, (32,)).astype(np.int32)
    tails = [rng.integers(0, TINY.vocab_size, (t,)).astype(np.int32)
             for t in (3, 7, 5)]
    mk = lambda base: [Request(uid=base + i,
                               tokens=np.concatenate([prefix, t]),
                               max_new_tokens=8, stop_on_eos=False)
                       for i, t in enumerate(tails)]
    base_out = _run_baseline(engine, mk(0))
    serving = engine.serving(max_slots=2, max_context=64, prefill_chunk=16,
                             enable_prefix_caching=True,
                             spec_decode={"drafter": "ngram", "draft_k": 3})
    cold = serving.run(mk(0))
    chunks_cold = serving.prefill_chunks
    warm = serving.run(mk(100))
    chunks_warm = serving.prefill_chunks - chunks_cold
    for i in range(len(tails)):
        np.testing.assert_array_equal(base_out[i].tokens, cold[i].tokens)
        np.testing.assert_array_equal(cold[i].tokens, warm[100 + i].tokens)
    assert chunks_warm < chunks_cold
    assert serving.stats()["prefix_cache"]["hit_blocks"] > 0


# ----------------------------------------------------------------------
# TPOT interpolation (satellite): window- and acceptance-aware, pinned
# with an injected clock
# ----------------------------------------------------------------------


def _mk_telemetry_engine(spec=None):
    return _mk_engine(spec=spec, telemetry={
        "enabled": True, "prometheus": False, "jsonl": False,
        "monitor_bridge": False})


def _drain_with_clock(serving, reqs, t, tick=1.0):
    for r in reqs:
        serving.submit(r)
    while serving.queue or serving.num_active:
        t["now"] += tick                      # one tick per scheduler sync
        serving.step()


def test_tpot_interpolates_across_decode_window():
    """Injected clock: with a K-token decode window, each burst of K
    tokens must land K samples of (sync interval / K) — not one sample of
    the whole interval, and not a single per-request mean. Trace: window
    4, max_new 9 -> prefill emits token 1 at t=1 and the first window is
    dispatched; the sync at t=2 dispatches the second and reads the first
    (tokens 2..5), the sync at t=3 reads the second (tokens 6..9) -> twice
    four samples of 1000ms/4 = 250ms, stamped at the read-back that
    delivered them."""
    t = {"now": 0.0}
    engine = _mk_telemetry_engine()
    serving = engine.serving(max_slots=1, max_context=64, prefill_chunk=16,
                             decode_steps_per_sync=4, clock=lambda: t["now"])
    rng = np.random.default_rng(0)
    reqs = [Request(uid=0, tokens=rng.integers(0, 256, (5,))
                    .astype(np.int32), max_new_tokens=9, stop_on_eos=False)]
    _drain_with_clock(serving, reqs, t)
    lat = serving.latency_snapshot()
    # 8 decode-phase tokens -> 8 per-token samples
    assert lat["tpot_ms"]["count"] == 8
    assert lat["tpot_ms"]["max"] == pytest.approx(250.0)
    assert lat["tpot_ms"]["min"] == pytest.approx(250.0)
    assert lat["tpot_ms"]["mean"] == pytest.approx(250.0)


def test_tpot_acceptance_aware_under_spec_decode():
    """Same injected clock under spec decode, fully deterministic via the
    copy model: every verify accepts all 4 drafts and emits 5 tokens, so
    each sync's interval spreads over exactly 5 samples. Trace (max_new
    11, prompt 16x the same token): prefill at t=1 emits token 1, the
    same-sync verify emits tokens 2..6 (dt 0), the t=2 verify emits
    tokens 7..11 -> five samples of 1000ms/5 = 200ms. The old
    one-token-per-step accounting would have logged a single 100ms mean
    per request and hidden the burst cadence entirely."""
    t = {"now": 0.0}
    engine = _mk_telemetry_engine(spec=_copy_model_spec())
    serving = engine.serving(max_slots=1, max_context=64, prefill_chunk=16,
                             clock=lambda: t["now"],
                             spec_decode={"drafter": "ngram", "draft_k": 4})
    prompt = np.full((16,), 7, np.int32)
    reqs = [Request(uid=0, tokens=prompt, max_new_tokens=11,
                    stop_on_eos=False)]
    _drain_with_clock(serving, reqs, t)
    st = serving.stats()["spec_decode"]
    assert st["verify_steps"] == 2
    assert st["accepted_tokens_per_step"] == 5.0
    lat = serving.latency_snapshot()
    assert lat["tpot_ms"]["count"] == 10          # every decode-phase token
    assert lat["tpot_ms"]["min"] == pytest.approx(0.0)
    assert lat["tpot_ms"]["max"] == pytest.approx(200.0)
    assert lat["tpot_ms"]["sum"] == pytest.approx(1000.0)
