"""SDAR-MoE (`models/sdar_moe.py`) served by DIFFUSION OVER BLOCKS through
`init_inference(...).serving(...)`: the block-causal mask, the denoise and
commit forwards, the call that commits whole blocks, and the scheduler's
bookkeeping around it — against the plain reference
(`benchmark/references/sdar_moe.py`: the whole sequence under the mask, no
cache, the sampler as a Python loop) at a small size on the CPU."""

import dataclasses
import hashlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.engine import (BLOCK_DIFFUSION_COUNTERS,
                                            BlockDiffusion)
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import gpt as gpt_mod
from deepspeed_tpu.models import moe_gpt, sdar_moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]

import harness  # noqa: E402

ref = harness.load_module("references", "sdar_moe")

B, MASK, VOCAB = 4, 511, 512
PUBLISHED = dict(
    model_type="sdar_moe", vocab_size=VOCAB, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, hidden_size=32,
    moe_intermediate_size=16, rope_theta=1000000, rms_norm_eps=1e-6,
    tie_word_embeddings=False, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, hidden_act="silu", attention_bias=False,
    rope_scaling=None, decoder_sparse_step=1, mlp_only_layers=[],
    use_sliding_window=False)
PAD = 32        # the reference's sequences run padded to multiples of this


def published(steps):
    return dict(PUBLISHED, generator=dict(
        block_length=B, mask_token_id=MASK, denoising_steps=steps,
        remasking="low_confidence_dynamic", confidence_threshold=0.9))


def lively(params, head=20.0, key=jax.random.PRNGKey(11)):
    """Weights at which a token depends on its context (at the initializer's
    0.02 a two-layer model answers every row alike): matrices at
    1.5 / sqrt(fan-in), the head times `head` (200: peaked logits, so that
    confidences pass the threshold and blocks end early)."""
    blocks = dict(params["blocks"])
    for i, name in enumerate(("attn_qkv_w", "attn_out_w", "moe_gate_w",
                              "moe_w_gate_up", "moe_w_down")):
        w = blocks[name]
        blocks[name] = jax.random.normal(
            jax.random.fold_in(key, i), w.shape, w.dtype) \
            * (1.5 / np.sqrt(w.shape[-2]))
    return {**params, "blocks": blocks, "lm_head": params["lm_head"] * head}


@pytest.fixture(scope="module")
def model():
    cfg = sdar_moe.sdar_moe_config(PUBLISHED, 256, B, dtype=jnp.float32)
    params = jax.jit(sdar_moe.sdar_moe_init_fn(
        cfg, dtype=jnp.float32, embedding_std=1.0))(jax.random.PRNGKey(3))
    return cfg, jax.jit(lively)(params), jax.jit(
        lambda p: lively(p, head=200.0))(params)


_engines = {}


def serving(model, steps=2, peaked=False, **knobs):
    """(engine, serving engine) at these settings — built once a module:
    every test leaves its engine drained, and an engine serves any number
    of runs (its counters and rings go on counting)."""
    key = (steps, peaked, tuple(sorted(knobs.items())))
    if key not in _engines:
        _engines[key] = _serving(model, steps, peaked, **knobs)
    return _engines[key]


def _serving(model, steps, peaked, **knobs):
    cfg, params, sharp = model
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    spec = sdar_moe.make_sdar_moe_decode_model(
        cfg, sdar_moe.generator(B, MASK, steps),
        params=sharp if peaked else params)
    engine = deepspeed_tpu.init_inference(spec, config={
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": 16, "max_out_tokens": 256})
    knobs = {"max_slots": 4, "max_context": 256, "prefill_chunk": 16,
             "blocks_per_call": 2, "prefill_chunks_per_step": 2, **knobs}
    return engine, engine.serving(**knobs)


SHAPES = [(21, 10), (16, 7), (3, 9), (40, 12), (35, 5), (18, 8)]


def requests(shapes=SHAPES, seed=0, **more):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, tokens=rng.integers(0, 500, (n,)),
                    max_new_tokens=m, stop_on_eos=False, **more)
            for i, (n, m) in enumerate(shapes)]


def reference_tokens(engine, reqs, steps):
    pub = published(steps)
    arch, sampler = ref.arch_from_config(pub), ref.sampler_from_config(pub)
    return {r.uid: ref.generate(engine.params, r.tokens, r.max_new_tokens,
                                arch, sampler, pad_to=PAD) for r in reqs}


# -- (b) the committed tokens are the reference sampler's --------------------


@pytest.mark.parametrize("steps,peaked", [(1, False), (2, False), (4, False),
                                          (4, True), (2, True)])
def test_served_tokens_are_the_reference_samplers(model, steps, peaked):
    """Prompts of every length mod 4 (one shorter than a block), `max_new`
    not a multiple of 4, chunks riding decode calls: a request's tokens are
    the reference sampler's. With peaked logits the rule ends blocks early
    (fewer than S denoise forwards a block)."""
    engine, srv = serving(model, steps, peaked)
    reqs = requests()
    done = srv.run(reqs)
    want = reference_tokens(engine, reqs, steps)
    for r in reqs:
        assert done[r.uid].finish_reason == "length"
        np.testing.assert_array_equal(done[r.uid].tokens, want[r.uid])
    stats = srv.stats()
    counted = stats["step_counters"]
    assert set(BLOCK_DIFFUSION_COUNTERS) <= set(counted)
    # (roles, not passes: a fused forward is a commit AND a denoise forward)
    roles = counted["denoise_forwards"] + counted["commit_forwards"]
    assert stats["generator"]["forwards"] == roles - counted["fused_forwards"]
    assert srv.fused_chunks > 0 and srv.compile_stats()["mixed_step"] == 1
    assert srv.compile_stats()["decode_step"] == 1
    assert srv.allocator.num_free == srv.allocator.capacity
    # every call opens its blocks together: a commit forward a block a call
    calls = [c for c in srv.steptrace.calls() if c.program != "prefill"]
    assert counted["commit_forwards"] == 2 * len(calls)
    # ... and the first of the two commits in the pass that opens the second
    assert counted["fused_forwards"] == len(calls)
    assert stats["generator"]["fused_forward_share"] == 0.5
    per_block = counted["denoise_forwards"] / counted["commit_forwards"]
    # (a first block that opens with 3 prompt tokens is clean sooner)
    assert per_block <= steps
    if peaked:
        assert per_block < steps - 0.2    # blocks that ended early
    for c in calls:
        # a call's one fused pass runs 2B rows a slot, the others B
        assert c.block_rows == B and c.win == (c.forwards + 1) * B
        assert c.emitted <= c.rows * 2 * B
    # the walk is booked where the forwards are: at the read-back, by the
    # walks the call took (a denoise or commit forward walks every live
    # slot's blocks once, a fused one twice)
    names = list(srv.step_counter_names)
    at = [names.index(n) for n in ("denoise_forwards", "commit_forwards")]
    booked = 0
    for r in srv.steptrace.records():
        walks = sum(r.counters[i] for i in at) if r.counters else 0
        assert (r.decode_live_blocks > 0) == (walks > 0)
        assert walks <= r.decode_live_blocks \
            <= walks * srv.max_slots * srv.nb
        booked += walks
    assert booked == roles


def test_unmask_rule_on_the_device_is_the_references(model):
    """`BlockDiffusion.unmask` against `unmask_rule`, row by row: ties, rows
    over the threshold, fewer masked rows than n, the mask token never
    drawn."""
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(64, B, VOCAB)).astype(np.float32) * 3
    logits[:, :, MASK] = 50.0                       # never drawn
    logits[::3, 1] = logits[::3, 2]                 # tied confidences
    logits[::4, 0, 7] = 40.0                        # over the threshold
    logits[::8, 3, 9] = 40.0
    masked = rng.random((64, B)) < 0.7
    x = np.where(masked, MASK, rng.integers(0, 500, (64, B))).astype(np.int32)
    with pytest.raises(ValueError, match="not built"):
        BlockDiffusion(B, MASK, 2, "low_confidence_static")
    gen, sampler = BlockDiffusion(B, MASK, 2), ref.Sampler(B, MASK, 2)
    for n in (0, 1, 2, 4):
        got_x, got_m, moved = jax.jit(gen.unmask)(
            jnp.asarray(logits).reshape(64 * B, VOCAB), jnp.asarray(x),
            jnp.asarray(masked), jnp.int32(n))
        for row in range(64):
            want_x, want_m = ref.unmask_rule(logits[row], x[row], masked[row],
                                             n, sampler)
            np.testing.assert_array_equal(np.asarray(got_x[row]), want_x)
            np.testing.assert_array_equal(np.asarray(got_m[row]), want_m)
            assert int(moved[row]) == int((masked[row] & ~want_m).sum())
        assert MASK not in np.asarray(got_x)[~np.asarray(got_m)]


# -- (a) every forward's logits and the committed K/V ------------------------


@pytest.mark.parametrize("prompt_len,chunk", [(16, 16), (21, 16), (40, 16),
                                              (43, 64), (3, 16)])
def test_forward_logits_and_committed_kv_match_the_reference(model,
                                                             prompt_len,
                                                             chunk):
    """The spec's own functions on a pool: the prompt's whole blocks
    prefilled in chunks of `chunk` (one chunk and several; a prompt whose
    length is and is not a multiple of 4), then two blocks of denoise and
    commit forwards — every forward's logits of all B rows, and the pool's
    rows of the committed blocks, against the reference's whole-sequence
    forward."""
    cfg, params, _ = model
    spec = sdar_moe.make_sdar_moe_decode_model(
        cfg, sdar_moe.generator(B, MASK, 2), params=params)
    pub = published(2)
    arch, sampler = ref.arch_from_config(pub), ref.sampler_from_config(pub)
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(0, 500, (prompt_len,)).astype(np.int32)
    trace = []
    ref.generate(params, prompt, 2 * B - prompt_len % B, arch, sampler,
                 trace=trace, pad_to=PAD)
    bs, nb, slots = 16, 8, 3
    pool = spec.init_paged_pool(1 + nb, bs, jnp.float32)
    tables = np.zeros((slots, nb), np.int32)
    tables[1] = 1 + rng.permutation(nb)             # slot 1; 0 and 2 are dead
    whole = prompt_len - prompt_len % B
    for start in range(0, whole, chunk):
        seg = prompt[start:min(start + chunk, whole)]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(seg)] = seg
        _, pool, _ = jax.jit(spec.prefill_paged_fn)(
            params, toks, np.asarray([start], np.int32),
            np.asarray([len(seg) - 1], np.int32), pool, tables[1][None])
    denoise = jax.jit(spec.denoise_paged_fn)
    for start, x, _masked, want, kind in trace:
        toks = np.zeros((slots, B), np.int32)
        toks[1] = x
        pos = np.asarray([0, start, 0], np.int32)
        got, pool, counts = denoise(params, toks, pos, pool, tables)
        np.testing.assert_allclose(np.asarray(got[B:2 * B]), want, rtol=2e-4,
                                   atol=2e-4)
        assert counts.shape == (4,)
    # the committed K/V: everything up to the end of the last block
    end = trace[-1][0] + B
    seq = np.concatenate([prompt[:whole]] + [t[1] for t in trace
                                             if t[4] == "commit"])
    kv = []
    ref.forward(params, jnp.asarray(seq), arch, kv=kv, pad_to=PAD)
    for layer, (k, v) in enumerate(kv):
        for leaf, want in (("k", k), ("v", v)):
            rows = np.asarray(pool[leaf][layer])[tables[1]]  # [nb,Hkv,bs,hd]
            rows = np.moveaxis(rows, 1, 2).reshape(nb * bs, *rows.shape[1:2],
                                                   rows.shape[-1])[:end]
            np.testing.assert_allclose(rows, np.asarray(want), rtol=2e-4,
                                       atol=2e-4)


# -- (c), (d) chunking and riding change nothing ----------------------------


def test_chunked_three_ways_is_one_pass_and_riding_changes_no_block(model):
    """A 40-token prompt in chunks of 16 (three), and of 64 (one pass);
    requests served one at a time (no chunk ever rides: `decode_step` and
    `prefill_step` alone) and together (chunks ride the block loop's
    forwards): the same tokens every way."""
    reqs = requests()
    _, alone = serving(model, max_slots=1)
    one_by_one = alone.run(reqs)
    assert alone.fused_chunks == 0
    assert alone.compile_stats() == {"decode_step": 1, "prefill_step": 1}
    _, riding = serving(model)
    together = riding.run(reqs)
    assert riding.fused_chunks > 0
    _, wide = serving(model, prefill_chunk=64)
    one_pass = wide.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(one_by_one[r.uid].tokens,
                                      together[r.uid].tokens)
        np.testing.assert_array_equal(one_pass[r.uid].tokens,
                                      together[r.uid].tokens)


ALIGNED = [(20, 10), (16, 7), (4, 9), (40, 12), (36, 5), (8, 8)]


@pytest.mark.parametrize("shapes", [SHAPES, ALIGNED],
                         ids=["any_prompt", "whole_blocks"])
@pytest.mark.parametrize("blocks_per_call", [1, 2, 4])
def test_blocks_per_call_changes_no_token(model, blocks_per_call, shapes):
    """... whatever a prompt's length mod B (`SHAPES`: a first block that
    opens with a prompt's tail is clean sooner), and every block of a call
    but its last commits in a fused forward: none at one block a call. With
    prompts of whole blocks (`ALIGNED`) every block takes its two denoise
    forwards and its commit, so a call's passes and rows are known."""
    reqs = requests(shapes)
    engine, srv = serving(model, blocks_per_call=blocks_per_call)
    before = dict(srv.stats().get("step_counters") or {})
    seen = len(srv.steptrace.calls())
    done = srv.run(reqs)
    want = reference_tokens(engine, reqs, 2)
    for r in reqs:
        np.testing.assert_array_equal(done[r.uid].tokens, want[r.uid])
    assert srv.window == blocks_per_call * B
    assert srv.ride_window == 3 * blocks_per_call - 2 * (blocks_per_call - 1)
    assert srv.allocator.num_free == srv.allocator.capacity
    counted = {name: n - before.get(name, 0)
               for name, n in srv.stats()["step_counters"].items()}
    calls = [c for c in srv.steptrace.calls()[seen:]
             if c.program != "prefill"]
    assert counted["fused_forwards"] == len(calls) * (blocks_per_call - 1)
    assert counted["commit_forwards"] == len(calls) * blocks_per_call
    roles = counted["denoise_forwards"] + counted["commit_forwards"]
    assert sum(c.forwards for c in calls) == roles - counted["fused_forwards"]
    assert sum(c.win for c in calls) == roles * B
    if shapes is SHAPES:
        assert counted["denoise_forwards"] <= 2 * counted["commit_forwards"]
        return
    assert counted["denoise_forwards"] == 2 * counted["commit_forwards"]
    for c in calls:
        assert c.forwards == 2 * blocks_per_call + 1
        assert c.win == 3 * blocks_per_call * B


@pytest.mark.parametrize("owed", [0, 1, 3, 8])
def test_chunks_owed_ride_the_forwards_of_b_rows_and_the_boundary_fuses(
        model, owed):
    """The SERVED `mixed_step` (`decode_step` where no chunk is owed) with
    `owed` chunks of a prompt riding a call of two blocks — up to
    `ride_window` x G = 4 x 2: none, one group half full, two groups, every
    forward of B rows carrying a full group — against the same chunks through
    `prefill_step` and then `decode_step`: the committed tokens of the
    running slots, the block loop's counters (the boundary between the two
    blocks fuses whatever rides: five passes for six roles) and the pool's
    rows of the riding prompt and of the committed blocks."""
    engine, srv = serving(model, prefill_chunks_per_step=6)
    G, ride, chunk, nb = (srv.programs.group, srv.ride_window, srv.chunk,
                          srv.nb)
    assert (G, ride) == (2, 4)
    rng = np.random.default_rng(owed)
    prompts = [rng.integers(0, 500, (n,)).astype(np.int32)
               for n in (16, 32, 8 * chunk)]
    tables = np.zeros((srv.max_slots, nb), np.int32)
    tables[:3] = 1 + np.arange(3 * nb).reshape(3, nb)
    programs, params = srv.programs, engine.params

    def chunk_args(slot, starts):
        toks = np.stack([prompts[slot][a:a + chunk] for a in starts])
        return (toks, np.asarray(starts, np.int32),
                np.full((len(starts),), chunk - 1, np.int32),
                np.tile(tables[slot], (len(starts), 1)))

    def prefilled():
        pool = jax.tree_util.tree_map(jnp.zeros_like, srv.pool)
        for slot in (0, 1):
            for start in range(0, len(prompts[slot]), chunk):
                _, pool = programs.prefill(
                    params, *(a[:1] for a in chunk_args(slot, [start])[:3]),
                    pool, tables[slot][None], srv._next_rng())
        return pool

    tok = np.zeros((srv.max_slots, B), np.int32)
    tok[:2] = MASK
    pos = np.asarray([16, 32, 0, 0], np.int32)
    shown = np.where((tok == MASK).any(1)[:, None], tables, 0)
    starts = [i * chunk for i in range(owed)]

    pool = prefilled()
    if owed:
        pad = lambda a: np.concatenate(
            [a, np.repeat(a[-1:], ride * G - owed, axis=0)]).reshape(
                (ride, G) + a.shape[1:])
        served, pool = programs.mixed(
            params, *(pad(a) for a in chunk_args(2, starts)),
            np.int32(owed), tok, pos, pool, shown, srv._next_rng())
    else:
        served, pool = programs.decode(params, tok, pos, pool, shown,
                                       srv._next_rng())
    apart = prefilled()
    for start in starts:
        _, apart = programs.prefill(
            params, *(a[:1] for a in chunk_args(2, [start])[:3]), apart,
            tables[2][None], srv._next_rng())
    alone, apart = programs.decode(params, tok, pos, apart, shown,
                                   srv._next_rng())

    (toks, counts), (want, want_counts) = jax.device_get((served, alone))
    np.testing.assert_array_equal(toks[:2], want[:2])
    names = list(srv.step_counter_names)
    loop = counts[[names.index(n) for n in BLOCK_DIFFUSION_COUNTERS]]
    assert list(loop[:3]) == [1, 4, 2] and loop[4] == 2 * 2
    np.testing.assert_array_equal(
        loop, want_counts[-len(BLOCK_DIFFUSION_COUNTERS):])
    for leaf in ("k", "v"):     # (block 0 is the trash block)
        np.testing.assert_allclose(np.asarray(pool[leaf][:, 1:]),
                                   np.asarray(apart[leaf][:, 1:]),
                                   rtol=2e-4, atol=2e-4)
    assert programs.compile_counts()["decode_step"] == 1
    assert programs.compile_counts().get("mixed_step", 1) == 1


@pytest.mark.parametrize("denoise,commit,fused", [(4, 2, 1), (4, 2, 0),
                                                  (3, 2, 1), (8, 4, 3)])
def test_a_calls_record_and_the_stats_count_passes_and_rows_apart(
        model, denoise, commit, fused):
    """`BlockDiffusionCalls.close` / `due` / `stats` on counters as a call
    reads them back: passes through the weights are denoise + commit - fused
    (`forwards`), rows a slot ran and walks are by role (`win`, what
    `sched_decode_useful_token_share.*` divides by; a fused forward is 2B
    rows and two walks)."""
    from deepspeed_tpu.telemetry.steptrace import CallRecord
    _, srv = serving(model)
    gen = srv.gen
    names = list(srv.step_counter_names)
    counts = np.zeros((len(names),), np.int64)
    for name, n in (("denoise_forwards", denoise), ("commit_forwards", commit),
                    ("fused_forwards", fused)):
        counts[names.index(name)] = n
    blank = CallRecord(*[0] * len(CallRecord._fields))
    rec = gen.close(blank._replace(win=gen.window * gen.row_forwards),
                    counts)
    assert rec.forwards == denoise + commit - fused
    assert rec.win == (denoise + commit) * B and rec.block_rows == B
    assert gen.close(blank, counts) is blank       # (a call with no window)
    # a forward's walk a block, booked by the walks the call took
    assert gen.due({"decode_live_blocks": 10}) == {}
    assert gen.due({"decode_live_blocks": 10}, rec) == {
        "decode_live_blocks": 10 * (denoise + commit) / gen.blocks_per_call}
    stats = gen.stats(dict(zip(names, counts)))
    assert stats["forwards"] == denoise + commit - fused
    assert stats["passes_per_block"] == (denoise + commit - fused) / commit
    assert stats["forwards_per_block"] == (denoise + commit) / commit
    assert stats["fused_forward_share"] == fused / commit
    if (denoise, commit, fused) == (4, 2, 1):       # the cell's call
        assert stats["passes_per_block"] == 2.5
        assert stats["forwards_per_block"] == 3.0   # (roles: S + 1)
        assert stats["fused_forward_share"] == 0.5


# -- (e) ends inside a block, in a call, in flight ---------------------------


def test_eos_inside_a_block_ends_the_request_there(model):
    engine, srv = serving(model)
    reqs = requests([(21, 12)])
    want = reference_tokens(engine, reqs, 2)[0]
    eos = int(want[5])
    first = int(np.flatnonzero(want == eos)[0])
    done = srv.run([Request(uid=0, tokens=reqs[0].tokens, max_new_tokens=12,
                            eos_token_id=eos, stop_on_eos=True)])[0]
    assert done.finish_reason == "eos"
    np.testing.assert_array_equal(done.tokens, want[:first + 1])
    assert srv.allocator.num_free == srv.allocator.capacity


def test_cancel_and_deadline_mid_call_and_a_slot_retiring_in_flight(model):
    """A cancel while the request's call is in flight reads that call first
    and keeps its tokens; a deadline retires a request between calls; a
    request that reaches `max_new` inside a call gives up its slot at
    dispatch, the next request's blocks run in the call behind it, and both
    get the reference's tokens."""
    engine, srv = serving(model, max_slots=2)
    clock = [0.0]
    srv.set_clock(lambda: clock[0])
    reqs = requests([(21, 30), (16, 80), (8, 6), (12, 9)])
    want = reference_tokens(engine, reqs, 2)
    srv.submit(reqs[0])
    srv.submit(dataclasses.replace(reqs[1], deadline_ms=50.0))
    for _ in range(4):
        srv.step()
    assert srv._pending is not None             # a call is in flight
    cancelled = srv.cancel(0)
    assert cancelled.finish_reason == "cancelled" and len(cancelled.tokens)
    np.testing.assert_array_equal(cancelled.tokens,
                                  want[0][:len(cancelled.tokens)])
    clock[0] = 1.0                              # past request 1's deadline
    finished = srv.step()
    late = [d for d in finished if d.uid == 1][0]
    assert late.finish_reason == "deadline"
    np.testing.assert_array_equal(late.tokens, want[1][:len(late.tokens)])
    done = srv.run(reqs[2:])
    for r in reqs[2:]:
        np.testing.assert_array_equal(done[r.uid].tokens, want[r.uid])
    left = [c for c in srv.steptrace.calls() if c.queued_behind]
    assert left                                 # calls went out behind calls
    assert srv.allocator.num_free == srv.allocator.capacity


def test_the_engine_refuses_what_the_generator_cannot_have(model):
    refused = {
        "spec_decode": dict(spec_decode={"drafter": "ngram"}),
        "kv_cache_dtype int8": dict(quantization={"kv_cache_dtype": "int8"}),
        "enable_prefix_caching": dict(enable_prefix_caching=True),
        "degradation": dict(degradation={"enabled": True}),
        "prefill_chunk 6": dict(prefill_chunk=6)}
    for what, knobs in refused.items():
        with pytest.raises(ValueError, match="diffusion over blocks") as e:
            _serving(model, 2, False, **knobs)
        assert what in str(e.value)
    engine, srv = serving(model)
    # ... and the contiguous cache's entries, which run the causal mask
    for entry in (engine.generate, engine.forward):
        with pytest.raises(ValueError, match="diffusion over blocks") as e:
            entry(np.zeros((1, 8), np.int32))
        assert "contiguous cache" in str(e.value)
    with pytest.raises(ValueError, match="block transplant"):
        srv.submit(requests([(8, 4)])[0], prefill_only=True)
    cfg = model[0]
    with pytest.raises(ValueError, match="block length"):
        moe_gpt.make_moe_gpt_decode_model(
            dataclasses.replace(cfg, block_length=1), params=model[1],
            generator=sdar_moe.generator(B, MASK))


# -- (f) the block-causal chunk walk against the dense oracle ----------------


@pytest.mark.parametrize("block_length", [1, 4])
def test_block_causal_chunk_walk_against_the_dense_oracle(block_length):
    """`dstpu_paged_prefill` (interpreted) with `block_length` against
    `_paged_attend` over the gathered table; at 1 it is today's causal mask,
    to the bit the call without the argument."""
    from deepspeed_tpu.ops.pallas.prefill_attention import \
        paged_prefill_attention
    rng = np.random.default_rng(block_length)
    H, Hkv, hd, bs, nb, C = 2, 1, 128, 128, 3, 128
    cfg = gpt_mod.GPTConfig(n_head=H, n_kv_head=Hkv, d_model=H * hd,
                            block_length=block_length, dtype=jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, C, H, hd)), jnp.float32)
    k_pool = jnp.asarray(rng.normal(size=(1 + 2 * nb, Hkv, bs, hd)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(1 + 2 * nb, Hkv, bs, hd)),
                         jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(2 * nb).reshape(2, nb),
                         jnp.int32)
    start = jnp.asarray([128, 64 * block_length], jnp.int32)
    more = {} if block_length == 1 else dict(block_length=block_length)
    got = paged_prefill_attention(q, k_pool, v_pool, tables, start, **more)
    gather = lambda pool: jnp.moveaxis(pool[tables], 2, 1).reshape(
        2, Hkv, nb * bs, hd)
    positions = start[:, None] + jnp.arange(C)[None]
    want = gpt_mod._paged_attend(q, gather(k_pool), gather(v_pool),
                                 positions, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    if block_length == 1:
        plain = paged_prefill_attention(q, k_pool, v_pool, tables, start)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))
        assert jax.jit(lambda *a: paged_prefill_attention(*a)).lower(
            q, k_pool, v_pool, tables, start).as_text() == jax.jit(
            lambda *a: paged_prefill_attention(*a, block_length=1)).lower(
            q, k_pool, v_pool, tables, start).as_text()
    else:
        # a row sees its whole block: not the causal result
        causal = paged_prefill_attention(q, k_pool, v_pool, tables, start)
        assert not np.allclose(np.asarray(got), np.asarray(causal))


def test_denoise_rows_through_the_decode_walk_equal_the_oracle(model,
                                                               monkeypatch):
    """The runner's path, interpreted: a block's B x G query rows a KV head
    through `dstpu_paged_decode` at the block's last position, against the
    gather oracle under the block-causal mask."""
    from deepspeed_tpu.ops import attention_dispatch
    cfg, params, _ = model
    rng = np.random.default_rng(2)
    wide = dataclasses.replace(cfg, attn_head_dim=128)
    big = jax.jit(lambda key: lively(sdar_moe.sdar_moe_init_fn(
        wide, dtype=jnp.float32)(key)))(jax.random.PRNGKey(0))
    tables = np.zeros((3, 2), np.int32)
    tables[0], tables[2] = [1, 2], [3, 4]
    toks = rng.integers(0, 500, (3, B)).astype(np.int32)
    pos = np.asarray([128, 0, 4], np.int32)

    def run(force):
        spec = sdar_moe.make_sdar_moe_decode_model(
            dataclasses.replace(wide, use_flash_attention=force),
            sdar_moe.generator(B, MASK, 2), params=big)
        pool = spec.init_paged_pool(5, 128, jnp.float32)
        pool = jax.tree_util.tree_map(
            lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype), pool)
        out = spec.denoise_paged_fn(big, toks, pos, pool, tables)
        return out[0], spec.paged_attn_programs

    rng = np.random.default_rng(7)
    oracle, programs = run(False)
    assert programs["paged_decode"] == "paged_gather"
    rng = np.random.default_rng(7)
    walked, programs = run(True)
    assert programs["paged_decode"] == "paged_kernel"
    live = [0, 2]
    rows = lambda a: np.asarray(a).reshape(3, B, -1)[live]
    np.testing.assert_allclose(rows(walked), rows(oracle), rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("force", [False, True], ids=["oracle", "walk"])
def test_a_fused_forward_is_a_commit_then_a_denoise_forward(model, force):
    """`denoise_paged_fn` with 2B rows a slot — a block's clean tokens and
    the next block's mask rows — against a commit forward of the first
    followed by a denoise forward of the second on the same pool: the second
    block's logits, both blocks' K/V rows and the routed counters, through
    the gather oracle and through `dstpu_paged_decode` (interpreted, a walk
    a group). Slot 0's first block is its pool block's last (`pos % block ==
    block - B`: the run of 2B rows straddles two pool blocks), slot 1 is
    dead, slot 2 has one block behind it, slot 3 sits inside a pool block."""
    cfg, _, _ = model
    bs, nb = 128, 3
    wide = dataclasses.replace(cfg, attn_head_dim=128,
                               use_flash_attention=force)
    params = jax.jit(lambda key: lively(sdar_moe.sdar_moe_init_fn(
        wide, dtype=jnp.float32)(key)))(jax.random.PRNGKey(0))
    spec = sdar_moe.make_sdar_moe_decode_model(
        wide, sdar_moe.generator(B, MASK, 2), params=params)
    rng = np.random.default_rng(9)
    tables = np.zeros((4, nb), np.int32)
    tables[0], tables[2], tables[3] = [1, 2, 3], [4, 5, 6], [7, 8, 9]
    pos = np.asarray([bs - B, 0, B, bs + 5 * B], np.int32)
    clean = rng.integers(0, 500, (4, B)).astype(np.int32)
    fresh = np.full((4, B), MASK, np.int32)
    filled = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        spec.init_paged_pool(1 + 3 * nb, bs, jnp.float32))
    forward = jax.jit(spec.denoise_paged_fn, static_argnames=("hidden",))

    _, pool, commit_counts = forward(params, clean, pos, filled, tables)
    want, pool, denoise_counts = forward(params, fresh, pos + B, pool, tables)
    got, fused_pool, counts = forward(
        params, np.concatenate([clean, fresh], axis=1), pos, filled, tables)
    assert spec.paged_attn_programs["paged_decode"] == (
        "paged_kernel" if force else "paged_gather")

    live = [0, 2, 3]
    rows = lambda a: np.asarray(a).reshape(4, B, -1)[live]
    assert got.shape == want.shape == (4 * B, VOCAB)
    np.testing.assert_allclose(rows(got), rows(want), rtol=2e-4, atol=2e-4)
    for leaf in ("k", "v"):     # (block 0 is the trash block: slot 1's rows)
        np.testing.assert_allclose(np.asarray(fused_pool[leaf][:, 1:]),
                                   np.asarray(pool[leaf][:, 1:]),
                                   rtol=2e-4, atol=2e-4)
        # ... and the 2B rows are there: slot 0's on both sides of the edge
        was = np.asarray(filled[leaf])
        now = np.asarray(fused_pool[leaf])
        assert (now[:, 1, :, bs - B:] != was[:, 1, :, bs - B:]).all()
        assert (now[:, 2, :, :B] != was[:, 2, :, :B]).all()
        np.testing.assert_array_equal(now[:, 2, :, B:], was[:, 2, :, B:])
    # one pass routed both groups' rows
    names = list(spec.step_counters)
    at = names.index("moe_assignments")
    assert counts[at] == commit_counts[at] + denoise_counts[at]
    # the rows the head reads, handed back in the logits' place
    hidden, _, _ = forward(params, np.concatenate([clean, fresh], axis=1),
                           pos, filled, tables, hidden=True)
    assert hidden.shape == (4 * B, wide.d_model)
    np.testing.assert_allclose(
        rows(jax.jit(spec.head_fn)(params, hidden)), rows(got), rtol=1e-5,
        atol=1e-5)


# -- (g) the other families' programs lower as the parent's ------------------


def _strip(text):
    text = re.sub(r'loc\([^)]*\)|#loc\d*( = .*)?', '', text)
    return re.sub(r'@(_?[A-Za-z_]+?)_\d+\b', r'@\1', text)


def _lowered_step_programs(kind):
    """The step programs of a tiny engine of `kind`, lowered as the scheduler
    calls them (`StepPrograms.examples`), locations stripped: name -> sha."""
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    if kind == "gpt":
        cfg = gpt_mod.GPTConfig(n_layer=2, n_head=4, n_kv_head=2, d_model=64,
                                vocab_size=256, max_seq_len=128,
                                use_rotary=True, use_swiglu=True,
                                use_rmsnorm=True, dtype=jnp.float32)
        spec = gpt_mod.make_gpt_decode_model(cfg, name="tiny")
    elif kind == "sdar_moe":
        cfg = sdar_moe.sdar_moe_config(PUBLISHED, 128, B, dtype=jnp.float32)
        params = sdar_moe.sdar_moe_init_fn(cfg, dtype=jnp.float32)(
            jax.random.PRNGKey(0))
        spec = sdar_moe.make_sdar_moe_decode_model(
            cfg, sdar_moe.generator(B, MASK, 2), params=params)
    else:
        cfg = moe_gpt.MoEGPTConfig(
            n_layer=2, n_head=4, n_kv_head=4, d_model=64, d_ff=32,
            vocab_size=256, max_seq_len=128, use_rotary=True, use_swiglu=True,
            use_rmsnorm=True, qk_norm=True, tie_embeddings=False,
            num_experts=8, top_k=2, moe_freq=1, dtype=jnp.float32)
        params = moe_gpt.moe_gpt_init_fn(cfg)(jax.random.PRNGKey(0))
        spec = moe_gpt.make_moe_gpt_decode_model(cfg, params=params,
                                                 name="tiny")
    engine = deepspeed_tpu.init_inference(spec, config={
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": 16, "max_out_tokens": 128})
    out = {}
    # (a block generator refuses spec decode: its own decode and mixed
    # programs, two blocks a call, and the chunk program)
    settings = ({"blocks_per_call": 2},) if kind == "sdar_moe" else (
        {"decode_steps_per_sync": 3},
        {"decode_steps_per_sync": 1,
         "spec_decode": {"drafter": "ngram", "draft_k": 3}})
    for more in settings:
        spec_decode = "spec_decode" in more
        srv = engine.serving(max_slots=4, max_context=128, prefill_chunk=16,
                             prefill_chunks_per_step=4, **more)
        for name, fn, args in srv.programs.examples(
                engine.params, srv.pool, srv._tables_arg(srv.tables),
                srv._rng):
            if spec_decode and name != "verify_step":
                continue
            text = _strip(jax.jit(fn).lower(*args).as_text())
            out[name] = hashlib.sha256(text.encode()).hexdigest()[:16]
    return out


@pytest.mark.parametrize("kind", ["gpt", "moe_gpt", "sdar_moe"])
def test_other_families_step_programs_lower_to_the_parents_text(kind):
    """`decode_step`, `prefill_step`, `mixed_step` and the spec-decode
    `verify_step` (whose chunk still has no runner: its rows are causal
    inside the chunk) of the dense and the routed GPT family at a tiny size,
    and since PR 58 the block generator's own three (`sdar_moe`: its
    `decode_step` and `mixed_step` are `_block_diffusion_steps`'):
    the hashes of their lowered text are those of the commit this PR started
    from (`tests/step_program_hashes.json`, written there by
    `_lowered_step_programs` on that commit). A PR that means to change
    these programs regenerates the file and says so."""
    with open(os.path.join(ROOT, "tests", "step_program_hashes.json")) as f:
        pinned = json.load(f)[kind]
    assert _lowered_step_programs(kind) == pinned


@pytest.mark.parametrize("family, cases, model, serving", [
    ("exaone_moe", "exaone_cases", {"held": (0, 8)}, {}),
    ("mimo_v2_flash", "mimo_cases", {"held": (0, 8)},
     {"prefill_chunks_per_step": 6}),
    ("glm4_moe_lite", "glm_cases", {"held": (0, 8)}, {})])
def test_the_held_and_latent_families_step_programs_lower_to_the_parents_text(
        family, cases, model, serving):
    """The other serving cells' families run the code PR 59 edited
    (`routed_experts`' combine, with half the experts held; `scan_paged`'s
    work list; `make_mixed_paged_fn`; MiMo a group of two chunks a token
    through `over_chunk_group`; GLM-4.7-Flash the latent pool): the three
    step programs of a tiny engine a family lower to the text they had on the
    commit PR 59 started from (`tests/step_program_hashes.json`, written
    there by this function's body in a `git archive` of 2bddd10)."""
    import importlib
    module = importlib.import_module("tests." + cases)
    cfg = module._cfg(**model)
    engine, srv = module._serving(cfg, module._params(cfg), one_device=True,
                                  **serving)
    assert srv.programs.group == (2 if family == "mimo_v2_flash" else 1)
    got = {}
    for name, fn, args in srv.programs.examples(
            engine.params, srv.pool, srv._tables_arg(srv.tables), srv._rng):
        text = _strip(jax.jit(fn).lower(*args).as_text())
        got[name] = hashlib.sha256(text.encode()).hexdigest()[:16]
    with open(os.path.join(ROOT, "tests", "step_program_hashes.json")) as f:
        assert got == json.load(f)[family]


# -- the benchmark's files ---------------------------------------------------


def test_benchmark_holds_the_cells_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[
        "serve_sdar_blockdiff_generate"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b-chat-6l", "blockdiff_generate_backlog", 1)
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    assert config["source"] == ("https://huggingface.co/JetLM/"
                                "SDAR-30B-A3B-Chat/blob/main/config.json")
    with open(os.path.join(ROOT, config["file"])) as f:
        file = json.load(f)
    assert config["reduced"] == file["reduced"] == ["num_hidden_layers"]
    assert file["reduced_from"] == {"num_hidden_layers": 48}
    for key, value in {
            "attention_bias": False, "decoder_sparse_step": 1,
            "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 6144, "max_position_embeddings": 32768,
            "max_window_layers": 48, "mlp_only_layers": [],
            "model_type": "sdar_moe", "moe_intermediate_size": 768,
            "norm_topk_prob": True, "num_attention_heads": 32,
            "num_experts": 128, "num_experts_per_tok": 8,
            "num_hidden_layers": 6, "num_key_value_heads": 4,
            "rms_norm_eps": 1e-06, "rope_scaling": None,
            "rope_theta": 1000000, "sliding_window": None,
            "tie_word_embeddings": False, "use_sliding_window": False,
            "vocab_size": 151936}.items():
        assert file[key] == value, key
    assert file["generator"] == {
        "block_length": 4, "mask_token_id": 151669, "denoising_steps": 2,
        "remasking": "low_confidence_dynamic", "confidence_threshold": 0.9}
    assert set(file["generator"]) <= set(file["assumed"])
    assert file["serving"]["max_slots"] == 128
    assert file["serving"]["blocks_per_call"] == 2
    for kind, name in (("drivers", file["driver"] + ".py"),
                       ("references", file["reference"] + ".py"),
                       ("traffic", cell["traffic"] + ".json"),
                       ("checks", "rehearsal_sdar.json")):
        assert os.path.exists(os.path.join(BENCH, kind, name)), name
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["min_queue"] == file["serving"]["max_slots"]
    reported = [m["name"] for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    assert sorted(reported) == ["serve_tokens_per_s", "setup_s"]
    # the table is full: the cell JOINS entries, it adds none
    assert len(bench["per_layer"]) <= 128
    layered = [m for m in bench["per_layer"]
               if cell["name"] in m.get("workloads", ())]
    assert len(layered) >= 20
    assert "moe_gmm_roofline.mixed" in [m["name"] for m in layered]
    for metric in layered:
        assert metric["moves"] == "serve_tokens_per_s"
        # appended: behind it stand only the cells later PRs appended
        names = metric["workloads"]
        assert set(names[names.index(cell["name"]) + 1:]) \
            <= {"serve_keyevl2_longctx_sparse_queue"}
        with open(os.path.join(BENCH, "layer_metrics",
                               metric["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))


def test_the_served_tree_counts_the_published_parameters():
    """6 layers at the published widths, counted from the built tree's
    SHAPES (nothing is allocated): 4,361,055,744 parameters, and the two
    zero biases the program's tree carries beside them."""
    with open(os.path.join(BENCH, "configs",
                           "sdar-30b-a3b-chat-6l.json")) as f:
        file = json.load(f)
    cfg = sdar_moe.sdar_moe_config(file, file["serving"]["max_context"],
                                   file["generator"]["block_length"])
    shapes = jax.eval_shape(sdar_moe.sdar_moe_init_fn(cfg, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    sizes = {"/".join(str(getattr(k, "key", k)) for k in path): int(
        np.prod(leaf.shape)) for path, leaf in
        jax.tree_util.tree_flatten_with_path(shapes)[0]}
    zero = {k: n for k, n in sizes.items() if k.endswith("_b")}
    assert sorted(zero) == ["blocks/attn_out_b", "blocks/attn_qkv_b"]
    assert sum(sizes.values()) - sum(zero.values()) == 4361055744
    layer = (sum(n for k, n in sizes.items() if k.startswith("blocks/"))
             - sum(zero.values())) // 6
    assert layer == 623120640


@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_the_cpu(trace):
    """The cell end to end at the rehearsal's tiny sizes (slow: a process of
    its own that compiles the three step programs, the check's scan and the
    reference; `benchmark/checks` runs the same rehearsal)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(BENCH, "checks", "rehearse_cell.py"),
           "--workload", "serve_sdar_blockdiff_generate", "--seed",
           str(2**31 + 17), "--seconds", "2", "--trace", str(trace)]
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    counted = line["notes"]["step_counters"]
    assert counted["blocks_committed"] > 0
    assert counted["denoise_forwards"] == 2 * counted["commit_forwards"]
    # two blocks a call: one of every two commits rides the next block's
    # first denoise forward, five passes for six roles
    assert 2 * counted["fused_forwards"] == counted["commit_forwards"]
    assert line["notes"]["generator"]["passes_per_block"] == 2.5
    assert line["notes"]["generator"]["forwards_per_block"] == 3.0
    assert line["notes"]["generator"]["fused_forward_share"] == 0.5
    assert line["notes"]["logits"]["served_call_counters_equal"] is True
    if trace:
        assert "sched_decode_useful_token_share.throughput" in line["metrics"]
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
