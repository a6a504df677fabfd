"""Granite 4.0-H family (`models/granite_moe_hybrid.py`) on the paged serving
path, through the hybrid loop it shares with Nemotron-H (`models/hybrid.py`):
the whole-sequence forward, chunked prefill and decode through the pool and
the state kind, and the MIXED program, each against the float32 reference's
full forward as LOGITS; the scheduler end to end; the served programs'
routing as one more result; the step ring's state fields; and each of the
family's multipliers shown to matter. The halves' pieces are
`tests/test_granite_moe_hybrid_layers.py`.

Everything at a small size on the CPU; `tests/granite_cases.py` has the
configuration and the reference the two files share."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import granite_moe_hybrid as gh
from tests.granite_cases import (LAYERS, MULTIPLIERS, _arch, _cfg, _params,
                                 _serving, ref)

# float32: the program and the reference differ by summation order (and the
# chunked form of the recurrence) alone. bfloat16: 8 bits of mantissa through
# ten halves of width 32 on the CPU.
_TOLERANCE = {"float32": (3e-4, 3e-4), "bfloat16": (0.05, 0.08)}
CHUNK, BLOCK, SLOTS, NB = 16, 16, 3, 6


def _errors(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return (float(np.sqrt(np.square(got - want).sum()
                          / np.square(want).sum())),
            float(np.abs(got - want).max() / np.abs(want).max()))


def _assert_close(got, want, dtype="float32"):
    rms, worst = _errors(got, want)
    rms_tol, max_tol = _TOLERANCE[dtype]
    assert rms <= rms_tol and worst <= max_tol, (rms, worst)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_gives_the_references_logits(dtype):
    jdtype = jnp.dtype(dtype)
    cfg = _cfg(jdtype, held=(4, 8))
    params = _params(cfg, seed=1, dtype=jdtype)
    toks = np.random.default_rng(0).integers(0, 128, (2, 45)).astype(np.int32)
    got = jax.jit(lambda p, t: gh.granite_moe_hybrid_forward(p, t, cfg))(
        params, jnp.asarray(toks))
    for row in range(2):
        _assert_close(got[row], ref.logits(params, jnp.asarray(toks[row]),
                                           _arch(cfg)), dtype)


def _paged(cfg, params):
    """The family's spec, a pool of its two kinds and one table row a slot
    (slot i: blocks 1 + i NB ..., state row 1 + i)."""
    spec = gh.make_granite_moe_hybrid_decode_model(cfg, params=params)
    pool = spec.init_paged_pool(1 + SLOTS * NB, BLOCK, jnp.float32,
                                state_rows=1 + SLOTS)
    kv = 1 + np.arange(SLOTS * NB, dtype=np.int32).reshape(SLOTS, NB)
    rows = 1 + np.arange(SLOTS, dtype=np.int32)[:, None]
    return spec, pool, kv, rows


def _prefill(spec, params, pool, prompt, kv, row, compare, want):
    """`prompt` through `prefill_paged_fn` a chunk at a time into one slot;
    EVERY chunk's last logits are compared."""
    for start in range(0, len(prompt), CHUNK):
        part = prompt[start:start + CHUNK]
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :len(part)] = part
        out, pool, _ = spec.prefill_paged_fn(
            params, toks, np.array([start], np.int32),
            np.array([len(part) - 1], np.int32), pool, (kv[None], row[None]))
        compare(out[0], want[start + len(part) - 1])
    return pool, int(np.asarray(out[0]).argmax())


def test_chunked_prefill_and_decode_through_the_pool_give_the_logits():
    """A prompt of three chunks (the third a part of one: the state stops at
    the last real position) and one shorter than a chunk, prefilled through
    the carried state and the pool, then decoded together with a dead slot
    between them; LOGITS at every chunk's end and every decode token against
    the reference's full forward of the same sequence."""
    cfg = _cfg(held=(4, 8))
    params = _params(cfg, seed=2)
    spec, pool, kv, rows = _paged(cfg, params)
    rng = np.random.default_rng(3)
    steps, live = 7, (0, 2)
    seqs = {0: list(rng.integers(0, 128, (37,))),
            2: list(rng.integers(0, 128, (5,)))}
    # the reference sees the whole sequence, so the program's greedy tokens
    # are found first (prefill + decode), then compared position by position
    got = {s: [] for s in live}
    for s in live:
        pool, nxt = _prefill(spec, params, pool, np.asarray(seqs[s]), kv[s],
                             rows[s], lambda g, w: got[s].append(g),
                             [None] * 64)
        seqs[s].append(nxt)
    for _ in range(steps):
        tok, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        tables, srows = np.zeros_like(kv), np.zeros_like(rows)
        for s in live:
            tok[s], pos[s] = seqs[s][-1], len(seqs[s]) - 1
            tables[s], srows[s] = kv[s], rows[s]
        out, pool, _ = spec.decode_paged_fn(params, tok, pos, pool,
                                            (tables, srows))
        for s in live:
            got[s].append(out[s])
            seqs[s].append(int(np.asarray(out[s]).argmax()))
    for s, n in zip(live, (37, 5)):
        want = np.asarray(ref.logits(
            params, jnp.asarray(seqs[s][:-1], jnp.int32), _arch(cfg)))
        ends = [min(c + CHUNK, n) - 1 for c in range(0, n, CHUNK)]
        where = ends + list(range(n, n + steps))
        assert len(where) == len(got[s])
        _assert_close(np.stack(got[s]), want[where])


def test_the_mixed_program_gives_the_references_logits():
    """One call: a prompt's chunk riding a decode token of two live slots —
    the chunk's state read, scanned and written and the slots' states
    rewritten in one program; the chunk's and the slots' LOGITS against the
    reference's full forward."""
    cfg = _cfg()
    params = _params(cfg, seed=5)
    spec, pool, kv, rows = _paged(cfg, params)
    rng = np.random.default_rng(7)
    noop = lambda g, w: None
    seqs = {1: list(rng.integers(0, 128, (21,))),
            2: list(rng.integers(0, 128, (9,)))}
    for s in seqs:
        pool, nxt = _prefill(spec, params, pool, np.asarray(seqs[s]), kv[s],
                             rows[s], noop, [None] * 64)
        seqs[s].append(nxt)
    riding = rng.integers(0, 128, (27,)).astype(np.int32)    # slot 0
    got_chunk, got = [], {1: [], 2: []}
    for start in range(0, len(riding), CHUNK):
        part = riding[start:start + CHUNK]
        chunk = np.zeros((1, CHUNK), np.int32)
        chunk[0, :len(part)] = part
        tok, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        tables, srows = np.zeros_like(kv), np.zeros_like(rows)
        for s in seqs:
            tok[s], pos[s] = seqs[s][-1], len(seqs[s]) - 1
            tables[s], srows[s] = kv[s], rows[s]
        logits, pool, counts = spec.mixed_paged_fn(
            params, chunk, np.array([start], np.int32),
            np.array([len(part) - 1], np.int32), (kv[:1], rows[:1]), tok, pos,
            pool, (tables, srows))
        assert logits.shape == (1 + SLOTS, cfg.vocab_size)
        # every row is routed: the chunk's positions and a token a slot
        assert int(counts[1]) + int(counts[4]) \
            == len(LAYERS) * (CHUNK + SLOTS) * cfg.top_k
        got_chunk.append(logits[0])
        for s in seqs:
            got[s].append(logits[1 + s])
            seqs[s].append(int(np.asarray(logits[1 + s]).argmax()))
    arch = _arch(cfg)
    want = np.asarray(ref.logits(params, jnp.asarray(riding), arch))
    _assert_close(np.stack(got_chunk), want[[CHUNK - 1, len(riding) - 1]])
    for s, n in ((1, 21), (2, 9)):
        want = np.asarray(ref.logits(
            params, jnp.asarray(seqs[s][:-1], jnp.int32), arch))
        _assert_close(np.stack(got[s]), want[n:])


def _requests(lengths, seed=11):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, tokens=rng.integers(0, 128, (n,), np.int32),
                    max_new_tokens=m, stop_on_eos=False)
            for i, (n, m) in enumerate(lengths)]


@pytest.mark.parametrize("window", [1, 3])
def test_the_scheduler_serves_the_family_through_all_three_programs(window):
    """`init_inference(...).serving(...)` on one device: chunks ride decode
    calls, slots are reused, and every request's tokens are the float32
    reference's greedy tokens on the same sequence."""
    cfg = _cfg(held=(4, 8))
    params = _params(cfg, seed=4, embedding_std=0.004)
    engine, srv = _serving(cfg, params, one_device=True, max_slots=2,
                           decode_steps_per_sync=window)
    reqs = _requests([(37, 9), (5, 12), (16, 7), (50, 5), (3, 11)], seed=6)
    done = srv.run(reqs)
    assert srv.fused_chunks > 0
    assert srv.compile_stats() == {"decode_step": 1, "prefill_step": 1,
                                   "mixed_step": 1}
    arch = _arch(cfg)
    for r in reqs:
        seq = np.concatenate([r.tokens, done[r.uid].tokens])
        want = np.asarray(ref.logits(params, jnp.asarray(seq[:-1]), arch))
        np.testing.assert_array_equal(
            done[r.uid].tokens, want.argmax(-1)[len(r.tokens) - 1:],
            err_msg=f"request {r.uid}")
    stats = srv.stats()
    kinds = stats["kv_pool_kinds"]
    assert kinds["full"]["layers"] == 1 and kinds["state"]["layers"] == 4
    assert kinds["state"]["blocks"] == 1 + 2 and kinds["state"]["block"] == 0
    # the five held counters: every layer routes in every call, half of the
    # sixteen experts are held here, the others' rows are counted and left
    moe = stats["step_counters"]
    assert moe["moe_router_calls"] > 0 \
        and moe["moe_router_calls"] % len(LAYERS) == 0
    assert moe["moe_assignments"] > 0 and moe["moe_routed_elsewhere"] > 0
    assert (moe["moe_assignments"] + moe["moe_routed_elsewhere"]) \
        % (len(LAYERS) * cfg.top_k) == 0
    assert srv.allocator.num_free == srv.allocator.capacity


def test_state_fields_of_the_step_ring_count_the_mamba_halves():
    cfg = _cfg()
    engine, srv = _serving(cfg, _params(cfg))
    srv.run(_requests([(37, 9), (5, 12)]))
    recs = srv.steptrace.records(-np.inf, np.inf)
    H, P, N = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size
    token = 2 * LAYERS.count("mamba") * H * P * N * 4   # read + write, f32
    for r in recs:
        assert r.ssm_state_bytes == r.decoding * srv.window * token
        assert r.ssm_chunk_tokens == r.prefill_chunks * srv.chunk
    assert sum(r.ssm_chunk_tokens for r in recs) == (3 + 1) * 16
    assert sum(r.ssm_state_bytes for r in recs) > 0


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_routing_is_one_more_result_of_the_served_programs(program):
    cfg = _cfg()
    params = _params(cfg, seed=8)
    spec = gh.make_granite_moe_hybrid_decode_model(cfg, params=params)
    toks = np.random.default_rng(2).integers(0, 128, (1, 16)).astype(np.int32)

    def dense(params, tokens):
        chosen = []
        gh.granite_moe_hybrid_forward(params, tokens, cfg, routing=chosen)
        return jnp.stack([jnp.sort(e, axis=-1).reshape(tokens.shape + (-1,))
                          for e in chosen])

    want = np.asarray(jax.jit(dense)(params, jnp.asarray(toks)))
    assert want.shape == (len(LAYERS), 1, 16, cfg.top_k)
    # ... which are the reference's own sets
    ref_sets = ref.forward(params, jnp.asarray(toks[0]), _arch(cfg))[1]
    np.testing.assert_array_equal(want[:, 0], ref_sets)
    pool = spec.init_paged_pool(4, 16, jnp.float32, state_rows=3)
    tables = (np.array([[1, 2]], np.int32), np.array([[2]], np.int32))
    n = 16 if program == "prefill" else 15
    out = spec.prefill_paged_fn(
        params, np.where(np.arange(16) < n, toks, 0), np.zeros(1, np.int32),
        np.array([n - 1], np.int32), pool, tables, routing=True)
    assert len(out) == 4
    np.testing.assert_array_equal(out[3][:, :, :n], want[:, :, :n])
    if program == "decode":
        out = spec.decode_paged_fn(params, toks[:, 15], np.array([15]),
                                   out[1], tables, routing=True)
        assert out[3].shape == (len(LAYERS), 1, 1, cfg.top_k)
        np.testing.assert_array_equal(out[3][:, :, 0], want[:, :, 15])


@pytest.mark.parametrize("name, wrong", [
    ("scale_attn", True),               # 1 / sqrt(head_dim), not 1 / 128
    ("embedding_multiplier", 1.0),
    ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0),
])
def test_each_multiplier_matters(name, wrong):
    """The program with one of the family's four scalars at another family's
    value is NOT the reference: the limit that holds the right program (3e-4)
    is missed by orders of magnitude."""
    cfg = _cfg()
    assert getattr(cfg, name) == MULTIPLIERS[name] != wrong
    params = _params(cfg, seed=9, embedding_std=0.05)
    # scores that say something: drawn at 0.02 every softmax is flat
    for trees in params["runs"]:
        for tree in trees:
            if "attn_qkv_w" in tree:
                tree["attn_qkv_w"] = tree["attn_qkv_w"] * 60.0
    toks = np.random.default_rng(1).integers(0, 128, (1, 40)).astype(np.int32)
    want = ref.logits(params, jnp.asarray(toks[0]), _arch(cfg))
    right = gh.granite_moe_hybrid_forward(params, jnp.asarray(toks), cfg)[0]
    _assert_close(right, want)
    off = dataclasses.replace(cfg, **{name: wrong})
    got = gh.granite_moe_hybrid_forward(params, jnp.asarray(toks), off)[0]
    assert _errors(got, want)[0] > 30 * _TOLERANCE["float32"][0]


def test_the_model_spec_refuses_the_paths_it_does_not_serve():
    cfg = _cfg()
    spec = gh.make_granite_moe_hybrid_decode_model(cfg, params=_params(cfg))
    with pytest.raises(ValueError, match="int8 pool is not built"):
        spec.init_paged_pool(8, 16, jnp.int8, state_rows=5)
    with pytest.raises(ValueError, match="state_rows"):
        spec.init_paged_pool(8, 16, jnp.float32)
    with pytest.raises(NotImplementedError, match="paged scheduler only"):
        spec.prefill_fn()
    assert spec.verify_paged_fn is None
    assert "wte" in spec.params and "lm_head" not in spec.params


def test_config_is_the_familys_whatever_is_passed():
    cfg = _cfg(tie_embeddings=False, norm_topk_prob=False, use_swiglu=False)
    assert cfg.tie_embeddings and cfg.norm_topk_prob and cfg.use_swiglu
    assert cfg.halves == "MEME*EMEME" and cfg.n_layer == 5
    with pytest.raises(ValueError, match="a letter a layer"):
        _cfg(pattern=("ME", "XE"))
    with pytest.raises(ValueError, match="not a range"):
        _cfg(held=(12, 8))
