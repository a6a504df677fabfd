"""K-EXAONE family (`models/exaone_moe.py`) on the paged serving path: the
layer pattern as data, the two-kind pool through the scheduler against the
float32 reference, the served programs' routing as one more result, and what
is refused. The layer's pieces (the expert share, the router, the windowed
walks on a ring) are `tests/test_exaone_moe_layers.py`.

Everything at a small size on the CPU; `tests/exaone_cases.py` has the
configuration and the reference the two files share."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.inference.kv_cache import ring_blocks, ring_tables
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import exaone_moe as em
from deepspeed_tpu.models.gpt import GPTConfig
from tests.exaone_cases import G, L, _arch, _cfg, _params, _serving, ref


# ----------------------------------------------------------------------
# the layer pattern as data
# ----------------------------------------------------------------------


@pytest.mark.parametrize("layers, mlps, period, want", [
    ((L, L, L, L, G), ("dense",) + ("sparse",) * 4, 4, (1, 4, 1)),
    ((L, L, L, G) * 12, ("dense",) + ("sparse",) * 47, 4, (4, 4, 11)),
    ((L,) + (L, L, L, G) * 2, ("dense",) + ("sparse",) * 8, 4, (1, 4, 2)),
    ((L,) + (L, L, L, G) * 2, ("dense",) + ("sparse",) * 8, 0, (1, 4, 2)),
    ((G, G, G), ("sparse",) * 3, 0, (0, 1, 3)),
], ids=["one-chip-cut", "published-48", "two-periods", "period-found",
        "uniform"])
def test_layer_plan_is_a_prologue_and_whole_periods(layers, mlps, period,
                                                    want):
    cfg = _cfg(n_layer=len(layers), layer_types=layers, mlp_layer_types=mlps,
               pattern_period=period)
    prologue, body, periods = em.layer_plan(cfg)
    assert (len(prologue), len(body), periods) == want
    assert prologue + body * periods == list(zip(layers, mlps))


def test_layer_plan_refuses_a_pattern_that_is_not_whole_periods():
    layers = (L, L, G)           # shorter than one period
    cfg = _cfg(n_layer=3, layer_types=layers,
               mlp_layer_types=("sparse",) * 3, pattern_period=4)
    with pytest.raises(ValueError, match="whole periods"):
        em.layer_plan(cfg)


# ----------------------------------------------------------------------
# program against reference, through the scheduler
# ----------------------------------------------------------------------

# float32: the program and the reference differ by summation order alone.
# bfloat16: 8 bits of mantissa through five post-normed layers of width 32;
# the CPU gives ~1% rms at this size (the chip's limits, at real widths and
# with the routing held equal, are the driver's 2% / 2%); an 8-bit float
# lands several times over it.
_TOLERANCE = {"float32": (2e-4, 2e-4), "bfloat16": (0.04, 0.06)}


@pytest.mark.parametrize("dtype, periods", [("bfloat16", 1), ("float32", 2)])
def test_chunked_prefill_and_decode_give_the_references_logits(dtype,
                                                               periods):
    """A prompt longer than the ring (4 blocks of 8 = 32 positions), so ring
    blocks are reused and the windowed walks start above block 0; then a
    decode window. Teacher-forced through the program's own greedy tokens,
    compared as LOGITS via the dense forward of the same sequence."""
    jdtype = jnp.dtype(dtype)
    cfg = _cfg(jdtype, held=(4, 8), periods=periods)
    params = _params(cfg, seed=periods, dtype=jdtype)
    engine, srv = _serving(cfg, params, dtype)
    assert srv.ring == 4
    rng = np.random.default_rng(11)
    reqs = [Request(uid=i, tokens=rng.integers(0, 128, (n,), np.int32),
                    max_new_tokens=m, stop_on_eos=False)
            # equal totals: the reference compiles one length
            for i, (n, m) in enumerate([(45, 9), (7, 47)])]
    done = srv.run(reqs)
    arch = _arch(cfg)
    rms_tol, max_tol = _TOLERANCE[dtype]
    dense = jax.jit(lambda p, t: em.exaone_moe_forward(p, t, cfg))
    for r in reqs:
        seq = np.concatenate([r.tokens, done[r.uid].tokens])
        want = np.asarray(ref.logits(params, jnp.asarray(seq), arch),
                          np.float32)
        got = np.asarray(dense(params, jnp.asarray(seq[None]))[0],
                         np.float32)
        rms = np.sqrt(np.square(got - want).sum() / np.square(want).sum())
        assert rms <= rms_tol, (r.uid, rms)
        assert np.abs(got - want).max() <= max_tol * np.abs(want).max()
        if dtype == "float32":
            # ... and the PAGED programs emitted the dense forward's tokens
            greedy = want.argmax(-1)[len(r.tokens) - 1:-1]
            np.testing.assert_array_equal(done[r.uid].tokens, greedy)
    stats = srv.stats()
    assert stats["compiles"] == {"decode_step": 1, "prefill_step": 1}
    kinds = stats["kv_pool_kinds"]
    assert kinds["full"]["layers"] == periods
    assert kinds["window"]["layers"] == 1 + 3 * periods
    assert kinds["window"]["blocks"] == 1 + 3 * 4
    assert kinds["window"]["ring_blocks_per_slot"] == 4
    counted = stats["step_counters"]
    assert counted["moe_assignments"] + counted["moe_routed_elsewhere"] \
        == counted["moe_router_calls"] * 0 + sum(
            r.counters[1] + r.counters[4]
            for r in srv.steptrace.records(-np.inf, np.inf))


def _dense_routing(params, tokens, cfg, logits=False):
    """The experts `exaone_moe_forward` routes every token to: tokens
    [B, T] -> int32 [sparse layers, B, T, top_k], ascending in a token
    (`logits`: its logits in front)."""
    def run(params, tokens):
        chosen = []
        out = em.exaone_moe_forward(params, tokens, cfg, routing=chosen)
        return out, jnp.stack([
            jnp.sort(e, axis=-1).reshape(tokens.shape + (-1,))
            for e in chosen])
    out, sets = jax.jit(run)(params, tokens)
    return (np.asarray(out), np.asarray(sets)) if logits \
        else np.asarray(sets)


def test_the_references_held_share_is_the_programs():
    """`experts_held` in the reference leaves out what the program leaves
    out: with half the experts held, reference and program agree with each
    other and differ from the whole model."""
    cfg = _cfg(held=(0, 8))
    params = _params(cfg, seed=3)
    tokens = jnp.asarray(np.random.default_rng(12).integers(0, 128, (40,)),
                         jnp.int32)
    got, routing = _dense_routing(params, tokens[None], cfg, logits=True)
    want, sets = ref.forward(params, tokens, _arch(cfg))
    np.testing.assert_allclose(got[0], np.asarray(want), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_array_equal(routing[:, 0], np.asarray(sets))
    assert sets.shape == (4, 40, 4)


def test_forced_routing_holds_the_choice_and_returns_the_references_own():
    """`forward(forced=)`: given its own choice the reference gives its own
    logits; given another it gives other logits from the first sparse layer
    on, and still returns what IT would choose (the first sparse layer sees
    the same stream either way)."""
    cfg = _cfg(held=(0, 8))
    params = _params(cfg, seed=5)
    tokens = jnp.asarray(np.random.default_rng(13).integers(0, 128, (24,)),
                         jnp.int32)
    arch = _arch(cfg)
    want, sets = ref.forward(params, tokens, arch)
    same, sets_same = ref.forward(params, tokens, arch, forced=sets)
    # (the sets arrive ascending, `top_k`'s arrive by score: another order
    # of the same sum)
    np.testing.assert_allclose(np.asarray(same), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(sets_same), np.asarray(sets))
    other = (np.asarray(sets) + 1) % cfg.num_experts
    moved, sets_moved = ref.forward(params, tokens, arch, forced=other)
    assert np.abs(np.asarray(moved) - np.asarray(want)).max() > 1e-3
    np.testing.assert_array_equal(np.asarray(sets_moved)[0],
                                  np.asarray(sets)[0])


def test_the_reference_in_eight_bits_is_scaled_and_does_not_overflow():
    arch = dataclasses.replace(_arch(_cfg()), round_to=jnp.float8_e4m3fn)
    x = jnp.asarray([[1000.0, -3.0, 0.5], [0.0, 0.0, 0.0]])
    got = np.asarray(ref._rounded(x, arch))
    assert np.isfinite(got).all() and got[0, 0] == 1000.0
    assert abs(got[0, 1] + 3.0) <= 3.0 / 8      # a step of the row's scale
    plain = dataclasses.replace(arch, round_to=jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(ref._rounded(x, plain)),
                                  np.asarray(x.astype(jnp.bfloat16),
                                             np.float32))


_HEAVY = ("dot_general", "custom_call", "gather", "scatter", "while",
          "logistic", "exponential", "reduce")


@functools.lru_cache(None)
def _served_and_probe_prefill():
    """One chunk through the SERVED spec's prefill, without and with
    `routing=True`, for both cases of the test below."""
    cfg = _cfg()
    params = _params(cfg, seed=2)
    spec = em.make_exaone_moe_decode_model(cfg, params=params, name="tiny")
    ring = ring_blocks(cfg.sliding_window, cfg.window_block, 16, 1)
    tokens = np.random.default_rng(14).integers(0, 128, (1, 16), np.int32)
    tables = (np.asarray([[1, 2]], np.int32), ring_tables(1, 8, ring))
    pool = lambda: spec.init_paged_pool(4, 16, jnp.float32,
                                        window_blocks=1 + ring)
    args = (params, tokens, np.zeros((1,), np.int32),
            np.asarray([15], np.int32))
    served = jax.jit(spec.prefill_paged_fn)
    probe = jax.jit(lambda *a: spec.prefill_paged_fn(*a, routing=True))
    return (cfg, spec, served, probe, args, pool, tables,
            served(*args, pool(), tables), probe(*args, pool(), tables))


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_routing_is_one_more_result_of_the_served_programs(program):
    """`routing=True` on the SERVED spec's own paged functions: the same
    logits, counters and pool to the bit, the same heavy operations in the
    lowered program (what differs is the sort and the stacking of the extra
    result), and the experts the dense forward routes the same tokens to."""
    cfg, spec, served, probe, args, pool, tables, got, want = \
        _served_and_probe_prefill()
    params, tokens = args[:2]
    seq = jnp.asarray(tokens)
    if program == "decode":
        filled = got[1]
        pool = lambda: filled
        args = (params, np.asarray([7], np.int32), np.asarray([16], np.int32))
        served = jax.jit(spec.decode_paged_fn)
        probe = jax.jit(lambda *a: spec.decode_paged_fn(*a, routing=True))
        got = served(*args, pool(), tables)
        want = probe(*args, pool(), tables)
        seq = jnp.concatenate([seq, jnp.asarray([[7]], seq.dtype)], axis=1)
    *want, sets = want
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def heavy(jitted):
        text = jitted.lower(*args, pool(), tables).as_text()
        return {op: text.count(f"stablehlo.{op}") for op in _HEAVY}
    assert heavy(served) == heavy(probe)
    dense = _dense_routing(params, seq, cfg)[:, :, -sets.shape[2]:]
    np.testing.assert_array_equal(np.asarray(sets), dense)


def test_window_fields_of_the_step_ring_equal_a_hand_count():
    cfg = _cfg()
    engine, srv = _serving(cfg, _params(cfg), max_slots=2,
                           decode_steps_per_sync=2)
    # the kernels are not built on the CPU: the prefill fields stay 0, the
    # decode fields are host arithmetic from the positions
    srv.run([Request(uid=0, tokens=np.arange(30, dtype=np.int32) % 100,
                     max_new_tokens=5, stop_on_eos=False)])
    calls = [r for r in srv.steptrace.records(-np.inf, np.inf) if r.decoding]
    at = [30 + 2 * i for i in range(len(calls))]
    want_live = [sum((p // 8 + 1) - max(p - 7, 0) // 8 for p in (a, a + 1))
                 for a in at]
    want_table = [sum(p // 8 + 1 for p in (a, a + 1)) for a in at]
    assert [r.decode_window_live_blocks for r in calls] == want_live
    assert [r.decode_window_table_blocks for r in calls] == want_table
    assert all(r.prefill_window_live_blocks == 0 for r in calls)


# ----------------------------------------------------------------------
# what a pool of two kinds refuses
# ----------------------------------------------------------------------


@pytest.mark.parametrize("knobs, match", [
    (dict(enable_prefix_caching=True), "enable_prefix_caching is not built"),
    (dict(quantization={"kv_cache_dtype": "int8"}), "int8 is not built"),
    (dict(spec_decode={"drafter": "ngram", "draft_k": 2}),
     "spec_decode is not built|no verify_paged_fn"),
], ids=["prefix-cache", "int8-pool", "spec-decode"])
def test_serving_refuses_what_is_not_built_for_two_kinds(knobs, match):
    cfg = _cfg()
    with pytest.raises(ValueError, match=match):
        _serving(cfg, _params(cfg), **knobs)


def test_transplant_is_refused_on_a_pool_of_two_kinds():
    cfg = _cfg()
    engine, srv = _serving(cfg, _params(cfg))
    req = Request(uid=0, tokens=np.arange(5, dtype=np.int32),
                  max_new_tokens=2, stop_on_eos=False)
    with pytest.raises(ValueError, match="block transplant"):
        srv.submit(req, prefill_only=True)
    with pytest.raises(ValueError, match="block transplant"):
        srv.adopt_handoff({"uid": 0}, srv.pool)


def test_the_model_spec_refuses_the_paths_it_does_not_serve():
    cfg = _cfg()
    spec = em.make_exaone_moe_decode_model(cfg, params=_params(cfg))
    with pytest.raises(NotImplementedError, match="paged scheduler only"):
        spec.prefill_fn(None, None, None, None)
    with pytest.raises(ValueError, match="int8 pool is not built"):
        spec.init_paged_pool(8, 16, jnp.int8, window_blocks=5)
    with pytest.raises(ValueError, match="window_blocks"):
        spec.init_paged_pool(8, 16, jnp.float32)
    assert spec.verify_paged_fn is None


def test_config_refuses_lists_that_do_not_fit():
    with pytest.raises(ValueError, match="list 5 layers each"):
        _cfg(layer_types=(L, G))
    with pytest.raises(ValueError, match="not a range"):
        _cfg(held=(12, 8))


def test_one_kind_models_have_no_kinds_and_one_table():
    """The dense family through the same scheduler: no `cache_kinds`, the
    tables go to the programs as one array, the window fields stay 0."""
    from deepspeed_tpu.models.gpt import make_gpt_decode_model
    mesh_mod.clear_mesh()
    cfg = GPTConfig(vocab_size=128, n_layer=2, n_head=4, d_model=32,
                    max_seq_len=128, use_rotary=True, dtype=jnp.float32)
    engine = deepspeed_tpu.init_inference(
        make_gpt_decode_model(cfg, name="dense"),
        config={"dtype": "float32", "kv_cache_dtype": "float32",
                "greedy": True, "kv_block_size": 16, "max_out_tokens": 128})
    srv = engine.serving(max_slots=2, max_context=128)
    assert srv.cache_kinds is None and srv.ring_tables is None
    tables = np.ones((2, 8), np.int32)
    assert srv._tables_arg(tables) is tables
    srv.run([Request(uid=0, tokens=np.arange(9, dtype=np.int32),
                     max_new_tokens=3, stop_on_eos=False)])
    assert "kv_pool_kinds" not in srv.stats()
    assert all(r.decode_window_live_blocks == 0
               for r in srv.steptrace.records(-np.inf, np.inf))


def test_init_takes_the_embeddings_and_the_routers_ranges():
    """The benchmark's weights: the embedding at a few times the RMS of what
    the post-normed halves add (or a random router sends a whole chunk to a
    few experts), the router's matrix that much smaller (or its sigmoid
    saturates and `top_k` breaks the ties by index)."""
    cfg = _cfg(d_model=64, attn_head_dim=16, vocab_size=512)
    plain = _params(cfg)
    wide = em.exaone_moe_init_fn(cfg, embedding_std=8.0, router_std=0.0025)(
        jax.random.PRNGKey(0))
    assert abs(float(jnp.std(plain["wte"])) - 0.02) < 0.002
    assert abs(float(jnp.std(wide["wte"])) - 8.0) < 0.3
    gate = lambda p: float(jnp.std(p["period"][0]["moe_gate_w"]))
    assert abs(gate(plain) - 0.02) < 0.003
    assert abs(gate(wide) - 0.0025) < 0.0004
    np.testing.assert_array_equal(np.asarray(plain["lm_head"]),
                                  np.asarray(wide["lm_head"]))
