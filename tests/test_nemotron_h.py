"""Nemotron-H family (`models/nemotron_h.py`) on the paged serving path: a pool
with a STATE kind through the scheduler against the float32 reference —
chunked prefill handing the state forward, a chunk that is mostly padding, a
slot reused by a second request, a chunk riding a decode call — the served
programs' routing as one more result, the step ring's state fields, and what
is refused. The layers' pieces are `tests/test_nemotron_h_layers.py`.

Everything at a small size on the CPU; `tests/nemotron_cases.py` has the
configuration and the reference the two files share."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_cache import state_rows
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import nemotron_h as nh
from tests.nemotron_cases import _arch, _cfg, _params, _serving, ref

# float32: the program and the reference differ by summation order (and the
# chunked form of the recurrence) alone. bfloat16: 8 bits of mantissa through
# five layers of width 32 on the CPU.
_TOLERANCE = {"float32": (3e-4, 3e-4), "bfloat16": (0.05, 0.08)}


def _requests(lengths, seed=11):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, tokens=rng.integers(0, 128, (n,), np.int32),
                    max_new_tokens=m, stop_on_eos=False)
            for i, (n, m) in enumerate(lengths)]


def _assert_reference_tokens(reqs, done, params, arch):
    """Each request's emitted tokens are the float32 reference's greedy
    tokens on the same sequence (teacher-forced through the program's own)."""
    for r in reqs:
        seq = np.concatenate([r.tokens, done[r.uid].tokens])
        want = np.asarray(ref.logits(params, jnp.asarray(seq[:-1]), arch))
        np.testing.assert_array_equal(
            done[r.uid].tokens, want.argmax(-1)[len(r.tokens) - 1:],
            err_msg=f"request {r.uid}")


@pytest.mark.parametrize("dtype, pattern", [("bfloat16", "EMEM*"),
                                            ("float32", "ME*EMEM")])
def test_chunked_prefill_and_decode_give_the_references_logits(dtype,
                                                               pattern):
    """A prompt of three chunks (the third a part of one: the state stops at
    the last real position), one shorter than a chunk; then decode windows.
    Teacher-forced through the program's own greedy tokens, compared as
    LOGITS via the dense forward of the same sequence."""
    jdtype = jnp.dtype(dtype)
    cfg = _cfg(jdtype, held=(4, 8), pattern=pattern)
    params = _params(cfg, seed=len(pattern), dtype=jdtype)
    engine, srv = _serving(cfg, params, dtype)
    reqs = _requests([(37, 9), (5, 41)])    # equal totals: one length
    done = srv.run(reqs)
    arch = _arch(cfg)
    rms_tol, max_tol = _TOLERANCE[dtype]
    dense = jax.jit(lambda p, t: nh.nemotron_h_forward(p, t, cfg))
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
        # ... and the PAGED programs emitted the reference's tokens
        _assert_reference_tokens(reqs, done, params, arch)
    stats = srv.stats()
    assert stats["compiles"] == {"decode_step": 1, "prefill_step": 1}
    kinds = stats["kv_pool_kinds"]
    assert kinds["full"]["layers"] == pattern.count("*")
    assert kinds["state"]["layers"] == pattern.count("M")
    assert kinds["state"]["blocks"] == 1 + 3 and kinds["state"]["block"] == 0
    H, P, N = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size
    assert kinds["state"]["bytes"] == pattern.count("M") * 4 * (
        H * P * N * 4 + 3 * cfg.conv_width * jdtype.itemsize)


def test_a_reused_slot_starts_from_a_zero_state():
    """Five requests through two slots: every slot is admitted to again
    while it still holds its last request's state and tail; a prompt's first
    chunk (`start_pos == 0`) takes nothing from them."""
    cfg = _cfg()
    params = _params(cfg, seed=3)
    engine, srv = _serving(cfg, params, max_slots=2)
    reqs = _requests([(21, 5), (3, 7), (40, 4), (16, 6), (9, 9)], seed=5)
    done = srv.run(reqs)
    _assert_reference_tokens(reqs, done, params, _arch(cfg))
    assert srv.allocator.num_free == srv.allocator.capacity


@pytest.mark.parametrize("window", [1, 3])
def test_a_chunk_riding_a_decode_call_gives_the_same_tokens(window):
    """One device (the mixed step engages there): chunks ride decode calls,
    the chunk's state read, scanned and written and the slots' states
    rewritten in ONE program; the tokens are the two-call path's and the
    reference's."""
    cfg = _cfg()
    params = _params(cfg, seed=4)
    lengths = [(37, 9), (5, 12), (16, 7), (50, 5), (3, 11)]
    engine, srv = _serving(cfg, params, one_device=True,
                           decode_steps_per_sync=window)
    reqs = _requests(lengths, seed=6)
    done = srv.run(reqs)
    assert srv.fused_chunks > 0
    assert srv.compile_stats() == {"decode_step": 1, "prefill_step": 1,
                                   "mixed_step": 1}
    _assert_reference_tokens(reqs, done, params, _arch(cfg))
    engine, two = _serving(cfg, params, one_device=True,
                           decode_steps_per_sync=window)
    two._chunks_riding = lambda due, decoding: 0        # the oracle
    apart = two.run(_requests(lengths, seed=6))
    assert two.fused_chunks == 0
    for uid in done:
        np.testing.assert_array_equal(done[uid].tokens, apart[uid].tokens)


def _dense_routing(params, tokens, cfg):
    def run(params, tokens):
        chosen = []
        nh.nemotron_h_forward(params, tokens, cfg, routing=chosen)
        return jnp.stack([jnp.sort(e, axis=-1).reshape(tokens.shape + (-1,))
                          for e in chosen])
    return np.asarray(jax.jit(run)(params, tokens))


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_routing_is_one_more_result_of_the_served_programs(program):
    cfg = _cfg(pattern="EMEME*")
    params = _params(cfg, seed=8)
    spec = nh.make_nemotron_h_decode_model(cfg, params=params)
    toks = np.random.default_rng(2).integers(0, 128, (1, 16)).astype(np.int32)
    want = _dense_routing(params, jnp.asarray(toks), cfg)   # [3, 1, 16, k]
    pool = spec.init_paged_pool(4, 16, jnp.float32, state_rows=3)
    tables = (np.array([[1, 2]], np.int32), np.array([[2]], np.int32))
    n = 16 if program == "prefill" else 15
    out = spec.prefill_paged_fn(
        params, np.where(np.arange(16) < n, toks, 0), np.zeros(1, np.int32),
        np.array([n - 1], np.int32), pool, tables, routing=True)
    assert len(out) == 4 and out[3].shape == (3, 1, 16, cfg.top_k)
    np.testing.assert_array_equal(out[3][:, :, :n], want[:, :, :n])
    if program == "decode":
        out = spec.decode_paged_fn(params, toks[:, 15], np.array([15]),
                                   out[1], tables, routing=True)
        assert out[3].shape == (3, 1, 1, cfg.top_k)
        np.testing.assert_array_equal(out[3][:, :, 0], want[:, :, 15])
    # the scheduler's call: three results
    assert len(spec.decode_paged_fn(params, toks[:, 0], np.array([0]),
                                    out[1], tables)) == 3


def test_state_fields_of_the_step_ring_equal_a_hand_count():
    cfg = _cfg()
    engine, srv = _serving(cfg, _params(cfg))
    srv.run(_requests([(37, 9), (5, 12)]))
    recs = srv.steptrace.records(-np.inf, np.inf)
    H, P, N = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size
    token = 2 * cfg.pattern.count("M") * H * P * N * 4  # read + write, f32
    for r in recs:
        assert r.ssm_state_bytes == r.decoding * srv.window * token
        assert r.ssm_chunk_tokens == r.prefill_chunks * srv.chunk
    assert sum(r.ssm_chunk_tokens for r in recs) == (3 + 1) * 16
    assert sum(r.ssm_state_bytes for r in recs) > 0
    assert all(r.decode_window_live_blocks == 0 for r in recs)
    np.testing.assert_array_equal(srv.ring_tables, state_rows(3))
    np.testing.assert_array_equal(state_rows(3), [[1], [2], [3]])
    # a call's dead slots go to the trash row
    tables = np.zeros((3, srv.nb), np.int32)
    tables[1, 0] = 5
    np.testing.assert_array_equal(srv._tables_arg(tables)[1],
                                  [[0], [2], [0]])


# ----------------------------------------------------------------------
# what a pool with a state kind refuses
# ----------------------------------------------------------------------


@pytest.mark.parametrize("knobs, match", [
    (dict(enable_prefix_caching=True),
     "recurrent state.*enable_prefix_caching is not built"),
    (dict(quantization={"kv_cache_dtype": "int8"}), "int8 is not built"),
    (dict(spec_decode={"drafter": "ngram", "draft_k": 2}),
     "spec_decode is not built|no verify_paged_fn"),
], ids=["prefix-cache", "int8-pool", "spec-decode"])
def test_serving_refuses_what_is_not_built_for_a_state_kind(knobs, match):
    cfg = _cfg()
    with pytest.raises(ValueError, match=match):
        _serving(cfg, _params(cfg), **knobs)


def test_transplant_is_refused_on_a_pool_with_a_state_kind():
    cfg = _cfg()
    engine, srv = _serving(cfg, _params(cfg))
    req = Request(uid=0, tokens=np.arange(5, dtype=np.int32),
                  max_new_tokens=2, stop_on_eos=False)
    with pytest.raises(ValueError, match="recurrent state belongs to"):
        srv.submit(req, prefill_only=True)
    with pytest.raises(ValueError, match="block transplant"):
        srv.adopt_handoff({"uid": 0}, srv.pool)


def test_the_model_spec_refuses_the_paths_it_does_not_serve():
    cfg = _cfg()
    spec = nh.make_nemotron_h_decode_model(cfg, params=_params(cfg))
    with pytest.raises(NotImplementedError, match="paged scheduler only"):
        spec.prefill_fn(None, None, None, None)
    with pytest.raises(ValueError, match="int8 pool is not built"):
        spec.init_paged_pool(8, 16, jnp.int8, state_rows=5)
    with pytest.raises(ValueError, match="state_rows"):
        spec.init_paged_pool(8, 16, jnp.float32)
    assert spec.verify_paged_fn is None
    engine, srv = _serving(cfg, spec.params)
    with pytest.raises(NotImplementedError, match="paged scheduler only"):
        engine.generate(np.zeros((1, 4), np.int32), max_new_tokens=2)


def test_config_refuses_a_pattern_or_a_share_that_does_not_fit():
    with pytest.raises(ValueError, match="a letter a layer"):
        _cfg(pattern="EMX")
    with pytest.raises(ValueError, match="a letter a layer"):
        _cfg(pattern="")
    with pytest.raises(ValueError, match="not a range"):
        _cfg(held=(12, 8))
    with pytest.raises(ValueError, match="divide into n_groups"):
        _cfg(n_groups=3)


def test_init_draws_what_the_configs_own_keys_initialise():
    cfg = _cfg(pattern="M")
    p = jax.tree_util.tree_map(lambda a: a[0], _params(cfg)["runs"][0][0])
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert (dt >= cfg.time_step_min * 0.999).all() \
        and (dt <= cfg.time_step_max * 1.001).all()
    A = np.exp(np.asarray(p["A_log"]))
    assert (A >= 1).all() and (A <= 16).all()
    assert (np.asarray(p["ssm_D"]) == 1).all()
    assert np.abs(np.asarray(p["conv_w"])).max() <= 0.5
    for leaf in ("dt_bias", "A_log", "ssm_D"):
        assert p[leaf].dtype == jnp.float32
    served = _params(cfg, dtype=jnp.bfloat16)["runs"][0][0]
    assert served["ssm_in_w"].dtype == jnp.bfloat16 \
        and served["A_log"].dtype == jnp.float32


# ----------------------------------------------------------------------
# the benchmark's readers this family brings (hand-made records)
# ----------------------------------------------------------------------


def _reader(monkeypatch, name, steps):
    import collections
    import importlib
    import os
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    monkeypatch.syspath_prepend(bench)
    monkeypatch.syspath_prepend(os.path.join(bench, "readers"))
    reader = importlib.import_module(name)
    Step = collections.namedtuple("Step", ["t_end"] + sorted(steps[0][1]))
    monkeypatch.setattr(
        reader.steprings, "steps",
        lambda obs, subsystem: [Step(t, **fields) for t, fields in steps])
    return reader


def test_the_scan_is_timed_a_position_of_the_traced_steps(monkeypatch):
    reader = _reader(monkeypatch, "trace_time_per_step_field",
                     [(1.0, dict(ssm_chunk_tokens=512)),      # before
                      (3.0, dict(ssm_chunk_tokens=1024)),
                      (4.0, dict(ssm_chunk_tokens=1536))])
    args = dict(match=r"^(?!dstpu_).*f32\[4,8,128,128\]", subsystem="serving",
                field="ssm_chunk_tokens", scale=1e6)
    trace = {"ops": {"%fusion.1 = f32[4,8,128,128]{3,2,1,0} fusion(": 0.256,
                     "dstpu_ssm_update = f32[4,8,128,128]": 9.0,
                     "%dot = bf16[640,4096]": 1.0}}
    obs = {"traced": (2.0, 4.0)}
    assert reader.read(obs, trace, args) == pytest.approx(100.0)
    assert reader.read(obs, None, args) is None
    assert reader.read({"traced": (None, None)}, trace, args) is None
    assert reader.read(obs, trace, {**args, "field": "absent"}) is None
    assert reader.read(obs, {"ops": {}}, args) is None


def test_the_walks_roofline_counts_the_patterns_attention_layers(monkeypatch):
    reader = _reader(monkeypatch, "paged_walk_roofline_pattern",
                     [(1.0, dict(decode_live_blocks=10 ** 6)),    # before
                      (3.0, dict(decode_live_blocks=4000))])
    args = dict(match="^dstpu_paged_decode", subsystem="serving",
                pattern_key="hybrid_override_pattern", letter="*")
    cfg = {"hybrid_override_pattern": "EMEM*EM*", "num_attention_heads": 32,
           "num_key_value_heads": 2, "head_dim": 128,
           "serving": {"kv_block_size": 512}}
    obs = {"traced": (2.0, 4.0), "config": cfg, "device_kind": "TPU v5 lite"}
    trace = {"ops": {"dstpu_paged_decode": 0.01, "dstpu_paged_prefill": 5.0}}
    # two layers x 4000 blocks x 512 rows x (K + V) x 2 heads x 128 x 2 bytes
    nbytes = 2 * 4000 * 512 * 2 * 2 * 128 * 2
    assert reader.read(obs, trace, args) == pytest.approx(
        100.0 * nbytes / 819e9 / 0.01, rel=0.02)
    assert reader.read({**obs, "config": {**cfg, "hybrid_override_pattern":
                                          "EMEM"}}, trace, args) is None
    no_key = {k: v for k, v in cfg.items() if k != "hybrid_override_pattern"}
    assert reader.read({**obs, "config": no_key}, trace, args) is None
    assert reader.read(obs, None, args) is None


@pytest.mark.parametrize("slots, chunk, block, window",
                         [(128, 512, 512, 8), (4, 16, 16, 3)],
                         ids=["served", "rehearsal"])
def test_the_benchmarks_check_shares_every_call_between_a_chunk_and_slots(
        monkeypatch, slots, chunk, block, window):
    """`drivers/serve_nemotron_h.py::schedule`: what the chip check drives
    through the served mixed program, as host arithmetic."""
    import os
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    monkeypatch.syspath_prepend(bench)
    from drivers import serve_nemotron_h as drv
    seqs, ticks = drv.schedule(slots, chunk, block, window, 1000,
                               np.random.default_rng(5))
    nb = 24
    x = drv._tick_inputs(seqs, ticks, slots, chunk, nb)
    # every call carries ONE real chunk, and a slot's chunk never rides a
    # call in which the slot decodes
    assert (x["chunk_state"][:, 0, 0] == 1 + x["slot"]).all()
    assert (x["state"][np.arange(ticks), x["slot"], 0] == 0).all()
    # a slot decodes from the tick after its last chunk, a position a tick
    blocks = np.concatenate([s["blocks"] for s in seqs])
    assert len(set(blocks)) == len(blocks) and blocks.min() == 1
    for s in seqs:
        n = -(-len(s["prompt"]) // chunk)
        assert s["live"] == s["first"] + n and x["final"][s["live"] - 1]
        assert x["final"][s["first"]:s["live"]].sum() == 1
        live = np.arange(s["live"], s["end"])
        assert (x["state"][live, s["slot"], 0] == 1 + s["slot"]).all()
        assert (x["pos"][live, s["slot"]]
                == len(s["prompt"]) + np.arange(len(live))).all()
        assert (len(s["prompt"]) + len(live)) <= len(s["blocks"]) * block
        assert len(s["blocks"]) <= nb
    # the long prompt and the two-chunk one ride beside every short slot,
    # and decode beside riding chunks
    long = next(s for s in seqs if s["slot"] == 0)
    assert len(long["prompt"]) > 6 * chunk and 0 < len(long["prompt"]) % chunk
    assert (x["state"][long["first"]:long["live"], 1:slots - 1, 0] > 0).all()
    assert long["end"] - long["live"] >= window
    # a slot is handed on to a second request, whose chunk starts at 0
    again = [s for s in seqs if s["first"] >= ticks - window]
    assert len(again) == window
    assert all(x["start"][s["first"], 0] == 0 for s in again)
    compared = [s for s in seqs if s["compared"]]
    assert {0, slots - 1} <= {s["slot"] for s in compared}
    assert all(s["end"] == ticks for s in compared)
    if slots == 128:
        short = [s for s in compared if s["slot"] not in (0, slots - 1)]
        assert len(short) == 4
        assert len({len(s["prompt"]) + s["end"] - s["live"]
                    for s in short}) == 1
        assert max(s["end"] - s["live"] for s in short) > 100


@pytest.mark.parametrize("window", [1, 3])
def test_the_benchmarks_warm_up_runs_all_three_step_programs(monkeypatch,
                                                             window):
    """`drivers/serve_nemotron_h.py::_warm`: the mixed program has run before
    the traffic's pre-roll begins (a prompt's two chunks ride a decoding
    slot's call), every program compiled once, and the engine is left
    empty."""
    import os
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    monkeypatch.syspath_prepend(bench)
    from drivers import serve_nemotron_h as drv
    cfg = _cfg()
    engine, srv = _serving(cfg, _params(cfg, seed=4), one_device=True,
                           decode_steps_per_sync=window)
    assert drv._warm(srv, cfg.vocab_size, 3400001237) > 0
    assert srv.fused_chunks == min(2, window)
    assert srv.compile_stats() == {"decode_step": 1, "prefill_step": 1,
                                   "mixed_step": 1}
    assert not srv.queue and srv.num_active == 0
    assert srv.allocator.available == srv.allocator.capacity
