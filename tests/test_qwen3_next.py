"""Qwen3-Next family (`models/qwen3_next.py`) on the paged serving path,
through the hybrid loop it shares with Nemotron-H and Granite 4.0-H
(`models/hybrid.py`), its recurrent halves Gated DeltaNet on the state kind:
the whole-sequence forward, chunked prefill and decode through the pool and
the state kind, and the MIXED program, each against the float32 reference's
full forward (the delta rule a position at a time) as LOGITS; the scheduler
end to end; the served programs' routing as one more result; the step ring's
state fields; and each of the family's new factors shown to matter. The
halves' pieces are `tests/test_qwen3_next_layers.py`.

Everything at a small size on the CPU; `tests/qwen3_next_cases.py` has the
configuration and the reference the two files share."""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import qwen3_next as qn
from tests.qwen3_next_cases import (LAYERS, _arch, _cfg, _params, _serving,
                                    ref)

# float32: the program and the reference differ by summation order (and the
# chunked form of the delta rule) alone. bfloat16: 8 bits of mantissa through
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
    got = jax.jit(lambda p, t: qn.qwen3_next_forward(p, t, cfg))(
        params, jnp.asarray(toks))
    for row in range(2):
        _assert_close(got[row], ref.logits(params, jnp.asarray(toks[row]),
                                           _arch(cfg)), dtype)


def _paged(cfg, params):
    """The family's spec, a pool of its two kinds and one table row a slot
    (slot i: blocks 1 + i NB ..., state row 1 + i)."""
    spec = qn.make_qwen3_next_decode_model(cfg, params=params)
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


def test_state_fields_of_the_step_ring_count_the_deltanet_halves():
    """The step ring counts a state kind's bytes from the pool's own leaf,
    whatever recurrence writes it: a decode token of a slot reads and
    writes each Gated DeltaNet layer's [H, K, V] float32 state once."""
    cfg = _cfg()
    engine, srv = _serving(cfg, _params(cfg))
    srv.run(_requests([(37, 9), (5, 12)]))
    recs = srv.steptrace.records(-np.inf, np.inf)
    H, K, V = cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    token = 2 * LAYERS.count("linear_attention") * H * K * V * 4
    for r in recs:
        assert r.ssm_state_bytes == r.decoding * srv.window * token
        assert r.ssm_chunk_tokens == r.prefill_chunks * srv.chunk
    assert sum(r.ssm_chunk_tokens for r in recs) == (3 + 1) * 16
    assert sum(r.ssm_state_bytes for r in recs) > 0


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_routing_is_one_more_result_of_the_served_programs(program):
    cfg = _cfg()
    params = _params(cfg, seed=8)
    spec = qn.make_qwen3_next_decode_model(cfg, params=params)
    toks = np.random.default_rng(2).integers(0, 128, (1, 16)).astype(np.int32)

    def dense(params, tokens):
        chosen = []
        qn.qwen3_next_forward(params, tokens, cfg, routing=chosen)
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


def _without(cfg, **wrong):
    off = copy.copy(cfg)                # no `__post_init__`: it would put
    for name, value in wrong.items():   # the family's value back
        setattr(off, name, value)
    return off


def _edited(params, leaf, edit):
    return {**params, "runs": [[
        {k: (edit(v) if k == leaf else v) for k, v in tree.items()}
        for tree in trees] for trees in params["runs"]]}


def _dropped(params, leaf):
    return {**params, "runs": [[
        {k: v for k, v in tree.items() if k != leaf} for tree in trees]
        for trees in params["runs"]]}


# the program with ONE of the family's factors wrong: (cfg, params) -> the
# same pair with that factor another family's
_WRONG = {
    # the attention's result straight into the out-projection
    "output_gate": lambda cfg, p: (_without(cfg, attn_output_gate=False), p),
    # the shared expert added as it is (Granite's, K-EXAONE's)
    "shared_expert_gate": lambda cfg, p: (cfg, _dropped(p, "shared_scale_w")),
    # the whole head rotated (Mistral's), not its first quarter
    "partial_rotary": lambda cfg, p: (_without(cfg, rotary_pct=1.0), p),
    # no rotation at all (Granite's attention)
    "rotary": lambda cfg, p: (_without(cfg, rotary_attention=False), p),
    # q and k into the scores as projected
    "qk_norm": lambda cfg, p: (_without(cfg, qk_norm_per_head=False), p),
    # beta = sigmoid(0) everywhere: a write that ignores its token
    "beta": lambda cfg, p: (cfg, _edited(
        p, "gdn_ba_w", lambda w: w.at[..., :w.shape[-1] // 2].set(0.0))),
    # a state that never forgets: g = -exp(A_log) softplus(.) ~ 0
    "decay": lambda cfg, p: (cfg, _edited(
        p, "A_log", lambda a: jnp.full_like(a, -30.0))),
}


@pytest.mark.parametrize("factor", sorted(_WRONG))
def test_each_new_factor_matters(factor):
    """The program with one of the family's factors at another family's
    value is NOT the reference: the limit that holds the right program (3e-4)
    is missed by orders of magnitude."""
    cfg = _cfg()
    params = _params(cfg, seed=9, embedding_std=0.5)
    # scores, gates and decays that say something: drawn at 0.02 every
    # softmax is flat and every sigmoid a half, and what a mixer or the
    # shared expert adds to the stream is drawn small
    for leaf, times in (("attn_qkv_w", 40.0), ("gdn_ba_w", 100.0),
                        ("shared_scale_w", 100.0), ("gdn_out_w", 10.0),
                        ("attn_out_w", 10.0), ("shared_down_w", 50.0)):
        params = _edited(params, leaf, lambda w, times=times: w * times)
    toks = np.random.default_rng(1).integers(0, 128, (1, 40)).astype(np.int32)
    want = ref.logits(params, jnp.asarray(toks[0]), _arch(cfg))
    right = qn.qwen3_next_forward(params, jnp.asarray(toks), cfg)[0]
    _assert_close(right, want)
    off_cfg, off_params = _WRONG[factor](cfg, params)
    got = qn.qwen3_next_forward(off_params, jnp.asarray(toks), off_cfg)[0]
    assert _errors(got, want)[0] > 30 * _TOLERANCE["float32"][0]


def test_the_model_spec_refuses_the_paths_it_does_not_serve():
    cfg = _cfg()
    spec = qn.make_qwen3_next_decode_model(cfg, params=_params(cfg))
    with pytest.raises(ValueError, match="int8 pool is not built"):
        spec.init_paged_pool(8, 16, jnp.int8, state_rows=5)
    with pytest.raises(ValueError, match="state_rows"):
        spec.init_paged_pool(8, 16, jnp.float32)
    with pytest.raises(NotImplementedError, match="paged scheduler only"):
        spec.prefill_fn()
    assert spec.verify_paged_fn is None
    # the head is its own matrix (`tie_word_embeddings` false)
    assert "wte" in spec.params and "lm_head" in spec.params


def test_serving_refuses_what_a_state_kind_refuses():
    """A recurrent state has no snapshot a block boundary, no scale leaves
    and no rewind: prefix caching, the int8 pool and speculative verify are
    refused by name for this family as for the Mamba-2 ones."""
    cfg = _cfg()
    params = _params(cfg)
    with pytest.raises(ValueError, match="enable_prefix_caching is not built"):
        _serving(cfg, params, enable_prefix_caching=True)


def test_config_is_the_familys_whatever_is_passed():
    cfg = _cfg(tie_embeddings=True, norm_topk_prob=False, use_swiglu=False,
               qk_norm_per_head=False, attn_output_gate=False)
    assert not cfg.tie_embeddings and cfg.norm_topk_prob and cfg.use_swiglu
    assert cfg.qk_norm_per_head and cfg.attn_output_gate \
        and cfg.rotary_attention
    assert cfg.halves == "DEDEDE*EDE" and cfg.n_layer == 5
    assert qn.layer_types(48, 4) == ("linear_attention",) * 3 \
        + ("full_attention",) + qn.layer_types(44, 4)
    with pytest.raises(ValueError, match="one recurrent kind a stack"):
        _cfg(pattern=("DE", "ME"))
    with pytest.raises(ValueError, match="not a range"):
        _cfg(held=(12, 8))


# ----------------------------------------------------------------------
# the benchmark holds the cell
# ----------------------------------------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "serve_qwen3next_multisession"


def _published():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, config["file"])) as f:
        return bench, cell, config, json.load(f)


def test_benchmark_holds_the_cells_files():
    bench, cell, config, published = _published()
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b-12l-ep8", "multisession192_backlog", 1)
    assert config["reduced"] == published["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert published["reduced_from"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    # every width as published
    for key, value in {
            "hidden_size": 2048, "num_attention_heads": 16,
            "num_key_value_heads": 2, "head_dim": 256,
            "linear_num_key_heads": 16, "linear_num_value_heads": 32,
            "linear_key_head_dim": 128, "linear_value_head_dim": 128,
            "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
            "shared_expert_intermediate_size": 512,
            "num_experts_per_tok": 10, "published_num_experts": 512,
            "partial_rotary_factor": 0.25, "rope_theta": 10000000,
            "rms_norm_eps": 1e-06, "full_attention_interval": 4,
            "intermediate_size": 5120, "max_position_embeddings": 262144,
            "experts_held_range": [0, 64], "num_hidden_layers": 12,
            "num_experts": 64, "vocab_size": 18992}.items():
        assert published[key] == value, key
    for kind, name in (("drivers", published["driver"] + ".py"),
                       ("references", published["reference"] + ".py"),
                       ("traffic", cell["traffic"] + ".json"),
                       ("checks", "rehearsal_qwen3next.json")):
        assert os.path.exists(os.path.join(BENCH, kind, name)), name
    for key in ("assumed", "why_reduced", "why_serving", "check_limits",
                "departures_of_the_program", "deployment"):
        assert published[key], key
    reported = [m["name"] for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    assert sorted(reported) == ["serve_tokens_per_s", "setup_s"]
    own = [m for m in bench["per_layer"]
           if m.get("workloads") == [cell["name"]]]
    assert all(m["name"].endswith(".deltanet") for m in own)
    assert {"gdn_update_kernel_time_share.deltanet",
            "gdn_update_roofline.deltanet"} <= {m["name"] for m in own}
    # this cell's own entries, and the contract's cap on the whole table
    # (PR 50's cell took it to 127)
    assert len(own) <= 14 and len(bench["per_layer"]) <= 128
    for metric in own:
        with open(os.path.join(BENCH, "layer_metrics",
                               metric["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    with open(os.path.join(BENCH, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "closed_backlog"
    assert traffic["min_queue"] == published["serving"]["max_slots"] == 192
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "min": 256,
                                        "max": 4096}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 256,
                                        "max": 768}
    assert traffic["shared_prefix_tokens"] == 0 and traffic["grid"] == 64


def test_the_drivers_config_is_the_files_and_the_built_tree_its_count():
    """`drivers/serve_qwen3_next.py::model_config` of the file: the program's
    configuration at the published widths, and the parameter count the file
    states, from `jax.eval_shape` of the initializer."""
    import importlib
    import sys
    sys.path.insert(0, BENCH)
    try:
        driver = importlib.import_module("drivers.serve_qwen3_next")
    finally:
        sys.path.remove(BENCH)
    _, _, _, published = _published()
    cfg = driver.model_config(published, published["serving"]["max_context"])
    assert cfg.halves == "DEDEDE*E" * 3 and cfg.head_dim == 256
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.chunk_size) == (16, 32, 128, 128, 64)
    assert cfg.experts_held == (0, 64) and cfg.num_experts == 512
    shapes = jax.eval_shape(qn.qwen3_next_init_fn(cfg, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert count == 2_929_408_192
    assert f"{count:,}" in published["why_reduced"]
    with pytest.raises(ValueError, match="is not Qwen3-Next"):
        driver.model_config(dict(published, model_type="granitemoehybrid"),
                            5120)
