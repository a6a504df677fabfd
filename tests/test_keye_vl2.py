"""Keye-VL-2.0 family (`models/keye_vl2.py`) on the paged serving path: a
learned sparse-attention indexer in every layer — the forward, then chunks
riding decode calls + decoding through the pool (index keys a third leaf)
against the float32 reference with the selection ACTIVE, the layer at a
context under `topk` against the same configuration with no indexer, the
expert share, the exact selection against a stable sort with planted ties
(the `jax.numpy` twin and the kernels in the interpreter), the kernels
through the scheduler, what is refused, the step ring's fields, the
benchmark's files, and the walks with no selection lowering as the parent's.

Everything at a small size on the CPU; `tests/keye_cases.py` has the
configuration and the reference."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import exaone_moe as em
from deepspeed_tpu.models import gpt as gpt_mod
from deepspeed_tpu.ops import attention_dispatch as attn_dispatch
from deepspeed_tpu.ops.pallas import sparse_index
from deepspeed_tpu.parallel.moe import routed_experts, topk_routing
from tests.keye_cases import TOPK, _arch, _cfg, _params, _serving, kv2, ref
from tests.test_sdar_moe import _strip

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "serve_keyevl2_longctx_sparse_queue"
CONFIG = "keye-vl-2.0-30b-a3b-12l-ep8"


def _rms(got, want):
    return float(np.sqrt(np.square(got - want).sum()
                         / np.square(want).sum()))


# ----------------------------------------------------------------------
# the kind, as data
# ----------------------------------------------------------------------


def test_the_sparse_kind_is_the_full_kinds_blocks_with_a_third_leaf():
    cfg = _cfg()
    assert em.pool_kinds(cfg) == (em.SELECTED,)
    assert em.layer_plan(cfg) == ([], [(em.SELECTED, em.SPARSE)], 3)
    kind, = em.cache_kinds(cfg, 16)
    assert (kind.name, kind.leaves, kind.layers, kind.window) \
        == ("full", ("k", "v", "ik"), 3, 0)
    assert kind.entry_values == 2 * (16 + 16) + 8 and kind.index_topk == TOPK
    spec = kv2.make_keye_vl2_decode_model(cfg, params=_params(cfg))
    pool = spec.init_paged_pool(5, 16, jnp.float32)
    assert {k: v.shape for k, v in pool.items()} == {
        "k": (3, 5, 2, 16, 16), "v": (3, 5, 2, 16, 16),
        "ik": (3, 5, 1, 16, 128)}
    assert not spec.mixed_chunk_groups
    with pytest.raises(ValueError, match="index keys have no scale leaves"):
        spec.init_paged_pool(5, 16, jnp.int8)


# ----------------------------------------------------------------------
# (a) the whole-sequence forward
# ----------------------------------------------------------------------


def test_forward_scores_selects_and_attends_as_the_reference():
    cfg = _cfg(held=(4, 8))
    params = _params(cfg, seed=3)
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 128, (70,)),
                         jnp.int32)
    probed = []
    got = np.asarray(kv2.keye_vl2_forward(params, tokens[None], cfg,
                                          probed=probed)[0])
    rows = (5, 40, 69)
    want, _, probes = ref.forward(params, tokens, _arch(cfg),
                                  probe_rows=rows)
    assert _rms(got, np.asarray(want)) <= 2e-4
    for (scores, chosen), layer in zip(probed, probes):
        for t in rows:
            np.testing.assert_allclose(np.asarray(scores[0, t, :t + 1]),
                                       np.asarray(layer[t][0][:t + 1]),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_array_equal(np.asarray(chosen[0, t]),
                                          np.asarray(layer[t][1]))
            assert int(chosen[0, t].sum()) == min(t + 1, TOPK)
    # ... and the selection MOVES the logits: the reference that attends
    # everything differs by far more than float32's tolerance
    everything = dataclasses.replace(_arch(cfg), topk=1 << 20)
    assert _rms(got, np.asarray(ref.logits(params, tokens, everything))) \
        > 40 * 2e-4


# ----------------------------------------------------------------------
# (b) chunks riding decode calls, then decoding, through the paged pool
# ----------------------------------------------------------------------


def _paged_rows(spec, params, prompts, chunk, block, nb, blocks,
                decode_tokens=3):
    """Two sequences through the spec's three paged programs: the second's
    chunks by `prefill_paged_fn`, then the first's every chunk RIDING a
    decode token of the second (`mixed_paged_fn`), then both decoding ->
    [per sequence: (tokens, [(position, logits, index scores [layers, nb *
    block], selection)])]."""
    slots = 3
    rows = (2, 0)
    pool = spec.init_paged_pool(blocks, block, jnp.float32)
    tables = np.zeros((slots, nb), np.int32)
    free = iter(range(1, blocks))
    for row, prompt in zip(rows, prompts):
        need = -(-(len(prompt) + 2 * chunk + decode_tokens) // block)
        tables[row, :need] = [next(free) for _ in range(need)]
    seqs = [dict(tokens=list(p), rows=[]) for p in prompts]

    def chunk_args(prompt, start):
        seg = prompt[start:start + chunk]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(seg)] = seg
        return (toks, np.asarray([start], np.int32),
                np.asarray([len(seg) - 1], np.int32), len(seg))

    def slot_args(live):
        tok, pos = np.zeros((slots,), np.int32), np.zeros((slots,), np.int32)
        for i in live:
            tok[rows[i]] = seqs[i]["tokens"][-1]
            pos[rows[i]] = len(seqs[i]["tokens"]) - 1
        mine = np.isin(np.arange(slots), [rows[i] for i in live])
        return tok, pos, np.where(mine[:, None], tables, 0)

    def keep(seq, position, logits, scores, chosen):
        seq["rows"].append((position, np.asarray(logits), np.asarray(scores),
                            np.asarray(chosen)))

    first, second = seqs
    for start in range(0, len(prompts[1]), chunk):
        toks, s, last, n = chunk_args(prompts[1], start)
        logits, pool, _, (scores, chosen) = spec.prefill_paged_fn(
            params, toks, s, last, pool, tables[rows[1]][None], probe=last[0])
        keep(second, start + n - 1, logits[0], scores[:, 0], chosen[:, 0])
    second["tokens"].append(int(np.asarray(logits[0]).argmax()))
    for start in range(0, len(prompts[0]), chunk):
        toks, s, last, n = chunk_args(prompts[0], start)
        tok, pos, live = slot_args([1])
        logits, pool, _, (scores, chosen) = spec.mixed_paged_fn(
            params, toks, s, last, tables[rows[0]][None], tok, pos, pool,
            live, probe=last[0])
        keep(first, start + n - 1, logits[0], scores[:, 0], chosen[:, 0])
        at = 1 + rows[1]
        keep(second, int(pos[rows[1]]), logits[at], scores[:, at],
             chosen[:, at])
        second["tokens"].append(int(np.asarray(logits[at]).argmax()))
    first["tokens"].append(int(np.asarray(logits[0]).argmax()))
    for _ in range(decode_tokens):
        tok, pos, live = slot_args([0, 1])
        logits, pool, _, (scores, chosen) = spec.decode_paged_fn(
            params, tok, pos, pool, live, probe=jnp.int32(0))
        for i, seq in enumerate(seqs):
            keep(seq, int(pos[rows[i]]), logits[rows[i]],
                 scores[:, rows[i]], chosen[:, rows[i]])
            seq["tokens"].append(int(np.asarray(logits[rows[i]]).argmax()))
    return [(np.asarray(s["tokens"][:-1], np.int32), s["rows"])
            for s in seqs]


def test_chunks_riding_and_decoding_match_the_reference_with_selection_on():
    """A 75-token prompt in chunks of 16 (its last a part of one) riding a
    35-token sequence's decode tokens, `topk` 12: LOGITS, index scores and
    selected sets of every chunk end and decode token against the
    reference's full forward."""
    cfg = _cfg(held=(0, 8))
    params = _params(cfg, seed=5)
    spec = kv2.make_keye_vl2_decode_model(cfg, params=params, name="tiny")
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 128, (n,), np.int32) for n in (75, 35)]
    arch = _arch(cfg)
    compared = 0
    for tokens, rows in _paged_rows(spec, params, prompts, 16, 16, 16, 24):
        positions = [r[0] for r in rows]
        want, _, probes = ref.forward(params, jnp.asarray(tokens), arch,
                                      head_rows=positions,
                                      probe_rows=tuple(positions))
        got = np.stack([r[1] for r in rows])
        assert _rms(got, np.asarray(want)) <= 2e-4
        assert np.abs(got - np.asarray(want)).max() \
            <= 2e-4 * np.abs(np.asarray(want)).max()
        for t, _, scores, chosen in rows:
            for layer, probed in enumerate(probes):
                np.testing.assert_allclose(
                    scores[layer, :t + 1], np.asarray(probed[t][0][:t + 1]),
                    rtol=1e-4, atol=1e-5)
                np.testing.assert_array_equal(
                    chosen[layer, :t + 1], np.asarray(probed[t][1][:t + 1]))
                compared += 1
    assert compared == 3 * (5 + 3 + 3 + 5 + 3)


# ----------------------------------------------------------------------
# (c) under `topk` the layer IS the dense one
# ----------------------------------------------------------------------


def test_at_a_context_under_topk_the_programs_are_the_qwen3_moe_familys():
    """`topk` 256 over contexts of 37-75: the selection is every position,
    and the model IS the Qwen3-MoE forward — the family SDAR's cell serves
    (`models/moe_gpt.py`, `moe_freq` 1, per-head q/k norms, softmax top-k
    renormalised) on the SAME weights less the indexer's: the scheduler
    emits the same tokens through chunks riding decode calls, and the pools
    hold the same K and V at every position and layer."""
    from deepspeed_tpu.models import moe_gpt
    cfg = _cfg(index_topk=256)
    params = _params(cfg, seed=7)
    tree, = params["period"]
    dense_cfg = moe_gpt.MoEGPTConfig(
        vocab_size=128, n_layer=3, n_head=8, n_kv_head=2, d_model=32,
        attn_head_dim=16, d_ff=16, max_seq_len=256, use_rotary=True,
        rope_theta=1e7, norm_eps=1e-6, use_swiglu=True, use_rmsnorm=True,
        qk_norm_per_head=True, tie_embeddings=False, num_experts=16, top_k=4,
        norm_topk_prob=True, moe_freq=1, dtype=jnp.float32,
        use_flash_attention=False)
    dense = {"wte": params["wte"], "lm_head": params["lm_head"],
             "lnf_scale": params["lnf_scale"],
             "blocks": {k: v for k, v in tree.items()
                        if not k.startswith("idx_") and k != "moe_gate_bias"}}
    rng = np.random.default_rng(4)
    reqs = [Request(uid=i, tokens=rng.integers(0, 128, (n,), np.int32),
                    max_new_tokens=m, stop_on_eos=False)
            for i, (n, m) in enumerate([(45, 9), (7, 30), (70, 5)])]
    _, srv = _serving(cfg, params, one_device=True)
    got = srv.run(reqs)
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    engine = deepspeed_tpu.init_inference(
        moe_gpt.make_moe_gpt_decode_model(dense_cfg, params=dense,
                                          name="dense"),
        config={"dtype": "float32", "kv_cache_dtype": "float32",
                "greedy": True, "kv_block_size": 16, "max_out_tokens": 256})
    twin = engine.serving(max_slots=3, max_context=256, prefill_chunk=16,
                          num_kv_blocks=40, decode_steps_per_sync=3)
    want = twin.run(reqs)
    assert srv.stats()["fused_chunks"] == twin.stats()["fused_chunks"] > 0
    for r in reqs:
        np.testing.assert_array_equal(got[r.uid].tokens, want[r.uid].tokens)
    for leaf in ("k", "v"):     # block 0 is the trash block
        np.testing.assert_allclose(np.asarray(srv.pool[leaf])[:, 1:],
                                   np.asarray(twin.pool[leaf])[:, 1:],
                                   rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# (d) the share
# ----------------------------------------------------------------------


def test_eight_shares_add_up_to_the_uncut_references_layer():
    """The routed parts eight shares compute (`held` = 0-1, 2-3, ... of 16
    experts), summed, equal the reference's whole layer (no shared
    expert)."""
    cfg = _cfg()
    p = jax.tree_util.tree_map(lambda a: a[1], _params(cfg, seed=7)
                               ["period"][0])
    assert "shared_gate_w" not in p
    h = jnp.asarray(np.random.default_rng(3).normal(size=(24, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.routed_sum(h, p, _arch(cfg, held=(0, 16)))
        top_p, top_e = topk_routing(h, p["moe_gate_w"], cfg.top_k, True,
                                    scoring="softmax")
        total, elsewhere = jnp.zeros_like(h), 0
        for first in range(0, 16, 2):
            stacks = {"w_gate_up": p["moe_w_gate_up"][first:first + 2],
                      "w_down": p["moe_w_down"][first:first + 2]}
            part, counters = routed_experts(h, top_p, top_e, stacks,
                                            held=(first, 2))
            total = total + part
            elsewhere += int(counters[4])
            share = dataclasses.replace(cfg, experts_held=(first, 2))
            out, _, _ = em._sparse_mlp(h[None], {**p, **{
                "moe_" + k: v for k, v in stacks.items()}}, share)
            np.testing.assert_allclose(np.asarray(out[0]), np.asarray(part),
                                       rtol=2e-5, atol=2e-5)
    assert elsewhere == 7 * h.shape[0] * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------------
# (e) the exact selection, and the kernels against their twins
# ----------------------------------------------------------------------


def _stable_sort_sets(scores, limit, topk):
    out = np.zeros(scores.shape, bool)
    for r, row in enumerate(np.asarray(scores, np.float64)):
        n = int(limit[r])
        order = np.argsort(-row[:n], kind="stable")[:topk]
        out[r, order] = True
    return out


def _planted(rng, rows, S):
    scores = rng.normal(size=(rows, S)).astype(np.float32)
    scores[:, 5] = scores[:, 9] = scores[:, 100 % S] = 0.25  # ties, and
    scores[0, :] = 0.0              # a whole row of them, of both signs
    scores[0, ::2] = -0.0
    scores[1, :40] = np.float32(-3.0)
    scores[2, :] = np.sort(scores[2])[::-1]     # strictly ordered rows
    scores[3, :] = np.sort(scores[3])
    return scores


@pytest.mark.parametrize("topk", [1, 37, 200])
def test_the_selection_is_the_stable_sorts_with_planted_ties(topk):
    rng = np.random.default_rng(topk)
    rows, S = 24, 160
    scores = _planted(rng, rows, S)
    limit = np.asarray([S, S, S, S] + list(rng.integers(1, S + 1, rows - 4)))
    got = np.asarray(sparse_index.select_topk(
        jnp.asarray(scores), jnp.asarray(limit, jnp.int32), topk))
    np.testing.assert_array_equal(got, _stable_sort_sets(scores, limit, topk))
    assert (got.sum(-1) == np.minimum(limit, topk)).all()


@pytest.mark.parametrize("bias", [False, True], ids=["mask", "bias"])
def test_the_select_kernel_is_its_twin_block_major(bias):
    rng = np.random.default_rng(8)
    nb, block, rows, topk = 4, 128, 32, 50
    scores = _planted(rng, rows, nb * block)
    limit = np.concatenate([[nb * block] * 4,
                            rng.integers(1, nb * block + 1, rows - 4)])
    # past a row's own positions: whatever the memory held
    seen = np.arange(nb * block)[None] < limit[:, None]
    dirty = np.where(seen, scores, np.nan).astype(np.float32)
    major = jnp.asarray(dirty.reshape(rows, nb, block).transpose(1, 0, 2))
    got, _ = sparse_index.sparse_select(
        major[None], jnp.asarray(limit, jnp.int32)[None], topk, bias=bias,
        interpret=True)
    got = np.asarray(got[0]).transpose(1, 0, 2).reshape(rows, nb * block)
    chosen = got == 0 if bias else got > 0
    want = _stable_sort_sets(scores, limit, topk)
    live = (np.arange(nb * block)[None] // block
            <= (limit.max() - 1) // block)
    np.testing.assert_array_equal(chosen & live & seen, want)
    # ... and nothing past a row's own positions in a block the walk reads
    assert not (chosen & live & ~seen).any()


def _select_case(case, rng):
    """-> (scores [rows, S] float32, limit [rows], topk, block): what the
    bracket's search has to get right, a case a kind of input."""
    rows, nb, block, topk = 64, 8, 128, 192
    S = nb * block
    limit = np.full(rows, S)
    if case == "model_form":
        # 16 heads of w * relu(q . k), rows of three tiles under their own
        # frontiers (a chunk's: one more position a row)
        rows = 192
        limit = np.full(rows, S)
        q, k = rng.normal(size=(rows, 16, 8)), rng.normal(size=(S, 8))
        w = rng.normal(size=(rows, 16, 1))
        scores = (np.maximum(np.einsum("rhd,sd->rhs", q, k), 0) * w).sum(1)
        limit = S - rows + 1 + np.arange(rows)
    elif case == "ties_with_surplus":
        # eight levels: every row has more keys AT its k-th than it needs
        scores = np.round(rng.normal(size=(rows, S)) * 2) / 2
    elif case == "ties_without_surplus":
        # the k-th key is one of a tied group that the set takes WHOLE
        scores = rng.normal(size=(rows, S))
        order = np.argsort(-scores, axis=1)
        tied = scores[np.arange(rows), order[:, topk - 1]]
        for r in range(rows):
            scores[r, order[r, topk - 5:topk]] = tied[r]
    elif case == "all_equal":
        scores = np.full((rows, S), 0.75)
        scores[1::2] = -2.5
    elif case == "zeros_of_both_signs":
        scores = np.where(rng.random((rows, S)) < 0.5, 0.0, -0.0)
        scores[::3, ::7] = 1e-3     # fewer positives than topk
    elif case == "negative_and_subnormal":
        scores = -np.abs(rng.normal(size=(rows, S)))
        scores[:, ::5] = rng.normal(size=(rows, (S + 4) // 5)) * 1e-41
        scores[0] *= 1e30
    elif case == "limits_from_one":
        # one tile's rows from a single valid position to the table's end
        rows = 32
        scores = rng.normal(size=(rows, S))
        limit = np.unique(np.concatenate(
            [[1, 2, topk - 1, topk, topk + 1, S],
             rng.integers(1, S + 1, 64)]))[:rows]
        limit = np.sort(np.resize(limit, rows))[::-1].copy()
    elif case == "within_topk":
        # a tile no row of which has more positions than topk: nothing read
        rows = 32
        scores = np.full((rows, S), np.nan)
        limit = rng.integers(1, topk + 1, rows)
    else:
        assert case == "adversarial"
        # every key the sample holds small, every other large: the bracket
        # lies under the k-th key and the proving sweep must say so
        stride = sparse_index._sample_stride(topk, block // 128)
        pos = np.arange(S)
        sampled = (pos % 128) % stride == (pos // block) % stride
        scores = np.abs(rng.normal(size=(rows, S))) + 1.0
        scores = np.where(sampled[None], -scores, scores)
    return scores.astype(np.float32), limit.astype(np.int32), topk, block


@pytest.mark.parametrize("bias", [False, True], ids=["mask", "bias"])
@pytest.mark.parametrize("case", [
    "model_form", "ties_with_surplus", "ties_without_surplus", "all_equal",
    "zeros_of_both_signs", "negative_and_subnormal", "limits_from_one",
    "within_topk", "adversarial"])
def test_the_select_kernel_finds_the_twins_set_from_a_verified_bracket(
        case, bias):
    """Bit-equal to `select_topk` whatever the sample said: the bracket only
    decides how many sweeps the search makes (the counters)."""
    scores, limit, topk, block = _select_case(
        case, np.random.default_rng(len(case)))
    rows, S = scores.shape
    nb, tr = S // block, sparse_index.select_rows(rows)
    seen = np.arange(S)[None] < limit[:, None]
    # past a row's own positions: whatever the memory held
    dirty = np.where(seen, scores, np.nan).astype(np.float32)
    major = jnp.asarray(dirty.reshape(rows, nb, block).transpose(1, 0, 2))
    got, (tiles, sweeps, fallback) = sparse_index.sparse_select(
        major[None], jnp.asarray(limit)[None], topk, bias=bias,
        interpret=True)
    got = np.asarray(got[0]).transpose(1, 0, 2).reshape(rows, S)
    chosen = got == 0 if bias else got > 0
    want = np.asarray(sparse_index.select_topk(
        jnp.asarray(np.where(seen, scores, 0.0)), jnp.asarray(limit), topk))
    assert (want.sum(-1) == np.minimum(limit, topk)).all()
    frontier = (limit.reshape(-1, tr).max(-1) - 1) // block
    live = np.arange(S)[None] // block <= np.repeat(frontier, tr)[:, None]
    np.testing.assert_array_equal(chosen & live & seen, want)
    assert not (chosen & live & ~seen).any()
    # the counters: a sweep to make the keys, the sample's search (8 to 32
    # bits of two ranks at its share), the proof, the bisection, the write
    stride = sparse_index._sample_stride(topk, block // 128)
    least = 3 + 16 // (stride * (block // 128))
    fixed = 3 + 64 // (stride * (block // 128))
    pos_bits = (S - 1).bit_length()
    assert int(tiles) == rows // tr
    if case == "within_topk":
        assert (int(sweeps), int(fallback)) == (int(tiles), 0)
    elif case == "adversarial":
        assert int(fallback) == int(tiles)
        # ... and the search still ends inside the key's 32 bits
        assert int(sweeps) <= int(tiles) * (fixed + 32)
    elif case in ("ties_with_surplus", "all_equal", "zeros_of_both_signs"):
        # the position search ran: one sweep for `need`, one a position bit
        assert int(sweeps) >= int(tiles) * (least + 1 + pos_bits)
    else:
        assert int(fallback) == 0
        # no tie is cut: no position search, and the bisection is over the
        # interval the bracket left, not over the key's 32 bits (but where
        # the bracket spans subnormal AND normal floats, 2**29 keys apart,
        # and two of a row's few subnormals may tie at its k-th key)
        most = 32 + 1 + pos_bits if case == "negative_and_subnormal" else 24
        assert int(tiles) * least <= int(sweeps) <= int(tiles) * (fixed + most)


def test_the_score_walks_are_their_twin_over_the_paged_index_keys():
    rng = np.random.default_rng(0)
    C, Hi, d, block, nb, M = 128, 4, 64, 128, 4, 9
    keys = jnp.asarray(rng.normal(size=(M, 1, block, 128)),
                       jnp.float32).at[..., d:].set(0)
    qi = jnp.asarray(rng.normal(size=(1, C, Hi, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(1, C, Hi)), jnp.float32)
    tables = jnp.asarray([[3, 5, 7, 0]], jnp.int32)
    start = jnp.asarray([200], jnp.int32)
    got = sparse_index.paged_index_scores(qi, w, keys, tables, start,
                                          interpret=True)
    ctx = keys[tables[0], 0].reshape(1, nb * block, 128)[..., :d]
    want = sparse_index.index_scores(qi, w, ctx)
    got = np.asarray(got).transpose(0, 2, 1, 3).reshape(1, C, nb * block)
    live = (200 + C - 1) // block + 1
    np.testing.assert_allclose(got[..., :live * block],
                               np.asarray(want)[..., :live * block],
                               rtol=1e-5, atol=1e-5)
    # the slots' rows over the decode walk's work list; slot 2 is dead
    B = 5
    qd = jnp.asarray(rng.normal(size=(B, Hi, d)), jnp.float32)
    wd = jnp.asarray(rng.normal(size=(B, Hi)), jnp.float32)
    tb = jnp.asarray(rng.integers(1, M, (B, nb)), jnp.int32).at[2].set(0)
    pos = jnp.asarray([0, 127, 5, 300, 511], jnp.int32)
    got = np.asarray(sparse_index.paged_index_scores_decode(
        qd, wd, keys, tb, pos, interpret=True)).reshape(B, nb * block)
    ctx = keys[tb, 0].reshape(B, nb * block, 128)[..., :d]
    want = np.asarray(sparse_index.index_scores(qd[:, None], wd[:, None],
                                                ctx)[:, 0])
    for b in (0, 1, 3, 4):
        n = (int(pos[b]) // block + 1) * block
        np.testing.assert_allclose(got[b, :n], want[b, :n], rtol=1e-5,
                                   atol=1e-5)


def test_the_kernels_serve_the_references_tokens_through_the_carried_pool(
        monkeypatch):
    """The served path steered onto the in-place form (on the CPU the rule
    declines; the kernels run in the interpreter): heads of 128, index keys
    of 64 in their own leaf, the writer, the score walks, the selection and
    both masked walks through the scheduler — the reference's greedy
    tokens with `topk` 150 under contexts of 40-300."""
    monkeypatch.setattr(attn_dispatch, "kv_pool_writer",
                        lambda pool: attn_dispatch.KV_POOL_WRITE_KERNEL)
    cfg = _cfg(n_layer=2, n_head=4, n_kv_head=2, attn_head_dim=128,
               index_head_dim=64, index_topk=150, use_flash_attention=True)
    params = _params(cfg, seed=9)
    _, srv = _serving(cfg, params, one_device=True, block=128, max_slots=2,
                      max_context=512, num_kv_blocks=10,
                      decode_steps_per_sync=2, prefill_chunk=128)
    assert set(srv.pool) == {"k", "v", "ik"}
    rng = np.random.default_rng(13)
    reqs = [Request(uid=i, tokens=rng.integers(0, 128, (n,), np.int32),
                    max_new_tokens=m, stop_on_eos=False)
            for i, (n, m) in enumerate([(300, 4), (40, 5), (200, 3)])]
    done = srv.run(reqs)
    arch = _arch(cfg)
    for r in reqs:
        seq = np.concatenate([r.tokens, done[r.uid].tokens])
        greedy = np.asarray(ref.logits(params, jnp.asarray(seq),
                                       arch)).argmax(-1)
        np.testing.assert_array_equal(done[r.uid].tokens,
                                      greedy[len(r.tokens) - 1:-1])
    stats = srv.stats()
    assert set(stats["kv_pool_writer"].values()) \
        == {attn_dispatch.KV_POOL_WRITE_KERNEL}
    assert stats["attention_program"] == {
        "decode_step": "paged_kernel", "prefill_step": "paged_prefill_kernel",
        "mixed_step": "paged_prefill_kernel+paged_kernel"}
    assert stats["compiles"] == {"decode_step": 1, "prefill_step": 1,
                                 "mixed_step": 1}
    records = srv.steptrace.records()
    assert sum(r.prefill_live_blocks for r in records) > 0
    assert sum(r.decode_live_blocks for r in records) > 0
    # the selection's own counters, read back with the tokens: a chunk of
    # 128 rows is two row tiles a layer, a decode token one (both slots'
    # rows); a tile makes one sweep where no row is past `topk` positions
    # (the first chunk of a prompt) and at most the sample's 32 + 3, the
    # key's 32 bits and a position search; no bracket of this model's
    # scores is refuted
    counted = stats["step_counters"]
    assert sparse_index.select_rows(128) == 64
    tiles = cfg.n_layer * (2 * stats["prefill_chunks"]
                           + 2 * stats["decode_steps"])
    assert counted["sparse_select_tiles"] == tiles
    most = 3 + 64 // sparse_index._sample_stride(150, 1) + 32 + 1 + 9
    assert tiles < counted["sparse_select_sweeps"] <= tiles * most
    assert counted["sparse_select_fallback_tiles"] == 0
    names = srv.step_counter_names
    assert names[-3:] == sparse_index.SELECT_COUNTERS
    assert np.sum([r.counters for r in records], axis=0)[-3:].tolist() \
        == [counted[name] for name in sparse_index.SELECT_COUNTERS]


@pytest.mark.parametrize("family, cases, builder", [
    ("exaone_moe", "exaone_cases", "make_exaone_moe_decode_model"),
    ("glm4_moe_lite", "glm_cases", "make_glm4_moe_lite_decode_model"),
    ("mimo_v2_flash", "mimo_cases", "make_mimo_v2_flash_decode_model"),
    ("keye_vl2", "keye_cases", "make_keye_vl2_decode_model")])
def test_only_the_selected_kind_names_the_selections_counters(
        family, cases, builder):
    """K-EXAONE's, GLM's and MiMo's `step_counters` are the routed experts'
    five, as before PR 61 (their step programs' text is pinned in
    `tests/step_program_hashes.json`); the family with the `SELECTED` kind
    adds the selection's three BEHIND them, so a reader by index keeps its
    places."""
    import importlib
    from deepspeed_tpu.parallel.moe import HELD_ROUTED_COUNTERS
    module = importlib.import_module("tests." + cases)
    model = importlib.import_module("deepspeed_tpu.models." + family)
    cfg = module._cfg()
    spec = getattr(model, builder)(cfg, params=module._params(cfg))
    own = sparse_index.SELECT_COUNTERS if family == "keye_vl2" else ()
    assert spec.step_counters == HELD_ROUTED_COUNTERS + own
    assert [kind.counters for kind in em.ATTN_KINDS.values()] \
        == [(), (), (), sparse_index.SELECT_COUNTERS]


# ----------------------------------------------------------------------
# the scheduler: the ring's fields, the pool's bytes, what is refused
# ----------------------------------------------------------------------


def test_the_scheduler_serves_the_references_tokens_and_books_the_indexer():
    cfg = _cfg(held=(4, 8))
    params = _params(cfg, seed=3)
    _, srv = _serving(cfg, params, one_device=True,
                      enable_prefix_caching=True)
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 128, (40,), np.int32)
    reqs = [Request(uid=i, tokens=t, max_new_tokens=m, stop_on_eos=False)
            for i, (t, m) in enumerate([
                (rng.integers(0, 128, (45,), np.int32), 9),
                (rng.integers(0, 128, (7,), np.int32), 47),
                (np.concatenate([shared, [5, 6]]).astype(np.int32), 4)])]
    done = srv.run(reqs)
    # a later request finds the shared prefix's blocks, index keys and all
    again = Request(uid=9, tokens=np.concatenate([shared, [8, 9, 10]])
                    .astype(np.int32), max_new_tokens=4, stop_on_eos=False)
    done.update(srv.run([again]))
    assert done[9].cached_prefix_tokens == 32
    arch = _arch(cfg)
    for r in reqs + [again]:
        seq = np.concatenate([r.tokens, done[r.uid].tokens])
        greedy = np.asarray(ref.logits(params, jnp.asarray(seq),
                                       arch)).argmax(-1)
        np.testing.assert_array_equal(done[r.uid].tokens,
                                      greedy[len(r.tokens) - 1:-1])
    stats = srv.stats()
    assert stats["fused_chunks"] > 0
    assert stats["compiles"] == {"decode_step": 1, "prefill_step": 1,
                                 "mixed_step": 1}
    kind = stats["kv_pool_kinds"]["full"]
    # K and V of 2 heads of 16 + the index key STORED in a lane tile, 3
    # layers of float32; the model's entry has 8 index values
    assert kind["bytes_per_token"] == 3 * (2 * 32 + 128) * 4
    assert kind["model_bytes_per_token"] == 3 * (2 * 32 + 8) * 4
    records = srv.steptrace.records()
    scored = sum(r.index_scored_positions for r in records)
    kept = sum(r.selected_positions for r in records)
    assert sum(r.sparse_walk_positions for r in records) == scored > kept > 0


def test_the_ring_counts_what_was_scored_selected_and_walked():
    cfg = _cfg()
    _, srv = _serving(cfg, _params(cfg), one_device=True)
    prompt = np.random.default_rng(1).integers(0, 128, (40,), np.int32)
    srv.run([Request(uid=0, tokens=prompt, max_new_tokens=5,
                     stop_on_eos=False)])
    records = srv.steptrace.records()
    # three chunks of 16 rows (the last's padding too), then two decode
    # windows of three tokens from position 40
    seen = list(range(1, 49)) + list(range(41, 47))
    assert sum(r.index_scored_positions for r in records) == sum(seen)
    assert sum(r.selected_positions for r in records) \
        == sum(min(t, TOPK) for t in seen)
    assert sum(r.sparse_walk_positions for r in records) == sum(seen)


@pytest.mark.parametrize("knobs, match", [
    ({"quantization": {"kv_cache_dtype": "int8"}},
     "an index key a position.*kv_cache_dtype int8 is not built"),
    ({"spec_decode": {"drafter": "ngram", "draft_k": 2}},
     "no verify_paged_fn"),
])
def test_serving_refuses_by_name_what_the_sparse_kind_does_not_take(knobs,
                                                                    match):
    cfg = _cfg()
    with pytest.raises(ValueError, match=match):
        _serving(cfg, _params(cfg), **knobs)


def test_the_model_spec_refuses_the_contiguous_cache():
    cfg = _cfg()
    spec = kv2.make_keye_vl2_decode_model(cfg, params=_params(cfg))
    with pytest.raises(NotImplementedError, match="paged"):
        spec.prefill_fn(None, None, None, None)
    with pytest.raises(ValueError, match="one lane tile"):
        _cfg(index_head_dim=256)


# ----------------------------------------------------------------------
# (g) the walks with no selection lower as the parent's
# ----------------------------------------------------------------------


def _kernel_step_programs():
    """The three step programs of a tiny dense engine steered onto the
    KERNELS (the in-place writer, `dstpu_paged_decode`, `dstpu_paged_prefill`
    through the interpreter), lowered as the scheduler calls them, locations
    stripped: name -> sha. The walks took `selected=` in PR 60; with none
    given their text is the parent's (`tests/step_program_hashes.json`,
    `gpt_kernels`, written by this function in a `git archive` of
    1a1ebad)."""
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    cfg = gpt_mod.GPTConfig(n_layer=2, n_head=2, n_kv_head=1, d_model=256,
                            vocab_size=256, max_seq_len=512, use_rotary=True,
                            use_swiglu=True, use_rmsnorm=True,
                            use_flash_attention=True, dtype=jnp.float32)
    engine = deepspeed_tpu.init_inference(
        gpt_mod.make_gpt_decode_model(cfg, name="tiny"), config={
            "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
            "kv_block_size": 128, "max_out_tokens": 512})
    srv = engine.serving(max_slots=2, max_context=512, prefill_chunk=128,
                         decode_steps_per_sync=2)
    out = {}
    for name, fn, args in srv.programs.examples(
            engine.params, srv.pool, srv._tables_arg(srv.tables), srv._rng):
        text = _strip(jax.jit(fn).lower(*args).as_text())
        out[name] = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert set(srv.attention_programs().values()) == {
        "paged_kernel", "paged_prefill_kernel",
        "paged_prefill_kernel+paged_kernel"}
    return out


def test_the_walks_with_no_selection_lower_to_the_parents_text(monkeypatch):
    monkeypatch.setattr(attn_dispatch, "kv_pool_writer",
                        lambda pool: attn_dispatch.KV_POOL_WRITE_KERNEL)
    with open(os.path.join(ROOT, "tests", "step_program_hashes.json")) as f:
        assert _kernel_step_programs() == json.load(f)["gpt_kernels"]


# ----------------------------------------------------------------------
# (f) the benchmark's files
# ----------------------------------------------------------------------


def _benchmark_module(kind, name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name[:-3]}", os.path.join(BENCH, kind, name))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_benchmark_holds_the_cells_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longctx_sparse_queue_backlog", 1)
    config = {c["name"]: c for c in bench["configs"]}[CONFIG]
    with open(os.path.join(ROOT, config["file"])) as f:
        published = json.load(f)
    assert config["source"] == published["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    assert config["reduced"] == published["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert published["reduced_from"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    # every width, the head counts and `sa_config` whole, as published
    for key, value in {
            "attention_bias": False, "decoder_sparse_step": 1,
            "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 6144, "max_position_embeddings": 262144,
            "max_window_layers": 48, "mlp_only_layers": [],
            "model_type": "KeyeVL2", "moe_intermediate_size": 768,
            "norm_topk_prob": True, "num_attention_heads": 32,
            "num_experts": 16, "num_experts_per_tok": 8,
            "num_hidden_layers": 12, "num_key_value_heads": 4,
            "num_local_experts": 128, "rms_norm_eps": 1e-06,
            "rope_scaling": {"mrope_section": [16, 24, 24],
                             "rope_type": "default", "type": "default"},
            "rope_theta": 10000000,
            "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                          "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                          "q_chunk_size": 512, "topk": 2048},
            "sliding_window": None, "tie_word_embeddings": False,
            "use_sliding_window": False, "vocab_size": 18992,
            "published_num_experts": 128,
            "experts_held_range": [0, 16]}.items():
        assert published[key] == value, key
    assert published["parameters_held"]["by_the_issue"] == 1240586752
    for kind, name in (("drivers", published["driver"] + ".py"),
                       ("references", published["reference"] + ".py"),
                       ("traffic", cell["traffic"] + ".json"),
                       ("checks", "rehearsal_keyevl2.json")):
        assert os.path.exists(os.path.join(BENCH, kind, name)), name
    for key in ("assumed", "why_reduced", "why_serving", "check_limits",
                "deployment", "departures_of_the_program"):
        assert published[key], key
    reported = [m["name"] for m in bench["end_to_end"]
                if CELL in m.get("workloads", [CELL])]
    assert sorted(reported) == ["serve_tokens_per_s", "setup_s"]
    # the table is FULL: the cell joins entries, by its name, exactly those
    # `serve_sdar_blockdiff_generate` joined, and brings none
    assert len(bench["per_layer"]) <= 128
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    sdar = {m["name"] for m in bench["per_layer"]
            if "serve_sdar_blockdiff_generate" in m.get("workloads", [])}
    assert mine == sdar and len(mine) == 24
    assert all("workloads" in m for m in bench["per_layer"]
               if m["name"] in mine)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["kind"], traffic["min_queue"], traffic["grid"]) \
        == ("closed_backlog", 16, 64)
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "min": 8192,
                                        "max": 65536}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 256,
                                        "max": 1024}
    found = _benchmark_module("", "harness.py").load_cell(CELL)
    assert found["config_json"]["driver"] == "serve_keye_vl2"
    assert found["traffic_json"]["preroll_s"] >= 100
    # ... the 24 it joined and the two every cell reports (no list)
    assert {m["name"] for m in found["per_layer"]} \
        == mine | {"init_s", "compile_s"}


def test_the_sparse_rooflines_count_the_models_work():
    roofline = _benchmark_module("", "roofline_sparse.py")
    # the score walk: 2 x 16 x 64 a pair; 128 B of index key a position a
    # call reads (a chunk's rows share it)
    flops, nbytes = roofline.index_scores(pairs=1000, keys_read=40, layers=12,
                                          heads=16, dim=64, itemsize=2)
    assert (flops, nbytes) == (12 * 1000 * 2 * 16 * 64, 12 * 40 * 128)
    # the selection: 32 key passes + the tie rule's, an element each
    assert roofline.select_passes(table_positions=67584) == 32 + 17 + 3
    # the sparse walk: the SELECTED pairs' products and the entries a call
    # has to read for them, whatever form reads them
    flops, nbytes = roofline.sparse_walk(pairs=2048, entries_read=2048,
                                         layers=12, heads=32, kv_heads=4,
                                         head_dim=128, itemsize=2)
    assert nbytes == 12 * 2048 * 2 * 4 * 128 * 2
    assert flops == 12 * 2048 * 2 * 2 * 32 * 128
