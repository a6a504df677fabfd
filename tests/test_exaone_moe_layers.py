"""K-EXAONE's layer-level pieces on the CPU (the serving path is
`tests/test_exaone_moe.py`): the share of an
expert-parallel layer adds up to the model, the sigmoid router against a
transcription of `DeepseekV3TopkRouter`, and the windowed walks (Pallas
interpreter) against `_paged_attend`, on a ring as on an ordinary table."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_cache import (gather_block_kv, ring_blocks,
                                              ring_tables)
from deepspeed_tpu.models import exaone_moe as em
from deepspeed_tpu.models.gpt import GPTConfig, _paged_attend
from deepspeed_tpu.ops.pallas.decode_attention import (
    paged_decode_attention, paged_decode_work, window_first_block)
from deepspeed_tpu.ops.pallas.prefill_attention import (
    paged_prefill_attention, paged_prefill_live_blocks)
from deepspeed_tpu.parallel.moe import routed_experts, topk_routing
from tests import glm_cases
from tests.exaone_cases import _arch, _cfg, ref

# ----------------------------------------------------------------------
# the share adds up to the model
# ----------------------------------------------------------------------


def _sparse_layer(seed=0, E=16, D=32, F=16, rows=24):
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.normal(0, 0.3, shape),
                                        jnp.float32)
    p = {"moe_gate_w": normal(D, E), "moe_gate_bias": jnp.zeros((E,)),
         "moe_w_gate_up": normal(E, D, 2 * F), "moe_w_down": normal(E, F, D),
         "shared_gate_w": normal(D, F), "shared_up_w": normal(D, F),
         "shared_down_w": normal(F, D)}
    return p, normal(rows, D)


# the two families that serve one chip's share of a sigmoid-routed layer: the
# family's test cases (configuration, reference and its `Arch`)
_SHARED_FAMILIES = {
    "exaone_moe": (_cfg, _arch, ref),
    "glm4_moe_lite": (glm_cases._cfg, glm_cases._arch, glm_cases.ref),
}


@pytest.mark.parametrize("family", sorted(_SHARED_FAMILIES))
def test_eight_shares_and_the_shared_expert_once_add_up_to_the_whole_layer(
        family):
    """Guide §4's one test: the routed parts that the eight shares compute
    (`held` = 0-1, 2-3, ... of 16 experts), summed, plus the shared expert
    ONCE, equal the reference's whole sparse layer with every expert."""
    make_cfg, make_arch, ref = _SHARED_FAMILIES[family]
    p, h = _sparse_layer()
    cfg = make_cfg()
    arch = make_arch(cfg, held=None)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.routed_sum(h, p, arch)
        whole = whole + ref.shared_expert(h, p, arch)
        top_p, top_e = topk_routing(h, p["moe_gate_w"], cfg.top_k, True,
                                    scoring="sigmoid",
                                    bias=p["moe_gate_bias"],
                                    scale=cfg.routed_scaling_factor)
        total = jnp.zeros_like(h)
        elsewhere = 0
        for share in range(8):
            first = 2 * share
            stacks = {"w_gate_up": p["moe_w_gate_up"][first:first + 2],
                      "w_down": p["moe_w_down"][first:first + 2]}
            part, counters = routed_experts(h, top_p, top_e, stacks,
                                            held=(first, 2))
            total = total + part
            assert int(counters[1]) + int(counters[4]) == h.shape[0] * 4
            elsewhere += int(counters[4])
        total = total + em._swiglu(h, p["shared_gate_w"], p["shared_up_w"],
                                   p["shared_down_w"])
    # every assignment is held by exactly one share
    assert elsewhere == 7 * h.shape[0] * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)


def test_a_token_whose_experts_all_live_elsewhere_gets_the_shared_expert():
    p, h = _sparse_layer(seed=1)
    cfg = _cfg(held=(12, 4))
    # the bias sends every token to experts 0-3: none of them is held
    p["moe_gate_bias"] = jnp.where(jnp.arange(16) < 4, 10.0, 0.0)
    tree = dict(p, moe_w_gate_up=p["moe_w_gate_up"][12:],
                moe_w_down=p["moe_w_down"][12:])
    out, counters, top_e = em._sparse_mlp(h[None], tree, cfg)
    assert np.asarray(top_e).max() < 4
    assert [int(c) for c in counters] == [1, 0, 0, 0, h.shape[0] * 4]
    shared = em._swiglu(h, p["shared_gate_w"], p["shared_up_w"],
                        p["shared_down_w"])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(shared))


@pytest.mark.parametrize("held", [(0, 16), (4, 4), (14, 2)])
def test_held_counters_count_the_rows_the_grouped_matmul_multiplies(held):
    p, h = _sparse_layer(seed=2)
    top_p, top_e = topk_routing(h, p["moe_gate_w"], 4, True,
                                scoring="sigmoid")
    first, count = held
    stacks = {"w_gate_up": p["moe_w_gate_up"][first:first + count],
              "w_down": p["moe_w_down"][first:first + count]}
    _, counters = routed_experts(h, top_p, top_e, stacks, held=held)
    e = np.asarray(top_e).reshape(-1)
    mine = e[(e >= first) & (e < first + count)]
    loads = np.bincount(mine - first, minlength=count)
    assert [int(c) for c in counters] == [
        1, len(mine), int((loads > 0).sum()), int(loads.max()),
        len(e) - len(mine)]


# ----------------------------------------------------------------------
# the router, against a transcription of DeepseekV3TopkRouter
# ----------------------------------------------------------------------


def _deepseek_v3_router(x, weight, bias, top_k, n_group, topk_group,
                        norm_topk_prob, routed_scaling_factor):
    """`DeepseekV3TopkRouter.forward` in numpy (float32), line for line."""
    logits = x.astype(np.float32) @ weight.astype(np.float32).T
    scores = 1.0 / (1.0 + np.exp(-logits))
    n, E = scores.shape
    choice = scores + bias[None]
    grouped = choice.reshape(n, n_group, E // n_group)
    group_scores = np.sort(grouped, axis=-1)[..., -2:].sum(-1)
    group_idx = np.argsort(-group_scores, axis=-1)[:, :topk_group]
    group_mask = np.zeros_like(group_scores)
    np.put_along_axis(group_mask, group_idx, 1.0, axis=1)
    score_mask = np.repeat(group_mask[:, :, None], E // n_group,
                           axis=2).reshape(n, E)
    choice = np.where(score_mask > 0, choice, 0.0)
    idx = np.argsort(-choice, axis=-1, kind="stable")[:, :top_k]
    weights = np.take_along_axis(scores, idx, axis=1)
    if norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return idx, weights * routed_scaling_factor


@pytest.mark.parametrize("with_bias, normalize, scale", [
    (False, True, 2.5), (True, True, 2.5), (True, False, 1.0),
], ids=["no-bias", "bias", "bias-unnormalised"])
def test_sigmoid_router_is_deepseek_v3s_with_one_group(with_bias, normalize,
                                                       scale):
    rng = np.random.default_rng(3)
    x = rng.normal(0, 1, (40, 32)).astype(np.float32)
    w = rng.normal(0, 0.5, (16, 32)).astype(np.float32)      # [E, D]
    bias = rng.normal(0, 0.3, (16,)).astype(np.float32) if with_bias \
        else np.zeros((16,), np.float32)
    want_e, want_w = _deepseek_v3_router(x, w, bias, 4, 1, 1, normalize,
                                         scale)
    with jax.default_matmul_precision("highest"):
        top_w, top_e = topk_routing(jnp.asarray(x), jnp.asarray(w.T), 4,
                                    normalize, scoring="sigmoid",
                                    bias=jnp.asarray(bias), scale=scale)
    order = np.argsort(np.asarray(top_e), axis=-1)
    want_order = np.argsort(want_e, axis=-1)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(top_e), order, -1),
        np.take_along_axis(want_e, want_order, -1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(top_w), order, -1),
        np.take_along_axis(want_w, want_order, -1), rtol=1e-5)
    if normalize:
        np.testing.assert_allclose(np.asarray(top_w).sum(-1), scale,
                                   rtol=1e-5)


def test_the_bias_moves_the_choice_and_never_the_weight():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 1, (8, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.5, (32, 16)), jnp.float32)
    bias = jnp.where(jnp.arange(16) == 5, 100.0, 0.0)
    plain_w, plain_e = topk_routing(x, w, 4, False, scoring="sigmoid")
    top_w, top_e = topk_routing(x, w, 4, False, scoring="sigmoid", bias=bias)
    assert (np.asarray(top_e) == 5).any(-1).all()       # chosen everywhere
    scores = jax.nn.sigmoid(x @ w)
    np.testing.assert_allclose(
        np.asarray(top_w),
        np.take_along_axis(np.asarray(scores), np.asarray(top_e), -1),
        rtol=1e-6)                                      # ... at its own score
    assert np.asarray(top_w).max() <= 1.0
    assert not (np.asarray(plain_e) == 5).any(-1).all()


def test_the_reference_router_is_the_same_transcription():
    cfg = _cfg()
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(0, 1, (12, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.5, (32, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.3, (16,)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got_w, got_e = ref.route(h, w, bias, _arch(cfg))
    want_e, want_w = _deepseek_v3_router(np.asarray(h), np.asarray(w).T,
                                         np.asarray(bias), 4, 1, 1, True, 2.5)
    np.testing.assert_array_equal(np.sort(np.asarray(got_e), -1),
                                  np.sort(want_e, -1))
    np.testing.assert_allclose(np.sort(np.asarray(got_w), -1),
                               np.sort(want_w, -1), rtol=1e-5)


def test_softmax_routing_is_what_it_was():
    """The routers share one function: OLMoE's call (no scoring argument)
    still gives the k largest softmax probabilities as they are."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(0, 1, (8, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.5, (32, 16)), jnp.float32)
    top_p, top_e = topk_routing(x, w, 4)
    probs = np.asarray(jax.nn.softmax(x @ w, -1))
    want = np.sort(probs, -1)[:, ::-1][:, :4]
    np.testing.assert_allclose(np.asarray(top_p), want, rtol=1e-5)
    with pytest.raises(ValueError, match="unknown router scoring"):
        topk_routing(x, w, 4, scoring="tanh")


# ----------------------------------------------------------------------
# the windowed walks, Pallas interpreter, against `_paged_attend`
# ----------------------------------------------------------------------

_B, _H, _HKV, _HD, _BLK, _W = 3, 4, 2, 32, 32, 32


def _walk_pool(seed, nb):
    rng = np.random.default_rng(seed)
    n = 1 + _B * nb
    pool = [jnp.asarray(rng.normal(size=(n, _HKV, _BLK, _HD)), jnp.float32)
            for _ in range(2)]
    tables = 1 + np.arange(_B)[:, None] * nb + np.arange(nb)[None]
    return pool, tables.astype(np.int32), rng


def _window_cfg():
    return GPTConfig(n_head=_H, n_kv_head=_HKV, d_model=_H * _HD,
                     sliding_window=_W)


@pytest.mark.parametrize("pos", [
    (3, 0, 90),                 # below the window
    (_W - 1, _W, _W + 1),       # at it
    (5 * _W + 7, 9 * _W - 1, 4 * _W),   # far above
], ids=["below", "at", "far-above"])
def test_windowed_decode_walk_is_the_oracle_with_the_window(pos):
    (kp, vp), tables, rng = _walk_pool(7, nb=10)
    q = jnp.asarray(rng.normal(size=(_B, _H, _HD)), jnp.float32)
    pos = jnp.asarray(pos, jnp.int32)
    out = paged_decode_attention(q, kp, vp, jnp.asarray(tables), pos,
                                 window=_W, interpret=True)
    k, v = gather_block_kv(kp, vp, jnp.asarray(tables))
    want = _paged_attend(q[:, None], k, v, pos[:, None], _window_cfg())
    np.testing.assert_allclose(np.asarray(out).reshape(_B, -1),
                               np.asarray(want[:, 0]), rtol=2e-5, atol=2e-5)
    # the work list visits the window's blocks and no other
    work = paged_decode_work(jnp.asarray(tables), pos, _BLK, window=_W)
    first = np.asarray(window_first_block(np.asarray(pos), _BLK, _W))
    assert int(work.count[0]) == int((np.asarray(pos) // _BLK + 1
                                      - first).sum())
    assert int(work.count[0]) <= 2 * _B


def test_windowed_decode_walk_skips_dead_slots():
    (kp, vp), tables, rng = _walk_pool(8, nb=6)
    tables[1] = 0
    q = jnp.asarray(rng.normal(size=(_B, _H, _HD)), jnp.float32)
    pos = jnp.asarray([100, 0, 5], jnp.int32)
    out = paged_decode_attention(q, kp, vp, jnp.asarray(tables), pos,
                                 window=_W, interpret=True)
    assert np.abs(np.asarray(out[1])).max() == 0.0
    work = paged_decode_work(jnp.asarray(tables), pos, _BLK, window=_W)
    assert int(work.count[0]) == 2 + 1


@pytest.mark.parametrize("start", [
    (0, 0, 0), (_W, 2 * _W, _W // 2), (6 * _W, 3 * _W + 8, 7 * _W),
], ids=["below", "at", "far-above"])
def test_windowed_prefill_walk_is_the_oracle_with_the_window(start):
    chunk = 64
    (kp, vp), tables, rng = _walk_pool(9, nb=10)
    q = jnp.asarray(rng.normal(size=(_B, chunk, _H, _HD)), jnp.float32)
    start = jnp.asarray(start, jnp.int32)
    out = paged_prefill_attention(q, kp, vp, jnp.asarray(tables), start,
                                  window=_W, interpret=True)
    k, v = gather_block_kv(kp, vp, jnp.asarray(tables))
    positions = start[:, None] + jnp.arange(chunk)[None]
    want = _paged_attend(q, k, v, positions, _window_cfg())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("start, want", [(0, 2), (128, 3), (1024, 3),
                                         (1000, 4), (10200, 2)])
def test_prefill_walk_host_twin_counts_from_the_windows_first_block(start,
                                                                    want):
    assert paged_prefill_live_blocks(start, 256, 128, 80, window=128) == want
    assert paged_prefill_live_blocks(start, 256, 128, 80) \
        == min((start + 255) // 128 + 1, 80)


def test_a_ring_reads_what_a_full_pool_of_the_same_layer_would():
    """A context of more than `ring` blocks, written chunk by chunk through
    a ring table and through an ordinary table: every chunk's windowed walk
    and the decode step after it read the same."""
    from deepspeed_tpu.ops.pallas.kv_pool import kv_pool_write_reference
    chunk, nb = 32, 8
    ring = ring_blocks(_W, _BLK, chunk, 1)
    assert ring == 3 and ring < nb
    rng = np.random.default_rng(10)
    _H, _HKV = 2, 1             # one group: the ring is the point here
    shape = (1 + nb, _HKV, _BLK, _HD)
    full = [jnp.zeros(shape, jnp.float32) for _ in range(2)]
    rings = [jnp.zeros((1 + ring,) + shape[1:], jnp.float32)
             for _ in range(2)]
    full_t = jnp.asarray(1 + np.arange(nb)[None], jnp.int32)
    ring_t = jnp.asarray(ring_tables(1, nb, ring))
    assert len(set(np.asarray(ring_t)[0])) == ring
    for start in range(0, 4 * chunk, chunk):
        rows = [jnp.asarray(rng.normal(size=(1, chunk, _HKV, _HD)),
                            jnp.float32) for _ in range(2)]
        at = jnp.asarray([start], jnp.int32)
        full = [kv_pool_write_reference(p, r, at, full_t)
                for p, r in zip(full, rows)]
        rings = [kv_pool_write_reference(p, r, at, ring_t)
                 for p, r in zip(rings, rows)]
        q = jnp.asarray(rng.normal(size=(1, chunk, _H, _HD)), jnp.float32)
        a = paged_prefill_attention(q, *full, full_t, at, window=_W,
                                    interpret=True)
        b = paged_prefill_attention(q, *rings, ring_t, at, window=_W,
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    pos = jnp.asarray([4 * chunk - 1], jnp.int32)
    a = paged_decode_attention(q[:, -1], *full, full_t, pos, window=_W,
                               interpret=True)
    b = paged_decode_attention(q[:, -1], *rings, ring_t, pos, window=_W,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
