"""The pieces of the Granite 4.0-H family's layer (`models/
granite_moe_hybrid.py` through `models/hybrid.py`): the block pattern as
runs, each half against the float32 reference, the four expert-parallel
shares adding up to the uncut layer, and the kernels at the shapes the family
brings — ONE group of B and C in `dstpu_ssm_update` and `ssm_chunk_scan`, the
grouped matmul at the gated experts' widths with 18 groups."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import granite_moe_hybrid as gh
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.gpt import _attn_half
from deepspeed_tpu.ops.pallas import moe_gmm, ssm
from tests.granite_cases import _arch, _cfg, _params, ref
from tests.nemotron_cases import assert_update_kernel_is_the_jnp_update

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@pytest.mark.parametrize("periods, want", [
    (1, [("ME", 5), ("*E", 1), ("ME", 4)]),
    (4, [("MEMEMEMEME*EMEMEMEME", 4)]),
], ids=["the-cut", "published"])
def test_a_block_is_the_patterns_kind_and_its_runs_are_scanned(periods, want):
    """`repeated_runs` over BLOCKS (a pair of halves a layer): the published
    40 layers are ONE run, four scanned periods of twenty halves; the
    one-period cut three runs; each run's unit is its halves' letters, which
    the loop traces a position at a time."""
    cfg = _cfg(layers=PERIOD * periods)
    assert hybrid.layer_runs(cfg) == want
    assert cfg.halves == "".join(u * n for u, n in want)
    assert cfg.n_layer == 10 * periods
    assert ref.pattern_runs(cfg.pattern) == tuple(
        (len(u) // 2, n) for u, n in want)
    kinds = hybrid.cache_kinds(cfg, 16)
    assert [k.layers for k in kinds] == [periods, 9 * periods]
    shapes = jax.eval_shape(gh.granite_moe_hybrid_init_fn(cfg),
                            jax.random.PRNGKey(0))
    assert [len(trees) for trees in shapes["runs"]] \
        == [len(u) for u, _ in want]
    assert [trees[0]["ln1_scale"].shape[0] for trees in shapes["runs"]] \
        == [n for _, n in want]


def _half(kind, seed, **over):
    """One half's leaves of a one-layer model, and an input."""
    cfg = _cfg(layers=("attention" if kind == "*" else "mamba",), **over)
    params = _params(cfg, seed=seed)
    at = 1 if kind == "E" else 0
    tree = jax.tree_util.tree_map(lambda a: a[0], params["runs"][0][at])
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 21, cfg.d_model))
    return cfg, tree, x


@pytest.mark.parametrize("groups", [1, 2])
def test_mamba_half_is_the_references_recurrence(groups):
    """ONE group as published (every head reads the same B and C, the gated
    norm over all the inner columns), and the grouped form the loop keeps."""
    cfg, p, x = _half("M", 3, n_groups=groups)
    got, _ = hybrid._mamba_half(x, p, cfg)
    for b in range(2):
        want, _ = ref._mamba(x[b], p, _arch(cfg))
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-6)


def test_attention_half_scores_by_the_multiplier_without_positions():
    cfg, p, x = _half("*", 4)
    p = dict(p, attn_qkv_w=p["attn_qkv_w"] * 60.0)  # scores that matter
    positions = jnp.broadcast_to(jnp.arange(21)[None], (2, 21))
    got, _, _ = _attn_half(x, p, hybrid._attention_cfg(cfg), positions,
                           constrain=False)
    for b in range(2):
        want = ref._attention(x[b], p, _arch(cfg))
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-6)
        other = ref._attention(x[b], p, _arch(cfg, attention_multiplier=0.25))
        assert np.abs(np.asarray(other - want)).max() \
            > 0.01 * np.abs(np.asarray(want)).max()


@pytest.mark.parametrize("held", [(0, 16), (4, 8)])
def test_expert_half_is_the_references(held):
    cfg, p, x = _half("E", 5, held=held)
    got, counters, top_e = gh._gated_moe(x, p, cfg)
    for b in range(2):
        want, sets = ref.experts(x[b], p, _arch(cfg))
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-6)
        np.testing.assert_array_equal(
            np.sort(top_e.reshape(2, 21, -1)[b], -1), sets)
    calls, here, _active, _largest, elsewhere = (int(c) for c in counters)
    assert calls == 1 and here + elsewhere == 2 * 21 * cfg.top_k
    assert here == int(((top_e >= held[0])
                        & (top_e < held[0] + held[1])).sum())


def test_the_chosen_experts_weights_are_a_softmax_over_the_chosen():
    cfg, p, x = _half("E", 6)
    u = ref._rms_norm(x[0], p["ln1_scale"], _arch(cfg))
    weights, chosen = ref.route(u, p["moe_gate_w"], _arch(cfg))
    logits = np.asarray(u @ p["moe_gate_w"])
    np.testing.assert_array_equal(
        np.sort(chosen, -1), np.sort(np.argsort(-logits, -1)[:, :cfg.top_k]))
    picked = np.take_along_axis(logits, np.asarray(chosen), -1)
    want = np.exp(picked) / np.exp(picked).sum(-1, keepdims=True)
    np.testing.assert_allclose(weights, want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-5)


def test_four_shares_and_the_shared_expert_once_add_up_to_the_whole_layer():
    """The deployment's four chips: each holds a quarter of the experts; the
    routed parts, with the shared expert's result counted once, are the
    uncut reference's layer."""
    cfg, p, x = _half("E", 7)                   # holds all 16
    x = x[:1, :19]
    whole, _ = ref.experts(x[0], p, _arch(cfg, held=(0, 16)))
    # a chip that holds no expert: the shared expert alone
    shared_only, _ = ref.experts(x[0], p, _arch(cfg, held=(16, 0)))
    parts = jnp.zeros_like(whole)
    for chip in range(4):
        first = 4 * chip
        share = dict(p, moe_w_gate_up=p["moe_w_gate_up"][first:first + 4],
                     moe_w_down=p["moe_w_down"][first:first + 4])
        # the program's share ...
        scfg = _cfg(layers=("mamba",), held=(first, 4))
        got, _, _ = gh._gated_moe(x, share, scfg)
        # ... is the reference's, and its routed part alone is what adds
        want, _ = ref.experts(x[0], share, _arch(scfg))
        np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-6)
        routed, _ = ref.experts(x[0], share, _arch(scfg), shared=False)
        parts = parts + routed
    np.testing.assert_allclose(parts + shared_only, whole, rtol=2e-4,
                               atol=2e-6)


# ----------------------------------------------------------------------
# the kernels at the family's shapes
# ----------------------------------------------------------------------


@pytest.mark.parametrize("rows, rows_a_step, H", [
    ([3, 1], None, 16), ([3, 1, 6, 2], 1, 16), ([3, 1, 6, 2], 2, 16),
    ([0, 5, 0, 0, 2, 8], 2, 16), ([4, 0, 0, 7, 0], 1, 16),
    ([6, 0, 3], 1, 128),
], ids=["one-step", "a-row-a-step", "two-rows-a-step", "trash-2-a-step",
        "trash-1-a-step", "eight-blocks-of-heads"])
def test_ssm_update_kernel_with_one_group_is_the_jnp_update(
        monkeypatch, rows, rows_a_step, H):
    """G = 1: a `(., 1, N)` block of B and C that all H heads read — the
    group spans both halves of a row's heads, every block of them, and
    every row of a step."""
    assert_update_kernel_is_the_jnp_update(monkeypatch, H, 8, 128, 1, rows,
                                           rows_a_step)


@pytest.mark.parametrize("T, chunk", [(32, 8), (37, 16)],
                         ids=["divides", "ragged"])
def test_chunked_scan_with_one_group_is_the_sequential_recurrence(T, chunk):
    k = jax.random.split(jax.random.PRNGKey(T), 6)
    b, H, P, N = 2, 8, 8, 16
    x = jax.random.normal(k[0], (b, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, T, H))) * 0.2
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    B, C = (jax.random.normal(key, (b, T, 1, N)) for key in k[3:5])
    S = jax.random.normal(k[5], (b, H, P, N))
    want_y, want_S = ssm.ssm_scan_reference(x, dt, A, B, C, S)
    got_y, got_S = ssm.ssm_chunk_scan(x, dt, A, B, C, S, chunk)
    np.testing.assert_allclose(got_y, want_y, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got_S, want_S, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("K, N", [(256, 1536), (768, 256)],
                         ids=["gate-up-1536-columns", "down-768-deep"])
def test_grouped_matmul_at_the_gated_experts_widths_with_18_groups(K, N):
    """18 held experts (no power of two), some with no row; 1536 columns
    fall to the 512 tile (`_col_tile`); the down projection is 768 deep."""
    assert moe_gmm._col_tile(1536) == 512
    E, M = 18, 256
    k = jax.random.split(jax.random.PRNGKey(K), 3)
    lhs = jax.random.normal(k[0], (M, K), jnp.float32)
    rhs = jax.random.normal(k[1], (2 * E, K, N), jnp.float32) * 0.05
    sizes = np.random.default_rng(K).multinomial(200, np.ones(E) / E)
    sizes[[3, 11]] = 0                          # idle experts
    sizes = jnp.asarray(sizes, jnp.int32)
    rows = int(sizes.sum())
    want = moe_gmm.moe_gmm_reference(lhs, rhs, sizes, group_offset=E)
    got = moe_gmm.moe_gmm(lhs, rhs, sizes, group_offset=E, interpret=True)
    np.testing.assert_allclose(got[:rows], want[:rows], rtol=2e-4, atol=2e-4)


def test_holding_the_in_projection_changes_no_logit(monkeypatch):
    """`_mamba_half` holds `u @ ssm_in_w` behind an `optimization_barrier`
    from the convolution to the gate, so that XLA computes it once (PR 42).
    The barrier is an identity: every Mamba-2 half passes its product through
    one, and the whole-sequence forward's logits are bit-equal to the same
    formula without it."""
    cfg = _cfg()
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                cfg.vocab_size)
    barrier, held = jax.lax.optimization_barrier, []
    monkeypatch.setattr(jax.lax, "optimization_barrier",
                        lambda x: held.append(x.shape) or barrier(x))
    with_it = np.asarray(jax.jit(
        lambda p, t: gh.granite_moe_hybrid_forward(p, t, cfg))(params, tokens))
    width = hybrid.mixer_shapes(cfg, hybrid.MAMBA)["ssm_in_w"][0][-1]
    assert held == [(2, 24, width)] * cfg.halves.count(hybrid.MAMBA)
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    without = np.asarray(jax.jit(
        lambda p, t: gh.granite_moe_hybrid_forward(p, t, cfg))(params, tokens))
    assert np.ptp(with_it) > 0 and np.array_equal(with_it, without)
