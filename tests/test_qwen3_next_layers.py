"""The pieces of the Qwen3-Next family's layer (`models/qwen3_next.py`
through `models/hybrid.py` and `models/gpt.py`): the block pattern as runs,
each half against the float32 reference, the eight expert-parallel shares
adding up to the uncut layer, the delta rule's decode kernel
(`dstpu_gdn_update`, interpreted) against its `jax.numpy` twin and its
chunked form against the position-at-a-time recurrence, and the kernels at
the shapes the family brings — the paged walks at head width 256 with eight
query heads a key-value head, the grouped matmul at 64 groups of experts
512 wide."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_cache import TRASH_BLOCK
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models import qwen3_next as qn
from deepspeed_tpu.models.gpt import GPTConfig, _attn_half, _paged_attend
from deepspeed_tpu.ops.pallas import gdn, moe_gmm, ssm
from deepspeed_tpu.ops.pallas.decode_attention import paged_decode_attention
from deepspeed_tpu.ops.pallas.kv_pool import kv_pool_gather, kv_pool_write
from deepspeed_tpu.ops.pallas.prefill_attention import (
    _tiles, paged_prefill_attention)
from tests.qwen3_next_cases import _arch, _cfg, _params, ref


@pytest.mark.parametrize("layers, want", [
    (12, [("DEDEDE*E", 3)]), (48, [("DEDEDE*E", 12)]),
    (5, [("DE", 3), ("*E", 1), ("DE", 1)]),
], ids=["the-cut", "published", "a-period-and-a-layer"])
def test_a_block_is_the_patterns_kind_and_its_runs_are_scanned(layers, want):
    """Full attention every fourth layer: the published 48 layers are ONE
    run, twelve scanned periods of eight halves, and the cut three of them."""
    cfg = _cfg(layers=qn.layer_types(layers, 4))
    assert hybrid.layer_runs(cfg) == want
    assert cfg.halves == "".join(u * n for u, n in want)
    assert ref.pattern_runs(cfg.pattern) == tuple(
        (len(u) // 2, n) for u, n in want)
    assert ref.layer_blocks(layers, 4) == tuple(cfg.pattern)
    kinds = hybrid.cache_kinds(cfg, 16)
    assert [k.layers for k in kinds] == [layers // 4, layers - layers // 4]
    assert kinds[1].state and kinds[1].leaves == ("ssm", "conv")
    # the state kind's leaves take their shapes from the family
    assert hybrid.state_leaves(cfg) == {"ssm": (4, 8, 16), "conv": (3, 96)}
    spec = qn.make_qwen3_next_decode_model(
        cfg, params=jax.eval_shape(qn.qwen3_next_init_fn(cfg),
                                   jax.random.PRNGKey(0)))
    pool = jax.eval_shape(lambda: spec.init_paged_pool(
        6, 16, jnp.bfloat16, state_rows=4))
    assert pool["ssm"].shape == (kinds[1].layers, 4, 4, 8, 16) \
        and pool["ssm"].dtype == jnp.float32
    assert pool["conv"].shape == (kinds[1].layers, 4, 3, 96) \
        and pool["conv"].dtype == jnp.bfloat16
    assert pool["k"].shape == (kinds[0].layers, 6, 2, 16, 16)


def _half(kind, seed, **over):
    """One half's leaves of a one-layer model, and an input."""
    cfg = _cfg(layers=("full_attention" if kind == "*"
                       else "linear_attention",), **over)
    params = _params(cfg, seed=seed)
    at = 1 if kind == "E" else 0
    tree = jax.tree_util.tree_map(lambda a: a[0], params["runs"][0][at])
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 21, cfg.d_model))
    return cfg, tree, x


def test_deltanet_half_is_the_references_recurrence():
    """The chunked form (chunks of 8 over 21 positions: a ragged last chunk)
    against the reference's position-at-a-time delta rule, with gates and
    decays that say something."""
    cfg, p, x = _half("D", 3)
    p = dict(p, gdn_ba_w=p["gdn_ba_w"] * 50.0)
    got, _ = hybrid._gdn_half(x, p, cfg)
    for b in range(2):
        want, _ = ref._deltanet(x[b], p, _arch(cfg))
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-6)


def test_attention_half_gates_norms_and_rotates_a_quarter_of_the_head():
    cfg, p, x = _half("*", 4)
    p = dict(p, attn_qkv_w=p["attn_qkv_w"] * 60.0,      # scores that matter
             q_norm_scale=1.0 + 0.3 * jax.random.normal(
                 jax.random.PRNGKey(0), p["q_norm_scale"].shape))
    positions = jnp.broadcast_to(jnp.arange(21)[None], (2, 21))
    got, _, _ = _attn_half(x, p, hybrid._attention_cfg(cfg), positions,
                           constrain=False)
    assert p["attn_qkv_w"].shape[-1] == (2 * 4 + 2 * 2) * 16   # q|k|v|gate
    for b in range(2):
        want = ref._attention(x[b], p, _arch(cfg))
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-6)
        other = ref._attention(x[b], p, _arch(cfg, rotary_dims=16))
        assert np.abs(np.asarray(other - want)).max() \
            > 0.01 * np.abs(np.asarray(want)).max()


@pytest.mark.parametrize("held", [(0, 16), (4, 8)])
def test_expert_half_is_the_references(held):
    cfg, p, x = _half("E", 5, held=held)
    p = dict(p, shared_scale_w=p["shared_scale_w"] * 100.0)
    got, counters, top_e = qn._gated_shared_moe(x, p, cfg)
    for b in range(2):
        want, sets = ref.experts(x[b], p, _arch(cfg))
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-6)
        np.testing.assert_array_equal(
            np.sort(top_e.reshape(2, 21, -1)[b], -1), sets)
    calls, here, _active, _largest, elsewhere = (int(c) for c in counters)
    assert calls == 1 and here + elsewhere == 2 * 21 * cfg.top_k
    assert here == int(((top_e >= held[0])
                        & (top_e < held[0] + held[1])).sum())


def test_the_chosen_experts_weights_are_the_softmax_renormalised():
    """Softmax over ALL the router's outputs, the k largest, renormalised:
    the reference computes it as a softmax over the chosen logits."""
    cfg, p, x = _half("E", 6)
    u = ref._rms_norm(x[0], p["ln1_scale"], _arch(cfg))
    weights, chosen = ref.route(u, p["moe_gate_w"], _arch(cfg))
    probs = np.asarray(jax.nn.softmax(u @ p["moe_gate_w"], -1))
    np.testing.assert_array_equal(
        np.sort(chosen, -1), np.sort(np.argsort(-probs, -1)[:, :cfg.top_k]))
    picked = np.take_along_axis(probs, np.asarray(chosen), -1)
    np.testing.assert_allclose(weights, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)


def test_eight_shares_and_the_gated_shared_expert_once_add_up_to_the_layer():
    """The deployment's eight chips: each holds an eighth of the experts;
    the routed parts, with the GATED shared expert's result counted once,
    are the uncut reference's layer."""
    cfg, p, x = _half("E", 7)                   # holds all 16
    p = dict(p, shared_scale_w=p["shared_scale_w"] * 100.0)
    x = x[:1, :19]
    whole, _ = ref.experts(x[0], p, _arch(cfg, held=(0, 16)))
    # a chip that holds no expert: the gated shared expert alone
    shared_only, _ = ref.experts(x[0], p, _arch(cfg, held=(16, 0)))
    ungated, _ = ref.experts(x[0], dict(p, shared_scale_w=jnp.full_like(
        p["shared_scale_w"], 1e4) * jnp.sign(x[0, 0])), _arch(cfg, held=(16, 0)))
    assert np.abs(np.asarray(shared_only - ungated)).max() \
        > 0.01 * np.abs(np.asarray(ungated)).max()
    parts = jnp.zeros_like(whole)
    for chip in range(8):
        first = 2 * chip
        share = dict(p, moe_w_gate_up=p["moe_w_gate_up"][first:first + 2],
                     moe_w_down=p["moe_w_down"][first:first + 2])
        # the program's share ...
        scfg = _cfg(layers=("linear_attention",), held=(first, 2))
        got, _, _ = qn._gated_shared_moe(x, share, scfg)
        # ... is the reference's, and its routed part alone is what adds
        want, _ = ref.experts(x[0], share, _arch(scfg))
        np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-6)
        routed, _ = ref.experts(x[0], share, _arch(scfg), shared=False)
        parts = parts + routed
    np.testing.assert_allclose(parts + shared_only, whole, rtol=2e-4,
                               atol=2e-6)


# ----------------------------------------------------------------------
# the delta rule: the decode kernel and the chunked form
# ----------------------------------------------------------------------


def _update_inputs(seed, M, H, G, K, V, b):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (jax.random.normal(k[0], (M, H, K, V)),
            jnp.exp(-jax.nn.softplus(jax.random.normal(k[1], (b, H)))),
            jax.nn.sigmoid(jax.random.normal(k[2], (b, H))),
            unit(jax.random.normal(k[3], (b, G, K))) * K ** -0.5,
            unit(jax.random.normal(k[4], (b, G, K))),
            jax.random.normal(k[5], (b, H, V)))


@pytest.mark.parametrize("rows, rows_a_step, heads", [
    ([3, 1], None, (4, 2)), ([3, 1, 6, 2], 1, (4, 2)),
    ([3, 1, 6, 2], 2, (4, 2)), ([3, 1, 6, 2], 4, (4, 4)),
    ([0, 5, 0, 0, 2, 8], 2, (4, 2)), ([4, 0, 0, 7, 0], 1, (4, 1)),
    ([6, 0, 3, 1], 4, (32, 16)),
], ids=["one-step", "a-row-a-step", "two-rows-a-step", "four-rows-a-step",
        "trash-2-a-step", "trash-1-a-step", "the-published-heads"])
def test_gdn_update_kernel_is_the_jnp_update(monkeypatch, rows, rows_a_step,
                                             heads):
    """`gdn.gdn_update` through the interpreter against its twin on `rows` of
    an `[M, H, K, V]` state, on the shell it shares with `dstpu_ssm_update`:
    the same bursts, the rows a step steered through the same byte budget.
    Row 0 is the trash row: entries that name it may share it, and what they
    leave there (and their o) is not compared."""
    (H, G), K, V, M = heads, 8, 128, 9
    if rows_a_step is not None:
        monkeypatch.setattr(ssm, "_BURST_BYTES", rows_a_step * H * K * V * 4)
        assert ssm._rows_per_step(len(rows), H * K * V * 4) == rows_a_step
    rows = np.asarray(rows, np.int32)
    live = np.flatnonzero(rows != 0)
    named = rows[live]
    others = np.setdiff1d(np.arange(1, M), named)
    state, *small = _update_inputs(H + G, M, H, G, K, V, len(rows))
    want_o, want_s = map(np.asarray, jax.jit(gdn.gdn_update_reference)(
        state, rows, *small))
    got_o, got_s = map(np.asarray, jax.jit(functools.partial(
        gdn.gdn_update, interpret=True))(state, rows, *small))
    np.testing.assert_allclose(got_o[live], want_o[live], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got_s[named], want_s[named], rtol=1e-5,
                               atol=1e-6)
    # rows nobody named are untouched
    np.testing.assert_array_equal(got_s[others], state[others])


def _scan_inputs(seed, b, T, H, G, K, V, carried=True):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    S = jax.random.normal(k[5], (b, H, K, V))
    return (unit(jax.random.normal(k[0], (b, T, G, K))) * K ** -0.5,
            unit(jax.random.normal(k[1], (b, T, G, K))),
            jax.random.normal(k[2], (b, T, H, V)),
            -jax.nn.softplus(jax.random.normal(k[3], (b, T, H))) * 0.3,
            jax.nn.sigmoid(jax.random.normal(k[4], (b, T, H)) * 2.0),
            S if carried else jnp.zeros_like(S))


@pytest.mark.parametrize("T, chunk, carried", [
    (32, 8, False), (64, 64, True), (37, 16, True), (100, 64, True),
    (5, 64, True)],
    ids=["divides", "one-chunk-of-64", "ragged", "not-a-multiple-of-64",
         "shorter-than-a-chunk"])
def test_chunked_delta_rule_is_the_sequential_recurrence(T, chunk, carried):
    args = _scan_inputs(T, 2, T, 4, 2, 8, 16, carried)
    want_o, want_S = gdn.gdn_scan_reference(*args)
    got_o, got_S = gdn.gdn_chunk_scan(*args, chunk)
    np.testing.assert_allclose(got_o, want_o, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got_S, want_S, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("zeroed", ["both", "g-alone", "beta-alone"])
def test_a_padded_tail_needs_g_zero_and_beta_zero(zeroed):
    """Past the last real position `g = 0` AND `beta = 0` leave the state
    where it was; either alone does not (Mamba-2 needed only `dt = 0`)."""
    q, k, v, g, beta, S = _scan_inputs(11, 2, 24, 4, 2, 8, 16)
    real = 13
    _, want = gdn.gdn_chunk_scan(q[:, :real], k[:, :real], v[:, :real],
                                 g[:, :real], beta[:, :real], S, 8)
    tail = (jnp.arange(24) >= real)[None, :, None]
    if zeroed != "beta-alone":
        g = jnp.where(tail, 0.0, g)
    if zeroed != "g-alone":
        beta = jnp.where(tail, 0.0, beta)
    _, got = gdn.gdn_chunk_scan(q, k, v, g, beta, S, 8)
    err = float(np.abs(np.asarray(got - want)).max())
    assert (err < 1e-5) == (zeroed == "both"), err


def test_gdn_update_is_one_step_of_the_sequential_scan():
    q, k, v, g, beta, S = _scan_inputs(5, 2, 1, 4, 2, 8, 128)
    o, state = gdn.gdn_update(S, jnp.arange(2), jnp.exp(g[:, 0]), beta[:, 0],
                              q[:, 0], k[:, 0], v[:, 0], interpret=True)
    want_o, want_S = gdn.gdn_scan_reference(q, k, v, g, beta, S)
    np.testing.assert_allclose(o, want_o[:, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(state, want_S, rtol=1e-5, atol=1e-6)


def test_two_chunks_hand_the_state_and_the_convolutions_tail_forward():
    """`_gdn_half` twice over a carried cache (the second chunk mostly
    padding), then a decode token, against one whole-sequence pass."""
    cfg, p, _ = _half("D", 8)
    p = dict(p, gdn_ba_w=p["gdn_ba_w"] * 50.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16 + 5 + 1, cfg.d_model))
    want, _ = hybrid._gdn_half(x, p, cfg)
    row = hybrid.state_leaves(cfg)
    cache = (jnp.ones((3,) + row["ssm"], jnp.float32),      # stale rows
             jnp.ones((3,) + row["conv"], jnp.float32))
    rows = jnp.array([[2]], jnp.int32)
    got = []
    for start, n in ((0, 16), (16, 5)):
        part = jnp.zeros((1, 16, cfg.d_model)).at[:, :n].set(
            x[:, start:start + n])
        out, cache = hybrid._gdn_half(
            part, p, cfg, cache, rows, jnp.array([[start]], jnp.int32),
            jnp.array([n], jnp.int32))
        got.append(out[:, :n])
    out, cache = hybrid._gdn_half(x[:, 21:], p, cfg, cache, rows)
    got.append(out)
    np.testing.assert_allclose(jnp.concatenate(got, axis=1), want, rtol=2e-4,
                               atol=2e-6)
    # the other rows were not touched
    np.testing.assert_array_equal(cache[0][:2], 1.0)


def test_on_a_tpu_a_state_the_kernel_does_not_address_is_refused(monkeypatch):
    state, *small = _update_inputs(1, 3, 4, 2, 8, 16, 2)    # V of 16 lanes
    rows = jnp.arange(2)
    gdn.gdn_update(state, rows, *small)                      # the twin
    monkeypatch.setattr(ssm, "pallas_interpret", lambda: False)
    with pytest.raises(ValueError, match="no other in-place path"):
        gdn.gdn_update(state, rows, *small)


# ----------------------------------------------------------------------
# the kernels at the family's shapes
# ----------------------------------------------------------------------

BLOCK, HD, HEADS = 512, 256, (1, 8)         # (Hkv, G): 16 / 2 a KV head


def _gather_attend(q, k, v, tables, positions):
    """The walks' oracle: `paged_gather` — the row's whole table gathered
    and attended densely."""
    H, hd = q.shape[2:]
    cfg = GPTConfig(vocab_size=64, n_layer=1, n_head=H, n_kv_head=k.shape[1],
                    d_model=64, attn_head_dim=hd, d_ff=64,
                    max_seq_len=tables.shape[1] * BLOCK)
    return _paged_attend(q, kv_pool_gather(k, tables),
                         kv_pool_gather(v, tables), positions, cfg)


def _pool(rng, tables):
    N = int(tables.max()) + 1
    shape = (N, HEADS[0], BLOCK, HD)
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


@pytest.mark.parametrize("start", [0, 256, 3 * BLOCK + 256],
                         ids=["first-chunk", "inside-a-block", "blocks-in"])
def test_the_chunk_walk_at_head_width_256_matches_gather(start):
    Hkv, G = HEADS
    rng = np.random.default_rng(start)
    chunk, nb = 256, 5
    live = (start + chunk - 1) // BLOCK + 1
    tables = np.full((1, nb), TRASH_BLOCK, np.int32)
    tables[0, :live] = rng.permutation(np.arange(1, 1 + nb))[:live]
    k, v = _pool(rng, np.asarray([[nb]]))
    q = jnp.asarray(rng.normal(size=(1, chunk, Hkv * G, HD)), jnp.float32)
    starts = jnp.asarray([start], jnp.int32)
    got = paged_prefill_attention(q, k, v, jnp.asarray(tables), starts,
                                  interpret=True)
    want = _gather_attend(q, k, v, jnp.asarray(tables),
                          starts[:, None] + jnp.arange(chunk)[None])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the served tile: a whole chunk's rows of one KV head's eight query
    # heads a step, inside the step's VMEM budget at head width 256
    assert _tiles(512, 512, 2, 8, 256, 2) == (512, 256, 1)


def test_the_decode_walk_at_head_width_256_matches_gather():
    Hkv, G = HEADS
    rng = np.random.default_rng(5)
    pos = np.asarray([0, 511, 512, 700, 1535, 300], np.int32)
    live = np.asarray([True, True, True, False, True, True])
    nb = 3
    tables = np.zeros((len(pos), nb), np.int32)
    physical = iter(rng.permutation(np.arange(1, 1 + nb * len(pos))))
    for b in np.flatnonzero(live):
        for j in range(pos[b] // BLOCK + 1):
            tables[b, j] = next(physical)
    k, v = _pool(rng, np.asarray([[nb * len(pos)]]))
    q = jnp.asarray(rng.normal(size=(len(pos), Hkv * G, HD)), jnp.float32)
    got = np.asarray(paged_decode_attention(
        q, k, v, jnp.asarray(tables), jnp.asarray(pos), interpret=True))
    want = np.asarray(_gather_attend(
        q[:, None], k, v, jnp.asarray(tables),
        jnp.asarray(pos)[:, None])).reshape(got.shape)
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    assert not got[~live].any()         # a dead slot's row costs no step


def test_the_pool_writer_at_head_width_256_writes_where_the_table_says():
    rng = np.random.default_rng(9)
    N, Hkv, C = 6, 2, 24
    pool = jnp.asarray(rng.normal(size=(N, Hkv, BLOCK, HD)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(1, C, Hkv, HD)), jnp.float32)
    tables = jnp.asarray([[4, 2, 5]], jnp.int32)
    start = BLOCK - 10                      # the chunk crosses into block 2
    got = np.asarray(kv_pool_write(pool, rows, jnp.asarray([start]), tables,
                                   interpret=True))
    want = np.asarray(pool).copy()
    for c in range(C):
        at = start + c
        want[int(tables[0, at // BLOCK]), :, at % BLOCK] = rows[0, c]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("K, N", [(256, 1024), (512, 256)],
                         ids=["gate-up-1024-columns", "down-512-deep"])
def test_grouped_matmul_at_the_experts_widths_with_64_groups(K, N):
    """64 held experts of width 512 with ~5 rows a group, some with none:
    the gate-up stack's 2 x 512 columns are ONE column tile (`_col_tile`), an
    expert's down projection 512 deep."""
    assert moe_gmm._col_tile(1024) == 1024 == moe_gmm._col_tile(2048)
    E, M = 64, 512
    k = jax.random.split(jax.random.PRNGKey(K), 3)
    lhs = jax.random.normal(k[0], (M, K), jnp.float32)
    rhs = jax.random.normal(k[1], (2 * E, K, N), jnp.float32) * 0.05
    sizes = np.random.default_rng(K).multinomial(320, np.ones(E) / E)
    sizes[[3, 11, 40]] = 0                      # idle experts
    sizes = jnp.asarray(sizes, jnp.int32)
    rows = int(sizes.sum())
    want = moe_gmm.moe_gmm_reference(lhs, rhs, sizes, group_offset=E)
    got = moe_gmm.moe_gmm(lhs, rhs, sizes, group_offset=E, interpret=True)
    np.testing.assert_allclose(got[:rows], want[:rows], rtol=2e-4, atol=2e-4)


def test_holding_the_in_projection_changes_no_logit(monkeypatch):
    """`_gdn_half` holds `u @ gdn_qkvz_w` behind an `optimization_barrier`
    from the convolution to the gate, as `_mamba_half` holds its product
    (PR 42). The barrier is an identity: every Gated DeltaNet half passes its
    product through one (and the chunk's system L through another on its
    way into the inverse, `gdn._inverse_of_unit_lower`, PR 56), and the
    logits are bit-equal without them."""
    cfg = _cfg()
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                cfg.vocab_size)
    barrier, held = jax.lax.optimization_barrier, []
    monkeypatch.setattr(jax.lax, "optimization_barrier",
                        lambda x: held.append(x.shape) or barrier(x))
    forward = lambda p, t: qn.qwen3_next_forward(p, t, cfg)
    with_it = np.asarray(jax.jit(forward)(params, tokens))
    width = hybrid.mixer_shapes(cfg, hybrid.DELTANET)["gdn_qkvz_w"][0][-1]
    layers = cfg.halves.count(hybrid.DELTANET)
    assert [s for s in held if len(s) == 3] == [(2, 24, width)] * layers
    Q = cfg.chunk_size
    assert [s[-2:] for s in held if len(s) != 3] == [(Q, Q)] * layers
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    without = np.asarray(jax.jit(forward)(params, tokens))
    assert np.ptp(with_it) > 0 and np.array_equal(with_it, without)
