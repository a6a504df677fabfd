"""The Nemotron-H family's pieces (`models/nemotron_h.py`, `ops/pallas/ssm.py`,
`parallel/moe.py`'s plain branch), each against the float32 reference
(`benchmark/references/nemotron_h.py`) or a `jax.numpy` oracle: the three
kinds of layer, the chunked scan against the recurrence a position at a time,
the state kernels in the interpreter, the expert share. The serving path is
`tests/test_nemotron_h.py`.

Everything at a small size on the CPU; `tests/nemotron_cases.py` has the
configuration and the reference the two files share."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import nemotron_h as nh
from deepspeed_tpu.models.layer_pattern import repeated_runs
from deepspeed_tpu.ops.pallas import ssm
from deepspeed_tpu.parallel.moe import relu2, routed_experts, topk_routing
from tests.nemotron_cases import (_arch, _cfg, _params,
                                  assert_update_kernel_is_the_jnp_update, ref)

PUBLISHED = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


# ----------------------------------------------------------------------
# the pattern as data
# ----------------------------------------------------------------------


@pytest.mark.parametrize("pattern, want", [
    ("EMEMEMEMEM*", [("EM", 5), ("*", 1)]),
    ("EMEM*", [("EM", 2), ("*", 1)]),
    ("M*E", [("M", 1), ("*", 1), ("E", 1)]),
    ("MMMM", [("M", 4)]),
    ("*EMEMEEE", [("*", 1), ("EM", 2), ("E", 3)]),
], ids=["the-cut", "tiny", "no-repeat", "one-kind", "mixed"])
def test_repeated_runs_cover_the_pattern_in_order(pattern, want):
    runs = repeated_runs(pattern)
    assert [("".join(unit), n) for unit, n in runs] == want
    assert "".join("".join(unit) * n for unit, n in runs) == pattern


def test_the_published_pattern_is_a_few_scanned_runs():
    runs = repeated_runs(PUBLISHED)
    assert "".join("".join(unit) * n for unit, n in runs) == PUBLISHED
    assert len(PUBLISHED) == 88 and PUBLISHED.count("M") == 40 \
        and PUBLISHED.count("E") == 40 and PUBLISHED.count("*") == 8
    assert len(runs) <= 8 and sum(len(u) for u, _ in runs) < 40
    # ... and the reference restates the same split
    assert list(ref.pattern_runs(PUBLISHED)) == [(len(u), n) for u, n in runs]


# ----------------------------------------------------------------------
# each kind of layer against the reference
# ----------------------------------------------------------------------


def _one_layer(kind, seed):
    cfg = _cfg(pattern=kind)
    params = _params(cfg, seed=seed)
    tree = jax.tree_util.tree_map(lambda a: a[0], params["runs"][0][0])
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 21, cfg.d_model))
    return cfg, tree, x


def test_mamba_layer_is_the_references_recurrence():
    cfg, p, x = _one_layer("M", 3)
    got, _ = nh._mamba_half(x, p, cfg)
    for b in range(2):
        want, _ = ref._mamba(x[b], p, _arch(cfg))
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-6)


def test_attention_layer_is_the_references_without_rotary():
    cfg, p, x = _one_layer("*", 4)
    positions = jnp.broadcast_to(jnp.arange(21)[None], (2, 21))
    got, _, _ = nh._attn_half(x, p, nh._attention_cfg(cfg), positions,
                              constrain=False)
    for b in range(2):
        want = ref._attention(x[b], p, _arch(cfg))
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("held", [(0, 16), (4, 8)])
def test_latent_moe_layer_is_the_references(held):
    cfg = _cfg(pattern="E", held=held)
    p = jax.tree_util.tree_map(lambda a: a[0],
                               _params(cfg, seed=5)["runs"][0][0])
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 21, cfg.d_model))
    got, counters, top_e = nh._latent_moe(x, p, cfg)
    for b in range(2):
        want, sets = ref.latent_moe(x[b], p, _arch(cfg))
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-6)
        np.testing.assert_array_equal(
            np.sort(top_e.reshape(2, 21, -1)[b], -1), sets)
    calls, here, _active, _largest, elsewhere = (int(c) for c in counters)
    assert calls == 1 and here + elsewhere == 2 * 21 * cfg.top_k
    assert here == int(((top_e >= held[0])
                        & (top_e < held[0] + held[1])).sum())


def test_four_shares_and_the_shared_expert_once_add_up_to_the_whole_layer():
    """The deployment's four chips: each holds a quarter of the experts and
    applies the latent up-projection to ITS part of the sum; the parts, with
    the latent projections' and the shared expert's results counted once,
    are the uncut layer."""
    cfg = _cfg(pattern="E")                     # holds all 16
    p = jax.tree_util.tree_map(lambda a: a[0],
                               _params(cfg, seed=7)["runs"][0][0])
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 19, cfg.d_model))
    whole, _ = ref.latent_moe(x[0], p, _arch(cfg, held=(0, 16)))
    # a chip that holds no expert: the shared expert alone
    shared_only, _ = ref.latent_moe(x[0], p, _arch(cfg, held=(16, 0)))
    parts = jnp.zeros_like(whole)
    for chip in range(4):
        first = 4 * chip
        share = dict(p, moe_w_up=p["moe_w_up"][first:first + 4],
                     moe_w_down=p["moe_w_down"][first:first + 4])
        # the program's share ...
        scfg = _cfg(pattern="E", held=(first, 4))
        got, _, _ = nh._latent_moe(x, share, scfg)
        # ... is the reference's, and its routed part alone is what adds
        want, _ = ref.latent_moe(x[0], share, _arch(scfg))
        np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-6)
        routed, _ = ref.latent_moe(x[0], share, _arch(scfg), shared=False)
        parts = parts + routed
    np.testing.assert_allclose(parts + shared_only, whole, rtol=2e-4,
                               atol=2e-6)


def test_plain_experts_take_an_activation_and_need_no_biases():
    """`routed_experts`' plain branch: with zero biases and without them the
    same numbers; `relu2` between the two products."""
    key = jax.random.split(jax.random.PRNGKey(9), 4)
    N, D, F, E, k = 24, 16, 24, 8, 3
    x = jax.random.normal(key[0], (N, D))
    experts = {"w_up": jax.random.normal(key[1], (E, D, F)) * 0.1,
               "w_down": jax.random.normal(key[2], (E, F, D)) * 0.1}
    top_p, top_e = topk_routing(x, jax.random.normal(key[3], (D, E)), k, True,
                                scoring="sigmoid")
    got, counters = routed_experts(x, top_p, top_e, experts, activation=relu2)
    biased, _ = routed_experts(
        x, top_p, top_e, dict(experts, b_up=jnp.zeros((E, F)),
                              b_down=jnp.zeros((E, D))), activation=relu2)
    np.testing.assert_array_equal(got, biased)
    want = sum(top_p[:, j, None] * jnp.einsum(
        "nf,nfd->nd", jnp.square(jax.nn.relu(jnp.einsum(
            "nd,ndf->nf", x, experts["w_up"][top_e[:, j]]))),
        experts["w_down"][top_e[:, j]]) for j in range(k))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert int(counters[1]) == N * k


# ----------------------------------------------------------------------
# the chunked scan against the recurrence a position at a time
# ----------------------------------------------------------------------


def _scan_inputs(T, b=2, H=8, P=8, G=2, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (b, T, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (b, T, H))) * 0.2,
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (b, T, G, N)),
            jax.random.normal(k[4], (b, T, G, N)),
            jax.random.normal(k[5], (b, H, P, N)))


@pytest.mark.parametrize("T, chunk, carried", [
    (32, 8, False), (32, 8, True), (37, 8, True), (5, 8, True),
    (24, 24, True), (19, 4, False),
], ids=["divides", "divides-carried", "ragged-carried", "shorter-than-chunk",
        "one-chunk", "ragged"])
def test_chunked_scan_is_the_sequential_recurrence(T, chunk, carried):
    x, dt, A, B, C, S = _scan_inputs(T, seed=T)
    if not carried:
        S = jnp.zeros_like(S)
    want_y, want_S = ssm.ssm_scan_reference(x, dt, A, B, C, S)
    got_y, got_S = ssm.ssm_chunk_scan(x, dt, A, B, C, S, chunk)
    np.testing.assert_allclose(got_y, want_y, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got_S, want_S, rtol=2e-4, atol=2e-5)


def test_a_padded_tail_with_dt_zero_leaves_the_state_where_it_was():
    x, dt, A, B, C, S = _scan_inputs(24, seed=1)
    real = 13
    _, want = ssm.ssm_chunk_scan(x[:, :real], dt[:, :real], A, B[:, :real],
                                 C[:, :real], S, 8)
    dt = dt.at[:, real:].set(0.0)
    got_y, got = ssm.ssm_chunk_scan(x, dt, A, B, C, S, 8)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.isfinite(np.asarray(got_y)).all()


def test_two_chunks_hand_the_state_and_the_convolutions_tail_forward():
    """`_mamba_chunk` twice over a carried cache (the second chunk mostly
    padding) is one pass over the real positions; a chunk at position 0
    takes nothing from what the row held."""
    cfg, p, x = _one_layer("M", 11)
    x = x[:1]                                           # [1, 21, D]
    whole, _ = nh._mamba_half(x, p, cfg)
    W = cfg.conv_width
    cache = (jnp.full((3, cfg.mamba_num_heads, cfg.mamba_head_dim,
                       cfg.ssm_state_size), 7.0),       # garbage: a reused row
             jnp.full((3, cfg.conv_kernel - 1, W), 7.0))
    rows = jnp.array([[2]], jnp.int32)
    pad = jnp.zeros((1, 11, cfg.d_model))
    first, cache = nh._mamba_half(
        x[:, :16], p, cfg, cache, rows,
        positions=jnp.arange(16)[None], valid=jnp.array([16]))
    second, cache = nh._mamba_half(
        jnp.concatenate([x[:, 16:], pad], axis=1), p, cfg, cache, rows,
        positions=16 + jnp.arange(16)[None], valid=jnp.array([5]))
    np.testing.assert_allclose(first, whole[:, :16], rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(second[:, :5], whole[:, 16:], rtol=2e-4,
                               atol=2e-6)
    # the other rows were left alone
    assert float(cache[0][0].min()) == 7.0 and float(cache[1][1].min()) == 7.0
    # a decode token from there is position 21 of the longer sequence
    nxt = jax.random.normal(jax.random.PRNGKey(12), (1, 1, cfg.d_model))
    token, _ = nh._mamba_half(nxt, p, cfg, cache, rows)
    longer, _ = nh._mamba_half(jnp.concatenate([x, nxt], axis=1), p, cfg)
    np.testing.assert_allclose(token[:, 0], longer[:, 21], rtol=2e-4,
                               atol=2e-6)


# ----------------------------------------------------------------------
# the state kernels in the interpreter
# ----------------------------------------------------------------------


@pytest.mark.parametrize("H, P, N, G, rows, rows_a_step", [
    (8, 16, 128, 2, [3, 1, 6, 2], None),
    (16, 8, 128, 16, [3, 1, 6, 2], 2),
    # a row a step, its heads in two halves: a half spans four groups
    (16, 8, 128, 8, [3, 1, 6, 2], 1),
    # ... and a half is one group's heads
    (16, 8, 128, 2, [5, 8, 2], 1),
    # two rows a step over three steps; dead slots share the trash row, next
    # to each other (across a step's edge, and inside a step) and apart
    (16, 8, 128, 4, [4, 0, 0, 2, 6, 0], 2),
    (8, 16, 128, 2, [0, 0, 3, 5, 0, 1, 7, 0], 2),
    # the same with a row a step, and with every row in ONE step
    (8, 16, 128, 4, [4, 0, 0, 2, 6, 0], 1),
    (8, 16, 128, 2, [0, 7, 0], None),
    # whole lane tiles of heads: a loop over blocks of sixteen, a block one
    # group (the served G = 8), four groups, or an eighth of one
    (128, 8, 128, 8, [3, 0, 1, 0], 2),
    (128, 8, 128, 32, [2, 5, 1], 1),
    (128, 8, 128, 1, [4, 0, 0, 2], 2),
], ids=["2-groups", "a-group-a-head", "half-spans-groups", "half-is-a-group",
        "trash-2-a-step", "trash-inside-a-step", "trash-1-a-step",
        "trash-one-step", "block-is-a-group", "block-spans-groups",
        "group-spans-blocks"])
def test_ssm_update_kernel_is_the_jnp_update(monkeypatch, H, P, N, G, rows,
                                             rows_a_step):
    assert_update_kernel_is_the_jnp_update(monkeypatch, H, P, N, G, rows,
                                           rows_a_step)


def test_a_steps_rows_and_a_loops_heads_come_from_the_shapes_the_call_sees():
    """Two rows of the served face (4 MiB a row) a step, a row where the
    count is odd, never more rows than the call has; sixteen heads a loop
    iteration where the heads are whole lane tiles and a block is whole
    groups or a part of one, else every head unrolled."""
    served = 128 * 64 * 128 * 4
    assert ssm._rows_per_step(128, served) == 2
    assert ssm._rows_per_step(127, served) == 1
    assert ssm._rows_per_step(128, served // 4) == 8
    assert ssm._rows_per_step(6, served // 16) == 6
    assert ssm._rows_per_step(9, served) == 1
    assert ssm._rows_per_step(1, 2 * served) == 1
    assert [ssm._heads_per_block(128, G) for G in (1, 8, 32, 128)] == [16] * 4
    assert ssm._heads_per_block(256, 2) == 16
    assert ssm._heads_per_block(128, 4) == 16       # half a group a block
    assert ssm._heads_per_block(96, 2) == 96        # not whole lane tiles
    assert ssm._heads_per_block(384, 128) == 384    # a group of three heads
    assert ssm._heads_per_block(16, 2) == 16


def test_ssm_update_is_one_step_of_the_sequential_scan():
    x, dt, A, B, C, S = _scan_inputs(1, seed=2)
    want_y, want_S = ssm.ssm_scan_reference(x, dt, A, B, C, S)
    y, state = ssm.ssm_update(S, jnp.arange(2), jnp.exp(dt[:, 0] * A),
                              dt[:, 0, :, None] * x[:, 0], B[:, 0], C[:, 0])
    np.testing.assert_allclose(y, want_y[:, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(state, want_S, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape, dtype", [((6, 4, 8, 128), jnp.float32),
                                          ((6, 3, 256), jnp.bfloat16)],
                         ids=["state", "conv-tail"])
def test_state_rows_are_read_and_written_by_index(shape, dtype):
    buf = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
    rows = jnp.array([4, 1], jnp.int32)
    got = ssm.state_read(buf, rows, interpret=True)
    np.testing.assert_array_equal(got, buf[rows])
    new = (got.astype(jnp.float32) * 2).astype(dtype)
    out = ssm.state_write(buf, rows, new, interpret=True)
    np.testing.assert_array_equal(out, buf.at[rows].set(new))


def test_the_kernels_take_float32_state_of_whole_tiles_only():
    assert ssm.state_in_place_supported(jnp.zeros((2, 4, 8, 128)))
    assert not ssm.state_in_place_supported(jnp.zeros((2, 4, 8, 64)))
    assert not ssm.state_in_place_supported(
        jnp.zeros((2, 4, 8, 128), jnp.bfloat16))


@pytest.mark.parametrize("shape, dtype", [((3, 4, 8, 128), jnp.bfloat16),
                                          ((3, 4, 8, 64), jnp.float32)],
                         ids=["bfloat16", "half-a-tile"])
def test_on_a_tpu_a_state_the_kernel_does_not_address_is_refused(
        monkeypatch, shape, dtype):
    """Off the TPU the twin runs it; on one there is no second path (the
    twin is a scatter that copies the whole carried state a token)."""
    state = jnp.zeros(shape, dtype)
    rows = jnp.array([1], jnp.int32)
    args = (rows, jnp.ones((1, 4)), jnp.ones((1, 4, 8)),
            jnp.ones((1, 2, shape[-1])), jnp.ones((1, 2, shape[-1])))
    y, _ = ssm.ssm_update(state, *args)
    assert y.shape == (1, 4, 8)
    monkeypatch.setattr(ssm, "pallas_interpret", lambda: False)
    with pytest.raises(ValueError, match="no other in-place path"):
        ssm.ssm_update(state, *args)


# ----------------------------------------------------------------------
# the reference's lower precisions
# ----------------------------------------------------------------------


def test_the_reference_rounds_the_state_alone_when_asked():
    cfg, p, x = _one_layer("M", 13)
    exact, state = ref._mamba(x[0], p, _arch(cfg))
    rounded, coarse = ref._mamba(x[0], p,
                                 _arch(cfg, state_round_to=jnp.bfloat16))
    share = lambda a, b: float(jnp.abs(a - b).max() / jnp.abs(b).max())
    assert 1e-4 < share(coarse, state) < 0.05
    assert 0 < share(rounded, exact) < 0.05
    eight, _ = ref._mamba(x[0], p, _arch(cfg, round_to=jnp.float8_e4m3fn))
    assert np.isfinite(np.asarray(eight)).all()
    assert share(eight, exact) > share(rounded, exact)
