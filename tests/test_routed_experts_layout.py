"""`routed_experts` carries a token's k assignments K-MAJOR (assignment `a` is
choice `a // N` of token `a % N`): the result against a per-token dense
reference over every k the cells serve, the counters, a token's result
whatever shares its batch, the layout itself (no `[N, k, D]` intermediate),
and the held mask as a `where` over rows nobody wrote."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import moe_gmm as moe_gmm_mod
from deepspeed_tpu.parallel.moe import relu2, routed_experts

ROUTED, D, F = 32, 16, 8          # experts the router chose among; widths


def _experts(rng, gated, count, dtype=np.float32):
    draw = lambda *shape: jnp.asarray(rng.normal(0, 0.5, shape).astype(dtype))
    if gated:
        return {"w_gate_up": draw(count, D, 2 * F), "w_down": draw(count, F, D)}
    return {"w_up": draw(count, D, F), "w_down": draw(count, F, D),
            "b_up": draw(count, F), "b_down": draw(count, D)}


def _routing(rng, N, k):
    """What `topk_routing` hands over: k DISTINCT experts a token, the
    largest weight first."""
    scores = rng.random((N, ROUTED)).astype(np.float32)
    top_e = np.argsort(-scores, axis=1)[:, :k].astype(np.int32)
    return np.take_along_axis(scores, top_e, 1), top_e


def _dense_reference(x, top_p, top_e, experts, held):
    """Each token's k experts applied one by one, weighted and summed in
    float32 in the token's own top-k order; an expert that lives elsewhere
    adds nothing."""
    w = {name: np.asarray(a, np.float32) for name, a in experts.items()}
    first, count = held or (0, ROUTED)
    out = np.zeros((x.shape[0], D), np.float32)
    for n, row in enumerate(np.asarray(x, np.float32)):
        for p, e in zip(top_p[n], top_e[n] - first):
            if not 0 <= e < count:
                continue
            if "w_gate_up" in w:
                gate, up = np.split(row @ w["w_gate_up"][e], 2)
                h = gate / (1.0 + np.exp(-gate)) * up
                y = h @ w["w_down"][e]
            else:
                h = np.square(np.maximum(row @ w["w_up"][e] + w["b_up"][e], 0))
                y = h @ w["w_down"][e] + w["b_down"][e]
            out[n] += np.float32(p) * y.astype(np.float32)
    return out


def _counters(top_e, held):
    e = top_e.reshape(-1)
    if held is None:
        loads = np.bincount(e, minlength=ROUTED)
        return [1, len(e), int((loads > 0).sum()), int(loads.max())]
    first, count = held
    mine = e[(e >= first) & (e < first + count)]
    loads = np.bincount(mine - first, minlength=count)
    return [1, len(mine), int((loads > 0).sum()), int(loads.max()),
            len(e) - len(mine)]


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "plain_biased"])
@pytest.mark.parametrize("held", [None, (8, 8)], ids=["all", "held"])
@pytest.mark.parametrize("N", [16, 40, 704])
@pytest.mark.parametrize("k", [1, 4, 8, 10, 22])
def test_routed_experts_equal_the_per_token_dense_reference(k, N, held, gated):
    rng = np.random.default_rng(1000 * k + N)
    x = jnp.asarray(rng.normal(0, 1, (N, D)).astype(np.float32))
    top_p, top_e = _routing(rng, N, k)
    experts = _experts(rng, gated, held[1] if held else ROUTED)
    want = _dense_reference(x, top_p, top_e, experts, held)
    with jax.default_matmul_precision("highest"):
        got, counters = jax.jit(
            lambda x, p, e, w: routed_experts(
                x, p, e, w, activation=None if gated else relu2, held=held))(
            x, jnp.asarray(top_p), jnp.asarray(top_e), experts)
    assert got.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    assert [int(c) for c in counters] == _counters(top_e, held)


@pytest.mark.parametrize("held", [None, (8, 8)], ids=["all", "held"])
@pytest.mark.parametrize("k", [4, 10])
def test_a_tokens_result_is_the_same_bits_alone_and_in_a_batch_of_64(k, held):
    """The docstring's promise. Inputs and weights are small dyadic numbers,
    so every matrix product is exact whatever routine and order the CPU picks
    for a shape (`relu2` keeps them dyadic); the router's weights are
    arbitrary float32, so the k-term weighted sum rounds — and must round the
    same way for the token alone as among 63 others."""
    rng = np.random.default_rng(7 + k)
    dyadic = lambda *shape: jnp.asarray(
        rng.integers(-4, 5, shape).astype(np.float32) / 4)
    count = held[1] if held else ROUTED
    experts = {"w_up": dyadic(count, D, F), "w_down": dyadic(count, F, D)}
    x = dyadic(64, D)
    top_p, top_e = _routing(rng, 64, k)
    top_p, top_e = jnp.asarray(top_p), jnp.asarray(top_e)
    run = lambda rows: np.asarray(routed_experts(
        x[rows], top_p[rows], top_e[rows], experts, activation=relu2,
        held=held)[0])
    whole = run(slice(0, 64))
    assert np.abs(whole).max() > 0
    for t in (0, 17, 63):
        np.testing.assert_array_equal(run(slice(t, t + 1))[0], whole[t])
    perm = np.random.default_rng(8).permutation(64)
    np.testing.assert_array_equal(run(perm), whole[perm])


@pytest.mark.parametrize("held", [None, (8, 8)], ids=["all", "held"])
@pytest.mark.parametrize("groups", [2, 4])
def test_groups_of_rows_go_through_together_and_are_combined_apart(groups,
                                                                   held):
    """`groups`: equal runs of rows dispatched and multiplied as ONE call
    (the counters say so) and combined a run at a time — each run's result
    is the bits of a call of its own, gated experts and arbitrary weights
    (on the CPU the k-term sum keeps its order whatever the shape; on the
    chip it does not, which is what `groups` is for)."""
    rng = np.random.default_rng(11 * groups)
    n, k = 24, 4
    x = jnp.asarray(rng.normal(0, 1, (groups * n, D)).astype(np.float32))
    top_p, top_e = _routing(rng, groups * n, k)
    top_p, top_e = jnp.asarray(top_p), jnp.asarray(top_e)
    experts = _experts(rng, True, held[1] if held else ROUTED)
    run = lambda rows, **more: routed_experts(
        x[rows], top_p[rows], top_e[rows], experts, held=held, **more)
    together, counters = run(slice(None), groups=groups)
    for g in range(groups):
        rows = slice(g * n, (g + 1) * n)
        np.testing.assert_array_equal(np.asarray(together[rows]),
                                      np.asarray(run(rows)[0]))
    assert [int(c) for c in counters] == _counters(np.asarray(top_e), held)


def _shapes(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.update(tuple(v.aval.shape) for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, found)
    return found


@pytest.mark.parametrize("held", [None, (0, 18)], ids=["all", "held"])
def test_no_intermediate_lays_a_tokens_k_results_on_the_second_minor_axis(held):
    """k on the sublanes pads to the (8, 128) tile and makes `[N*k, D] ->
    [N, k, D]` a relayout on the chip (PR 48: 2.4% of Granite's cell); the
    k-major `[k, N, D]` is a bitcast. Pinned here, not by a comment."""
    N, k, width = 640, 10, 256
    sds = jax.ShapeDtypeStruct
    count = held[1] if held else 72
    experts = {"w_gate_up": sds((count, width, 2 * F), jnp.bfloat16),
               "w_down": sds((count, F, width), jnp.bfloat16)}
    jaxpr = jax.make_jaxpr(lambda x, p, e, w: routed_experts(
        x, p, e, w, held=held))(
        sds((N, width), jnp.bfloat16), sds((N, k), jnp.float32),
        sds((N, k), jnp.int32), experts)
    shapes = _shapes(jaxpr.jaxpr, set())
    assert (k, N, width) in shapes
    assert not [s for s in shapes if s[:2] == (N, k) and s[2:] not in ((), (1,))]


def test_rows_past_the_held_ones_may_hold_anything(monkeypatch):
    """`moe_gmm` leaves the rows past `sum(group_sizes)` alone: on the chip
    they are memory nobody wrote. A stub that fills them with NaN gives the
    same finite result: the mask is a `where`, never a multiply by zero."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1, (40, D)).astype(np.float32))
    top_p, top_e = map(jnp.asarray, _routing(rng, 40, 10))
    experts = _experts(rng, True, 8)
    run = lambda: routed_experts(x, top_p, top_e, experts, held=(8, 8))
    want, counters = run()
    assert int(counters[4]) > 0

    def unwritten(lhs, rhs, group_sizes, group_offset=0):
        out = moe_gmm_mod.moe_gmm_reference(lhs, rhs, group_sizes, group_offset)
        written = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(group_sizes)
        return jnp.where(written, out, jnp.nan)

    monkeypatch.setattr(moe_gmm_mod, "moe_gmm", unwritten)
    got, _ = run()
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
