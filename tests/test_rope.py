"""`models/gpt.py::_rope`, the one rotation every family's attention half
shares: its values and its transpose against a float64 NumPy rotation written
here (a complex multiply on the (even, odd) pairs), at every (head width,
rotary width) a benchmark configuration brings, and a guard that the
training half's gradient lowers without a gather or a scatter (the strided
slices' chain that `attn/qkv` spent a sixth of the training step in until
PR 53)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.gpt import (GPTConfig, _attn_half, _rope,
                                      gpt_init_fn)

THETA = 1_000_000.0


def _rotate(x, positions, rd, conjugate=False):
    """The reference, float64: columns (2i, 2i+1) of the first `rd` as one
    complex number times exp(i * positions * THETA**(-2i / rd)); the rest as
    they are. `conjugate`: the transpose (a rotation's transpose turns the
    other way)."""
    x = np.asarray(x, np.float64)
    freqs = THETA ** (-np.arange(0, rd, 2, dtype=np.float64) / rd)
    turn = np.exp(1j * positions[..., None, None].astype(np.float64) * freqs)
    pairs = (x[..., 0:rd:2] + 1j * x[..., 1:rd:2]) * (
        np.conj(turn) if conjugate else turn)
    out = x.copy()
    out[..., 0:rd:2], out[..., 1:rd:2] = pairs.real, pairs.imag
    return out


def _partner(x, rd):
    """x[..., j ^ 1] in the rotary columns, x itself beyond."""
    out = np.array(x)
    out[..., 0:rd:2], out[..., 1:rd:2] = x[..., 1:rd:2], x[..., 0:rd:2]
    return out


def _within_a_unit(have, want, pos, rd):
    """`have` within one unit of its dtype at `want`'s own size (the products
    and the sum are float32 and round ONCE), plus what the float32 ANGLE is
    off by: a few of its own units, which moves a value by that times its
    pair's size, whatever x's dtype."""
    eps = float(jnp.finfo(have.dtype).eps)
    have = np.asarray(have.astype(jnp.float32), np.float64)
    pair = np.abs(want) + np.abs(_partner(want, rd))
    angle = 4 * float(pos.max()) * 2.0 ** -24
    assert (np.abs(have - want) <= eps * np.abs(want) + angle * pair).all()
    # the columns beyond `rd` pass through exactly
    np.testing.assert_array_equal(have[..., rd:], want[..., rd:])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("hd, rd", [
    (128, 32), (128, 128), (64, 64), (192, 64), (256, 64),
], ids=["pythia", "mistral", "mla-slice", "mimo", "qwen3-next"])
def test_rope_is_the_complex_rotation_of_interleaved_pairs(hd, rd, dtype):
    """Against the float64 rotation at positions with a per-row offset:
    values, and `jax.vjp` against the rotation's transpose (the pairs turned
    the other way) alike, within one unit of x's dtype."""
    B, T, H = 2, 19, 3
    x = jax.random.normal(jax.random.PRNGKey(hd + rd), (B, T, H, hd),
                          jnp.float32).astype(dtype)
    g = jax.random.normal(jax.random.PRNGKey(7), x.shape,
                          jnp.float32).astype(dtype)
    positions = jnp.arange(T)[None, :] + jnp.asarray([[0], [977]])
    got, vjp = jax.vjp(lambda x: _rope(x, positions, rd, THETA), x)
    got_g, = vjp(g)
    assert got.dtype == x.dtype and got_g.dtype == x.dtype
    pos = np.asarray(positions)
    _within_a_unit(got, _rotate(x, pos, rd), pos, rd)
    _within_a_unit(got_g, _rotate(g, pos, rd, conjugate=True), pos, rd)
    # and the rotation says something: it is not the identity
    assert np.abs(np.asarray(got.astype(jnp.float32), np.float64)
                  - np.asarray(x.astype(jnp.float32)))[1, ..., :rd].max() > 0.5


def test_attn_half_gradient_lowers_without_gather_or_scatter():
    """A tiny Pythia-shaped half (LayerNorm, biases, a quarter of the head
    rotated): no `gather`, `scatter` or `scatter-add` anywhere in the jaxpr of
    its gradient, sub-jaxprs included."""
    cfg = GPTConfig(vocab_size=64, n_layer=1, n_head=4, d_model=64,
                    max_seq_len=16, use_rotary=True, rotary_pct=0.25,
                    parallel_residual=True, dtype=jnp.bfloat16)
    params = gpt_init_fn(cfg, dtype=jnp.bfloat16)(jax.random.PRNGKey(0))
    p = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64), jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))

    def loss(x, p):
        out, k, v = _attn_half(x, p, cfg, positions, constrain=False)
        return (out.astype(jnp.float32).sum() + k.astype(jnp.float32).sum()
                + v.astype(jnp.float32).sum())

    names = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            names.add(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, p).jaxpr)
    assert "dot_general" in names and "cos" in names      # it was walked
    assert not names & {"gather", "scatter", "scatter-add"}, \
        sorted(names)


def test_reverse_over_reverse_passes_the_rotation_and_forward_mode_does_not():
    """`_rope` is a `custom_vjp` (its backward is itself at `-positions`):
    `jax.jvp` through it is a TypeError, and the curvature estimates' product
    (`runtime/eigenvalue.py::hessian_vector_product`, grad of <grad, v>)
    passes it — a rotation keeps lengths, so the Hessian of half its squared
    norm is the identity."""
    from deepspeed_tpu.runtime.eigenvalue import hessian_vector_product
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, 8), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(1), x.shape, jnp.float32)
    positions = jnp.arange(4)[None]
    rope = lambda x: _rope(x, positions, 4, THETA)     # noqa: E731
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(rope, (x,), (v,))
    hv = hessian_vector_product(
        jax.grad(lambda x: 0.5 * jnp.sum(rope(x) ** 2)), x, v)
    np.testing.assert_allclose(hv, v, rtol=0, atol=1e-6)


def test_block_eigenvalues_read_a_rotary_model(monkeypatch):
    """MoQ's curvature estimate (`runtime/quantize.py::block_eigenvalues`)
    on a tiny rotary model: the same eigenvalues through the `custom_vjp` as
    with the rotation left to the autodiff (`_rotate` undecorated), so the
    written backward is the forward's transpose to second order too."""
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.runtime.quantize import block_eigenvalues
    cfg = GPTConfig(vocab_size=64, n_layer=2, n_head=2, d_model=32,
                    max_seq_len=16, use_rotary=True, rotary_pct=0.5,
                    dtype=jnp.float32, remat=False)
    params = gpt_init_fn(cfg, dtype=jnp.float32)(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(
        0, 64, (2, 16)), jnp.int32)}

    def loss(p, b):
        return gpt.gpt_loss(p, b, jax.random.PRNGKey(1), cfg=cfg)

    written = block_eigenvalues(loss, params, batch, max_iter=6)
    monkeypatch.setattr(gpt, "_rope", gpt._rotate)
    plain = block_eigenvalues(loss, params, batch, max_iter=6)
    assert np.isfinite(written).all() and np.abs(written).min() > 0
    np.testing.assert_allclose(written, plain, rtol=1e-4)
