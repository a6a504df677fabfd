"""Curvature (eigenvalue) estimation — power iteration on the loss Hessian.

Reference: `runtime/eigenvalue.py:1` — per-layer power iteration using repeated
autograd passes, feeding the compression scheduler's quantization period.
TPU-native: the Hessian-vector product is a single `jax.grad`-of-`jax.grad`
composition inside one jitted loop (`lax.while_loop` with a tolerance), so the
whole estimation compiles to one XLA program instead of N python-side backward
passes.
"""

import functools

import jax
import jax.numpy as jnp


class Eigenvalue:
    """API parity with the reference class (verbose/max_iter/tol/stability)."""

    def __init__(self, verbose=False, max_iter=100, tol=1e-2, stability=1e-6,
                 gas_boundary_resolution=1, layer_name="", layer_num=0):
        self.verbose = verbose
        self.max_iter = max_iter
        self.tol = tol
        self.stability = stability
        self.gas_boundary_resolution = gas_boundary_resolution
        self.layer_name = layer_name
        self.layer_num = layer_num

    def compute_eigenvalue(self, loss_fn, params, batch, rng=None, seed=0):
        """Dominant eigenvalue of the Hessian of `loss_fn(params, batch)` w.r.t.
        params. Returns (eigenvalue: f32, iterations_run: i32)."""
        return power_iteration_hessian(loss_fn, params, batch,
                                       max_iter=self.max_iter, tol=self.tol,
                                       stability=self.stability, seed=seed)


def hessian_vector_product(grad_fn, params, v):
    """H v as the gradient of <grad L, v>: reverse over reverse, because a
    `custom_vjp` in the loss (the rotation, the chunked loss) passes reverse
    mode any number of times and forward mode (`jax.jvp` of the gradient)
    never."""
    def along(p):
        return sum(jnp.vdot(t, g.astype(t.dtype)) for t, g in zip(
            jax.tree_util.tree_leaves(v),
            jax.tree_util.tree_leaves(grad_fn(p))))

    return jax.grad(along)(params)


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5))
def power_iteration_hessian(loss_fn, params, batch, max_iter=100, tol=1e-2,
                            stability=1e-6, seed=0):
    grad_fn = jax.grad(lambda p: loss_fn(p, batch))

    def hvp(v):
        return hessian_vector_product(grad_fn, params, v)

    leaves, treedef = jax.tree_util.tree_flatten(params)
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, len(leaves))
    v0 = treedef.unflatten([jax.random.normal(k, l.shape, l.dtype)
                            for k, l in zip(keys, leaves)])

    def normalize(v):
        n = jnp.sqrt(sum(jnp.vdot(x, x).real for x in jax.tree_util.tree_leaves(v)))
        return jax.tree_util.tree_map(lambda x: x / (n + stability), v)

    def body(carry):
        v, prev_ev, i, _ = carry
        w = hvp(v)
        ev = sum(jnp.vdot(a, b).real for a, b in zip(
            jax.tree_util.tree_leaves(v), jax.tree_util.tree_leaves(w)))
        done = jnp.abs(ev - prev_ev) <= tol * jnp.maximum(jnp.abs(ev), 1e-12)
        return normalize(w), ev.astype(jnp.float32), i + 1, done

    def cond(carry):
        _, _, i, done = carry
        return (~done) & (i < max_iter)

    v0 = normalize(v0)
    _, ev, iters, _ = jax.lax.while_loop(
        cond, body, (v0, jnp.asarray(jnp.inf, jnp.float32),
                     jnp.asarray(0, jnp.int32), jnp.asarray(False)))
    return ev, iters
