"""Mixed-precision quantization (MoQ) scheduling.

Reference: `runtime/quantize.py` (`Quantizer`) — progressive bit reduction
during QAT: each time a layer's quantization period expires its bit width
drops by one and the next period doubles; when eigenvalue estimation is on,
the period is additionally stretched by `1 + floor(ev * 4)` so high-curvature
layers keep precision longer (`quantize.py:129-137`, `engine.py:1769-1780`).

TPU-native split of responsibilities:
  * the fake-quant itself is a pure transform inside the compiled loss
    (`compression/basic_layer.fake_quantize`, STE);
  * `MoQScheduler` here is host-side bookkeeping — per-layer bits/periods
    advanced once per optimizer step. When bits change the engine retraces
    its step program (bounded by layers × (start_bits - target_bits)
    recompiles over a whole run, not per step);
  * `block_eigenvalues` replaces the reference's per-block autograd loops
    (`runtime/eigenvalue.py:60-120`) with ONE jitted program: the stacked
    `blocks` [L, ...] layout lets a vmapped Hessian-vector product run the
    power iteration for every layer's diagonal block H_ii simultaneously
    (masking v to one layer's slice makes (Hv)_i = H_ii v_i exact).
"""

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from deepspeed_tpu.runtime.eigenvalue import hessian_vector_product
from deepspeed_tpu.utils.logging import log_dist

TWO_D_PARAMS = 6  # reference quantize.py:17 — schedule granularity constant


class MoQScheduler:
    """Per-layer progressive bit-reduction schedule (reference `Quantizer`)."""

    def __init__(self, start_bits: int = 16, target_bits: int = 8,
                 period: int = 100, layer_num: int = 1):
        self.layer_num = max(int(layer_num), 1)
        self.target_bits = int(target_bits)
        self.bits = [int(start_bits)] * self.layer_num
        self.period = [int(period)] * self.layer_num
        self.qsteps = 0

    def any_precision_switch(self) -> bool:
        """True while some layer still has bits to shed (reference
        `any_precision_switch`, quantize.py:38)."""
        return any(b > self.target_bits for b in self.bits)

    def step(self, block_eigenvalue: Optional[Sequence[float]] = None) -> bool:
        """Advance one optimizer step. `block_eigenvalue`: per-layer values in
        [0, 1] (see `post_process_eigenvalues`). Returns True when any layer's
        bit width changed — the caller must retrace its compiled loss."""
        self.qsteps += 1
        changed = False
        for i in range(self.layer_num):
            if self.bits[i] <= self.target_bits:
                continue
            if self.qsteps >= self.period[i]:
                ev = None
                if block_eigenvalue is not None and len(block_eigenvalue):
                    ev = float(block_eigenvalue[min(i, len(block_eigenvalue) - 1)])
                factor = 1 + math.floor(ev * 4) if ev is not None else 1
                # reference quantize.py:133-135: double, then scale by curvature
                self.period[i] = self.period[i] * 2 * factor
                self.bits[i] -= 1
                changed = True
                log_dist(f"MoQ: layer {i} -> {self.bits[i]} bits "
                         f"(next period {self.period[i]}"
                         + (f", ev factor {factor}" if ev is not None else "")
                         + ")", ranks=[0])
        return changed

    def bits_vector(self, n_layers: int):
        """Per-layer bits broadcast to `n_layers` (models whose stacked depth
        differs from the schedule's layer_num reuse the last entry)."""
        if self.layer_num >= n_layers:
            return list(self.bits[:n_layers])
        return list(self.bits) + [self.bits[-1]] * (n_layers - self.layer_num)


def post_process_eigenvalues(evs):
    """Map raw per-layer eigenvalues to [0, 1] relative to the largest;
    non-finite / zero entries become 1.0 (keep full precision longest) —
    reference `Eigenvalue.post_process` (`runtime/eigenvalue.py:145-149`)."""
    evs = [float(v) for v in evs]
    finite = [abs(v) for v in evs if math.isfinite(v) and v != 0.0]
    if not finite:
        return [1.0] * len(evs)
    mx = max(finite)
    return [abs(v) / mx if math.isfinite(v) and v != 0.0 else 1.0 for v in evs]


def block_eigenvalues(loss_fn, params, batch, max_iter: int = 100,
                      tol: float = 1e-2, stability: float = 1e-6,
                      seed: int = 0):
    """Per-layer dominant eigenvalue of the block-diagonal Hessian.

    `params` must carry the model zoo's stacked layout (`params['blocks']`
    leaves with leading layer dim L). For a tangent v supported on layer i
    only, the Hessian-vector product restricted to slice i equals H_ii v_i
    exactly, so one vmapped hvp advances all L power iterations per sweep —
    the whole estimation is a single XLA program vs the reference's L
    Python-side autograd loops. Returns a length-L list of raw eigenvalues
    (feed through `post_process_eigenvalues` before scheduling).
    """
    blocks = params["blocks"]
    L = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    rest = {k: v for k, v in params.items() if k != "blocks"}

    grad_fn = jax.grad(lambda b: loss_fn({**rest, "blocks": b}, batch))

    def layer_mask(i, tree):
        def leaf(a):
            sel = (jnp.arange(a.shape[0]) == i).astype(a.dtype)
            return a * sel.reshape((a.shape[0],) + (1,) * (a.ndim - 1))
        return jax.tree_util.tree_map(leaf, tree)

    def layer_hvp(i, v):
        # v: blocks-shaped, row i of every leaf holds layer i's vector.
        # Slice row i of the product: (Hv)_i = H_ii v_i exactly (the tangent
        # is supported on layer i only), and returning just that row keeps the
        # mapped output at [L, ...] — one model's worth — instead of an
        # [L, L, ...] stack of masked copies.
        hv = hessian_vector_product(grad_fn, blocks, layer_mask(i, v))
        return jax.tree_util.tree_map(lambda l: l[i], hv)

    def norms(v):
        """Per-layer L2 norms [L] over all leaves."""
        sq = sum(jnp.sum((l.astype(jnp.float32))**2,
                         axis=tuple(range(1, l.ndim)))
                 for l in jax.tree_util.tree_leaves(v))
        return jnp.sqrt(sq)

    def normalize(v):
        n = norms(v)
        return jax.tree_util.tree_map(
            lambda l: l / (n.reshape((L,) + (1,) * (l.ndim - 1)) + stability), v)

    @jax.jit
    def run():
        leaves, treedef = jax.tree_util.tree_flatten(blocks)
        keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
        v0 = treedef.unflatten([jax.random.normal(k, l.shape, jnp.float32)
                                for k, l in zip(keys, leaves)])
        v0 = normalize(v0)
        idx = jnp.arange(L)

        def body(carry):
            v, prev, it, _ = carry
            # vmap batches L tangent copies (L x model memory in
            # intermediates) — fine for typical depths; deep models switch to
            # lax.map (sequential: one tangent's activations live at a time,
            # same one-program property). Both produce [L, ...] outputs.
            if L <= 16:
                hv = jax.vmap(layer_hvp, in_axes=(0, None))(idx, v)
            else:
                hv = jax.lax.map(lambda i: layer_hvp(i, v), idx)
            ev = sum(jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32),
                             axis=tuple(range(1, a.ndim)))
                     for a, b in zip(jax.tree_util.tree_leaves(v),
                                     jax.tree_util.tree_leaves(hv)))
            done = jnp.all(jnp.abs(ev - prev) <=
                           tol * jnp.maximum(jnp.abs(ev), 1e-12))
            return normalize(hv), ev, it + 1, done

        def cond(carry):
            _, _, it, done = carry
            return (~done) & (it < max_iter)

        _, ev, _, _ = jax.lax.while_loop(
            cond, body, (v0, jnp.full((L,), jnp.inf, jnp.float32),
                         jnp.asarray(0, jnp.int32), jnp.asarray(False)))
        return ev

    return [float(x) for x in jax.device_get(run())]
