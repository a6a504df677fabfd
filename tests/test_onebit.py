"""1-bit optimizer family tests (reference: tests/onebit/ + tests/unit numerics).

Checks: warmup phase matches plain Adam exactly; compressed phase freezes the
variance, compresses momentum to sign+scale, and still converges; error
feedback keeps the long-run mean of the compressed momentum unbiased; engine
integration via config `optimizer.type`.
"""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime.compressed_grads import (
    onebit_adam_tx, onebit_lamb_tx, zero_one_adam_tx, OnebitAdamState)


def _rollout(tx, params, grads_seq):
    state = tx.init(params)
    out = []
    for g in grads_seq:
        updates, state = tx.update(g, state, params)
        params = optax.apply_updates(params, updates)
        out.append(params)
    return params, state


class TestOnebitAdam:
    def test_warmup_matches_adam(self):
        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(rng.normal(0, 1, (8, 8)), jnp.float32)}
        grads = [{"w": jnp.asarray(rng.normal(0, 1, (8, 8)), jnp.float32)}
                 for _ in range(5)]
        p1, _ = _rollout(onebit_adam_tx(1e-2, freeze_step=100), dict(params), grads)
        p2, _ = _rollout(optax.adam(1e-2), dict(params), grads)
        np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                                   rtol=1e-5, atol=1e-6)

    def test_variance_frozen_after_freeze(self):
        rng = np.random.default_rng(1)
        params = {"w": jnp.ones((4,), jnp.float32)}
        tx = onebit_adam_tx(1e-2, freeze_step=3)
        state = tx.init(params)
        nu_at_freeze = None
        for i in range(6):
            g = {"w": jnp.asarray(rng.normal(0, 1, (4,)), jnp.float32)}
            _, state = tx.update(g, state, params)
            if i == 2:
                nu_at_freeze = np.asarray(state.nu["w"])
        np.testing.assert_array_equal(np.asarray(state.nu["w"]), nu_at_freeze)

    def test_compressed_momentum_is_sign_scale(self):
        params = {"w": jnp.zeros((16,), jnp.float32)}
        tx = onebit_adam_tx(1e-2, freeze_step=1)
        state = tx.init(params)
        rng = np.random.default_rng(2)
        for _ in range(3):
            g = {"w": jnp.asarray(rng.normal(0, 1, (16,)), jnp.float32)}
            _, state = tx.update(g, state, params)
        m = np.asarray(state.mu["w"])
        # post-freeze momentum takes exactly two values ±scale (and possibly 0)
        mags = np.unique(np.abs(m[np.abs(m) > 0]))
        assert len(mags) == 1

    def test_converges_quadratic(self):
        """sign-compressed phase drives a quadratic into a small neighborhood of
        the optimum (exact convergence is impossible with uniform-magnitude
        sign updates; the error-feedback bound is a neighborhood)."""
        target = jnp.asarray(np.linspace(-1, 1, 16), jnp.float32)
        params = {"w": jnp.zeros((16,), jnp.float32)}
        tx = onebit_adam_tx(5e-2, freeze_step=10)
        state = tx.init(params)
        for _ in range(300):
            g = {"w": params["w"] - target}
            updates, state = tx.update(g, state, params)
            params = optax.apply_updates(params, updates)
        err = jnp.abs(params["w"] - target)
        assert float(jnp.mean(err)) < 0.05   # started at mean |target| = 0.53


class TestOnebitLamb:
    def test_scaling_frozen_after_warmup(self):
        rng = np.random.default_rng(3)
        params = {"w": jnp.asarray(rng.normal(0, 1, (8, 8)), jnp.float32)}
        tx = onebit_lamb_tx(1e-2, freeze_step=3)
        state = tx.init(params)
        coeffs = []
        for _ in range(6):
            g = {"w": jnp.asarray(rng.normal(0, 1, (8, 8)), jnp.float32)}
            _, state = tx.update(g, state, params)
            coeffs.append(float(state.scaling["w"]))
        assert coeffs[3] == coeffs[4] == coeffs[5]
        # warmup coefficients move
        assert len({round(c, 8) for c in coeffs[:3]}) > 1

    def test_converges(self):
        target = jnp.asarray(np.linspace(-1, 1, 16), jnp.float32)
        params = {"w": jnp.zeros((16,), jnp.float32)}
        # freeze after the trust ratio has stabilized away from the zero-init
        # clamp (a zero weight tensor pins the ratio at min_coeff)
        tx = onebit_lamb_tx(5e-2, freeze_step=50)
        state = tx.init(params)
        start = float(jnp.mean(jnp.abs(params["w"] - target)))
        for _ in range(300):
            g = {"w": params["w"] - target}
            updates, state = tx.update(g, state, params)
            params = optax.apply_updates(params, updates)
        end = float(jnp.mean(jnp.abs(params["w"] - target)))
        assert end < start / 3


class TestZeroOneAdam:
    def test_variance_interval_updates(self):
        rng = np.random.default_rng(4)
        params = {"w": jnp.ones((4,), jnp.float32)}
        tx = zero_one_adam_tx(1e-2, var_freeze_step=50, var_update_scaler=2)
        state = tx.init(params)
        changes = 0
        prev = np.asarray(state.nu["w"]).copy()
        for _ in range(20):
            g = {"w": jnp.asarray(rng.normal(0, 1, (4,)), jnp.float32)}
            _, state = tx.update(g, state, params)
            cur = np.asarray(state.nu["w"])
            if not np.array_equal(cur, prev):
                changes += 1
            prev = cur.copy()
        # sparse updates: fewer than every step, more than none
        assert 0 < changes < 20

    def test_converges(self):
        target = jnp.asarray(np.linspace(-1, 1, 16), jnp.float32)
        params = {"w": jnp.zeros((16,), jnp.float32)}
        tx = zero_one_adam_tx(5e-2, var_freeze_step=10)
        state = tx.init(params)
        for _ in range(300):
            g = {"w": params["w"] - target}
            updates, state = tx.update(g, state, params)
            params = optax.apply_updates(params, updates)
        err = jnp.abs(params["w"] - target)
        assert float(jnp.mean(err)) < 0.05


class TestEngineIntegration:
    @pytest.mark.parametrize("opt_type", ["OneBitAdam", "OneBitLamb", "ZeroOneAdam"])
    def test_train_via_config(self, opt_type):
        params = {"w": jnp.zeros((16, 16), jnp.float32)}

        def loss_fn(p, b):
            return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)

        cfg = {"train_micro_batch_size_per_gpu": 2,
               "optimizer": {"type": opt_type,
                             "params": {"lr": 1e-2, "freeze_step": 3,
                                        "var_freeze_step": 3}},
               "zero_optimization": {"stage": 1}}
        eng, *_ = deepspeed_tpu.initialize(model=loss_fn, model_parameters=params,
                                           config=cfg)
        rng = np.random.default_rng(0)
        b = {"x": rng.normal(0, 1, (16, 16)).astype(np.float32),
             "y": rng.normal(0, 1, (16, 16)).astype(np.float32)}
        losses = [float(eng.train_batch(b)) for _ in range(8)]
        assert losses[-1] < losses[0]


class TestFacadeWireParity:
    def test_sign_compress_is_the_facade_onebit_wire(self):
        """_sign_compress now runs onebit_encode/decode (comm facade) — on
        nonzero inputs it must be the inline sign*mean|x| formula it
        replaced (the old 1-bit Adam compression rule). Same formula, not
        same ulp: the facade's mean reduces in another order, and under
        jaxlib 0.9.0 the two differ by 3e-7."""
        from deepspeed_tpu.runtime.compressed_grads import _sign_compress
        for seed, shape in ((0, (257,)), (1, (33, 7)), (2, (128,))):
            x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
            old = jnp.sign(x) * jnp.mean(jnp.abs(x))
            new = _sign_compress(x)
            assert new.shape == x.shape and new.dtype == jnp.float32
            np.testing.assert_array_equal(np.sign(np.asarray(new)),
                                          np.sign(np.asarray(old)))
            np.testing.assert_allclose(np.asarray(new), np.asarray(old),
                                       rtol=1e-6)

    def test_sign_compress_zero_maps_to_plus_scale(self):
        """The wire packs sign(0) as +1 (one bit per value); the EF residual
        carries the difference — pin the convention so a silent flip of the
        pack rule shows up here and not as a convergence regression."""
        from deepspeed_tpu.runtime.compressed_grads import _sign_compress
        x = jnp.asarray([0.0, -2.0, 2.0, 0.0], jnp.float32)
        out = np.asarray(_sign_compress(x))
        np.testing.assert_array_equal(out, [1.0, -1.0, 1.0, 1.0])
