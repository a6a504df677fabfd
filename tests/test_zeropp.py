"""ZeRO++ (qwZ/qgZ/hpZ) and MiCS tests on the virtual 8-device mesh.

Reference analogs: `tests/unit/runtime/zero/test_zeropp.py`, MiCS tests in
`tests/unit/runtime/zero/`.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig


def _mk_mesh(**axes):
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    return mesh_mod.init_mesh(MeshConfig(**{**dict(data=1, zero=1, tensor=1,
                                                   sequence=1, expert=1, pipe=1),
                                            **axes}))


def _base_config(**zero_kw):
    return {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 3, **zero_kw},
        "steps_per_print": 10**9,
    }


def _tiny_model():
    import jax.numpy as jnp

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["w1"])
        return jnp.mean((h @ p["w2"] - b["y"]) ** 2)

    params = {"w1": jnp.asarray(np.random.default_rng(0).normal(0, 0.1, (64, 64)),
                                jnp.float32),
              "w2": jnp.asarray(np.random.default_rng(1).normal(0, 0.1, (64, 64)),
                                jnp.float32)}
    return loss_fn, params


def _batch(n):
    rng = np.random.default_rng(2)
    return {"x": rng.normal(0, 1, (n, 64)).astype(np.float32),
            "y": rng.normal(0, 1, (n, 64)).astype(np.float32)}


# ----------------------------------------------------------------------
# quantized collectives
# ----------------------------------------------------------------------


class TestQuantizedCollectives:
    def test_quantized_all_gather_matches_plain(self, devices8):
        mesh = _mk_mesh(data=8)
        from deepspeed_tpu.runtime.quantized_collectives import quantized_all_gather
        x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (64, 32)), jnp.float32)

        def body(xs):
            return quantized_all_gather(xs, "data")

        out = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P(),
                        check_vma=False)(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=0.02)

    def test_quantized_reduce_scatter_matches_psum(self, devices8):
        mesh = _mk_mesh(data=8)
        from deepspeed_tpu.runtime.quantized_collectives import quantized_reduce_scatter
        # per-device distinct contributions: deterministic from axis index
        full = jnp.asarray(np.random.default_rng(1).normal(0, 1, (8, 64, 16)),
                           jnp.float32)

        def body(contrib):
            # contrib[0]: [64, 16] this device's contribution, tiled to full size
            # so chunk j sent to rank j is this device's own block
            x = jnp.concatenate([contrib[0]] * 8, axis=0)  # [512, 16]
            return quantized_reduce_scatter(x, "data")

        out = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                        check_vma=False)(full)
        # rank j's shard = sum_i (device i's chunk j) = sum_i full[i]
        expect_full = jnp.concatenate([jnp.sum(full, axis=0)] * 8, axis=0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect_full),
                                   rtol=0.05, atol=0.15)

    def test_qgz_allreduce_matches_psum(self, devices8):
        mesh = _mk_mesh(data=8)
        from deepspeed_tpu.runtime.quantized_collectives import qgz_allreduce
        full = jnp.asarray(np.random.default_rng(3).normal(0, 1, (8, 33, 7)),
                           jnp.float32)  # odd shape exercises padding

        def body(contrib):
            return qgz_allreduce(contrib[0], "data")

        out = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P(),
                        check_vma=False)(full)
        expect = jnp.sum(full, axis=0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=0.05, atol=0.2)


# ----------------------------------------------------------------------
# MiCS / hpZ sharding domains
# ----------------------------------------------------------------------


class TestMicsHpz:
    def test_mics_mesh_factoring_and_param_sharding(self, devices8):
        loss_fn, params = _tiny_model()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=loss_fn, model_parameters=params,
            config=_base_config(mics_shard_size=4,
                               stage3_param_persistence_threshold=0))
        assert engine.spec.zero == 4 and engine.spec.data == 2
        # params shard over the inner sub-group only
        spec = engine.param_shardings["w1"].spec
        assert "zero" in str(spec) and "data" not in str(spec)
        # states too (MiCS shards everything within the group)
        mspec = engine.master_shardings["w1"].spec
        assert "zero" in str(mspec) and "data" not in str(mspec)
        losses = [float(engine.train_batch(_batch(engine.train_batch_size())))
                  for _ in range(3)]
        assert np.isfinite(losses).all() and losses[-1] < losses[0]

    def test_hpz_params_subgroup_states_full(self, devices8):
        loss_fn, params = _tiny_model()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=loss_fn, model_parameters=params,
            config=_base_config(zero_hpz_partition_size=4,
                               stage3_param_persistence_threshold=0))
        assert engine.spec.zero == 4 and engine.spec.data == 2
        pspec = engine.param_shardings["w1"].spec
        mspec = engine.master_shardings["w1"].spec
        assert "zero" in str(pspec) and "data" not in str(pspec)   # secondary copy
        assert "data" in str(mspec)                                 # full domain
        losses = [float(engine.train_batch(_batch(engine.train_batch_size())))
                  for _ in range(3)]
        assert np.isfinite(losses).all() and losses[-1] < losses[0]


# ----------------------------------------------------------------------
# quantized train step (qwZ / qgZ)
# ----------------------------------------------------------------------


class TestQuantizedStep:
    @pytest.mark.parametrize("knobs", [
        {"zero_quantized_gradients": True, "stage": 1},
        {"zero_quantized_weights": True, "stage": 3,
         "stage3_param_persistence_threshold": 0},
        {"zero_quantized_weights": True, "zero_quantized_gradients": True,
         "stage": 3, "stage3_param_persistence_threshold": 0},
    ])
    def test_quantized_step_trains_close_to_exact(self, devices8, knobs):
        loss_fn, params = _tiny_model()
        stage = knobs.pop("stage")
        cfg = _base_config(**knobs)
        cfg["zero_optimization"]["stage"] = stage
        cfg["mesh"] = {"data": 8}
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=loss_fn, model_parameters=params, config=cfg)
        batch = _batch(engine.train_batch_size())
        losses = [float(engine.train_batch(batch)) for _ in range(6)]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]

        # exact (unquantized) engine on the same data: trajectories stay close
        mesh_mod._CURRENT_MESH = None
        mesh_mod._CURRENT_SPEC = None
        cfg2 = _base_config()
        cfg2["zero_optimization"]["stage"] = stage
        cfg2["mesh"] = {"data": 8}
        loss_fn2, params2 = _tiny_model()
        exact, _, _, _ = deepspeed_tpu.initialize(
            model=loss_fn2, model_parameters=params2, config=cfg2)
        ref = [float(exact.train_batch(batch)) for _ in range(6)]
        np.testing.assert_allclose(losses, ref, rtol=0.08)


class TestQuantizedStepZooModel:
    """ZeRO++ on a zoo model whose leaves carry TP-annotated PartitionSpecs.

    Regression: the qwZ/qgZ shard_map gather picked the FIRST non-None spec
    dim, but zoo leaves look like P(None, 'tensor', ('data','zero','sequence'))
    — the data-sharded dim is not first, and under hpZ it is sharded over
    'zero' only. Caught only by a model with real TP specs (r4)."""

    @pytest.mark.parametrize("knobs", [
        {"zero_quantized_weights": True},
        {"zero_quantized_gradients": True},
        {"zero_quantized_weights": True, "zero_quantized_gradients": True,
         "zero_hpz_partition_size": 2},
    ])
    def test_gpt_zeropp_trains(self, devices8, knobs):
        import jax.numpy as jnp
        from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_model

        mesh_mod._CURRENT_MESH = None
        mesh_mod._CURRENT_SPEC = None
        cfg = GPTConfig(n_layer=2, n_head=4, d_model=64, d_ff=256,
                        max_seq_len=64, vocab_size=512, dtype=jnp.bfloat16,
                        remat=True)
        model = make_gpt_model(cfg=cfg, name="q", abstract=True)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 3,
                                  "stage3_param_persistence_threshold": 0,
                                  **knobs},
            "mesh": {"data": 8},
            "steps_per_print": 1000})
        batch = {"tokens": np.random.default_rng(4).integers(
            0, cfg.vocab_size,
            (engine.train_batch_size(), 32)).astype(np.int32)}
        losses = [float(engine.train_batch(batch)) for _ in range(3)]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]
