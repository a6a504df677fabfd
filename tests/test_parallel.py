"""Ulysses SP, MoE EP, and AutoTP planner tests (reference gap: Ulysses had no
unit tests in the snapshot — SURVEY §4 says don't copy that omission)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig


def _mk_mesh(**axes):
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    return mesh_mod.init_mesh(MeshConfig(data=axes.get("data", 1),
                                         tensor=axes.get("tensor", 1),
                                         sequence=axes.get("sequence", 1),
                                         expert=axes.get("expert", 1),
                                         pipe=axes.get("pipe", 1)))


def _ref_attention(q, k, v, causal=True):
    """THE dense-softmax reference every parity class in this module
    compares against — one definition, causal togglable."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))[None, None]
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


class TestUlysses:
    def test_constraint_form_matches_local(self):
        mesh = _mk_mesh(data=2, sequence=4)
        from deepspeed_tpu.parallel.ulysses import DistributedAttention
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (2, 16, 8, 4)), jnp.float32) for _ in range(3))
        dist_attn = DistributedAttention(_ref_attention)
        out = jax.jit(dist_attn)(q, k, v)
        ref = _ref_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_shard_map_form_matches_local(self):
        mesh = _mk_mesh(sequence=4)
        from deepspeed_tpu.parallel.ulysses import ulysses_shard_map_attention

        def plain_attn(q, k, v):  # non-causal for the shard_map form
            scale = 1.0 / np.sqrt(q.shape[-1])
            logits = jnp.einsum("bthd,bshd->bhts", q, k) * scale
            probs = jax.nn.softmax(logits, axis=-1)
            return jnp.einsum("bhts,bshd->bthd", probs, v)

        rng = np.random.default_rng(1)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (2, 16, 8, 4)), jnp.float32) for _ in range(3))
        fn = ulysses_shard_map_attention(plain_attn, mesh=mesh)
        out = jax.jit(fn)(q, k, v)
        ref = plain_attn(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


class TestMoE:
    def test_top1_gating_shapes_and_capacity(self):
        from deepspeed_tpu.parallel.moe import top1_gating
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(0, 1, (32, 4)), jnp.float32)
        l_aux, dispatch, combine, counts = top1_gating(logits, capacity_factor=1.0, min_capacity=4)
        N, E, C = dispatch.shape
        assert (N, E) == (32, 4) and C == 8
        # every slot holds at most one token
        assert np.asarray(dispatch.sum(axis=0).max()) <= 1
        # each token dispatched at most once
        assert np.asarray(dispatch.sum(axis=(1, 2)).max()) <= 1
        assert float(l_aux) > 0

    def test_top2_gating(self):
        from deepspeed_tpu.parallel.moe import top2_gating
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(0, 1, (32, 4)), jnp.float32)
        l_aux, dispatch, combine, counts = top2_gating(logits)
        assert np.asarray(dispatch.sum(axis=(1, 2)).max()) <= 2
        # combine weights for a token sum to ~1 when both experts kept
        s = np.asarray(combine.sum(axis=(1, 2)))
        assert (s <= 1.0 + 1e-5).all()

    def test_moe_layer_forward_backward(self):
        mesh = _mk_mesh(data=2, expert=4)
        from deepspeed_tpu.parallel.moe import MoELayer
        layer = MoELayer(num_experts=4, k=1, capacity_factor=2.0)
        params = layer.init_params(16, 32)
        x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (2, 8, 16)), jnp.float32)

        def loss(p):
            y, l_aux, _ = layer(p, x)
            return jnp.mean(y**2) + 0.01 * l_aux

        g = jax.jit(jax.grad(loss))(params)
        assert np.isfinite(np.asarray(jax.flatten_util.ravel_pytree(g)[0])).all()

    def test_moe_in_engine(self):
        """MoE transformer-ish model trains under the engine with expert axis."""
        mesh = _mk_mesh(data=2, expert=4)
        from deepspeed_tpu.parallel.moe import MoELayer
        from deepspeed_tpu.runtime.engine import ModelSpec
        layer = MoELayer(num_experts=4, k=2, capacity_factor=2.0)
        rng = np.random.default_rng(0)
        params = {
            "proj_in": jnp.asarray(rng.normal(0, 0.1, (8, 16)), jnp.float32),
            "moe": layer.init_params(16, 32),
            "proj_out": jnp.asarray(rng.normal(0, 0.1, (16, 8)), jnp.float32),
        }
        specs = {"proj_in": P(None, None), "moe": layer.param_specs(),
                 "proj_out": P(None, None)}

        def loss_fn(p, batch, rng=None):
            h = batch["x"] @ p["proj_in"]
            h = h[:, None, :]  # [B,1,D]
            y, l_aux, _ = layer(p["moe"], h)
            out = y[:, 0, :] @ p["proj_out"]
            return jnp.mean((out - batch["y"])**2) + 0.01 * l_aux

        model = ModelSpec(loss_fn=loss_fn, params=params, param_specs=specs)
        engine, *_ = deepspeed_tpu.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "mesh": {"data": 2, "expert": 4},
            "steps_per_print": 1000,
        }, mesh=mesh)
        batch = {"x": rng.normal(0, 1, (16, 8)).astype(np.float32),
                 "y": rng.normal(0, 1, (16, 8)).astype(np.float32)}
        losses = [float(engine.train_batch(batch)) for _ in range(8)]
        assert losses[-1] < losses[0], losses


class TestAutoTP:
    def test_plan_classifies(self):
        from deepspeed_tpu.parallel.tp import plan_tp_specs
        params = {
            "attn": {"q_proj": jnp.zeros((8, 8)), "out_proj": jnp.zeros((8, 8))},
            "mlp": {"up_proj": jnp.zeros((8, 32)), "down_proj": jnp.zeros((32, 8))},
            "ln": {"scale": jnp.ones((8,))},
            "embed_tokens": jnp.zeros((100, 8)),
        }
        specs = plan_tp_specs(params)
        assert specs["attn"]["q_proj"] == P(None, "tensor")
        assert specs["attn"]["out_proj"] == P("tensor", None)
        assert specs["mlp"]["up_proj"] == P(None, "tensor")
        assert specs["mlp"]["down_proj"] == P("tensor", None)
        assert specs["ln"]["scale"] == P(None)
        assert specs["embed_tokens"] == P("tensor", None)

    def test_tp_sharded_mlp_matches_dense(self):
        mesh = _mk_mesh(tensor=4)
        from deepspeed_tpu.parallel.tp import plan_tp_specs
        from jax.sharding import NamedSharding
        rng = np.random.default_rng(0)
        params = {"up_proj": jnp.asarray(rng.normal(0, 0.1, (16, 64)), jnp.float32),
                  "down_proj": jnp.asarray(rng.normal(0, 0.1, (64, 16)), jnp.float32)}
        specs = plan_tp_specs(params)
        sharded = jax.device_put(params, jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs))
        x = jnp.asarray(rng.normal(0, 1, (4, 16)), jnp.float32)

        def f(p, x):
            return jax.nn.gelu(x @ p["up_proj"]) @ p["down_proj"]

        ref = f(params, x)
        out = jax.jit(f)(sharded, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)

    def test_tiled_linear(self):
        from deepspeed_tpu.parallel.tp import tiled_linear
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(0, 1, (4, 16)), jnp.float32)
        w = jnp.asarray(rng.normal(0, 1, (16, 32)), jnp.float32)
        b = jnp.asarray(rng.normal(0, 1, (32,)), jnp.float32)
        np.testing.assert_allclose(np.asarray(tiled_linear(x, w, b, splits=4)),
                                   np.asarray(x @ w + b), rtol=1e-5, atol=1e-5)


class TestRingAttention:
    def _ref(self, q, k, v, causal=True):
        return _ref_attention(q, k, v, causal=causal)

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal):
        mesh = _mk_mesh(data=2, sequence=4)
        from deepspeed_tpu.parallel.ring import ring_attention
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (2, 32, 4, 8)), jnp.float32) for _ in range(3))
        out = jax.jit(lambda q, k, v: ring_attention(q, k, v, causal=causal, mesh=mesh))(q, k, v)
        ref = self._ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)

    def test_flash_inner_matches_einsum_and_grads(self):
        """The flash-kernel ring path (interpret mode on CPU) reproduces the
        einsum ring path AND plain attention, forward and grads — including
        the dlse cotangent through the partial-merge weights."""
        mesh = _mk_mesh(sequence=4)
        from deepspeed_tpu.parallel.ring import ring_attention
        rng = np.random.default_rng(5)
        # local shard Tl = 512/4 = 128: flash block constraint satisfied
        q, k, v = (jnp.asarray(rng.normal(0, 1, (1, 512, 2, 32)), jnp.float32)
                   for _ in range(3))

        out_f = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, causal=True, mesh=mesh, use_flash=True))(q, k, v)
        out_e = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, causal=True, mesh=mesh, use_flash=False))(q, k, v)
        ref = self._ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_e),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

        def loss(fn):
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), argnums=(0, 1, 2)))

        g_f = loss(lambda q, k, v: ring_attention(q, k, v, causal=True,
                                                  mesh=mesh, use_flash=True))(q, k, v)
        g_ref = loss(lambda q, k, v: self._ref(q, k, v, causal=True))(q, k, v)
        for a, b, name in zip(g_f, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-3, err_msg=f"d{name}")

    def test_gradients_flow(self):
        mesh = _mk_mesh(sequence=4)
        from deepspeed_tpu.parallel.ring import ring_attention
        rng = np.random.default_rng(1)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (1, 16, 2, 8)), jnp.float32) for _ in range(3))

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, causal=True, mesh=mesh) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(self._ref(q, k, v, causal=True) ** 2)

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_ring, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4,
                                       err_msg=f"d{name}")


@pytest.mark.longctx
class TestRingFlashParity:
    """Ring flash attention (the PRIMARY long-context path) vs the
    blockwise einsum oracle and plain dense attention — forward and grads,
    causal and non-causal, plus the shapes the kernel cannot tile."""

    def _ref(self, q, k, v, causal=True):
        return _ref_attention(q, k, v, causal=causal)

    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_matches_oracle_and_dense(self, causal):
        """Both ring paths (flash kernel per step / blockwise einsum)
        reproduce dense attention — including the NON-causal flash ring,
        where every step runs the unmasked kernel and merges by lse."""
        mesh = _mk_mesh(sequence=4)
        from deepspeed_tpu.parallel.ring import (ring_attention_blockwise,
                                                 ring_flash_attention)
        rng = np.random.default_rng(7)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (1, 512, 2, 32)), jnp.float32)
                   for _ in range(3))
        out_f = jax.jit(lambda q, k, v: ring_flash_attention(
            q, k, v, causal=causal, mesh=mesh))(q, k, v)
        out_o = jax.jit(lambda q, k, v: ring_attention_blockwise(
            q, k, v, causal=causal, mesh=mesh))(q, k, v)
        ref = self._ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_o),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_grads_match_dense(self, causal):
        """The online-softmax state carries across ring steps in the
        BACKWARD too (lse cotangent through the kernel's custom VJP)."""
        mesh = _mk_mesh(sequence=4)
        from deepspeed_tpu.parallel.ring import ring_flash_attention
        rng = np.random.default_rng(8)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (1, 512, 2, 32)), jnp.float32)
                   for _ in range(3))
        g_f = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(ring_flash_attention(
                q, k, v, causal=causal, mesh=mesh) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        g_r = jax.grad(
            lambda q, k, v: jnp.sum(self._ref(q, k, v, causal=causal) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_f, g_r, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-3,
                                       err_msg=f"d{name}")

    def test_untileable_shard_auto_falls_back_and_forced_raises(self):
        """T not a multiple of sp*128: auto dispatch keeps the blockwise
        oracle (parity intact); use_flash=True surfaces the kernel's tile
        contract as a clear ValueError, not a deep block assert."""
        mesh = _mk_mesh(sequence=4)
        from deepspeed_tpu.parallel.ring import ring_attention
        rng = np.random.default_rng(9)
        # T=192 -> local shard 48: not 128-tileable
        q, k, v = (jnp.asarray(rng.normal(0, 1, (1, 192, 2, 16)), jnp.float32)
                   for _ in range(3))
        out = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, causal=True, mesh=mesh))(q, k, v)
        ref = self._ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        with pytest.raises(ValueError, match="128-multiple"):
            ring_attention(q, k, v, causal=True, mesh=mesh, use_flash=True)
        with pytest.raises(ValueError, match="does not divide"):
            ring_attention(q[:, :30], k[:, :30], v[:, :30], mesh=mesh)


@pytest.mark.longctx
class TestRingUlyssesComposition:
    """The reference hybrid: sp = ulysses_degree x ring_degree over ONE
    `sequence` axis — head all-to-all around the K/V ring."""

    def _ref(self, q, k, v, causal=True):
        return _ref_attention(q, k, v, causal=causal)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("ulysses_degree", [1, 2, 4, None])
    def test_composed_matches_dense(self, causal, ulysses_degree):
        """Every factoring of sp=4 (pure ring, hybrid, pure Ulysses, and
        the auto pick) reproduces dense attention."""
        mesh = _mk_mesh(data=2, sequence=4)
        from deepspeed_tpu.parallel.ring import ring_ulysses_attention
        rng = np.random.default_rng(11)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (2, 32, 4, 8)), jnp.float32)
                   for _ in range(3))
        out = jax.jit(lambda q, k, v: ring_ulysses_attention(
            q, k, v, causal=causal, ulysses_degree=ulysses_degree,
            mesh=mesh))(q, k, v)
        ref = self._ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_composed_grads_match_dense(self):
        mesh = _mk_mesh(data=2, sequence=4)
        from deepspeed_tpu.parallel.ring import ring_ulysses_attention
        rng = np.random.default_rng(12)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (2, 32, 4, 8)), jnp.float32)
                   for _ in range(3))
        g = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(ring_ulysses_attention(
                q, k, v, ulysses_degree=2, mesh=mesh) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(self._ref(q, k, v) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4,
                                       err_msg=f"d{name}")

    def test_composed_flash_matches_dense(self):
        """Flash forced through the COMPOSED path: the ring's per-step
        kernel runs on the post-all-to-all local shape (T/ring_degree
        tokens x H/ulysses heads)."""
        mesh = _mk_mesh(sequence=4)
        from deepspeed_tpu.parallel.ring import ring_ulysses_attention
        rng = np.random.default_rng(13)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (1, 512, 2, 32)), jnp.float32)
                   for _ in range(3))
        out = jax.jit(lambda q, k, v: ring_ulysses_attention(
            q, k, v, ulysses_degree=2, mesh=mesh, use_flash=True))(q, k, v)
        ref = self._ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    def test_indivisible_degrees_raise_clearly(self):
        mesh = _mk_mesh(sequence=4)
        from deepspeed_tpu.parallel.ring import ring_ulysses_attention
        q = jnp.zeros((1, 32, 3, 8), jnp.float32)   # 3 heads
        with pytest.raises(ValueError, match="does not divide"):
            ring_ulysses_attention(q, q, q, ulysses_degree=2, mesh=mesh)
        with pytest.raises(ValueError, match="ulysses_degree 3 does not"):
            ring_ulysses_attention(q, q, q, ulysses_degree=3, mesh=mesh)

    def test_gpt_ring_ulysses_backend_matches_default(self):
        """attention_backend='ring_ulysses' through the dispatch layer:
        the composed program carries a whole GPT forward (GQA heads
        repeated by the external-program path) at the default loss."""
        import dataclasses as dc
        from deepspeed_tpu.models.gpt import GPTConfig, gpt_loss, init_gpt_params
        mesh = _mk_mesh(data=2, sequence=4)
        cfg = GPTConfig(n_layer=2, n_head=4, n_kv_head=2, d_model=64,
                        d_ff=256, max_seq_len=64, vocab_size=256,
                        dtype=jnp.float32, remat=False)
        hybrid = dc.replace(cfg, attention_backend="ring_ulysses")
        params = init_gpt_params(cfg, seed=0)
        batch = {"tokens": jnp.asarray(np.random.default_rng(1).integers(
            0, 256, (4, 33)), jnp.int32)}
        loss_h = jax.jit(lambda p: gpt_loss(p, batch, None, cfg=hybrid))(params)
        loss_r = jax.jit(lambda p: gpt_loss(p, batch, None, cfg=cfg))(params)
        np.testing.assert_allclose(float(loss_h), float(loss_r),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.longctx
class TestUlyssesValidation:
    def test_heads_not_divisible_raises_clear_valueerror(self):
        """heads % sp != 0 used to die as a shape mismatch deep inside
        XLA's all-to-all lowering; now it is a ValueError naming the
        contract and the ring_ulysses escape."""
        mesh = _mk_mesh(sequence=4)
        from deepspeed_tpu.parallel.ulysses import ulysses_shard_map_attention

        def plain_attn(q, k, v):
            scale = 1.0 / np.sqrt(q.shape[-1])
            logits = jnp.einsum("bthd,bshd->bhts", q, k) * scale
            probs = jax.nn.softmax(logits, axis=-1)
            return jnp.einsum("bhts,bshd->bthd", probs, v)

        fn = ulysses_shard_map_attention(plain_attn, mesh=mesh)
        q6 = jnp.zeros((2, 16, 6, 4), jnp.float32)      # 6 heads, sp=4
        with pytest.raises(ValueError, match="divisible by tp\\*sp"):
            fn(q6, q6, q6)
        # the divisible case still runs through the SAME wrapped fn
        rng = np.random.default_rng(2)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (2, 16, 8, 4)), jnp.float32)
                   for _ in range(3))
        out = jax.jit(fn)(q, k, v)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(plain_attn(q, k, v)),
                                   rtol=1e-5, atol=1e-5)


class TestRingAttentionInModel:
    """Long-context path: GPT wired with ring attention over the sequence axis
    (context parallelism — capability the reference lacks; its long-context
    answer is Ulysses + sparse attention only, SURVEY.md §2.3)."""

    def test_gpt_with_ring_attention_matches_default(self):
        from functools import partial
        from deepspeed_tpu.models.gpt import GPTConfig, gpt_loss, init_gpt_params
        from deepspeed_tpu.parallel.ring import ring_attention
        mesh = _mk_mesh(data=2, sequence=4)
        cfg = GPTConfig(n_layer=2, n_head=4, d_model=64, d_ff=256, max_seq_len=64,
                        vocab_size=256, dtype=jnp.float32, remat=False)
        params = init_gpt_params(cfg, seed=0)
        toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (4, 33)),
                           jnp.int32)
        batch = {"tokens": toks}
        ring_fn = partial(ring_attention, mesh=mesh)
        loss_ring = jax.jit(lambda p: gpt_loss(p, batch, None, cfg=cfg,
                                               attn_fn=ring_fn))(params)
        loss_ref = jax.jit(lambda p: gpt_loss(p, batch, None, cfg=cfg))(params)
        np.testing.assert_allclose(float(loss_ring), float(loss_ref),
                                   rtol=2e-5, atol=2e-5)

    def _train_dp_ring(self, stage, name):
        """Shared body: dp=2 x sp=4 ring-attention GPT under the engine at the
        given ZeRO stage; asserts loss decreases over 4 steps."""
        from functools import partial
        import deepspeed_tpu
        from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_model
        from deepspeed_tpu.parallel.ring import ring_attention
        _mk_mesh(data=2, sequence=4)
        cfg = GPTConfig(n_layer=2, n_head=4, d_model=64, d_ff=256, max_seq_len=64,
                        vocab_size=256, dtype=jnp.float32, remat=False)
        model = make_gpt_model(cfg=cfg, name=name,
                               attn_fn=partial(ring_attention, mesh=None))
        eng, *_ = deepspeed_tpu.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": stage}})
        batch = {"tokens": np.random.default_rng(0).integers(
            0, 256, (4, 33)).astype(np.int32)}
        losses = [float(eng.train_batch(batch)) for _ in range(4)]
        assert losses[-1] < losses[0], losses

    def test_gpt_ring_attention_trains(self):
        """dp x ring training under the engine — at ZeRO stage 1.

        KNOWN CPU-HARNESS EXCLUSION: with stage>=2 (grad reduce-scatter /
        param all-gather over `data`) + ring ppermute, XLA CPU's thunk
        executor orders the two INDEPENDENT collectives differently on
        different device partitions ~40% of runs and the rendezvous
        deadlocks (observed: 7 devices in the permute, 1 in a data-pair
        all-gather, 60s termination timeout -> abort). TPU linearizes
        collective scheduling, so the stage>=2 combination is exercised on
        hardware only (the tpu-marked variant below); stages 0/1 (plain
        allreduce) measured 0/8 failures."""
        self._train_dp_ring(stage=1, name="ring-gpt")

    @pytest.mark.tpu
    def test_gpt_ring_attention_trains_stage2_tpu(self):
        """dp x ring at ZeRO stage 2 — the combination excluded from the CPU
        harness (see test_gpt_ring_attention_trains). Real TPU linearizes
        collective scheduling, so the combo is exercised here, in the
        hardware lane only. Needs a pod slice: 8+ chips for the dp=2 x sp=4
        mesh (a one- or four-chip host can't hold it — then the test skips,
        documenting the coverage hole rather than hiding it)."""
        if len(jax.devices()) < 8:
            pytest.skip("dp=2 x sp=4 ring mesh needs 8+ real chips")
        self._train_dp_ring(stage=2, name="ring-gpt-s2")


class TestZero3SPMDEfficiency:
    def test_zero3_tp_sp_no_replicate_then_partition(self):
        """The zero3 x sp x tp train step must compile without the SPMD
        partitioner's "replicate the tensor and then partition it" fallback.

        Round-2 regression: the wte/wpe feature dims are ZeRO-3-sharded over
        the 4-way zero domain, and XLA could not transition the embedding
        gather's output from feature-sharded to batch/seq-sharded without a
        full rematerialization on every device — on a pod that is a silent
        full all-gather inside the backward, the exact cliff ZeRO-3 exists to
        avoid (reference `zero/stage3.py:72`). Fixed by constraining the
        tables to their gathered (TP-only) layout at the lookup
        (`models/gpt.py::_embed`). The warning is a compiler diagnostic, so
        this asserts on a fresh subprocess's stderr (compilation caching
        inside this process would mask it)."""
        import subprocess
        import sys

        script = r"""
import numpy as np, jax
jax.config.update("jax_platforms", "cpu")
import deepspeed_tpu
from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_model
cfg = GPTConfig(n_layer=2, n_head=4, d_model=64, d_ff=256, max_seq_len=64,
                vocab_size=512, dtype=jax.numpy.bfloat16, remat=True)
model = make_gpt_model(cfg=cfg, name="spmd-check", abstract=True)
engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
    "train_micro_batch_size_per_gpu": 1, "gradient_accumulation_steps": 2,
    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
    "bf16": {"enabled": True}, "gradient_clipping": 1.0,
    "zero_optimization": {"stage": 3, "stage3_param_persistence_threshold": 0},
    "mesh": {"data": 2, "sequence": 2, "tensor": 2}, "steps_per_print": 1000})
batch = {"tokens": np.random.default_rng(0).integers(
    0, cfg.vocab_size, (engine.train_batch_size(), 32)).astype(np.int32)}
loss = float(engine.train_batch(batch))
assert np.isfinite(loss)
print("STEP_OK", loss)
"""
        import os
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=repo,
                              capture_output=True, text=True, timeout=600)
        out = proc.stdout + proc.stderr
        assert proc.returncode == 0, out[-3000:]
        assert "STEP_OK" in out, out[-3000:]
        assert "SPMD will replicate" not in out, (
            "replicate-then-partition fallback is back:\n" +
            "\n".join(l for l in out.splitlines() if "SPMD" in l)[:3000])
