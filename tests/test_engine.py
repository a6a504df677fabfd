"""End-to-end engine tests — ZeRO stages × precisions on the 8-device CPU mesh.

Mirrors the reference's `tests/unit/runtime/zero/test_zero.py` +
`runtime/half_precision` structure: tiny model, real collectives, loss must drop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from tests.simple_model import make_simple_model, random_batches, simple_config

HIDDEN = 16


def _train(cfg, n_steps=8, hidden=HIDDEN, gas=1):
    model = make_simple_model(hidden_dim=hidden)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    batch_size = engine.train_batch_size()
    # overfit one fixed batch: loss must drop monotonically-ish
    batch = random_batches(1, batch_size, hidden_dim=hidden)[0]
    losses = [float(engine.train_batch(batch)) for _ in range(n_steps)]
    return engine, losses


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stages_train(stage):
    cfg = simple_config(stage=stage, mesh={"data": 8})
    engine, losses = _train(cfg)
    assert losses[-1] < losses[0], f"loss did not drop: {losses}"
    assert engine.global_steps == 8


@pytest.mark.parametrize("dtype", ["bf16", "fp16"])
@pytest.mark.parametrize("stage", [0, 2, 3])
def test_mixed_precision(stage, dtype):
    cfg = simple_config(stage=stage, dtype=dtype, mesh={"data": 8})
    engine, losses = _train(cfg)
    assert losses[-1] < losses[0], f"loss did not drop: {losses}"
    if dtype == "bf16":
        assert engine.state.params["layer_0"]["w"].dtype == jnp.bfloat16
        assert engine.state.master["layer_0"]["w"].dtype == jnp.float32


def test_gradient_accumulation_matches_large_batch():
    """gas=4 × micro=2 must match gas=1 × micro=8 numerically (fp32)."""
    cfg_a = simple_config(stage=0, gas=4, micro=2, mesh={"data": 1})
    cfg_b = simple_config(stage=0, gas=1, micro=8, mesh={"data": 1})
    batches = random_batches(4, 8)
    model_a = make_simple_model()
    model_b = make_simple_model()
    ea, _, _, _ = deepspeed_tpu.initialize(model=model_a, config=cfg_a)
    from deepspeed_tpu.comm import mesh as mesh_mod
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    eb, _, _, _ = deepspeed_tpu.initialize(model=model_b, config=cfg_b)
    for b in batches:
        la = ea.train_batch(b)
        lb = eb.train_batch(b)
        np.testing.assert_allclose(float(la), float(lb), rtol=1e-5)
    wa = jax.device_get(ea.state.params["layer_0"]["w"])
    wb = jax.device_get(eb.state.params["layer_0"]["w"])
    np.testing.assert_allclose(wa, wb, rtol=1e-5, atol=1e-6)


def test_zero3_params_are_sharded():
    cfg = simple_config(stage=3, mesh={"data": 8})
    cfg["zero_optimization"]["stage3_param_persistence_threshold"] = 0
    model = make_simple_model(hidden_dim=32)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    w = engine.state.params["layer_0"]["w"]
    shard_shape = w.sharding.shard_shape(w.shape)
    assert np.prod(shard_shape) < np.prod(w.shape), "zero-3 params should be sharded"


def test_zero1_master_sharded_params_replicated():
    cfg = simple_config(stage=1, dtype="bf16", mesh={"data": 8})
    model = make_simple_model(hidden_dim=32)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    w = engine.state.params["layer_0"]["w"]
    m = engine.state.master["layer_0"]["w"]
    assert np.prod(w.sharding.shard_shape(w.shape)) == np.prod(w.shape)
    assert np.prod(m.sharding.shard_shape(m.shape)) < np.prod(m.shape)


def test_forward_backward_step_parity():
    """The forward/backward/step triplet must match train_batch numerically."""
    batches = random_batches(3, 8)
    cfg = simple_config(stage=0, micro=8, mesh={"data": 1})
    ea, _, _, _ = deepspeed_tpu.initialize(model=make_simple_model(), config=cfg)
    from deepspeed_tpu.comm import mesh as mesh_mod
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    eb, _, _, _ = deepspeed_tpu.initialize(model=make_simple_model(), config=cfg)
    for b in batches:
        la = ea.train_batch(b)
        loss = eb.forward(b)
        eb.backward(loss)
        eb.step()
        np.testing.assert_allclose(float(la), float(loss), rtol=1e-5)
    wa = jax.device_get(ea.state.params["layer_0"]["w"])
    wb = jax.device_get(eb.state.params["layer_0"]["w"])
    np.testing.assert_allclose(wa, wb, rtol=1e-5, atol=1e-6)


def test_lr_schedule():
    cfg = simple_config(stage=0, mesh={"data": 8})
    cfg["scheduler"] = {
        "type": "WarmupLR",
        "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 0.01, "warmup_num_steps": 10},
    }
    engine, losses = _train(cfg, n_steps=4)
    lr = engine.get_lr()[0]
    assert 0.0 < lr < 0.01


def test_fp16_overflow_skips_step():
    """Inject an inf gradient: step must be skipped and scale halved."""
    cfg = simple_config(stage=0, dtype="fp16", mesh={"data": 8})
    cfg["fp16"]["hysteresis"] = 1  # cut scale on the first overflow
    model = make_simple_model()

    def exploding_loss(params, batch, rng=None):
        return jnp.sum(params["layer_0"]["w"]) * jnp.inf

    from deepspeed_tpu.runtime.engine import ModelSpec
    bad = ModelSpec(loss_fn=exploding_loss, params=model.params)
    engine, _, _, _ = deepspeed_tpu.initialize(model=bad, config=cfg)
    scale0 = engine.cur_scale
    w0 = jax.device_get(engine.state.params["layer_0"]["w"])
    engine.train_batch(random_batches(1, engine.train_batch_size())[0])
    assert engine.cur_scale == scale0 / 2
    assert engine.skipped_steps == 1
    assert int(engine.state.step) == 0
    np.testing.assert_array_equal(jax.device_get(engine.state.params["layer_0"]["w"]), w0)


def test_optimizer_type_aliases():
    """Reference config type strings (FusedAdam, DeepSpeedCPUAdam, ...) resolve
    (reference: ops/adam/fused_adam.py:18, cpu_adam.py:13)."""
    from deepspeed_tpu.config.core import OptimizerConfig
    from deepspeed_tpu.ops.optim import build_optimizer
    for t in ("FusedAdam", "FusedLamb", "FusedLion", "DeepSpeedCPUAdam",
              "DeepSpeedCPULion", "DeepSpeedCPUAdagrad", "OneBitAdam", "AdamW"):
        opt = build_optimizer(OptimizerConfig(type=t, params={"lr": 1e-3}))
        assert opt is not None, t


class TestCommParitySurface:
    """Reference deepspeed.comm facade ops (comm/comm.py:13-21) under SPMD."""

    def _mesh(self, **axes):
        from deepspeed_tpu.comm import mesh as mesh_mod
        from deepspeed_tpu.config.core import MeshConfig
        mesh_mod._CURRENT_MESH = None
        mesh_mod._CURRENT_SPEC = None
        return mesh_mod.init_mesh(MeshConfig(**{**dict(data=8, zero=1, tensor=1,
                                                       sequence=1, expert=1,
                                                       pipe=1), **axes}))

    def test_reduce_gather_scatter(self):
        import deepspeed_tpu.comm as comm
        self._mesh(data=8)
        # leading dim = per-rank shards (the collectives' contract)
        x = jnp.ones((8,), jnp.float32)
        np.testing.assert_allclose(np.asarray(comm.reduce(x, axis="data")),
                                   np.full(8, 8.0))
        np.testing.assert_allclose(np.asarray(comm.gather(x, axis="data")),
                                   np.ones(8))
        sc = comm.scatter(jnp.arange(16, dtype=jnp.float32), axis="data")
        assert "data" in str(sc.sharding.spec)

    def test_single_tensor_variants(self):
        import deepspeed_tpu.comm as comm
        self._mesh(data=8)
        x = jnp.arange(64, dtype=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(comm.all_gather_into_tensor(input_tensor=x, axis="data")),
            np.asarray(comm.all_gather(x, axis="data")))
        np.testing.assert_allclose(
            np.asarray(comm.all_to_all_single(input=x, axis="data")),
            np.asarray(comm.all_to_all(x, axis="data")))
        outs = comm.all_reduce_coalesced([x, x * 2], axis="data")
        assert len(outs) == 2

    def test_inference_all_reduce_tensor_axis(self):
        import deepspeed_tpu.comm as comm
        self._mesh(data=2, tensor=4)
        x = jnp.ones((8,), jnp.float32)
        out = comm.inference_all_reduce(x)
        assert out.shape == x.shape

    def test_p2p_eager_raises_with_guidance(self):
        import deepspeed_tpu.comm as comm
        for fn in (comm.send, comm.recv, comm.isend, comm.irecv):
            with pytest.raises(NotImplementedError, match="p2p_shift"):
                fn(jnp.zeros(4), 0)

    def test_p2p_shift_in_shard_map(self):
        import deepspeed_tpu.comm as comm
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        mesh = self._mesh(data=8)
        x = jnp.arange(8, dtype=jnp.float32)

        def body(x):
            return comm.p2p_shift(x, "data", shift=1)

        out = shard_map(body, mesh=mesh, in_specs=(P(("data",)),),
                        out_specs=P(("data",)), check_vma=False)(x)
        np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8), 1))

    def test_new_group_warns_and_defaults(self):
        import deepspeed_tpu.comm as comm
        from deepspeed_tpu.comm import mesh as mesh_mod
        self._mesh(data=8)
        # new_group falls back to the data domain; the world group spans ALL
        # mesh axes (reference all-ranks semantics, even with tp/pp axes).
        assert comm.new_group([0, 1]) == tuple(mesh_mod.ZERO_AXES)
        assert comm.get_world_group() == tuple(mesh_mod.ALL_AXES)
        # identity fast-path holds for the data domain only while it spans
        # the whole mesh
        assert comm.get_global_rank(comm.new_group([0, 1]), 3) == 3
        assert comm.get_global_rank(comm.get_world_group(), 5) == 5

    def test_scatter_list_and_group_semantics(self):
        import deepspeed_tpu.comm as comm
        self._mesh(data=8)
        chunks = [jnp.full((2,), float(i)) for i in range(8)]
        out = comm.scatter(None, scatter_list=chunks, axis="data")
        np.testing.assert_allclose(np.asarray(out),
                                   np.repeat(np.arange(8, dtype=np.float32), 2))

    def test_all_to_all_single_uneven(self):
        """pad → exchange → slice path: result equals the numpy block
        transpose at uneven chunk granularity."""
        import deepspeed_tpu.comm as comm
        self._mesh(data=4)
        W, splits = 4, [1, 3, 0, 2]
        S = sum(splits)
        x = np.arange(W * S, dtype=np.float32)
        out = np.asarray(comm.all_to_all_single(
            input=jnp.asarray(x), axis="data", input_split_sizes=splits))
        # expected: receiver block r = concat over senders s of sender s's
        # chunk r (splits[r] long)
        offs = np.cumsum([0] + splits)
        blocks = x.reshape(W, S)
        expect = np.concatenate(
            [blocks[:, offs[r]:offs[r + 1]].reshape(-1) for r in range(W)])
        np.testing.assert_allclose(out, expect)
        assert out.shape == x.shape
        # asymmetric split lists are rejected (no global-view formulation)
        with pytest.raises(ValueError, match="symmetric"):
            comm.all_to_all_single(input=jnp.asarray(x), axis="data",
                                   input_split_sizes=splits,
                                   output_split_sizes=[2, 2, 1, 1])

    def test_get_global_rank_sub_axis(self):
        """Mesh-coordinate rank math for sub-axis groups (reference
        utils/groups.py:473 role): global rank = lexicographic mesh position."""
        import deepspeed_tpu.comm as comm
        mesh = self._mesh(data=2, tensor=4)
        names = list(mesh.axis_names)
        # tensor group, first instance (data coord 0): ranks 0..3
        t_idx, d_idx = names.index("tensor"), names.index("data")
        for gr in range(4):
            want = np.ravel_multi_index(
                [gr if n == "tensor" else 0 for n in names],
                [mesh.shape[n] for n in names])
            assert comm.get_global_rank("tensor", gr) == want
        # second data row via coords
        got = comm.get_global_rank("tensor", 1, coords={"data": 1})
        want = np.ravel_multi_index(
            [1 if n in ("tensor", "data") else 0 for n in names],
            [mesh.shape[n] for n in names])
        assert got == want
        # world group stays identity
        assert comm.get_global_rank(comm.get_world_group(), 6) == 6

    def test_inference_all_reduce_honors_group(self):
        import deepspeed_tpu.comm as comm
        self._mesh(data=2, tensor=4)
        x = jnp.ones((8,), jnp.float32)
        # group="data" (2-way) must NOT silently become the 4-way tensor axis
        out = comm.inference_all_reduce(x, group="data")
        np.testing.assert_allclose(np.asarray(out), np.full(8, 2.0))
        out_t = comm.inference_all_reduce(x)
        np.testing.assert_allclose(np.asarray(out_t), np.full(8, 4.0))

    def test_coalesced_single_dispatch_and_global_rank(self):
        import deepspeed_tpu.comm as comm
        self._mesh(data=8)
        xs = [jnp.ones((8,), jnp.float32), jnp.full((16,), 2.0)]
        outs = comm.all_reduce_coalesced(xs, axis="data")
        np.testing.assert_allclose(np.asarray(outs[0]), np.full(8, 8.0))
        gath = comm.all_gather_coalesced(xs, axis="data")
        assert gath[0].shape == (8,) and gath[1].shape == (16,)
        assert comm.get_global_rank(None, 3) == 3
        # pure-data mesh: "tensor" has size 1 here -> sub-axis math still
        # resolves (group rank 0 of a singleton axis = instance coords)
        assert comm.get_global_rank("tensor", 0) == 0

    def test_destroy_process_group(self):
        import deepspeed_tpu.comm as comm
        from deepspeed_tpu.comm import mesh as mesh_mod
        self._mesh(data=8)
        assert comm.is_available()
        comm.destroy_process_group()
        assert not mesh_mod.has_mesh()
        # fresh bring-up works after teardown
        comm.init_distributed()
        assert mesh_mod.has_mesh()


def test_zero_init_construction_time_partitioning():
    """zero.Init path (`zero/partition_parameters.py:723`): initialize() with an
    init_fn materializes every leaf directly into its stage-3 shard — the full
    model never exists replicated — and training matches the concrete-params
    engine built from the same initializer."""
    H = 32

    def init_fn(rng):
        ks = jax.random.split(rng, 2)
        return {f"layer_{i}": {"w": jax.random.normal(ks[i], (H, H)) * 0.1,
                               "b": jnp.zeros((H,))} for i in range(2)}

    def loss_fn(params, batch, rng=None):
        h = batch["x"]
        for i in range(2):
            p = params[f"layer_{i}"]
            h = jnp.tanh(h @ p["w"] + p["b"])
        return jnp.mean((h - batch["y"])**2)

    cfg = simple_config(stage=3, dtype="bf16", mesh={"data": 8})
    cfg["zero_optimization"]["stage3_param_persistence_threshold"] = 0
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=loss_fn, model_parameters=init_fn, config=cfg)
    w = engine.state.params["layer_0"]["w"]
    assert w.dtype == jnp.bfloat16
    assert np.prod(w.sharding.shard_shape(w.shape)) < np.prod(w.shape), \
        "zero.Init params must be born sharded"

    batch = random_batches(1, engine.train_batch_size(), hidden_dim=H)[0]
    losses = [float(engine.train_batch(batch)) for _ in range(6)]
    assert losses[-1] < losses[0], losses

    # parity: concrete-params engine from the same initializer + seed
    from deepspeed_tpu.comm import mesh as mesh_mod
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    from deepspeed_tpu.runtime.engine import ModelSpec
    params = init_fn(jax.random.PRNGKey(engine.config.seed))
    eb, _, _, _ = deepspeed_tpu.initialize(
        model=ModelSpec(loss_fn=loss_fn, params=params), config=cfg)
    lb = [float(eb.train_batch(batch)) for _ in range(6)]
    np.testing.assert_allclose(losses, lb, rtol=2e-2)


def test_gpt_abstract_init_trains():
    """make_gpt_model(abstract=True): the flagship family through the
    zero.Init path — params born sharded, loss drops."""
    from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_model
    cfg_m = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=32,
                      vocab_size=128, dtype=jnp.float32, remat=False)
    spec = make_gpt_model(cfg=cfg_m, abstract=True)
    assert spec.params is None and spec.init_fn is not None
    cfg = simple_config(stage=3, mesh={"data": 8}, micro=4)
    cfg["zero_optimization"]["stage3_param_persistence_threshold"] = 0
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config=cfg)
    w = engine.state.params["blocks"]["attn_qkv_w"]
    assert np.prod(w.sharding.shard_shape(w.shape)) < np.prod(w.shape)
    toks = np.random.default_rng(0).integers(0, 128, (engine.train_batch_size(), 16))
    batch = {"tokens": toks.astype(np.int32)}
    losses = [float(engine.train_batch(batch)) for _ in range(4)]
    assert losses[-1] < losses[0], losses


def test_zero_namespace_parity():
    """deepspeed.zero surface: Init context, GatheredParameters read/modify
    round-trip with re-partitioning, TiledLinear re-export, external-param
    no-ops (reference deepspeed/runtime/zero/__init__.py)."""
    import deepspeed_tpu
    from deepspeed_tpu import zero as z
    assert z.TiledLinear is not None
    assert z.register_external_parameter(None, None) is None
    assert z.unregister_external_parameter(None, None) is None

    # Init context + abstract/materialize primitives
    with z.Init(config_dict_or_path={"zero_optimization": {"stage": 3}}) as ctx:
        shapes = ctx.abstract(lambda: {"w": jnp.ones((8, 8))})
    assert shapes["w"].shape == (8, 8)

    # GatheredParameters: host copies in, modified leaves re-partitioned out
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.config.core import MeshConfig
    mesh = mesh_mod.init_mesh(MeshConfig(data=8))
    sharding = NamedSharding(mesh, P(("data", "zero")))
    params = {"w": jax.device_put(jnp.arange(16.0), sharding),
              "b": jax.device_put(jnp.zeros(4), NamedSharding(mesh, P()))}
    # modifier_rank=None: read-only, edits discarded (reference
    # partition_parameters.py:2258 semantics)
    with deepspeed_tpu.zero.GatheredParameters(params) as gathered:
        np.testing.assert_array_equal(np.asarray(gathered["w"]),
                                      np.arange(16.0))
        gathered["w"] = np.arange(16.0) * 3
    np.testing.assert_array_equal(np.asarray(params["w"]), np.arange(16.0))
    # modifier_rank set: replacement AND in-place mutation both persist,
    # re-partitioned to the original sharding
    with deepspeed_tpu.zero.GatheredParameters(params, modifier_rank=0) as gathered:
        gathered["w"] = np.arange(16.0) * 2      # replacement
        gathered["b"][:] = 1.0                   # in-place mutation
    np.testing.assert_array_equal(np.asarray(params["w"]), np.arange(16.0) * 2)
    assert params["w"].sharding == sharding      # re-partitioned, not replicated
    np.testing.assert_array_equal(np.asarray(params["b"]), np.ones(4))


def test_grad_accum_dtype_bf16_close_to_fp32():
    """data_types.grad_accum_dtype (reference runtime/config.py:876): bf16
    accumulators walk close to the fp32-accumulator trajectory at small gas
    (the knob exists for HBM-bound configs where fp32 accumulators OOM)."""
    import deepspeed_tpu
    from deepspeed_tpu.comm import mesh as mesh_mod
    from tests.simple_model import make_simple_model, random_batches

    def mk(accum):
        mesh_mod._CURRENT_MESH = None
        mesh_mod._CURRENT_SPEC = None
        cfg = {
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 4,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "mesh": {"data": 1},
            "steps_per_print": 10**9,
        }
        if accum:
            cfg["data_types"] = {"grad_accum_dtype": accum}
        e, *_ = deepspeed_tpu.initialize(model=make_simple_model(), config=cfg)
        return e

    e32, e16 = mk(None), mk("bf16")
    batches = random_batches(4, e32.train_batch_size(), seed=3)
    for b in batches:
        l32 = float(e32.train_batch(b))
        l16 = float(e16.train_batch(b))
        np.testing.assert_allclose(l16, l32, rtol=5e-3, atol=5e-3)

    import pytest as _pytest
    with _pytest.raises(AssertionError, match="grad_accum_dtype"):
        mesh_mod._CURRENT_MESH = None
        mesh_mod._CURRENT_SPEC = None
        bad, *_ = deepspeed_tpu.initialize(model=make_simple_model(), config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "data_types": {"grad_accum_dtype": "int8"},
            "mesh": {"data": 1}, "steps_per_print": 10**9})
        bad.train_batch(random_batches(1, bad.train_batch_size())[0])


def test_engine_accepts_dict_config_directly():
    """Direct Engine/HybridEngine construction is public surface: a raw dict
    (or JSON path) must be accepted like initialize() does — previously only
    a pre-parsed TpuTrainConfig worked."""
    from deepspeed_tpu.runtime.engine import Engine, ModelSpec
    from deepspeed_tpu.comm import mesh as mesh_mod
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    rng = np.random.default_rng(0)
    eng = Engine(
        ModelSpec(loss_fn=lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2),
                  params={"w": jnp.asarray(rng.normal(0, 0.1, (16, 16)),
                                           jnp.float32)}),
        {"train_micro_batch_size_per_gpu": 4,
         "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
    b = {"x": rng.normal(0, 1, (eng.train_batch_size(), 16)).astype(np.float32)}
    losses = [float(eng.train_batch(b)) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
