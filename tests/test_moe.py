"""Mixture-of-experts suite: gating + capacity math, facade-routed expert
dispatch over the `expert` mesh axis (parallel/moe.py through
comm/collectives.py's instrumented all_to_all), the Pallas token-sort kernel
and the dropless path, MoE-GPT training telemetry, paged MoE serving, expert
streaming / weight quantization, and memscope expert-placement pricing.

Everything rides the `moe` marker (tier-1; run alone with `pytest -m moe`).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import collectives as coll
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.engine import init_inference
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.moe_gpt import (MoEGPTConfig, init_moe_gpt_params,
                                          make_moe_gpt_decode_model,
                                          make_moe_gpt_model,
                                          moe_expert_store)
from deepspeed_tpu.ops.pallas.token_sort import token_sort, token_sort_oracle
from deepspeed_tpu.parallel.moe import (MoELayer, _capacity,
                                        can_use_expert_shard_map,
                                        dropless_moe, gating_drop_stats,
                                        top1_gating, top2_gating)
from tests.paged_cases import assert_one_compile_each

pytestmark = pytest.mark.moe


def _mk_mesh(**axes):
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    return mesh_mod.init_mesh(MeshConfig(**{**dict(data=1, tensor=1,
                                                   sequence=1, expert=1,
                                                   pipe=1), **axes}))


# ----------------------------------------------------------------------
# gating + capacity math
# ----------------------------------------------------------------------


def test_capacity_math():
    assert _capacity(64, 4, 1.0, 4) == 16
    assert _capacity(64, 4, 1.25, 4) == 20
    assert _capacity(8, 8, 1.0, 4) == 4          # min_capacity floor
    # the dispatch tensor carries exactly that capacity dim
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(32, 4)),
                         jnp.float32)
    _, dispatch, combine, _ = top1_gating(logits, capacity_factor=2.0)
    assert dispatch.shape == (32, 4, 16)
    assert combine.shape == (32, 4, 16)


def test_top1_overflow_accounting_exact():
    # all 16 tokens route to expert 0; C = max(16/4 * 1.0, 4) = 4 kept
    logits = jnp.tile(jnp.asarray([[9.0, 0.0, 0.0, 0.0]], jnp.float32),
                      (16, 1))
    _, dispatch, combine, counts = top1_gating(logits, 1.0, 4)
    stats = {k: float(v)
             for k, v in gating_drop_stats(dispatch, counts).items()}
    assert stats == {"routed": 16.0, "kept": 4.0, "overflow_tokens": 12.0,
                     "dropped_frac": 0.75}
    # overflowed tokens contribute zero combine weight (masked, not NaN)
    assert int(jnp.sum(combine > 0)) == 4


def test_aux_loss_unit_floor_and_penalizes_collapse():
    # balanced me with any ce keeps l_aux at its floor of 1; routing
    # collapse (all gate mass on one expert) pushes it toward E
    l0 = float(top1_gating(jnp.zeros((64, 8), jnp.float32), 4.0)[0])
    assert abs(l0 - 1.0) < 1e-5
    hot = jnp.full((64, 8), -20.0).at[:, 0].set(20.0)
    assert float(top1_gating(hot, 4.0)[0]) > 5.0


def test_top2_renorm_after_drop_and_explicit_rng():
    rng0 = np.random.default_rng(2)
    logits = jnp.asarray(rng0.normal(size=(64, 4)), jnp.float32)
    # generous capacity: nothing drops, per-token combine mass is exactly 1
    _, _, combine, _ = top2_gating(logits, capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(jnp.sum(combine, axis=(1, 2))),
                               1.0, rtol=1e-5)

    # force the SECOND expert to overflow while the first survives: tokens
    # 0..3 pick (e0, e1); tokens 4..15 flood e1 so its queue is full by the
    # time the second-choice assignments are placed. The survivor must
    # absorb the dropped expert's share (renorm AFTER the drop), not leak
    # it to nobody.
    hot = jnp.concatenate([
        jnp.tile(jnp.asarray([[5.0, 3.0, -9.0, -9.0]], jnp.float32), (4, 1)),
        jnp.tile(jnp.asarray([[-9.0, 5.0, 3.0, -9.0]], jnp.float32), (12, 1)),
    ])
    _, _, c2, _ = top2_gating(hot, capacity_factor=0.5)
    np.testing.assert_allclose(np.asarray(jnp.sum(c2[:4], axis=(1, 2))),
                               1.0, rtol=1e-5)
    assert float(jnp.sum(c2[:4, 1:])) == 0.0      # all mass on expert 0

    # the tie-break jitter takes an explicit key: same key, same routing
    key = jax.random.PRNGKey(3)
    a = top2_gating(logits, 8.0, rng=key)
    b = top2_gating(logits, 8.0, rng=key)
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


# ----------------------------------------------------------------------
# Pallas token sort + dropless routing
# ----------------------------------------------------------------------


def test_token_sort_kernel_matches_oracle():
    rng = np.random.default_rng(3)
    for n, e in ((64, 4), (256, 8), (128, 16), (96, 5)):
        idx = jnp.asarray(rng.integers(0, e, (n,)), jnp.int32)
        pos, counts = token_sort(idx, e)
        opos, ocounts = token_sort_oracle(idx, e)
        np.testing.assert_array_equal(np.asarray(pos), np.asarray(opos))
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(ocounts))
        # stable counting sort: (expert, pos) pairs are unique slots
        pairs = set(zip(np.asarray(idx).tolist(), np.asarray(pos).tolist()))
        assert len(pairs) == n


def test_dropless_matches_manual_argmax_oracle():
    rng = np.random.default_rng(4)
    N, D, F, E = 64, 16, 32, 4
    flat = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    gate_w = jnp.asarray(rng.normal(size=(D, E)), jnp.float32)
    wi = jnp.asarray(rng.normal(0, 0.1, (E, D, F)), jnp.float32)
    wo = jnp.asarray(rng.normal(0, 0.1, (E, F, D)), jnp.float32)

    def ffn(xe):
        h = jax.nn.gelu(jnp.einsum("end,edf->enf", xe, wi))
        return jnp.einsum("enf,efd->end", h, wo)

    out, l_aux, counts = dropless_moe(flat, gate_w, ffn, E)
    assert int(jnp.sum(counts)) == N              # dropless: nothing dropped

    gates = jax.nn.softmax(flat @ gate_w, axis=-1)
    eidx = jnp.argmax(gates, axis=-1)
    h = jax.nn.gelu(jnp.einsum("nd,ndf->nf", flat, wi[eidx]))
    ref = jnp.einsum("nf,nfd->nd", h, wo[eidx]) * jnp.max(gates, -1)[:, None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert np.isfinite(float(l_aux))


# ----------------------------------------------------------------------
# facade-routed expert dispatch (shard_map over the expert axis)
# ----------------------------------------------------------------------


def test_can_use_expert_shard_map_gates():
    mesh = _mk_mesh(expert=4, data=2)
    assert can_use_expert_shard_map(mesh, 4, 64)
    assert not can_use_expert_shard_map(mesh, 6, 64)   # E % ep != 0
    assert not can_use_expert_shard_map(mesh, 4, 60)   # N % token shards
    assert not can_use_expert_shard_map(None, 4, 64)
    mesh_t = _mk_mesh(expert=2, tensor=2, data=2)
    assert not can_use_expert_shard_map(mesh_t, 4, 64)  # tensor -> einsum
    mesh_e1 = _mk_mesh(data=8)
    assert not can_use_expert_shard_map(mesh_e1, 4, 64)  # no expert axis


def test_facade_dispatch_matches_einsum_oracle_and_meters_bytes():
    mesh = _mk_mesh(expert=4, data=2)
    layer = MoELayer(num_experts=4, capacity_factor=8.0)   # drop-free
    params = layer.init_params(d_model=16, d_ff=32, seed=0)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(8, 16, 16)), jnp.float32)   # N=128

    coll.stats.reset()
    y_f, l_f, c_f = jax.jit(lambda p, x: layer(p, x, mesh=mesh))(params, x)
    snap = coll.stats.snapshot()
    assert snap.get("all_to_all", {}).get("calls", 0) == 2   # dispatch pair
    assert snap["all_to_all"]["bytes"] > 0

    mesh_mod.clear_mesh()
    with mesh_mod.constraints_disabled():
        y_e, l_e, c_e = jax.jit(lambda p, x: layer(p, x, mesh=None))(params, x)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_e),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(c_f), np.asarray(c_e))
    # l_aux is the shard-mean of per-shard me.ce — close to, but not
    # bit-equal with, the global statistic
    assert abs(float(l_f) - float(l_e)) / float(l_e) < 0.25


def test_int8_dispatch_wire_roundtrip_and_smaller_wire():
    mesh = _mk_mesh(expert=4, data=2)
    layer = MoELayer(num_experts=4, capacity_factor=8.0)
    layer8 = dataclasses.replace(layer, dispatch_wire="int8")
    params = layer.init_params(d_model=16, d_ff=32, seed=1)
    x = jnp.asarray(np.random.default_rng(9).normal(size=(8, 16, 16)),
                    jnp.float32)

    coll.stats.reset()
    y_none, *_ = jax.jit(lambda p, x: layer(p, x, mesh=mesh))(params, x)
    b_none = coll.stats.snapshot()["all_to_all"]["bytes"]
    coll.stats.reset()
    y_int8, *_ = jax.jit(lambda p, x: layer8(p, x, mesh=mesh))(params, x)
    b_int8 = coll.stats.snapshot()["all_to_all"]["bytes"]

    # int8 payload + f32 group scales must beat half the f32 wire
    assert 0 < b_int8 < b_none / 2, (b_int8, b_none)
    err = (np.linalg.norm(np.asarray(y_int8) - np.asarray(y_none))
           / np.linalg.norm(np.asarray(y_none)))
    assert err < 0.05, err


# ----------------------------------------------------------------------
# MoE-GPT through the training engine (telemetry + facade accounting)
# ----------------------------------------------------------------------


TRAIN_CFG = MoEGPTConfig(n_layer=2, n_head=2, d_model=32, d_ff=64,
                         max_seq_len=64, vocab_size=128, dtype=jnp.float32,
                         remat=False, num_experts=4, moe_freq=2,
                         capacity_factor=1.25)


def test_moe_gpt_engine_trains_with_facade_telemetry(tmp_path):
    _mk_mesh(expert=4, data=2)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_moe_gpt_model(TRAIN_CFG, name="moe-tel"),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1},
                "steps_per_print": 10**9,
                "telemetry": {"enabled": True, "output_path": str(tmp_path),
                              "prometheus": False, "jsonl": False,
                              "monitor_bridge": False}})
    rng = np.random.default_rng(0)
    toks = rng.integers(0, TRAIN_CFG.vocab_size,
                        (engine.train_batch_size(), 33)).astype(np.int32)
    coll.stats.reset()
    l0 = float(engine.train_batch({"tokens": toks}))
    assert np.isfinite(l0)
    # the loss was traced under the expert mesh: the facade's trace-time
    # accounting must have seen the dispatch all_to_all pair
    assert coll.stats.snapshot().get("all_to_all", {}).get("bytes", 0) > 0

    m = engine._last_metrics
    for k in ("moe/aux_loss", "moe/overflow_tokens", "moe/dropped_frac"):
        assert k in m and np.isfinite(float(m[k])), k
    assert float(m["moe/aux_loss"]) > 0
    snap = engine.telemetry.registry.snapshot()
    assert snap["moe/aux_loss"]["value"] == pytest.approx(
        float(m["moe/aux_loss"]))

    l1 = float(engine.train_batch({"tokens": toks}))
    assert np.isfinite(l1) and l1 < l0       # same batch: one step improves


# ----------------------------------------------------------------------
# paged MoE serving + expert streaming + weight quant
# ----------------------------------------------------------------------


SERVE_CFG = MoEGPTConfig(n_layer=2, n_head=4, d_model=64, d_ff=128,
                         max_seq_len=256, vocab_size=256, dtype=jnp.float32,
                         remat=False, num_experts=4, moe_freq=2,
                         eval_capacity_factor=2.0)


def _mk_moe_serving_engine(**cfg_over):
    _mk_mesh(data=1)
    spec = make_moe_gpt_decode_model(cfg=SERVE_CFG, name="moe-tiny")
    return init_inference(model=spec, config={
        "dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
        "kv_block_size": 16, "max_out_tokens": 64, **cfg_over})


def test_moe_serving_matches_generate_and_compiles_once():
    engine = _mk_moe_serving_engine()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, SERVE_CFG.vocab_size, (L,)).astype(np.int32)
               for L in (5, 11, 3, 17, 8)]
    serving = engine.serving(max_slots=3, max_context=64, prefill_chunk=16)
    reqs = [Request(uid=i, tokens=p, max_new_tokens=3 + i % 4,
                    stop_on_eos=False) for i, p in enumerate(prompts)]
    res = serving.run(reqs)
    for i, p in enumerate(prompts):
        ref = engine.generate(p[None, :], max_new_tokens=3 + i % 4,
                              stop_on_eos=False)
        np.testing.assert_array_equal(res[i].tokens, ref[0])
    assert_one_compile_each(serving)


def test_expert_store_streams_expert_weights():
    from deepspeed_tpu.runtime.param_swap import LayerStreamer
    params = init_moe_gpt_params(SERVE_CFG, seed=0)
    layer_id = SERVE_CFG.moe_layer_ids()[0]
    store, expert_tree = moe_expert_store(params, layer_id)
    assert store.num_layers == SERVE_CFG.num_experts

    streamer = LayerStreamer(store, lookahead=1, cyclic=True)
    src = jax.tree_util.tree_leaves(expert_tree)
    for _pass in range(2):
        for e in range(store.num_layers):
            tree = streamer.layer(e)
            got = jax.tree_util.tree_leaves(tree)
            for g, ref in zip(got, src):
                np.testing.assert_array_equal(np.asarray(g),
                                              np.asarray(ref[e]))
    # the streamed working set stays at the double-buffer window, and the
    # cyclic wrap keeps the second pass warm
    assert streamer.peak_live_layers <= 2
    assert streamer.hits > 0


def test_weight_quant_int8_covers_expert_tensors():
    from deepspeed_tpu.inference.quantization import QuantizedTensor
    engine = _mk_moe_serving_engine()
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, SERVE_CFG.vocab_size, (1, 12)).astype(np.int32)
    dense = engine.generate(prompt, max_new_tokens=8, stop_on_eos=False)

    stats = engine.enable_weight_quant(bits=8, group_size=32)
    assert stats["quantized"] > 0 and stats["ratio"] > 2.0
    # the stacked expert weights are exactly the big-matrix leaves WOQ
    # exists for — they must be quantized, while the tiny gate stays dense
    moe_leaves = jax.tree_util.tree_leaves(
        engine.params["moe"],
        is_leaf=lambda x: isinstance(x, QuantizedTensor))
    assert any(isinstance(l, QuantizedTensor) for l in moe_leaves)

    q = engine.generate(prompt, max_new_tokens=8, stop_on_eos=False)
    assert q.shape == dense.shape


# ----------------------------------------------------------------------
# memscope expert-placement pricing
# ----------------------------------------------------------------------


def test_memscope_plan_prices_expert_placement_vs_xla(tmp_path):
    from deepspeed_tpu.telemetry.memscope import (TRAIN_PLAN_TOLERANCE,
                                                  _expert_param_count,
                                                  plan_training_from_engine)
    _mk_mesh(expert=4, data=2)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_moe_gpt_model(TRAIN_CFG, name="moe-plan"),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1},
                "steps_per_print": 10**9,
                "telemetry": {"enabled": True, "output_path": str(tmp_path),
                              "prometheus": False, "jsonl": False,
                              "monitor_bridge": False, "memscope": True,
                              "memscope_capacity_bytes": 256 * 2**20,
                              "measure_program_flops": False}})
    rng = np.random.default_rng(1)
    toks = rng.integers(0, TRAIN_CFG.vocab_size,
                        (engine.train_batch_size(), 33)).astype(np.int32)
    engine.train_batch({"tokens": toks})

    plan = plan_training_from_engine(engine)
    n_exp = _expert_param_count(engine.state.params, engine.param_shardings)
    assert n_exp > 0
    # expert-sharded leaves are priced /ep_size=4 (f32, params unsharded
    # under zero-1), separately from the replicated dense slice
    assert plan.device_bytes["moe_expert_params"] == n_exp * 4 // 4

    # planner vs XLA: the compiled step's per-device argument bytes are the
    # resident states (params incl. the expert slice + optim; grads are
    # step temporaries)
    ma = engine.memscope.program_memory()["train_step"]
    pred = plan.total_device_bytes - plan.device_bytes["grads"]
    rel = abs(ma["argument_bytes"] - pred) / pred
    assert rel < TRAIN_PLAN_TOLERANCE, (ma["argument_bytes"], pred, rel)
