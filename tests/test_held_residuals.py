"""What a GPT block holds of its forward for its backward: the names mark what
the backward READS, the fit takes as many as the free bytes allow, the engine
says what it chose. All on the CPU: counts and values, no times."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models import gpt, hybrid, olmo_hybrid
from deepspeed_tpu.models.gpt import (ATTN_OUT, MLP_PRODUCT, QKV_PRODUCT,
                                      GPTConfig, gpt_init_fn, gpt_loss,
                                      held_candidates, make_gpt_model)
from deepspeed_tpu.ops.pallas.flash_attention import FLASH_RESIDUALS
from deepspeed_tpu.ops.pallas.gdn import SCAN_OUTPUT
from deepspeed_tpu.platform.accelerator import get_accelerator
from deepspeed_tpu.runtime.activation_checkpointing import (HELD_MARGIN_SHARE,
                                                            fit_held,
                                                            held_budget,
                                                            held_plan)

GIB = 2**30
# one block, every width its own: the QKV product [.., 192], the MLP's
# [.., 256], the model's [.., 64], the head's [.., 512]
NEOX = GPTConfig(n_layer=1, n_head=2, d_model=64, d_ff=256, max_seq_len=128,
                 vocab_size=512, dtype=jnp.float32, use_rotary=True,
                 rotary_pct=0.25, parallel_residual=True, tie_embeddings=False,
                 use_flash_attention=True)
SWIGLU = dataclasses.replace(
    NEOX, parallel_residual=False, use_swiglu=True, use_rmsnorm=True,
    n_kv_head=1, rotary_pct=1.0, d_ff=256, use_flash_attention=False)
B, T = 2, 128


@pytest.fixture(autouse=True)
def _no_mesh():
    mesh_mod.clear_mesh()
    yield
    mesh_mod.clear_mesh()


def _batch(cfg, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (B, T)).astype(np.int32)
    return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}


def _grad_fn(cfg, free_bytes):
    """loss and gradients of `cfg`'s model, traced with `free_bytes` on offer
    (None: no budget at all); the plans the block reported ride along."""
    plans = []

    def run(params, batch):
        fn = jax.value_and_grad(lambda p: gpt_loss(p, batch, None, cfg))
        if free_bytes is None:
            return fn(params)
        with held_budget(free_bytes, report=plans.append):
            return fn(params)

    return run, plans


def _count(jaxpr, pred):
    """Equations of `jaxpr` and of every jaxpr inside it that `pred` takes."""
    n = 0
    for eqn in jaxpr.eqns:
        n += bool(pred(eqn))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, pred)
    return n


def _products(jaxpr, width):
    return _count(jaxpr, lambda e: e.primitive.name == "dot_general"
                  and e.outvars[0].aval.shape == (B, T, width))


def _flash_forwards(jaxpr):
    return _count(jaxpr, lambda e: e.primitive.name == "pallas_call"
                  and "dstpu_flash_fwd" in str(e.params.get("name", "")
                                               or e.params.get("name_and_src_info", "")))


def _trace(cfg, free_bytes):
    run, plans = _grad_fn(cfg, free_bytes)
    params = jax.eval_shape(gpt_init_fn(cfg), jax.random.PRNGKey(0))
    batch = jax.eval_shape(lambda: _batch(cfg))
    return jax.make_jaxpr(run)(params, batch).jaxpr, plans


# ---- (1) what the backward makes again ----------------------------------


@pytest.mark.parametrize("held, qkv, up, flash", [
    # [.., 256] is the up product AND its input's gradient: 2 with no remat
    ("every name", 1, 2, 1),
    ("none", 2, 3, 2),
])
def test_backward_makes_again_only_what_is_not_held(held, qkv, up, flash):
    jaxpr, plans = _trace(NEOX, 10**12 if held == "every name" else 0)
    want = (FLASH_RESIDUALS, MLP_PRODUCT, QKV_PRODUCT) \
        if held == "every name" else ()
    assert plans[-1].names == want
    assert _products(jaxpr, 3 * NEOX.d_model) == qkv
    assert _products(jaxpr, NEOX.d_ff) == up
    assert _flash_forwards(jaxpr) == flash


def test_holding_the_activations_result_makes_the_product_again(monkeypatch):
    """The regression PR 49 found in the old `save_matmuls`: the tensor it
    named was the activation's RESULT, and the activation's backward reads
    its input, so the 550 GFLOP product was still made twice."""
    from jax.ad_checkpoint import checkpoint_name
    act = gpt._act
    monkeypatch.setattr(
        gpt, "_act", lambda x, cfg: checkpoint_name(act(x, cfg), "mlp_act"))
    post = dataclasses.replace(
        NEOX, remat_policy=jax.checkpoint_policies.save_only_these_names(
            FLASH_RESIDUALS, QKV_PRODUCT, "mlp_act"))
    jaxpr, _ = _trace(post, None)
    assert _products(jaxpr, NEOX.d_ff) == 3          # ... again
    assert _products(jaxpr, 3 * NEOX.d_model) == 1
    assert _flash_forwards(jaxpr) == 1


@pytest.mark.parametrize("parallel, outs", [(True, 2), (False, 2)])
def test_out_projection_is_a_candidate_only_in_a_sequential_block(parallel,
                                                                  outs):
    cfg = dataclasses.replace(NEOX, parallel_residual=parallel,
                              use_flash_attention=False)
    held, _ = held_candidates(cfg, B, T)
    assert (ATTN_OUT in held) == (not parallel)
    assert list(held)[:2] == [MLP_PRODUCT, QKV_PRODUCT]    # no kernel, no name


def test_swiglu_names_both_products():
    jaxpr, plans = _trace(SWIGLU, 10**12)
    assert plans[-1].names == (MLP_PRODUCT, QKV_PRODUCT, ATTN_OUT)
    # gate and up once each and the gradient of what the down-projection
    # reads; with nothing held the two are made again
    assert _products(jaxpr, SWIGLU.d_ff) == 3
    assert _products(_trace(SWIGLU, 0)[0], SWIGLU.d_ff) == 5
    held, working_set = held_candidates(SWIGLU, B, T)
    assert held[MLP_PRODUCT] == 2 * B * T * SWIGLU.d_ff * 4
    # one product, its gradient, and the QKV product (4 heads of 32 wide)
    assert working_set["backward_bytes"] == B * T * (2 * 256 + 128) * 4


# ---- (2) the values do not depend on the set -----------------------------


@pytest.mark.parametrize("cfg", [NEOX, SWIGLU], ids=["neox", "swiglu"])
def test_loss_and_gradients_agree_between_no_name_and_every_name(cfg):
    params = gpt_init_fn(cfg)(jax.random.PRNGKey(1))
    batch = _batch(cfg, seed=1)
    results = []
    for free in (0, 10**12):
        run, plans = _grad_fn(cfg, free)
        results.append(jax.jit(run)(params, batch))
        assert bool(plans[-1].names) == bool(free)
    (loss0, g0), (loss1, g1) = results
    np.testing.assert_allclose(loss0, loss1, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


# ---- (3) the fit, as a table ---------------------------------------------

PYTHIA = GPTConfig(n_layer=24, n_head=16, d_model=2048, d_ff=8192,
                   max_seq_len=2048, vocab_size=50304, use_rotary=True,
                   rotary_pct=0.25, parallel_residual=True,
                   tie_embeddings=False, dtype=jnp.bfloat16)
V5E = 16909336064               # memory_stats()["bytes_limit"] of one v5e
MARGIN = int(V5E * HELD_MARGIN_SHARE)
N_PARAMS = 1_414_647_808        # bfloat16, two bfloat16 moments, no master
MIB = 2**20


def test_the_training_cells_bytes_a_layer():
    held, working_set = held_candidates(PYTHIA, 8, 2048)
    assert held == {FLASH_RESIDUALS: 72 * MIB, MLP_PRODUCT: 256 * MIB,
                    QKV_PRODUCT: 192 * MIB}
    assert list(held) == [FLASH_RESIDUALS, MLP_PRODUCT, QKV_PRODUCT]
    assert working_set == dict(
        carried_bytes=24 * 64 * MIB,                    # 24 layers' inputs
        loss_bytes=8 * 2048 * 50304 * 2 * 5 // 4,       # logits + a quarter
        backward_bytes=(2 * 256 + 192) * MIB)


@pytest.mark.parametrize("shards, free, names, unfit", [
    # the two training cells as the engine hands them over (PERF.md section
    # 6, PR 49): the whole state on one chip, a quarter under ZeRO-3 on four
    (1, V5E, (FLASH_RESIDUALS,), MLP_PRODUCT),
    (4, V5E, (FLASH_RESIDUALS, MLP_PRODUCT), QKV_PRODUCT),
    (1, 0, (), FLASH_RESIDUALS),
    (4, 7 * GIB, (), FLASH_RESIDUALS),
    (1, 64 * GIB, (FLASH_RESIDUALS, MLP_PRODUCT, QKV_PRODUCT), None),
])
def test_fit_table(shards, free, names, unfit):
    from deepspeed_tpu.runtime.activation_checkpointing import held_policy
    held, working_set = held_candidates(PYTHIA, 8, 2048)
    state, grads = 6 * N_PARAMS // shards, 2 * N_PARAMS // shards
    plans = []
    with held_budget(free - state, grads, MARGIN, plans.append):
        held_policy(held, PYTHIA.n_layer, **working_set)
    plan, = plans
    assert plan.names == names and plan.first_unfit == unfit
    assert plan.held_bytes == 24 * sum(held[n] for n in names)
    assert plan.held_bytes <= plan.free_bytes
    # what the chip read with nothing held: 12.724 and 5.444 GiB
    floor = free - state - MARGIN - plan.free_bytes
    if free == V5E:
        true = {1: 12.724, 4: 5.444}[shards] * GIB - state
        assert abs(floor - true) < 0.05 * GIB


def test_fit_is_monotone_and_never_over_budget():
    held = {"a": 3, "b": 10, "c": 2, "d": 7}
    before = ()
    for free in range(0, 300):
        plan = fit_held(free, held, layers=5, margin_bytes=11)
        assert plan.held_bytes <= max(0, free - 11)
        assert plan.names[:len(before)] == before       # nested prefixes
        assert plan.names == tuple(held)[:len(plan.names)]
        assert (plan.first_unfit is None) == (len(plan.names) == len(held))
        before = plan.names
    assert before == tuple(held)


def test_an_unknown_policy_name_is_an_error():
    cfg = dataclasses.replace(NEOX, remat_policy="save_matmuls")
    with pytest.raises(ValueError, match="save_matmuls"):
        _trace(cfg, None)
    # a jax.checkpoint_policies name still passes, and no plan is made
    named = dataclasses.replace(NEOX, remat_policy="dots_saveable")
    jaxpr, plans = _trace(named, 10**12)
    assert plans == [] and _products(jaxpr, NEOX.d_ff) == 2


# ---- (4) the engine says what it chose -----------------------------------

ENGINE_CFG = GPTConfig(n_layer=2, n_head=2, d_model=64, d_ff=256,
                       max_seq_len=64, vocab_size=256, dtype=jnp.float32,
                       parallel_residual=True, use_rotary=True)


def _engine(telemetry=None):
    config = {"train_micro_batch_size_per_gpu": 2,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 3}, "mesh": {"data": 4},
              "steps_per_print": 10**9}
    if telemetry:
        config["telemetry"] = telemetry
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_gpt_model(cfg=ENGINE_CFG, name="tiny"), config=config)
    toks = np.random.default_rng(0).integers(0, 256, (8, 33)).astype(np.int32)
    return engine, {"tokens": toks}


@pytest.mark.parametrize("limit, names", [
    (0, ()),                                  # the CPU reports no limit
    (V5E, (MLP_PRODUCT, QKV_PRODUCT)),        # room for everything
    (2**19, ()),                              # a limit the state fills
])
def test_engine_reports_its_plan(monkeypatch, limit, names):
    monkeypatch.setattr(type(get_accelerator()), "total_memory",
                        lambda self, device=None: limit)
    engine, batch = _engine()
    assert engine.held_plan is None           # nothing traced yet
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    assert np.isfinite(losses).all()
    plan = engine.held_plan
    assert plan.names == names and plan.layers == 2
    assert set(plan.bytes_per_layer) == {MLP_PRODUCT, QKV_PRODUCT}
    # the micro-batch of one device: 2 sequences of 32 tokens, float32
    assert plan.bytes_per_layer[MLP_PRODUCT] == 2 * 32 * 256 * 4
    assert engine.steptrace.facts["held_residuals"] == plan.to_dict()
    assert engine._compiled_train_programs() == 1     # one compile, no trial
    free, grads, margin = engine._activation_budget
    assert (free, grads, margin) == (0, 0, 0) if not limit else \
        (free < limit and 0 < grads
         and margin == int(limit * HELD_MARGIN_SHARE))
    if not names:
        assert plan.first_unfit == MLP_PRODUCT and plan.held_bytes == 0


def test_memscope_ledger_shows_what_is_held(monkeypatch, tmp_path):
    monkeypatch.setattr(type(get_accelerator()), "total_memory",
                        lambda self, device=None: V5E)
    engine, batch = _engine({"enabled": True, "memscope": True,
                             "output_path": str(tmp_path)})
    engine.train_batch(batch)
    snap = engine.memscope.snapshot(programs=False)
    assert snap["held_residual_bytes"] == engine.held_plan.held_bytes > 0
    assert snap["held_residual_free_bytes"] == engine.held_plan.free_bytes


def test_device_tree_bytes_reads_the_shards():
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.telemetry.memscope import device_tree_bytes, tree_bytes
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
    whole = jax.ShapeDtypeStruct((8, 6), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))
    split = jax.ShapeDtypeStruct((8, 6), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P("data")))
    bare = np.zeros((3,), np.float32)
    assert device_tree_bytes((whole, split, bare, None)) == 192 + 24 + 12
    assert tree_bytes((whole, split)) == 192 + 96


# ---- (5) the hybrid loop's table: blocks, not layers ----------------------

OLMO_PARAMS = 928_892_916       # bfloat16, two bfloat16 moments, no master
HYBRID_ORDER = (FLASH_RESIDUALS, QKV_PRODUCT, MLP_PRODUCT, SCAN_OUTPUT)


def _olmo_cell():
    """`train_olmohybrid_seq32k_1chip`'s model and its table at [1, 32768]."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "olmo-hybrid-7b-4l-vp8.json")) as f:
        cfg = olmo_hybrid.olmo_hybrid_config(
            json.load(f), max_seq_len=32768, use_flash_attention=True)
    return cfg, hybrid.held_candidates(cfg, 1, 32768)


def test_the_hybrid_cells_bytes_a_block():
    cfg, (held, carriers, working_sets) = _olmo_cell()
    assert hybrid.layer_runs(cfg) == [("DF", 3), ("*F", 1)]
    assert tuple(held) == HYBRID_ORDER
    assert held == {
        FLASH_RESIDUALS: 270 * MIB,         # 32768 x 30 x (128 x 2 + 32)
        QKV_PRODUCT: 720 * MIB,             # 32768 x 90 heads of 128
        MLP_PRODUCT: 1376 * MIB,            # gate and up, 32768 x 11008
        SCAN_OUTPUT: 90 * MIB}              # float32 [1, 4096, 30, 192]
    # from the stack's end; a scanned block's repeats are one group: the
    # three `D` halves are ONE position of a run scanned three times, eight
    # segments each, and their feed-forwards go together behind the last one
    assert carriers == {FLASH_RESIDUALS: (1,), QKV_PRODUCT: (1,),
                        MLP_PRODUCT: (1, 3), SCAN_OUTPUT: (3,) * 8}
    stream = 32768 * 3840 * 2
    carried = 30 * 96 * 192 * 4 + 3 * 11520 * 2     # a state and a tail
    first, last = working_sets
    assert first == dict(carried_bytes=3 * (2 * stream + 8 * carried),
                         grads_share=1.0,
                         backward_bytes=5 * 32768 * 11008 * 2)
    assert last["carried_bytes"] == first["carried_bytes"] + 2 * stream
    assert 0.30 < last["grads_share"] < 0.31        # `*F`, embedding, head
    # seven chunks of the 12544-row slice, float32, three arrays of them
    assert last["loss_bytes"] == 3 * 32768 * 4 * (12544 // 7) + 4 * stream


@pytest.mark.parametrize("limit, blocks, unfit", [
    # the cell as the engine hands it over: 15.75 GiB beside 5.19 of state.
    # The last feed-forward's products fit; the three of the run scanned
    # three times go together or not at all, and nothing behind them in the
    # order jumps the queue
    (V5E, {FLASH_RESIDUALS: 1, QKV_PRODUCT: 1, MLP_PRODUCT: 1}, MLP_PRODUCT),
    (V5E + 3 * GIB, {FLASH_RESIDUALS: 1, QKV_PRODUCT: 1, MLP_PRODUCT: 1},
     MLP_PRODUCT),
    (V5E + 4 * GIB, {FLASH_RESIDUALS: 1, QKV_PRODUCT: 1, MLP_PRODUCT: 4,
                     SCAN_OUTPUT: 3}, SCAN_OUTPUT),
    (V5E + 5 * GIB, {FLASH_RESIDUALS: 1, QKV_PRODUCT: 1, MLP_PRODUCT: 4,
                     SCAN_OUTPUT: 15}, SCAN_OUTPUT),
    (V5E + 6 * GIB, dict(zip(HYBRID_ORDER, (1, 1, 4, 24))), None),
    # less room
    (V5E - GIB, {FLASH_RESIDUALS: 1, QKV_PRODUCT: 1}, MLP_PRODUCT),
    (V5E - 3 * GIB + 200 * MIB, {FLASH_RESIDUALS: 1}, QKV_PRODUCT),
    (V5E - 3 * GIB, {}, FLASH_RESIDUALS),
    (0, {}, FLASH_RESIDUALS),
], ids=["v5e", "three_ffns_do_not_fit", "scan_a_segment", "scan_5_segments",
        "everything", "flash_and_qkv", "flash_alone", "nothing", "no_limit"])
def test_hybrid_fit_table(limit, blocks, unfit):
    _, (held, carriers, working_sets) = _olmo_cell()
    plans = []
    with held_budget(limit - 6 * OLMO_PARAMS, 2 * OLMO_PARAMS,
                     int(limit * HELD_MARGIN_SHARE), plans.append):
        held_plan(held, carriers, working_sets)
    plan, = plans
    assert plan.blocks == blocks and plan.first_unfit == unfit
    assert plan.names == HYBRID_ORDER[:len(blocks)]      # a nested prefix
    assert plan.layers == dict(zip(HYBRID_ORDER, (1, 1, 4, 24)))
    assert plan.held_bytes == sum(n * held[name]
                                  for name, n in blocks.items())
    assert plan.held_bytes <= plan.free_bytes
    if limit == V5E:
        # what the compiler read with nothing held (compiled for a described
        # v5e, PERF.md section 6, PR 57): 11.774 GiB
        floor = limit - plan.margin_bytes - plan.free_bytes
        assert abs(floor - 11.774 * GIB) < 0.1 * GIB
        assert plan.render() == (
            "held for the backward: flash_residuals in 1 of 1 blocks 270.00 "
            "MiB, qkv_product in 1 of 1 blocks 720.00 MiB, mlp_product in 1 "
            "of 4 blocks 1.34 GiB = 2.31 GiB of 3.03 GiB free (margin "
            "1007.87 MiB kept); one more block of mlp_product (1.34 GiB a "
            "block) does not fit")


def test_fit_by_groups_is_monotone_and_never_over_budget():
    held = {"a": 3, "b": 10, "c": 2}
    groups = {"a": (2, 1), "b": (3, 3, 1), "c": (1,) * 4}
    before = {}
    for free in range(0, 120):
        plan = fit_held(free, held, groups, margin_bytes=7)
        assert plan.held_bytes <= max(0, free - 7)
        assert plan.names == tuple(held)[:len(plan.names)]
        # whole groups, from the front, and no name behind one cut short
        for name, n in plan.blocks.items():
            sizes = groups[name]
            assert n in [sum(sizes[:k]) for k in range(1, len(sizes) + 1)]
            assert n >= before.get(name, 0)
        short = [n for n in plan.names if plan.blocks[n] < sum(groups[n])]
        assert short in ([], list(plan.names[-1:]))
        assert plan.first_unfit == (short[0] if short else
                                    None if len(plan.names) == len(held)
                                    else tuple(held)[len(plan.names)])
        before = plan.blocks
    assert before == {"a": 3, "b": 7, "c": 4}     # 9 + 70 + 8 of 113


@pytest.mark.parametrize("shards, names, line", [
    (1, (FLASH_RESIDUALS,),
     "held for the backward over 24 layers: flash_residuals 1.69 GiB = 1.69 "
     "GiB of 2.04 GiB free (margin 1007.87 MiB kept); mlp_product (6.00 "
     "GiB) does not fit"),
    (4, (FLASH_RESIDUALS, MLP_PRODUCT),
     "held for the backward over 24 layers: flash_residuals 1.69 GiB, "
     "mlp_product 6.00 GiB = 7.69 GiB of 9.37 GiB free (margin 1007.87 MiB "
     "kept); qkv_product (4.50 GiB) does not fit"),
], ids=["one_chip", "zero3_four_chips"])
def test_the_gpt_cells_plans_are_what_they_were(shards, names, line):
    """`fit_held` by groups left the GPT block's call alone: a number of
    layers, every layer or none, the plan's line as PR 49 logged it."""
    from deepspeed_tpu.runtime.activation_checkpointing import held_policy
    held, working_set = held_candidates(PYTHIA, 8, 2048)
    plans = []
    with held_budget(V5E - 6 * N_PARAMS // shards, 2 * N_PARAMS // shards,
                     MARGIN, plans.append):
        policy = held_policy(held, PYTHIA.n_layer, **working_set)
    plan, = plans
    assert plan.names == names and plan.layers == 24
    assert plan.blocks == {name: 24 for name in names}
    assert plan.render() == line
    assert policy is not jax.checkpoint_policies.nothing_saveable
