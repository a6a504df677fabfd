"""HBM memory observability (telemetry/memscope.py): byte-attribution
ledger, pre-flight capacity planner, and OOM forensics.

Everything here rides the `memscope` marker (tier-1; run alone with
`pytest -m memscope`). The acceptance story is in three layers:

  * PLANNER PARITY: the pre-flight predictions (pure arithmetic, computed
    before anything compiles) must agree with XLA's `memory_analysis()` of
    the REAL compiled programs — serving within SERVING_PLAN_TOLERANCE
    (5%), training within TRAIN_PLAN_TOLERANCE (10%); the slack is the
    small unmodeled arguments (token ids, tables, rng keys, the batch);
  * FORENSICS: an injected RESOURCE_EXHAUSTED at the dispatch boundary
    produces a dump carrying the ledger, the planner delta, and the
    flight-recorder ring — and re-raises the original error;
  * DISABLED DEFAULT: without `telemetry.memscope` there is no scope
    object, no `mem/*` gauge, no file, and `compile_stats()` is
    byte-identical — and the AOT `memory_analysis` pass never touches the
    jit call caches even when memscope is ON.
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig, TelemetryConfig
from deepspeed_tpu.inference.engine import init_inference
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model, \
    make_gpt_model
from deepspeed_tpu.telemetry import Telemetry
from deepspeed_tpu.telemetry import memscope as ms
from deepspeed_tpu.telemetry.memscope import (
    PredictedOOMError, SERVING_PLAN_TOLERANCE, TRAIN_PLAN_TOLERANCE,
    dtype_bytes, fmt_bytes, max_kv_blocks, plan_serving, plan_training,
    plan_training_from_engine, serving_pool_bytes, tree_bytes)
from tests.paged_cases import assert_one_compile_each

pytestmark = pytest.mark.memscope

TINY = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=256,
                 vocab_size=256, dtype=jnp.float32, remat=False)
DRAFT = GPTConfig(n_layer=1, n_head=2, d_model=32, max_seq_len=256,
                  vocab_size=256, dtype=jnp.float32, remat=False)


def _mk_mesh():
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    return mesh_mod.init_mesh(MeshConfig(data=1, tensor=1, sequence=1,
                                         expert=1, pipe=1))


def _tel(tmp_path, **over):
    """Registry-only telemetry config with memscope on (no file sinks, so
    a test run writes nothing unless a dump fires)."""
    cfg = {"enabled": True, "output_path": str(tmp_path),
           "prometheus": False, "jsonl": False, "monitor_bridge": False,
           "memscope": True}
    cfg.update(over)
    return cfg


def _mk_engine(tmp_path=None, telemetry=None, **cfg_over):
    _mk_mesh()
    spec = make_gpt_decode_model(cfg=TINY, name="tiny")
    cfg = {"dtype": "float32", "kv_cache_dtype": "float32", "greedy": True,
           "kv_block_size": 16, "max_out_tokens": 64, **cfg_over}
    if telemetry is not None:
        cfg["telemetry"] = telemetry
    return init_inference(model=spec, config=cfg)


def _reqs(n, rng, max_new=3):
    return [Request(uid=i, tokens=rng.integers(0, 256, (9,)).astype(np.int32),
                    max_new_tokens=max_new, stop_on_eos=False)
            for i in range(n)]


# ----------------------------------------------------------------------
# pure-math units: bytes, formulas, the ZeRO estimator, the inverse ask
# ----------------------------------------------------------------------


def test_fmt_and_dtype_bytes():
    assert fmt_bytes(512) == "512 B"
    assert fmt_bytes(2048) == "2.00 KiB"
    assert fmt_bytes(3 * 2**30) == "3.00 GiB"
    assert fmt_bytes(-2048) == "-2.00 KiB"
    assert dtype_bytes("bf16") == 2 and dtype_bytes("bfloat16") == 2
    assert dtype_bytes("float32") == 4 and dtype_bytes(np.int32) == 4
    assert dtype_bytes(jnp.float32) == 4          # scalar TYPE object
    assert dtype_bytes(jnp.dtype("bfloat16")) == 2
    assert tree_bytes({"a": np.zeros((4, 4), np.float32),
                       "b": np.zeros((8,), np.int8)}) == 64 + 8
    assert tree_bytes(None) == 0


def test_plan_training_zero_stage_sharding():
    n = 1000
    # stage 0: nothing sharded — bf16 params+grads, fp32 master + 2 moments
    p0 = plan_training(n, zero_stage=0, dp=4, dtype="bf16")
    assert p0.device_bytes == {"params": 2000, "grads": 2000,
                               "master": 4000, "optim": 8000}
    # stage 1 shards master+optim over dp; stage 2 adds grads; 3 adds params
    p1 = plan_training(n, zero_stage=1, dp=4, dtype="bf16")
    assert (p1.device_bytes["master"], p1.device_bytes["optim"]) == \
        (1000, 2000)
    assert p1.device_bytes["grads"] == 2000
    p2 = plan_training(n, zero_stage=2, dp=4, dtype="bf16")
    assert p2.device_bytes["grads"] == 500
    assert p2.device_bytes["params"] == 2000
    p3 = plan_training(n, zero_stage=3, dp=4, dtype="bf16")
    assert p3.device_bytes == {"params": 500, "grads": 500,
                               "master": 1000, "optim": 2000}
    # offload moves master+optim (and params) to the host column
    po = plan_training(n, zero_stage=3, dp=4, dtype="bf16",
                       offload_optimizer=True, offload_param=True)
    assert po.device_bytes["master"] == po.device_bytes["optim"] == 0
    assert po.device_bytes["params"] == 0
    assert po.host_bytes == {"params": 500, "master": 1000, "optim": 2000}
    # fp32 compute needs no separate master copy
    pf = plan_training(n, zero_stage=0, dtype="float32")
    assert "master" not in pf.device_bytes
    # capacity verdicts
    assert plan_training(n, dtype="bf16", capacity_bytes=10**6).fits is True
    assert plan_training(n, dtype="bf16", capacity_bytes=4000).fits is False
    assert plan_training(n, dtype="bf16").fits is None    # unknown capacity
    # the reference-named wrappers are the same math
    z3 = ms.estimate_zero3_model_states_mem_needs(n, num_devices=4,
                                                  dtype="bf16")
    assert z3.device_bytes == p3.device_bytes


def test_serving_pool_formula_and_inverse():
    kw = dict(n_layer=4, n_kv_head=2, head_dim=16, kv_block_size=32,
              kv_cache_dtype="float32")
    per_block = serving_pool_bytes(num_kv_blocks=1, **kw)
    assert per_block == 2 * 4 * 2 * 32 * 16 * 4
    params_b = 10 * per_block
    cap = params_b + 7 * per_block + per_block // 2   # 7.5 blocks of room
    n = max_kv_blocks(cap, params_bytes=params_b, **kw)
    assert n == 7
    # inverse property: n fits, n+1 does not
    assert plan_serving(num_kv_blocks=n, params_bytes=params_b,
                        capacity_bytes=cap, **kw).fits is True
    assert plan_serving(num_kv_blocks=n + 1, params_bytes=params_b,
                        capacity_bytes=cap, **kw).fits is False
    # the draft mirror grows the per-block cost, shrinking the answer
    n_d = max_kv_blocks(cap, params_bytes=params_b,
                        draft={"n_layer": 4, "n_kv_head": 2, "head_dim": 16,
                               "params_bytes": 0}, **kw)
    assert n_d == n // 2


def test_quantized_pool_formula_matches_tree_and_doubles_capacity():
    """The int8 pool's planner term: byte-identical to the real quantized
    pool tree (payload + f32 group scales), and >= 1.9x `max_kv_blocks`
    at the same HBM budget for production serving geometry — THE capacity
    claim of the quantized-serving tentpole, stated as planner math so it
    holds on any backend."""
    from deepspeed_tpu.models.gpt import init_paged_kv_pool
    # exact identity with init_paged_kv_pool's int8 layout (g = head_dim)
    pool = init_paged_kv_pool(TINY, 13, 16, jnp.int8)
    formula = serving_pool_bytes(
        n_layer=TINY.n_layer, n_kv_head=TINY.n_kv_head,
        head_dim=TINY.head_dim, kv_block_size=16, num_kv_blocks=13,
        kv_cache_dtype="int8", kv_group_size=0)
    assert formula == tree_bytes(pool)
    # ...and with an explicit sub-vector group
    pool8 = init_paged_kv_pool(TINY, 13, 16, jnp.int8, kv_group_size=8)
    formula8 = serving_pool_bytes(
        n_layer=TINY.n_layer, n_kv_head=TINY.n_kv_head,
        head_dim=TINY.head_dim, kv_block_size=16, num_kv_blocks=13,
        kv_cache_dtype="int8", kv_group_size=8)
    assert formula8 == tree_bytes(pool8) > formula
    # capacity: >= 1.9x blocks for the same budget at head_dim 128 (the
    # production MXU-lane geometry; the scales overhead is 4/g per element,
    # so the exact ratio is 2/(1 + 4/128) = 1.94x)
    kw = dict(n_layer=24, n_kv_head=8, head_dim=128, kv_block_size=512)
    cap, params_b = 16 * 2**30, 2 * 10**9
    n_bf16 = max_kv_blocks(cap, kv_cache_dtype="bfloat16",
                           params_bytes=params_b, **kw)
    n_int8 = max_kv_blocks(cap, kv_cache_dtype="int8",
                           params_bytes=params_b, **kw)
    assert n_int8 >= 1.9 * n_bf16
    assert n_int8 <= 2.0 * n_bf16          # scales overhead is not free
    # inverse property still holds with the scales term in the price
    assert plan_serving(num_kv_blocks=n_int8, params_bytes=params_b,
                        kv_cache_dtype="int8", capacity_bytes=cap,
                        **kw).fits is True
    assert plan_serving(num_kv_blocks=n_int8 + 1, params_bytes=params_b,
                        kv_cache_dtype="int8", capacity_bytes=cap,
                        **kw).fits is False


def test_int8_serving_planner_matches_xla_memory_analysis(tmp_path):
    """Planner-vs-XLA parity for the QUANTIZED serving engine: the int8
    pool (payload + scales) and the params are the compiled programs'
    argument bytes within SERVING_PLAN_TOLERANCE, exactly like the bf16
    case — the scales term keeps the identity exact."""
    engine = _mk_engine(telemetry=_tel(tmp_path))
    serving = engine.serving(max_slots=2, max_context=128,
                             quantization={"kv_cache_dtype": "int8"})
    assert serving.memscope is not None
    serving.run(_reqs(2, np.random.default_rng(0)))

    plan = serving.memscope.plan()
    assert plan.device_bytes["kv_pool"] == tree_bytes(serving.pool)
    assert plan.device_bytes["params"] == tree_bytes(engine.params)
    pred = plan.device_bytes["params"] + plan.device_bytes["kv_pool"]
    progs = serving.memscope.program_memory()
    assert set(progs) == {"decode_step", "prefill_step", "mixed_step"}
    for name, ma in progs.items():
        rel = abs(ma["argument_bytes"] - pred) / pred
        assert rel < SERVING_PLAN_TOLERANCE, (name, ma["argument_bytes"],
                                              pred, rel)
    assert_one_compile_each(serving)


# ----------------------------------------------------------------------
# planner-vs-XLA parity on the REAL compiled programs (tier-1 configs)
# ----------------------------------------------------------------------


def test_serving_planner_matches_xla_memory_analysis(tmp_path):
    engine = _mk_engine(telemetry=_tel(tmp_path))
    serving = engine.serving(max_slots=2, max_context=128)
    assert serving.memscope is not None
    serving.run(_reqs(2, np.random.default_rng(0)))

    # exact identity: predicted resident categories ARE the live trees
    plan = serving.memscope.plan()
    pred = plan.device_bytes["params"] + plan.device_bytes["kv_pool"]
    assert plan.device_bytes["params"] == tree_bytes(engine.params)
    assert plan.device_bytes["kv_pool"] == tree_bytes(serving.pool)

    # XLA validation: the compiled programs' argument bytes are the
    # resident prediction plus only small unmodeled args (tok/pos/tables/
    # rng) — within the documented tolerance
    progs = serving.memscope.program_memory()
    assert set(progs) == {"decode_step", "prefill_step", "mixed_step"}
    for name, ma in progs.items():
        rel = abs(ma["argument_bytes"] - pred) / pred
        assert rel < SERVING_PLAN_TOLERANCE, (name, ma["argument_bytes"],
                                              pred, rel)
        assert ma["temp_bytes"] > 0        # the workspace the plan can't see
        # the donated pool is aliased, not double-counted
        assert ma["alias_bytes"] >= tree_bytes(serving.pool)

    # the AOT memory_analysis pass never touched the jit CALL caches
    assert_one_compile_each(serving)


def test_train_planner_matches_state_and_xla(tmp_path):
    _mk_mesh()
    model = make_gpt_model(cfg=TINY, name="tiny")
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 10**9,
        "telemetry": _tel(tmp_path, measure_program_flops=False,
                          memscope_capacity_bytes=256 * 2**20)})
    assert engine.memscope is not None
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, (engine.train_batch_size(), 33)) \
        .astype(np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    engine.train_batch(batch)

    plan = plan_training_from_engine(engine)
    st = engine.state
    # plan vs the live state trees: params exact; optimizer within
    # tolerance (the plan's 2 fp32 moments vs optax's moments + scalars)
    assert plan.device_bytes["params"] == tree_bytes(st.params)
    opt = tree_bytes(st.opt_state)
    assert abs(plan.device_bytes["optim"] - opt) / opt < \
        TRAIN_PLAN_TOLERANCE

    # vs XLA: the compiled train step's arguments are the resident model
    # states (params + master + optim; grads are temporaries inside the
    # fused step) plus only the batch and bookkeeping scalars
    ma = engine.memscope.program_memory()["train_step"]
    pred = plan.total_device_bytes - plan.device_bytes["grads"]
    rel = abs(ma["argument_bytes"] - pred) / pred
    assert rel < TRAIN_PLAN_TOLERANCE, (ma["argument_bytes"], pred, rel)
    assert ma["temp_bytes"] > 0

    # the ledger gauges landed
    snap = engine.telemetry.registry.snapshot()
    assert snap["mem/params_bytes"]["value"] == tree_bytes(st.params)
    assert snap["mem/opt_state_bytes"]["value"] == opt
    assert 0.0 < snap["mem/headroom_frac"]["value"] < 1.0


# ----------------------------------------------------------------------
# the live ledger: gauges, draft mirror, prefix carve-out, router pool
# ----------------------------------------------------------------------


def test_serving_ledger_gauges_and_prefix_view(tmp_path):
    engine = _mk_engine(
        telemetry=_tel(tmp_path, memscope_capacity_bytes=64 * 2**20))
    serving = engine.serving(max_slots=2, max_context=128,
                             enable_prefix_caching=True)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 256, (32,)).astype(np.int32)
    reqs = [Request(uid=i, tokens=shared, max_new_tokens=3,
                    stop_on_eos=False) for i in range(3)]
    serving.run(reqs)

    snap = serving.memscope.snapshot()
    assert snap["kv_pool_bytes"] == tree_bytes(serving.pool)
    assert snap["params_bytes"] == tree_bytes(engine.params)
    # the prefix carve-out is a VIEW of the pool: sized by cached blocks,
    # never added to the attribution sum
    per_block = snap["kv_pool_bytes"] // serving.allocator.num_blocks
    assert snap["prefix_cached_bytes"] == \
        serving.prefix_cache.num_cached * per_block
    assert snap["prefix_cached_bytes"] > 0
    assert snap["attributed_bytes"] == (snap["params_bytes"]
                                        + snap["kv_pool_bytes"]
                                        + snap["program_temp_bytes"])
    assert 0.0 < snap["headroom_frac"] <= 1.0

    gauges = serving.telemetry.registry.snapshot()
    assert gauges["mem/kv_pool_bytes"]["value"] == snap["kv_pool_bytes"]
    assert gauges["mem/prefix_cached_bytes"]["value"] == \
        snap["prefix_cached_bytes"]
    assert gauges["mem/headroom_frac"]["value"] == \
        pytest.approx(snap["headroom_frac"], rel=1e-3)
    # the ledger also rides in stats()
    assert serving.stats()["memory"]["kv_pool_bytes"] == \
        snap["kv_pool_bytes"]
    # every published name is catalogued (the lint test's dynamic list)
    published = {k[len("mem/"):] for k in gauges if k.startswith("mem/")}
    assert published <= set(ms.LEDGER_GAUGES)


def test_draft_mirror_on_the_ledger(tmp_path):
    engine = _mk_engine(telemetry=_tel(tmp_path, memscope_programs=False))
    draft = make_gpt_decode_model(cfg=DRAFT, name="tiny-draft", seed=7)
    serving = engine.serving(max_slots=2, max_context=64, prefill_chunk=16,
                             draft_spec=draft,
                             spec_decode={"drafter": "model", "draft_k": 2})
    snap = serving.memscope.snapshot()
    assert snap["draft_pool_bytes"] == tree_bytes(serving.drafter.pool)
    assert snap["draft_params_bytes"] == tree_bytes(serving.drafter.params)
    # the mirror's formula: target's num_blocks/block_size, draft geometry
    assert snap["draft_pool_bytes"] == serving_pool_bytes(
        n_layer=DRAFT.n_layer, n_kv_head=DRAFT.n_kv_head or DRAFT.n_head,
        head_dim=DRAFT.head_dim, kv_block_size=serving.block_size,
        num_kv_blocks=serving.allocator.num_blocks,
        kv_cache_dtype="float32")
    plan = serving.memscope.plan()
    assert plan.device_bytes["draft_pool"] == snap["draft_pool_bytes"]
    assert plan.device_bytes["draft_params"] == snap["draft_params_bytes"]


def test_router_pool_aggregation(tmp_path):
    from deepspeed_tpu.serving import ServingRouter
    from deepspeed_tpu.serving.replica import InProcessReplica

    reps = []
    for i in range(2):
        eng = _mk_engine(telemetry=_tel(
            tmp_path / f"r{i}", memscope_programs=False,
            memscope_capacity_bytes=64 * 2**20))
        reps.append(InProcessReplica(
            engine=eng.serving(max_slots=2, max_context=128),
            replica_id=f"r{i}"))
    router = ServingRouter(replicas=reps)
    single = reps[0].memory_snapshot()
    agg = router.memory_snapshot()
    assert set(agg["replicas"]) == {"r0", "r1"}
    assert agg["kv_pool_bytes"] == 2 * single["kv_pool_bytes"]
    assert agg["params_bytes"] == 2 * single["params_bytes"]
    # headroom aggregates as the MINIMUM (the binding replica), not a sum
    assert agg["headroom_frac"] == pytest.approx(min(
        r["headroom_frac"] for r in agg["replicas"].values()))
    # allocator-global watermarks (capacity, in-use) aggregate as MAX —
    # in-process replicas share one device; summing would double it
    assert agg["capacity_bytes"] == single["capacity_bytes"]
    assert agg["bytes_in_use"] == max(
        r["bytes_in_use"] for r in agg["replicas"].values())
    assert router.stats()["memory"]["kv_pool_bytes"] == agg["kv_pool_bytes"]


# ----------------------------------------------------------------------
# preflight + pressure signal
# ----------------------------------------------------------------------


def test_preflight_refuses_predicted_oom(tmp_path, monkeypatch):
    engine = _mk_engine(telemetry=_tel(
        tmp_path, memscope_capacity_bytes=1024,     # nothing fits in 1 KiB
        memscope_preflight="refuse"))
    # the verdict must fire BEFORE the pool's device_put: on a real chip a
    # too-big pool crashes at allocation with a raw RESOURCE_EXHAUSTED, so
    # a post-allocation check would never get to run (the plan is pure
    # jax.eval_shape arithmetic — no device memory needed)
    import jax as _jax

    def _bomb(*a, **k):
        raise AssertionError("pool allocated before the preflight verdict")
    monkeypatch.setattr(_jax, "device_put", _bomb)
    with pytest.raises(PredictedOOMError, match="predicted OOM"):
        engine.serving(max_slots=2, max_context=128)
    monkeypatch.undo()
    # default "warn" builds fine under the same impossible capacity
    engine2 = _mk_engine(telemetry=_tel(tmp_path,
                                        memscope_capacity_bytes=1024))
    serving = engine2.serving(max_slots=2, max_context=128)
    assert serving.memscope.last_plan.fits is False


def test_headroom_feeds_pressure_controller(tmp_path):
    engine = _mk_engine(telemetry=_tel(
        tmp_path, memscope_programs=False,
        memscope_capacity_bytes=1024))          # headroom pinned to ~0
    serving = engine.serving(
        max_slots=2, max_context=128,
        degradation={"enabled": True, "eval_interval": 1,
                     # pool/queue signals stay calm in this test: only the
                     # memscope headroom signal can drive the ladder
                     "free_block_low": -1.0, "free_block_high": -1.0,
                     "queue_high": 10**6, "queue_low": 10**6,
                     "headroom_low": 0.2, "headroom_high": 0.3})
    assert serving.pressure is not None
    hf = serving.memscope.headroom_frac()
    assert hf is not None and hf < 0.2
    serving.run(_reqs(1, np.random.default_rng(0)))
    assert serving.pressure.level >= 1            # escalated on headroom
    assert serving.pressure._signals()["headroom_frac"] == pytest.approx(hf)


# ----------------------------------------------------------------------
# OOM forensics
# ----------------------------------------------------------------------


def test_is_resource_exhausted_matching():
    assert ms.is_resource_exhausted(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating 1G"))
    assert not ms.is_resource_exhausted(ValueError("bad shape"))
    # cause chains are walked
    try:
        try:
            raise RuntimeError("XLA: Out of memory")
        except RuntimeError as inner:
            raise ValueError("step failed") from inner
    except ValueError as outer:
        assert ms.is_resource_exhausted(outer)


def test_injected_oom_dumps_ledger_and_flight_events(tmp_path):
    engine = _mk_engine(telemetry=_tel(tmp_path, flight_recorder=True,
                                       memscope_programs=False))
    serving = engine.serving(max_slots=2, max_context=128)
    serving.run(_reqs(1, np.random.default_rng(0)))   # warm + flight events

    def boom(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory "
                           "allocating 12345 bytes")

    serving.programs.decode = boom
    serving.submit(Request(uid=99, tokens=np.arange(9, dtype=np.int32),
                           max_new_tokens=4, stop_on_eos=False))
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        while True:
            serving.step()

    dumps = sorted(tmp_path.glob("serving.memscope.oom.*.json"))
    assert len(dumps) == 1
    d = json.loads(dumps[0].read_text())
    assert "RESOURCE_EXHAUSTED" in d["reason"]
    # the ledger rides in the dump, with real numbers
    assert d["ledger"]["kv_pool_bytes"] == tree_bytes(serving.pool)
    assert d["ledger"]["params_bytes"] == tree_bytes(engine.params)
    # the planner delta says whether this was foreseeable
    assert d["plan_delta"]["predicted_peak_bytes"] > 0
    # the flight ring is embedded — admissions made it in before the OOM
    kinds = {e["kind"] for e in d["flight_events"]}
    assert "admit" in kinds
    # the PR 8 flight recorder's own dump fired alongside
    assert list(tmp_path.glob("serving.flightrec.*.json"))
    # non-OOM failures do NOT dump
    serving2 = _mk_engine(telemetry=_tel(tmp_path / "b",
                                         memscope_programs=False)) \
        .serving(max_slots=2, max_context=128)
    serving2.programs.decode = lambda *a, **k: (_ for _ in ()).throw(
        ValueError("not an OOM"))
    serving2.submit(Request(uid=1, tokens=np.arange(9, dtype=np.int32),
                            max_new_tokens=4, stop_on_eos=False))
    with pytest.raises(ValueError):
        while True:
            serving2.step()
    assert not list((tmp_path / "b").glob("*.oom.*.json"))


# ----------------------------------------------------------------------
# disabled default + satellites
# ----------------------------------------------------------------------


def test_disabled_default_no_scope_no_files(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    engine = _mk_engine()                       # no telemetry block at all
    serving = engine.serving(max_slots=2, max_context=128)
    assert serving.memscope is None
    serving.run(_reqs(2, np.random.default_rng(0)))
    assert_one_compile_each(serving)
    assert "memory" not in serving.stats()
    assert list(tmp_path.iterdir()) == []       # zero files
    # memscope flag without telemetry.enabled is also a no-op
    engine2 = _mk_engine(telemetry={"enabled": False, "memscope": True})
    assert engine2.serving(max_slots=2, max_context=128).memscope is None


def test_see_memory_usage_routes_through_registry(tmp_path, caplog):
    from deepspeed_tpu.utils import memory as um
    t = Telemetry(TelemetryConfig(enabled=True, output_path=str(tmp_path),
                                  prometheus=False, jsonl=False,
                                  monitor_bridge=False))
    um.see_memory_usage("tag", force=True, telemetry=t)
    snap = t.registry.snapshot()
    assert snap["mem/bytes_in_use"]["type"] == "gauge"
    assert snap["mem/peak_bytes"]["type"] == "gauge"
    # force=False records nothing (the reference's gate)
    t2 = Telemetry(TelemetryConfig(enabled=True, output_path=str(tmp_path),
                                   prometheus=False, jsonl=False,
                                   monitor_bridge=False))
    um.see_memory_usage("tag", force=False, telemetry=t2)
    assert t2.registry.snapshot() == {}


def test_host_rss_guarded_without_procfs(monkeypatch):
    from deepspeed_tpu.utils import memory as um
    monkeypatch.setattr(um.os.path, "exists", lambda p: False)
    assert um._host_rss_gb() == 0.0             # no procfs: 0, never a crash


def test_metrics_cli_renders_bytes_human_readably():
    from deepspeed_tpu.telemetry.cli import render
    record = {"step": 7, "time": 0,
              "metrics": {"mem/kv_pool_bytes":
                          {"type": "gauge", "value": 3 * 2**30},
                          "serving/queue_depth":
                          {"type": "gauge", "value": 4.0}}}
    table = render(record)
    assert "3.00 GiB" in table                  # *_bytes humanized
    assert "4" in table                         # plain gauges untouched
    # --json keeps raw integers (the CLI dumps the record verbatim)
    assert json.loads(json.dumps(record))["metrics"]["mem/kv_pool_bytes"][
        "value"] == 3 * 2**30


def test_memscope_cli_plan_and_live(tmp_path, capsys):
    # plan mode, scriptable: exit 0 on fits, 2 on predicted OOM
    rc = ms.main(["--plan", "train", "--params", "1e6", "--zero", "3",
                  "--dp", "8", "--capacity", "16G", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["fits"] is True
    assert out["device_bytes"]["params"] == 2 * 10**6 // 8
    rc = ms.main(["--plan", "serving", "--layers", "24", "--kv-heads", "16",
                  "--head-dim", "64", "--blocks", "99999",
                  "--capacity", "1G"])
    capsys.readouterr()
    assert rc == 2                              # predicted OOM
    # forgotten --blocks must NOT plan a zero-byte pool and exit 0
    rc = ms.main(["--plan", "serving", "--layers", "24", "--kv-heads", "16",
                  "--head-dim", "64", "--capacity", "1G"])
    assert rc == 1 and "--blocks" in capsys.readouterr().err
    # unparseable --capacity: clean error, not a traceback
    rc = ms.main(["--plan", "train", "--params", "1e6",
                  "--capacity", "lots"])
    assert rc == 1 and "--capacity" in capsys.readouterr().err
    # --fit honors --tp: sharded weights leave room for more blocks
    fit_args = ["--plan", "serving", "--layers", "4", "--kv-heads", "2",
                "--head-dim", "16", "--block-size", "32",
                "--params", "1e6", "--dtype", "float32",
                "--capacity", "4M", "--fit", "--json"]
    assert ms.main(fit_args) == 0
    tp1 = json.loads(capsys.readouterr().out)
    assert ms.main(fit_args + ["--tp", "4"]) == 0
    tp4 = json.loads(capsys.readouterr().out)
    assert tp4["params_bytes"] == 4 * 10**6 // 4
    assert tp4["max_kv_blocks"] > tp1["max_kv_blocks"]
    # the inverse ask
    rc = ms.main(["--plan", "serving", "--layers", "4", "--kv-heads", "2",
                  "--head-dim", "16", "--block-size", "32",
                  "--capacity", "1M", "--fit", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["max_kv_blocks"] == max_kv_blocks(
        2**20, n_layer=4, n_kv_head=2, head_dim=16, kv_block_size=32)
    # live-ledger mode over a telemetry JSONL log
    log = tmp_path / "serving.jsonl"
    log.write_text(json.dumps({
        "step": 3, "time": 1.0,
        "metrics": {"mem/params_bytes": {"type": "gauge", "value": 531456},
                    "mem/headroom_frac": {"type": "gauge", "value": 0.9},
                    "serving/queue_depth": {"type": "gauge", "value": 1}}})
        + "\n")
    rc = ms.main([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "mem/params_bytes" in out and "519.00 KiB" in out
    assert "0.900" in out                       # fracs render as fractions
    assert "serving/queue_depth" not in out     # mem/* only
    assert ms.main([str(tmp_path / "nope")]) == 1


def test_parse_size():
    assert ms._parse_size("16G") == 16 * 2**30
    assert ms._parse_size("16GiB") == 16 * 2**30
    assert ms._parse_size("512M") == 512 * 2**20
    assert ms._parse_size("1.5K") == 1536
    assert ms._parse_size("4096") == 4096
    assert ms._parse_size("1e6") == 10**6
    assert ms._parse_size("512B") == 512        # bare byte suffix
    with pytest.raises(ValueError):
        ms._parse_size("lots")
