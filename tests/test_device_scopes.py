"""The device-scope table (`telemetry/device_scopes.py`): which
`jax.named_scope` each instruction of an engine's compiled step programs
stands under, read from the programs' own text on demand, and the reader that
joins it to a device trace (`benchmark/readers/scope_time_share.py`).

All on the CPU at tiny sizes: names and counts, no times.
"""

import collections
import gc
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_model
from deepspeed_tpu.telemetry import device_scopes as ds
from deepspeed_tpu.telemetry import steptrace
from tests import glm_cases, granite_cases
from tests import test_mixed_step as families

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _latent():
    cfg = glm_cases._cfg()
    return glm_cases._serving(cfg, glm_cases._params(cfg),
                              one_device=True)[1]


# family -> (its serving engine, scopes its programs must name)
SERVING = {
    "dense": (families._dense,
              ("embed", "attn/qkv", "attn/kv_pool_write", "attn/walk",
               "attn/out", "mlp", "head", "sample")),
    "routed": (families._routed,
               ("embed", "attn/kv_pool_write", "mlp/moe/router",
                "mlp/moe/dispatch", "mlp/moe/experts", "mlp/moe/combine",
                "head", "sample")),
    "hybrid": (families._hybrid(granite_cases),
               ("embed", "ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/update",
                "ssm/out_proj", "attn_full/kv_pool_write", "attn_full/walk",
                "mlp/moe/combine", "mlp/moe/shared_expert", "head",
                "sample")),
    "latent": (_latent,
               ("embed", "attn_latent/mla/q_proj", "attn_latent/mla/kv_down",
                "attn_latent/mla/absorb", "attn_latent/kv_pool_write",
                "attn_latent/walk", "attn_latent/mla/out", "mlp", "head",
                "sample")),
}
STEP_PROGRAMS = {"decode_step", "prefill_step", "mixed_step"}


@pytest.fixture(autouse=True)
def _no_mesh():
    mesh_mod.clear_mesh()
    yield
    mesh_mod.clear_mesh()


@pytest.fixture(scope="module")
def tables():
    """family -> (compile counts before the table was asked for, after, the
    rows, the provider): one engine and one table a family for the file."""
    built = {}

    def table(family):
        if family not in built:
            serving = SERVING[family][0]()
            before = (dict(serving.compile_stats()),
                      dict(serving.programs.compile_counts()))
            rows = serving.steptrace.device_scopes()
            after = (dict(serving.compile_stats()),
                     dict(serving.programs.compile_counts()))
            built[family] = (before, after, rows,
                             serving.steptrace.scope_provider)
            mesh_mod.clear_mesh()
        return built[family]

    return table


def _named(rows, scope, **where):
    """The rows whose path holds `scope` from a name's start."""
    return [r for r in rows if f"/{scope}/" in f"/{r.scope}/"
            and all(getattr(r, k) == v for k, v in where.items())]


@pytest.mark.parametrize("family", sorted(SERVING))
def test_table_has_every_built_program_and_its_layers(tables, family):
    """`mixed_step` is in the table before any chunk has ridden (nothing
    has run at all: no program is compiled for a call), every scope the
    family's layers declare has rows, and every row's path is made of
    declared names."""
    before, after, rows, provider = tables(family)
    assert before == after                  # asking compiled no CALL
    assert not any(before[0].values())      # ... and none had been made
    assert {r.program for r in rows} == STEP_PROGRAMS
    assert set(provider.seconds) == STEP_PROGRAMS
    for scope in SERVING[family][1]:
        assert _named(rows, scope), scope
    undeclared = {r.scope for r in rows if ds.segments(r.scope) is None}
    assert not undeclared
    # a serving program has no backward pass
    assert not any(r.backward for r in rows)
    # and asking again reads nothing anew
    seconds = dict(provider.seconds)
    assert provider() == rows and provider.seconds == seconds


@pytest.mark.parametrize("family", sorted(SERVING))
def test_provider_holds_shapes_and_no_array(tables, family):
    """What the recorder keeps of the engine: the jitted callables and
    `ShapeDtypeStruct`s — never an array, never the engine."""
    provider = tables(family)[3]
    assert set(provider.names()) == STEP_PROGRAMS
    for name, (fn, args) in provider._programs.items():
        assert hasattr(fn, "lower"), name
        leaves = jax.tree_util.tree_leaves(args)
        assert leaves and all(isinstance(leaf, jax.ShapeDtypeStruct)
                              for leaf in leaves), name
    seen, stack = set(), [provider]
    while stack:                # the provider's own attributes, all the way
        obj = stack.pop()
        if id(obj) in seen or hasattr(obj, "lower"):
            continue            # (a jitted callable is what it may hold)
        seen.add(id(obj))
        assert not isinstance(obj, (jax.Array, np.ndarray)), type(obj)
        if isinstance(obj, dict):
            stack += list(obj.values())
        elif isinstance(obj, (list, tuple, set)):
            stack += list(obj)
        elif hasattr(obj, "__dict__"):
            stack += list(vars(obj).values())


def test_a_recorder_whose_engine_is_gone_still_answers():
    serving = families._dense()
    recorder = serving.steptrace
    assert steptrace.latest("serving") is recorder
    engine = serving.engine
    del serving, engine
    gc.collect()
    rows = steptrace.latest("serving").device_scopes()
    assert {r.program for r in rows} == STEP_PROGRAMS
    assert _named(rows, "attn/walk")


def test_a_streamed_engine_hands_over_nothing():
    assert steptrace.StepTrace("serving").device_scopes() == ()


# ----------------------------------------------------------------------
# the training step
# ----------------------------------------------------------------------

TRAIN = GPTConfig(n_layer=2, n_head=2, d_model=64, d_ff=256, max_seq_len=64,
                  vocab_size=256, dtype=jnp.float32, parallel_residual=True,
                  use_rotary=True, tie_embeddings=False)


@pytest.mark.parametrize("remat", [False, True])
def test_training_step_names_its_halves_on_both_passes(remat):
    import dataclasses
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_gpt_model(cfg=dataclasses.replace(TRAIN, remat=remat),
                             name="tiny"),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "gradient_clipping": 1.0,
                "zero_optimization": {"stage": 3}, "mesh": {"data": 4},
                "steps_per_print": 10**9})
    table = engine.steptrace.scope_provider
    assert engine.steptrace.device_scopes() == []       # no step, no shapes
    toks = np.random.default_rng(0).integers(0, 256, (8, 33)).astype(np.int32)
    for _ in range(2):
        engine.train_batch({"tokens": toks})
    assert table.names() == ["train_step"]
    compiled = engine._compiled_train_programs()
    rows = engine.steptrace.device_scopes()
    assert engine._compiled_train_programs() == compiled == 1
    assert {r.program for r in rows} == {"train_step"}
    for scope in ("attn", "attn/qkv", "attn/out", "mlp", "embed",
                  "head_loss"):
        assert _named(rows, scope, backward=False), scope
        assert _named(rows, scope, backward=True), scope
    assert _named(rows, "optimizer", backward=False)
    assert not _named(rows, "optimizer", backward=True)
    # the program's own ZeRO transitions: a gradient's scatter, a
    # parameter's gather, on a mesh that has them
    collectives = [r for r in rows if r.opcode.startswith(
        ("all-gather", "reduce-scatter", "all-reduce"))]
    assert collectives
    assert not {r.scope for r in rows if ds.segments(r.scope) is None}
    leaves = jax.tree_util.tree_leaves(table._programs["train_step"][1])
    assert all(isinstance(leaf, jax.ShapeDtypeStruct) for leaf in leaves)


# ----------------------------------------------------------------------
# the op_name normaliser and the text parser
# ----------------------------------------------------------------------

@pytest.mark.parametrize("op_name, scope, backward", [
    ("jit(f)/jit(main)/transpose(jvp(mlp))/dot_general", "mlp", True),
    ("jit(f)/jit(main)/jvp(mlp)/dot_general", "mlp", False),
    ("jit(mixed_step)/while/body/closed_call/moe/combine/reduce_sum",
     "moe/combine", False),
    ("while/body/closed_call/mlp/moe/combine/reduce_sum", "mlp/moe/combine",
     False),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/qkv/dot_general", "attn/qkv", True),
    ("jit(f)/transpose(jvp(ssm/in_proj))/mul", "ssm/in_proj", True),
    ("jit(prefill_step)/while/body/closed_call/ssm/scan/jit(cumsum)/"
     "ssm_chunk_scan/reduce_window_sum", "ssm/scan", False),
    ("jit(train_step)/jit(_threefry_split)/Engine.apply_grads/while/body/add",
     "", False),
    ("jit(f)/jvp(embed)/jvp(jit(_take))/gather", "embed", False),
    ("jit(f)/head_loss/jvp(btd,vd->btv)/dot_general", "head_loss", False),
    ("jit(f)/attn/custom_vjp_call/dstpu_flash_fwd/pallas_call",
     "attn/dstpu_flash_fwd", False),
    ("jit(f)/attn/while/body/cond/branch_1_fun/vmap()/mul", "attn", False),
    ("jit(f)/mlp/mul;jit(f)/attn/mul", "mlp", False),
    ("jit(f)/dot_general", "", False),
    ("params['wte']", "", False),
    ("", "", False),
])
def test_scope_of_an_op_name(op_name, scope, backward):
    assert ds.scope_of(op_name) == (scope, backward)


def test_segments_cut_a_path_into_declared_names():
    assert ds.segments("mlp/moe/router") == ["mlp", "moe/router"]
    assert ds.segments("attn_full/kv_pool_write") == ["attn_full",
                                                      "kv_pool_write"]
    assert ds.segments("attn/walk/dstpu_paged_decode") == [
        "attn", "walk", "dstpu_paged_decode"]
    assert ds.segments("") == []
    assert ds.segments("attn/softmax_of_my_own") is None
    assert ds.top("mlp/moe/router") == "mlp" and ds.top("") == ""


TEXT = """HloModule jit_step, is_scheduled=true

FileNames
1 "gpt.py"

%fused_computation (p: bf16[8,64]) -> bf16[8,64] {
  %p = bf16[8,64]{1,0} parameter(0)
  ROOT %mul.1 = bf16[8,64]{1,0} multiply(%p, %p), metadata={op_name="jit(step)/while/body/mlp/mul" stack_frame_id=3}
}

%fused_computation.1 (p.1: bf16[8,64]) -> (bf16[8,64], bf16[8,64]) {
  %p.1 = bf16[8,64]{1,0} parameter(0)
  %a = bf16[8,64]{1,0} add(%p.1, %p.1), metadata={op_name="jit(step)/while/body/attn/qkv/add"}
  %b = bf16[8,64]{1,0} multiply(%p.1, %p.1), metadata={op_name="jit(step)/while/body/attn/out/mul"}
  ROOT %t = (bf16[8,64]{1,0}, bf16[8,64]{1,0}) tuple(%a, %b)
}

%fused_computation.2 (p.2: bf16[8,64]) -> bf16[8,64] {
  %p.2 = bf16[8,64]{1,0} parameter(0)
  %c = bf16[8,64]{1,0} add(%p.2, %p.2), metadata={op_name="jit(step)/while/body/attn/out/add"}
  ROOT %d = bf16[8,64]{1,0} multiply(%c, %c), metadata={op_name="jit(step)/while/body/mlp/mul"}
}

%region_0.1 (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%x, %y), metadata={op_name="jit(step)/mlp/reduce_sum"}
}

%body (arg: (s32[], bf16[8,64])) -> (s32[], bf16[8,64]) {
  %arg = (s32[], bf16[8,64]{1,0}) parameter(0)
  %gte = bf16[8,64]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %fusion.7 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(%gte), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/while/body/mlp/mul" stack_frame_id=3}, backend_config={"x":{"y":"1"}}
  %fusion.8 = (bf16[8,64]{1,0}, bf16[8,64]{1,0}) fusion(%fusion.7), kind=kLoop, calls=%fused_computation.1
  %copy.3 = bf16[8,64]{0,1} copy(%fusion.7)
  %fusion.9 = bf16[8,64]{1,0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/while/body/mlp/mul"}
  %custom-call.2 = bf16[8,64]{1,0} custom-call(%fusion.9), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[8,64]{1,0}}, metadata={op_name="jit(step)/while/body/attn/walk/dstpu_paged_decode/pallas_call"}
  %reduce.4 = f32[] reduce(%custom-call.2, %gte), dimensions={0,1}, to_apply=%region_0.1, metadata={op_name="jit(step)/while/body/transpose(jvp(mlp))/reduce_sum"}
  ROOT %tuple.5 = (s32[], bf16[8,64]{1,0}) tuple(%gte, %fusion.9)
}

ENTRY %main.3 (tok: s32[8]) -> bf16[8,64] {
  %tok = s32[8]{0} parameter(0), metadata={op_name="tok"}
  %gather.1 = bf16[8,64]{1,0} gather(%tok), metadata={op_name="jit(step)/embed/jit(_take)/gather"}
  %while.2 = (s32[], bf16[8,64]{1,0}) while(%gather.1), condition=%cond, body=%body, metadata={op_name="jit(step)/while"}
  ROOT %gte.2 = bf16[8,64]{1,0} get-tuple-element(%while.2), index=1
}
"""


def test_rows_of_a_compiled_text():
    rows = {r.name: r for r in ds.rows_of("step", TEXT)}
    # the entry's and the loop body's instructions; nothing of what is fused
    # or applied
    assert set(rows) == {"arg", "gte", "fusion.7", "fusion.8", "copy.3",
                         "fusion.9", "custom-call.2", "reduce.4", "tuple.5",
                         "tok", "gather.1", "while.2", "gte.2"}
    one = rows["fusion.7"]
    assert one == ds.ScopeRow("step", "fusion.7", "fusion", "", "bf16[8,64]",
                              "mlp", False, False)
    # no metadata of its own: what its fused instructions share
    assert rows["fusion.8"].scope == "attn" and not rows["fusion.8"].straddles
    assert rows["fusion.8"].result == "(bf16[8,64], bf16[8,64])"
    # a layout copy the compiler made: what made its operand
    assert rows["copy.3"].scope == "mlp"
    # its own metadata speaks; the fused instructions say it straddles
    assert rows["fusion.9"].scope == "mlp" and rows["fusion.9"].straddles
    call = rows["custom-call.2"]
    assert (call.target, call.scope) == (
        "tpu_custom_call", "attn/walk/dstpu_paged_decode")
    assert rows["reduce.4"].backward and rows["reduce.4"].scope == "mlp"
    assert rows["gather.1"].scope == "embed"
    assert rows["while.2"].scope == "" and rows["tok"].scope == ""


# ----------------------------------------------------------------------
# the reader
# ----------------------------------------------------------------------

@pytest.fixture
def reader():
    sys.path.insert(0, BENCH)
    try:
        import harness
        yield harness.load_module("readers", "scope_time_share")
    finally:
        sys.path.remove(BENCH)


def _row(program, name, result, scope, opcode="fusion", target="",
         backward=False, straddles=False):
    return ds.ScopeRow(program, name, opcode, target, result, scope, backward,
                       straddles)


ROWS = [
    _row("decode_step", "fusion.1", "bf16[8,64]", "mlp/moe/router"),
    _row("decode_step", "fusion.2", "bf16[8,64]", "mlp/moe/combine"),
    _row("decode_step", "fusion.3", "bf16[8,64]", "attn_full/qkv"),
    _row("decode_step", "fusion.4", "f32[8]", ""),
    _row("decode_step", "custom-call.5", "bf16[8,64]", "attn/walk",
         opcode="custom-call", target="tpu_custom_call"),
    # the same label under another scope in another program
    _row("mixed_step", "fusion.2", "bf16[8,64]", "attn/out"),
    # ... and under the same one
    _row("mixed_step", "fusion.1", "bf16[8,64]", "mlp/moe/router"),
    _row("train_step", "fusion.6", "bf16[8,64]", "mlp", backward=True),
]
TRACE = {"busy_s": 10.0, "ops": {
    "fusion.1 fusion bf16[8,64]": 1.0, "fusion.2 fusion bf16[8,64]": 2.0,
    "fusion.3 fusion bf16[8,64]": 0.5, "fusion.4 fusion f32[8]": 0.25,
    "custom-call.5 custom-call:tpu_custom_call bf16[8,64]": 3.0,
    "fusion.6 fusion bf16[8,64]": 1.5, "copy.9 copy bf16[8,64]": 0.75}}


def _recorder(monkeypatch, rows):
    recorder = steptrace.StepTrace("serving")
    recorder.scope_provider = lambda: rows
    monkeypatch.setitem(steptrace._LATEST, "serving", recorder)


def _read(reader, scopes, **args):
    return reader.read({}, TRACE, {"subsystem": "serving", "scopes": scopes,
                                   **args})


def test_reader_sums_the_trace_by_scope(reader, monkeypatch):
    _recorder(monkeypatch, ROWS)
    assert reader.label(ROWS[4]) == \
        "custom-call.5 custom-call:tpu_custom_call bf16[8,64]"
    assert _read(reader, ["moe/router"]) == pytest.approx(10.0)
    assert _read(reader, ["mlp"]) == pytest.approx(25.0)    # + the backward
    assert _read(reader, ["mlp"], backward=False) == pytest.approx(10.0)
    assert _read(reader, ["mlp"], backward=True) == pytest.approx(15.0)
    # a name matches from its start: `attn` is not `attn_full`
    assert _read(reader, ["attn"]) == pytest.approx(30.0)
    assert _read(reader, ["attn", "attn_full"]) == pytest.approx(35.0)
    assert _read(reader, ["walk"]) == pytest.approx(30.0)
    # two programs scope `fusion.2` differently: nobody's; no row, or a row
    # with no scope: unnamed
    booked = reader.book(ROWS)
    assert booked["fusion.2 fusion bf16[8,64]"][0] == reader.AMBIGUOUS
    assert booked["fusion.4 fusion f32[8]"][0] == reader.UNNAMED
    assert "copy.9 copy bf16[8,64]" not in booked
    assert _read(reader, ["*"]) == pytest.approx(60.0)
    assert _read(reader, ["moe/combine"]) == 0.0
    # ... unless the programs asked for agree
    assert _read(reader, ["moe/combine"], programs=["decode_step"]) == \
        pytest.approx(20.0)
    assert _read(reader, ["*"], programs=["decode_step"]) == \
        pytest.approx(65.0)


def test_reader_never_passes_the_busy_time(reader, monkeypatch):
    _recorder(monkeypatch, ROWS)
    ops = sum(TRACE["ops"].values())
    assert _read(reader, ["*"]) <= 100.0 * ops / TRACE["busy_s"] <= 100.0


def test_reader_leaves_the_metric_out_without_a_table(reader, monkeypatch):
    args = {"subsystem": "serving", "scopes": ["*"]}
    _recorder(monkeypatch, ())                  # an engine with no program
    assert reader.read({}, TRACE, args) is None
    monkeypatch.setitem(steptrace._LATEST, "serving",
                        collections.namedtuple("Old", "facts")({}))
    assert reader.read({}, TRACE, args) is None     # a program with no table
    monkeypatch.delitem(steptrace._LATEST, "serving")
    assert reader.read({}, TRACE, args) is None     # no recorder at all
    _recorder(monkeypatch, ROWS)
    assert reader.read({}, None, args) is None      # no trace
    assert reader.read({}, {"busy_s": 0.0, "ops": {}}, args) is None


def test_the_benchmark_has_the_entry_and_its_file():
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "scoped_device_time_share.throughput"]
    tokens, = [m for m in bench["end_to_end"]
               if m["name"] == "serve_tokens_per_s"]
    assert entry["workloads"] == tokens["workloads"]
    assert entry["moves"] == "serve_tokens_per_s"
    with open(os.path.join(BENCH, "layer_metrics",
                           entry["name"] + ".json")) as f:
        spec = json.load(f)
    assert spec == {"reader": "scope_time_share",
                    "args": {"subsystem": "serving", "scopes": ["*"]}}


def test_the_document_lists_every_scope():
    """`docs/profiling.md` holds the table `python -m
    deepspeed_tpu.telemetry.device_scopes` prints, line for line."""
    with open(os.path.join(ROOT, "docs", "profiling.md")) as f:
        text = f.read()
    assert ds._markdown() in text
