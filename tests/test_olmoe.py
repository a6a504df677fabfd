"""OLMoE on the CPU at a small size (4 layers, hidden 64, 8 experts, top-4,
seeded random weights): the system against the plain float32 reference the
benchmark's chip check uses (`benchmark/references/olmoe.py`, loaded as
`harness.load_module` does, so the CPU tests and the chip hold the system to
one text), the routed path's invariance under chunking and batching, the
grouped matmul against the dense per-expert loop, and the scan form of the
paged programs against the per-layer loop form."""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.moe_gpt import (MoEGPTConfig, init_moe_gpt_params,
                                          make_moe_gpt_decode_model,
                                          moe_gpt_forward, moe_gpt_init_fn,
                                          moe_gpt_routing)
from deepspeed_tpu.ops.pallas.moe_gmm import (moe_gmm, moe_gmm_reference,
                                              pair_tables)
from deepspeed_tpu.parallel.moe import routed_experts, topk_routing
from tests.paged_cases import assert_one_compile_each

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

PUBLISHED = {"model_type": "olmoe", "num_hidden_layers": 4, "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "intermediate_size": 32, "num_experts": 8,
             "num_experts_per_tok": 4, "norm_topk_prob": False,
             "rope_theta": 10000, "rms_norm_eps": 1e-5, "vocab_size": 256,
             "clip_qkv": None, "rope_scaling": None}


def _reference():
    path = os.path.join(BENCH, "references", "olmoe.py")
    spec = importlib.util.spec_from_file_location("benchmark_ref_olmoe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _config(dtype=jnp.float32, **over):
    c = PUBLISHED
    return MoEGPTConfig(
        vocab_size=c["vocab_size"], n_layer=c["num_hidden_layers"],
        n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"],
        d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        max_seq_len=128, use_rotary=True, rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], use_swiglu=True, use_rmsnorm=True,
        qk_norm=True, tie_embeddings=False, num_experts=c["num_experts"],
        top_k=c["num_experts_per_tok"], norm_topk_prob=c["norm_topk_prob"],
        moe_freq=1, dtype=dtype, **over)


def _params(cfg, seed=3):
    """Seeded random weights with norm scales and biases that are not the
    identity, so a norm left out or misplaced shows."""
    params = jax.jit(moe_gpt_init_fn(cfg, dtype=jnp.float32))(
        jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    blocks = dict(params["blocks"])
    for name in ("ln1_scale", "ln2_scale", "q_norm_scale", "k_norm_scale"):
        blocks[name] = jnp.asarray(
            rng.uniform(0.5, 1.5, blocks[name].shape), jnp.float32)
    blocks["moe_gate_w"] = blocks["moe_gate_w"] * 20.0   # a peaked router
    return {**params, "blocks": blocks,
            "lnf_scale": jnp.asarray(rng.uniform(0.5, 1.5, (cfg.d_model,)),
                                     jnp.float32)}


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, PUBLISHED["vocab_size"],
                                                (n,), np.int32)


# Tolerance of the float32 comparisons below: program and reference both
# compute in float32 on the CPU and differ only in the order of their sums
# (fused qkv, grouped experts, k-way combine), so logits of size ~1 agree to
# a few 1e-6; 2e-5 leaves a factor of ten. A norm, a rotation, a probability
# or an expert out of place moves logits by 1e-2 or more.
F32_ATOL = 2e-5


def test_full_forward_matches_the_reference():
    ref = _reference()
    cfg = _config()
    params = _params(cfg)
    arch = ref.arch_from_config(PUBLISHED)
    tokens = _tokens(40)
    want, want_sets = ref.forward(params, jnp.asarray(tokens), arch)
    got, _ = moe_gpt_forward(params, jnp.asarray(tokens[None]), cfg,
                             training=False)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=F32_ATOL, rtol=0)
    got_sets = moe_gpt_routing(params, jnp.asarray(tokens[None]), cfg)[:, 0]
    assert got_sets.shape == (4, 40, 4)
    np.testing.assert_array_equal(np.asarray(got_sets), np.asarray(want_sets))
    # the rows the experts see are not all alike: the router spreads them
    assert len(np.unique(np.asarray(want_sets))) == 8


def _paged_logits(spec, params, prompt, chunk, block, window, dtype):
    """Chunked prefill then `window` decode steps in one scan on the carried
    pool (the served decode window's body): logits after the prompt and of
    each decode step, with the tokens the program fed itself."""
    pool = spec.init_paged_pool(8, block, dtype)
    table = np.arange(1, 8, dtype=np.int32)[None]           # 0 = trash block
    for start in range(0, len(prompt), chunk):
        seg = prompt[start:start + chunk]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(seg)] = seg
        out, pool, counts = jax.jit(spec.prefill_paged_fn)(
            params, toks, np.asarray([start], np.int32),
            np.asarray([len(seg) - 1], np.int32), pool, table)
    assert counts.shape == (4,) and int(counts[1]) == 4 * chunk * 4

    def run(params, tok, pos, pool):
        def body(carry, _):
            tok, pos, pool = carry
            logits, pool, _ = spec.decode_paged_fn(params, tok, pos, pool,
                                                   table)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (nxt, pos + 1, pool), (logits, nxt)
        return jax.lax.scan(body, (tok, pos, pool), None, length=window)[1]

    first = jnp.argmax(out, -1).astype(jnp.int32)
    logits, fed = jax.jit(run)(params, first,
                               jnp.asarray([len(prompt)], jnp.int32), pool)
    seq = np.concatenate([prompt, np.asarray(first), np.asarray(fed)[:-1, 0]])
    return np.asarray(out[0]), np.asarray(logits[:, 0]), seq


@pytest.mark.parametrize("window", [1, 4])
def test_paged_prefill_then_decode_window_matches_the_reference(window):
    ref = _reference()
    cfg = _config()
    params = _params(cfg)
    arch = ref.arch_from_config(PUBLISHED)
    spec = make_moe_gpt_decode_model(cfg, params=params, name="olmoe-small")
    prompt = _tokens(27, seed=window)
    after_prompt, steps, seq = _paged_logits(spec, params, prompt, chunk=16,
                                             block=16, window=window,
                                             dtype=jnp.float32)
    want = np.asarray(ref.logits(params, jnp.asarray(seq, jnp.int32), arch))
    np.testing.assert_allclose(after_prompt, want[len(prompt) - 1],
                               atol=F32_ATOL, rtol=0)
    for step in range(window):
        np.testing.assert_allclose(steps[step], want[len(prompt) + step],
                                   atol=F32_ATOL, rtol=0)


@pytest.mark.parametrize("window", [1, 4])
def test_scheduler_serves_the_reference_greedy_tokens(window):
    """Through `init_inference(...).serving(...)`: the tokens are the
    reference's greedy continuation (the peaked logits of `_params` leave no
    near-tie), every program compiled once, and the routed counters arrive
    on the step ring and in `stats()`."""
    ref = _reference()
    cfg = _config()
    params = _params(cfg)
    arch = ref.arch_from_config(PUBLISHED)
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    engine = deepspeed_tpu.init_inference(
        make_moe_gpt_decode_model(cfg, params=params, name="olmoe-small"),
        config={"dtype": "float32", "kv_cache_dtype": "float32",
                "greedy": True, "kv_block_size": 16, "max_out_tokens": 128})
    serving = engine.serving(max_slots=4, max_context=128, prefill_chunk=16,
                             num_kv_blocks=40, decode_steps_per_sync=window)
    prompts = [_tokens(n, seed=n) for n in (21, 5, 33)]
    done = serving.run([Request(uid=i, tokens=p, max_new_tokens=6,
                                stop_on_eos=False)
                        for i, p in enumerate(prompts)])
    for i, prompt in enumerate(prompts):
        seq = list(prompt)
        for _ in range(6):
            seq.append(int(np.asarray(ref.logits(
                params, jnp.asarray(seq, jnp.int32), arch))[-1].argmax()))
        assert list(done[i].tokens) == seq[len(prompt):], (window, i)
    stats = serving.stats()
    assert_one_compile_each(serving)
    counters = stats["step_counters"]
    # every router call routes all its rows: 4 experts a row, 4 layers
    ring = [r.counters for r in serving.steptrace.records()]
    assert all(len(c) == 4 for c in ring)
    assert counters["moe_assignments"] == sum(c[1] for c in ring) > 0
    chunks, decodes = stats["prefill_chunks"], stats["decode_steps"]
    # a chunk that rode a decode call went through the router WITH that
    # call's first token: one call a layer for the rows of both
    assert stats["fused_chunks"] > 0
    assert counters["moe_router_calls"] == 4 * (
        chunks - stats["fused_chunks"] + decodes * window)
    assert counters["moe_assignments"] == 4 * 4 * (
        chunks * 16 + decodes * window * 4)
    assert 0 < counters["moe_active_experts"] <= 8 * counters[
        "moe_router_calls"]


def test_routing_and_experts_do_not_depend_on_chunking_or_batching():
    """The scheduler's parity invariant, at the routed layer: a token's
    experts, probabilities and result are the same whatever else is in the
    call — the whole batch, halves, a permutation, one row alone. The
    experts are equal exactly; the numbers to a float32 rounding (XLA:CPU
    multiplies one row with another routine than 24, an ulp apart; on the
    MXU a row's product does not depend on its neighbours)."""
    cfg = _config()
    params = _params(cfg)
    p = jax.tree_util.tree_map(lambda a: a[1], params["blocks"])
    experts = {"w_gate_up": p["moe_w_gate_up"], "w_down": p["moe_w_down"]}
    x = jnp.asarray(np.random.default_rng(5).normal(0, 1, (24, 64)),
                    jnp.float32)

    def routed(rows):
        top_p, top_e = topk_routing(rows, p["moe_gate_w"], cfg.top_k)
        out, counters = routed_experts(rows, top_p, top_e, experts)
        assert int(counters[1]) == rows.shape[0] * cfg.top_k
        return np.asarray(top_p), np.asarray(top_e), np.asarray(out)

    whole = routed(x)
    perm = np.random.default_rng(6).permutation(24)
    for rows in (slice(0, 12), slice(12, 24), slice(7, 8), perm):
        part = routed(x[rows])
        np.testing.assert_array_equal(whole[1][rows], part[1])
        for a, b in ((whole[0], part[0]), (whole[2], part[2])):
            np.testing.assert_allclose(a[rows], b, atol=1e-6, rtol=1e-6)


def _dense_loop(lhs, rhs, sizes):
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    start = 0
    for e, n in enumerate(sizes):
        out[start:start + n] = np.asarray(lhs[start:start + n], np.float32) \
            @ np.asarray(rhs[e], np.float32)
        start += n
    return out


@pytest.mark.parametrize("sizes", [
    [100, 10, 10, 136, 0, 0, 0, 0],      # a row tile across three groups
    [0, 0, 256, 0, 0, 0, 0, 0],          # one group holds every row
    [0, 1, 0, 127, 1, 127, 0, 0],        # empty groups between one-row ones
    [32] * 8,
], ids=["straddle3", "one_group", "empty_groups", "even"])
def test_grouped_matmul_kernel_matches_the_dense_per_expert_loop(sizes):
    rng = np.random.default_rng(len(sizes) + sizes[0])
    lhs = jnp.asarray(rng.normal(0, 1, (256, 128)), jnp.float32)
    rhs = jnp.asarray(rng.normal(0, 1, (8, 128, 256)), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    want = _dense_loop(lhs, rhs, sizes)
    kernel = moe_gmm(lhs, rhs, group_sizes, interpret=True)
    oracle = moe_gmm_reference(lhs, rhs, group_sizes)
    np.testing.assert_array_equal(np.asarray(kernel), np.asarray(oracle))
    # the same groups inside a longer stack, found by an offset
    stack = jnp.concatenate([rhs[:3] * 0 + 7.0, rhs, rhs[:2] * 0 - 7.0])
    for got in (moe_gmm(lhs, stack, group_sizes, 3, interpret=True),
                jax.jit(moe_gmm_reference)(lhs, stack, group_sizes, 3)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))
    np.testing.assert_allclose(np.asarray(kernel), want, atol=1e-4, rtol=1e-5)
    # a grid step exists for a (row tile, group) pair that has rows, and for
    # no other: idle experts are never visited
    group, tile, _, _, pairs = (np.asarray(t) for t in
                                pair_tables(group_sizes, 256, 128))
    real = {(int(t), int(g)) for t, g in zip(tile[:pairs[0]],
                                             group[:pairs[0]])}
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    expected = {(t, e) for e, n in enumerate(sizes) if n
                for t in range(bounds[e] // 128, (bounds[e + 1] - 1) // 128 + 1)}
    assert real == expected and len(real) == pairs[0]
    assert set(zip(tile[pairs[0]:], group[pairs[0]:])) <= {
        (tile[pairs[0] - 1], group[pairs[0] - 1])}


def _unstacked(params):
    """The same weights in the per-layer layout (`params["moe"][str(l)]`),
    which the paged programs walk with the Python loop over layers — the
    form every MoE model had before the experts were stacked."""
    blocks = {k: v for k, v in params["blocks"].items()
              if not k.startswith("moe_")}
    moe = {str(l): {k[len("moe_"):]: v[l]
                    for k, v in params["blocks"].items()
                    if k.startswith("moe_")}
           for l in range(params["blocks"]["moe_gate_w"].shape[0])}
    return {**params, "blocks": blocks, "moe": moe}


def test_scan_paged_with_experts_equals_the_per_layer_loop():
    cfg = _config()
    params = _params(cfg)
    prompt = _tokens(27, seed=9)
    scan = _paged_logits(make_moe_gpt_decode_model(cfg, params=params),
                         params, prompt, 16, 16, 4, jnp.float32)
    loose = _unstacked(params)
    loop = _paged_logits(make_moe_gpt_decode_model(cfg, params=loose),
                         loose, prompt, 16, 16, 4, jnp.float32)
    for a, b in zip(scan, loop):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=0)


def test_top1_presets_serve_through_the_routed_path():
    """`top_k` 1 on per-layer gelu experts with biases (the MoE-GPT presets):
    paged serving equals `generate()`'s contiguous path token for token."""
    cfg = MoEGPTConfig(n_layer=2, n_head=4, d_model=64, d_ff=128,
                       vocab_size=256, max_seq_len=128, num_experts=4,
                       moe_freq=2, dtype=jnp.float32)
    params = init_moe_gpt_params(cfg, seed=1)
    params["moe"]["1"]["b_up"] = params["moe"]["1"]["b_up"] + 0.1
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    engine = deepspeed_tpu.init_inference(
        make_moe_gpt_decode_model(cfg, params=params, name="top1"),
        config={"dtype": "float32", "kv_cache_dtype": "float32",
                "greedy": True, "kv_block_size": 16, "max_out_tokens": 128})
    prompt = _tokens(19, seed=2)
    want = np.asarray(engine.generate(jnp.asarray(prompt[None]),
                                      max_new_tokens=5))[0]
    serving = engine.serving(max_slots=2, max_context=128, prefill_chunk=16,
                             num_kv_blocks=16)
    done = serving.run([Request(uid=0, tokens=prompt, max_new_tokens=5,
                                stop_on_eos=False)])
    assert list(done[0].tokens) == list(want)
    assert serving.stats()["step_counters"]["moe_router_calls"] > 0


def test_device_initializer_keeps_the_served_dtype():
    """Every leaf of the jitted initializer comes back in the type asked for
    (a numpy float64 scale once promoted the down projections to float32:
    2 GiB at the published widths)."""
    cfg = _config()
    shapes = jax.eval_shape(moe_gpt_init_fn(cfg, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    assert {leaf.dtype for leaf in jax.tree_util.tree_leaves(shapes)} == {
        jnp.dtype(jnp.bfloat16)}
    blocks = shapes["blocks"]
    assert blocks["moe_w_gate_up"].shape == (4, 8, 64, 64)
    assert blocks["moe_w_down"].shape == (4, 8, 32, 64)
    assert not any(k.startswith("mlp_") for k in blocks)     # no dense MLP


def test_benchmark_holds_the_cells_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}["serve_olmoe_generate"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe-1b-7b-0125-8l", "generate_backlog", 1)
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, config["file"])) as f:
        published = json.load(f)
    assert config["reduced"] == published["reduced"] == ["num_hidden_layers"]
    assert published["reduced_from"] == {"num_hidden_layers": 16}
    for key, value in {"hidden_size": 2048, "intermediate_size": 1024,
                       "num_attention_heads": 16, "num_key_value_heads": 16,
                       "num_experts": 64, "num_experts_per_tok": 8,
                       "norm_topk_prob": False, "vocab_size": 50304,
                       "max_position_embeddings": 4096,
                       "rope_theta": 10000}.items():
        assert published[key] == value, key
    for kind, name in (("drivers", published["driver"] + ".py"),
                       ("references", published["reference"] + ".py"),
                       ("traffic", cell["traffic"] + ".json")):
        assert os.path.exists(os.path.join(BENCH, kind, name)), name
    reported = [m["name"] for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    assert sorted(reported) == ["serve_tokens_per_s", "setup_s"]
    layered = [m for m in bench["per_layer"]
               if cell["name"] in m.get("workloads", ())]
    # PR 27's 15, PR 28's live-step share, PR 30's two of the prefill kernel,
    # PR 33's two of the mixed step (its program's share, the chunks riding),
    # PR 35's share of calls dispatched behind the call in flight: 21 the
    # cell has to itself today, and as many at least once the `.generate`
    # copies fold into entries it shares with the other backlog cells
    assert len(layered) >= 21
    for metric in layered:
        with open(os.path.join(BENCH, "layer_metrics",
                               metric["name"] + ".json")) as f:
            spec = json.load(f)
        if "workloads" in spec:
            assert spec["workloads"] == metric["workloads"]
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    with open(os.path.join(BENCH, "traffic", "generate_backlog.json")) as f:
        traffic = json.load(f)
    assert traffic["min_queue"] == published["serving"]["max_slots"] == 64


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_the_cpu(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(BENCH, "checks", "rehearse_cell.py"),
           "--workload", "serve_olmoe_generate", "--seed", str(2**31 + 17),
           "--seconds", "2", "--trace", str(trace)]
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    notes = line["notes"]
    assert notes["logits"]["decode_window_checked"] == 6
    assert notes["step_counters"]["moe_assignments"] > 0
    if trace:
        # the counter-read metrics need no device; the trace-read ones are
        # left out on the CPU
        assert {"moe_expert_load_max_over_mean.generate",
                "moe_idle_expert_share.generate",
                "sched_decode_occupancy.generate"} <= set(line["metrics"])
        assert "moe_gmm_roofline.generate" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
