"""GLM-4.7-Flash (`models/glm4_moe_lite.py`): latent attention (MLA) through
the paged pool — the family's forward against the float32 reference, the
absorbed form against the expanded one, chunked prefill and decoding through
a pool of the LATENT kind in the mixed program, and what the scheduler takes
and refuses of that kind. Small sizes, seeded weights, the CPU; what the
chip's compiler makes of the served sizes is `tests/test_steptrace.py`'s."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_cache import transplant_blocks
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import exaone_moe as em
from deepspeed_tpu.models import mla
from deepspeed_tpu.ops import attention_dispatch as attn_dispatch
from deepspeed_tpu.ops.pallas.mla_attention import latent_entry_width

from tests.glm_cases import _arch, _cfg, _params, _serving, gm, ref

pytestmark = pytest.mark.serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "serve_glm47flash_longctx_queue"


def _requests(lengths, seed=1, vocab=128):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, tokens=rng.integers(0, vocab, (n,), np.int32),
                    max_new_tokens=m, stop_on_eos=False)
            for i, (n, m) in enumerate(lengths)]


def _assert_reference_tokens(params, cfg, requests, results):
    """Every emitted token is the reference's greedy token on the sequence
    so far (teacher-forced with the program's own tokens: one causal forward
    over prompt + emitted gives every position's logits)."""
    assert _greedy_agreement(params, cfg, requests, results) == 1.0


def _greedy_agreement(params, cfg, requests, results):
    arch = _arch(cfg)
    same = total = 0
    for req in requests:
        emitted = np.asarray(results[req.uid].tokens)
        assert len(emitted) == req.max_new_tokens
        seq = np.concatenate([req.tokens, emitted[:-1]]).astype(np.int32)
        logits = ref.logits(params, jnp.asarray(seq), arch)
        want = np.asarray(jnp.argmax(logits[len(req.tokens) - 1:], axis=-1))
        same += int((want == emitted).sum())
        total += len(emitted)
    return same / total


# ----------------------------------------------------------------------
# the model against the reference
# ----------------------------------------------------------------------


def test_forward_matches_the_reference_in_float32():
    cfg = _cfg()
    params = _params(cfg)
    tokens = np.random.default_rng(0).integers(0, 128, (40,), np.int32)
    want, want_sets, latents = ref.forward(params, jnp.asarray(tokens),
                                           _arch(cfg))
    routing = []
    got = gm.glm4_moe_lite_forward(params, jnp.asarray(tokens)[None], cfg,
                                   routing=routing)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    # two sparse layers, four experts a token, and a cache entry a token a
    # layer of rank + rope values
    np.testing.assert_array_equal(
        np.sort(np.stack([np.asarray(r) for r in routing]), -1),
        np.asarray(want_sets))
    assert latents.shape == (3, 40, cfg.kv_lora_rank + cfg.qk_rope_head_dim)


def test_the_plan_is_a_dense_layer_then_one_scanned_period():
    cfg = _cfg(layers=5)
    assert em.layer_plan(cfg) == ([(em.LATENT, em.DENSE)],
                                  [(em.LATENT, em.SPARSE)], 4)
    assert cfg.head_dim == cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    assert not cfg.post_norm and not cfg.qk_norm_per_head
    (kind,) = em.cache_kinds(cfg, 16)
    assert (kind.name, kind.layers, kind.block, kind.window, kind.leaves) \
        == ("latent", 5, 16, 0, ("ckv",))


def test_absorbed_equals_expanded_for_one_layer_in_float32():
    """`paged_mla_half` (absorbed: the query folded through W_kb's key half,
    the walk over the pool's entries, the result unfolded through its value
    half) against `mla_attn_half` (expanded: every head's keys and values
    rebuilt), one layer, one chunk from position 0."""
    cfg = _cfg()
    p = _params(cfg)["prologue"][0]
    rng = np.random.default_rng(2)
    T, block = 24, 8
    x = jnp.asarray(rng.normal(size=(1, T, cfg.d_model)), jnp.float32)
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    want, _, _ = mla.mla_attn_half(x, p, cfg, positions)
    width = latent_entry_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    pool = {"ckv": jnp.zeros((6, 1, block, width), jnp.float32)}
    tables = jnp.asarray([[4, 2, 5]], jnp.int32)
    got, pool = mla.paged_mla_half(x, p, pool, positions, tables, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    # what the pool holds: the reference's c and k_r, then zeros
    entries = np.asarray(pool["ckv"])[np.asarray(tables[0]), 0].reshape(
        T, width)
    r = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    assert not entries[:, r:].any() and entries[:, :r].any()


@pytest.mark.parametrize("chunk", [16, 32], ids=["chunk=block", "chunk=2x"])
def test_chunks_and_decoding_through_the_latent_pool_match_the_reference(
        chunk):
    """Prompts of different lengths prefilled in chunks and decoded through
    the paged latent pool, chunks riding the slots' decode calls (one
    device: the mixed program), a slot reused: every token the reference's
    greedy token on the same sequence."""
    cfg = _cfg()
    params = _params(cfg)
    engine, srv = _serving(cfg, params, one_device=True, prefill_chunk=chunk)
    requests = _requests([(37, 9), (5, 12), (50, 6), (18, 7), (70, 5)])
    results = srv.run(requests)
    _assert_reference_tokens(params, cfg, requests, results)
    assert srv.compile_stats() == {"decode_step": 1, "prefill_step": 1,
                                   "mixed_step": 1}
    assert srv.fused_chunks > 0
    assert srv.allocator.num_free == srv.allocator.capacity
    stats = srv.stats()
    assert set(stats["attention_program"].values()) \
        == {"mla_gather", "mla_gather+mla_gather"}
    width = latent_entry_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    assert stats["kv_pool_kinds"] == {"latent": {
        "layers": 3, "block": 16, "window": 0, "blocks": 40,
        "bytes": 3 * 40 * 16 * width * 4,
        # stored in whole lane tiles; the model's entry is `[c | k_r]`
        "bytes_per_token": 3 * width * 4,
        "model_bytes_per_token":
            3 * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 4}}
    records = srv.steptrace.records()
    # the latent walk's pairs are the decode walk's; no chunk kernel here
    assert sum(r.latent_walk_blocks for r in records) \
        == sum(r.decode_live_blocks for r in records) > 0
    assert not any(r.latent_chunk_positions for r in records)
    assert srv.audit().ok and srv.close().ok


def test_bfloat16_serving_stays_close_to_the_reference():
    cfg = _cfg(dtype=jnp.bfloat16)
    params = _params(cfg, dtype=jnp.bfloat16)
    engine, srv = _serving(cfg, params, dtype="bfloat16", one_device=True)
    requests = _requests([(37, 8), (21, 8), (60, 8)], seed=3)
    results = srv.run(requests)
    assert _greedy_agreement(params, cfg, requests, results) > 0.8


# ----------------------------------------------------------------------
# the kernels on the served path (steered onto the in-place form: on the
# CPU the rule declines, and the kernels run in the interpreter)
# ----------------------------------------------------------------------


def test_the_kernels_serve_the_same_tokens_through_the_carried_pool(
        monkeypatch):
    monkeypatch.setattr(attn_dispatch, "kv_pool_writer",
                        lambda pool: attn_dispatch.KV_POOL_WRITE_KERNEL)
    cfg = _cfg(kv_lora_rank=128, qk_rope_head_dim=64, qk_nope_head_dim=16,
               q_lora_rank=24, v_head_dim=16, use_flash_attention=True)
    params = _params(cfg)
    _, srv = _serving(cfg, params, one_device=True, block=128, max_slots=2,
                      max_context=384, num_kv_blocks=8,
                      decode_steps_per_sync=2)
    requests = _requests([(150, 4), (40, 5)], seed=5)
    results = srv.run(requests)
    _assert_reference_tokens(params, cfg, requests, results)
    stats = srv.stats()
    assert set(stats["kv_pool_writer"].values()) \
        == {attn_dispatch.KV_POOL_WRITE_KERNEL}
    assert stats["attention_program"]["decode_step"] == "mla_decode_kernel"
    assert stats["attention_program"]["prefill_step"] == "mla_prefill_kernel"
    records = srv.steptrace.records()
    # a chunk of 128 from 0, then one from 128: 128 + 256 positions; the
    # other prompt's one chunk: 128
    assert sum(r.latent_chunk_positions for r in records) == 128 + 256 + 128
    assert sum(r.prefill_live_blocks for r in records) == 1 + 2 + 1


# ----------------------------------------------------------------------
# the scheduler's side of the latent kind
# ----------------------------------------------------------------------


def test_prefix_caching_works_on_latent_blocks():
    """Full prompt blocks of a latent kind are content-immutable allocator
    blocks like any other: a later request with the same prefix maps them
    and emits the tokens the cold engine emits."""
    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, 128, (48,), np.int32)
    prompts = [np.concatenate([prefix, rng.integers(0, 128, (n,), np.int32)])
               for n in (9, 5)]
    requests = [Request(uid=i, tokens=p, max_new_tokens=6, stop_on_eos=False)
                for i, p in enumerate(prompts)]
    _, cold = _serving(cfg, params, max_slots=1)
    want = cold.run(requests)
    _, srv = _serving(cfg, params, max_slots=1, enable_prefix_caching=True)
    got = srv.run(requests)
    for req in requests:
        np.testing.assert_array_equal(got[req.uid].tokens,
                                      want[req.uid].tokens)
    assert got[0].cached_prefix_tokens == 0
    assert got[1].cached_prefix_tokens == 48
    assert srv.allocator.available == srv.allocator.capacity
    assert srv.close().ok


def test_a_prefilled_slot_is_handed_on_with_its_latent_blocks():
    cfg = _cfg()
    params = _params(cfg)
    _, src = _serving(cfg, params)
    _, dst = _serving(cfg, params)
    req = _requests([(37, 6)], seed=9)[0]
    src.submit(req, prefill_only=True)
    while not src.handoff_ready():
        src.step()
    state = src.export_handoff(req.uid)
    assert dst.adopt_handoff(state, src.pool)
    slot = next(s for s in dst.slots if s.uid == req.uid)
    np.testing.assert_array_equal(
        np.asarray(src.pool["ckv"])[:, state["blocks"]],
        np.asarray(dst.pool["ckv"])[:, slot.blocks[:len(state["blocks"])]])
    assert np.asarray(src.pool["ckv"])[:, state["blocks"]].any()
    src.release_handoff(req.uid)
    done = {}
    while dst.num_active:
        for d in dst.step():
            done[d.uid] = d
    _, whole = _serving(cfg, params)
    np.testing.assert_array_equal(done[req.uid].tokens,
                                  whole.run([req])[req.uid].tokens)
    assert src.close().ok and dst.close().ok


def test_transplant_copies_a_latent_leaf_block_for_block():
    pool = {"ckv": jnp.arange(2 * 6 * 1 * 4 * 8, dtype=jnp.float32).reshape(
        2, 6, 1, 4, 8)}
    empty = {"ckv": jnp.zeros((2, 5, 1, 4, 8), jnp.float32)}
    out = transplant_blocks(pool, [3, 5], empty, [1, 4], pad_to=4)
    np.testing.assert_array_equal(np.asarray(out["ckv"])[:, [1, 4]],
                                  np.asarray(pool["ckv"])[:, [3, 5]])


@pytest.mark.parametrize("knobs, match", [
    (dict(quantization={"kv_cache_dtype": "int8"}),
     "latent kind: kv_cache_dtype int8 is not built"),
    (dict(spec_decode={"drafter": "ngram", "draft_k": 2}),
     "no verify_paged_fn"),
], ids=["int8-pool", "spec-decode"])
def test_serving_refuses_by_name_what_a_latent_kind_does_not_take(knobs,
                                                                  match):
    cfg = _cfg()
    with pytest.raises(ValueError, match=match):
        _serving(cfg, _params(cfg), **knobs)


def test_the_model_spec_refuses_the_paths_it_does_not_serve():
    cfg = _cfg()
    spec = gm.make_glm4_moe_lite_decode_model(cfg, params=_params(cfg))
    with pytest.raises(NotImplementedError, match="glm4_moe_lite.*paged"):
        spec.prefill_fn(None, None, None, None)
    with pytest.raises(ValueError, match="int8 pool is not built"):
        spec.init_paged_pool(8, 16, jnp.int8)
    pool = spec.init_paged_pool(8, 16, jnp.float32)
    assert {k: v.shape for k, v in pool.items()} == {
        "ckv": (3, 8, 1, 16, 128)}
    assert spec.verify_paged_fn is None
    assert spec.cache_fingerprint.startswith("glm4_moe_lite:")


def test_a_head_width_given_apart_need_not_divide_the_model_width():
    cfg = _cfg(n_head=5)            # 32 % 5 != 0, as GLM's 2048 % 20
    assert cfg.head_dim == 20
    tokens = jnp.arange(12, dtype=jnp.int32)[None]
    assert gm.glm4_moe_lite_forward(_params(cfg), tokens, cfg).shape \
        == (1, 12, 128)


# ----------------------------------------------------------------------
# the benchmark holds the cell
# ----------------------------------------------------------------------


def test_benchmark_holds_the_cells_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-4.7-flash-12l-ep8", "longctx_queue_backlog", 1)
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, config["file"])) as f:
        published = json.load(f)
    assert config["reduced"] == published["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert published["reduced_from"] == {
        "num_hidden_layers": 47, "n_routed_experts": 64,
        "vocab_size": 154880, "num_nextn_predict_layers": 1}
    # every width as published
    for key, value in {"hidden_size": 2048, "intermediate_size": 10240,
                       "moe_intermediate_size": 1536,
                       "num_attention_heads": 20, "q_lora_rank": 768,
                       "kv_lora_rank": 512, "qk_nope_head_dim": 192,
                       "qk_rope_head_dim": 64, "v_head_dim": 256,
                       "num_experts_per_tok": 4,
                       "routed_scaling_factor": 1.8, "rope_theta": 1000000,
                       "published_n_routed_experts": 64,
                       "experts_held_range": [0, 8]}.items():
        assert published[key] == value, key
    for kind, name in (("drivers", published["driver"] + ".py"),
                       ("references", published["reference"] + ".py"),
                       ("traffic", cell["traffic"] + ".json"),
                       ("checks", "rehearsal_glm47flash.json")):
        assert os.path.exists(os.path.join(BENCH, kind, name)), name
    for key in ("assumed", "why_reduced", "why_serving", "check_limits",
                "departures_of_the_program", "deployment"):
        assert published[key], key
    reported = [m["name"] for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    assert sorted(reported) == ["serve_tokens_per_s", "setup_s"]
    own = [m for m in bench["per_layer"]
           if m.get("workloads") == [cell["name"]]]
    assert sorted(m["name"] for m in own) == [
        "kv_pool_copy_time_share.longctx", "mla_decode_roofline.longctx",
        "mla_decode_time_share.longctx", "mla_prefill_roofline.longctx",
        "mla_prefill_time_share.longctx", "mla_proj_time_share.longctx",
        "moe_dispatch_time_share.longctx"]
    assert len(bench["per_layer"]) <= 128    # the contract's cap
    for metric in own:
        with open(os.path.join(BENCH, "layer_metrics",
                               metric["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    with open(os.path.join(BENCH, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "closed_backlog"
    assert traffic["min_queue"] == published["serving"]["max_slots"] == 128
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "min": 1024,
                                        "max": 12288}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 256,
                                        "max": 768}
    assert traffic["shared_prefix_tokens"] == 0
