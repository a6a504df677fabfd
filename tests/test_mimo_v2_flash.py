"""MiMo-V2-Flash family (`models/mimo_v2_flash.py`) on the paged serving path:
two kinds of attention layer that differ in their KV heads, their rotary base
and their sink, keys wider than values, no shared expert — the forward and
then chunks + decoding through rings and pool against the float32 reference,
each departure of the family visible on its own, the expert share, the walks
and the writer at the published widths (192 / 128) in the interpreter, the
layer plan, and what each kind's pool entry is.

Everything at a small size on the CPU; `tests/mimo_cases.py` has the
configuration and the reference."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_cache import (gather_block_leaf, ring_blocks,
                                              ring_tables)
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import exaone_moe as em
from deepspeed_tpu.models.gpt import GPTConfig, _paged_attend
from deepspeed_tpu.ops import attention_dispatch as attn_dispatch
from deepspeed_tpu.ops.pallas import kv_pool
from deepspeed_tpu.parallel.moe import routed_experts, topk_routing
from tests.mimo_cases import (PATTERN, _arch, _cfg, _params, _serving, mm,
                              ref)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "serve_mimov2flash_agent_longctx"
L, G = em.WINDOW, em.FULL


def _rms(got, want):
    return float(np.sqrt(np.square(got - want).sum()
                         / np.square(want).sum()))


# ----------------------------------------------------------------------
# the layer pattern and the kinds, as data
# ----------------------------------------------------------------------


def _published(name):
    with open(os.path.join(BENCH, "configs", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("pattern, freq, want", [
    ((0, 1, 1, 1, 1) + (0, 1, 1, 1, 1, 1) * 7 + (0,),
     (0,) + (1,) * 47, (6, 6, 7)),
    (PATTERN, (0,) + (1,) * 10, (5, 6, 1)),
], ids=["published-48", "the-cut"])
def test_layer_plan_reads_the_published_lists(pattern, freq, want):
    if len(pattern) == 48:      # the catalog's row, as the file keeps it
        assert list(pattern) == _published(
            "mimo-v2-flash-11l-ep32.json")["reduced_from"][
                "hybrid_layer_pattern"]
    cfg = _cfg(n_layer=len(pattern), layer_types=mm.layer_types(pattern),
               mlp_layer_types=mm.mlp_layer_types(freq))
    prologue, period, periods = em.layer_plan(cfg)
    assert (len(prologue), len(period), periods) == want
    assert [k for k, _ in period] == [L] * 5 + [G] if want[0] == 6 \
        else [k for k, _ in period] == [G] + [L] * 5
    assert prologue + period * periods == list(
        zip(cfg.layer_types, cfg.mlp_layer_types))


def test_each_kind_owns_its_heads_widths_base_and_sink():
    cfg = _cfg()
    full, window = em.cache_kinds(cfg, 16)
    assert (full.name, full.layers, full.block, full.window) \
        == ("full", 2, 16, 0)
    assert (window.name, window.layers, window.block, window.window) \
        == ("window", 9, 8, 8)
    assert full.leaves == ("k", "v") and window.leaves == ("wk", "wv")
    assert full.entry_values == 2 * (48 + 32)
    assert window.entry_values == 4 * (48 + 32)
    kcfg = em._kind_cfgs(cfg)
    assert (kcfg[G].n_kv_head, kcfg[G].rope_theta, kcfg[G].attn_sink,
            kcfg[G].sliding_window, kcfg[G].use_rotary) \
        == (2, 5e6, False, None, True)
    assert (kcfg[L].n_kv_head, kcfg[L].rope_theta, kcfg[L].attn_sink,
            kcfg[L].sliding_window) == (4, 1e4, True, 8)
    params = _params(cfg)
    first, second = params["prologue"][:2]      # a full layer, a window one
    assert first["attn_qkv_w"].shape == (32, 8 * 48 + 2 * 48 + 2 * 32)
    assert second["attn_qkv_w"].shape == (32, 8 * 48 + 4 * 48 + 4 * 32)
    assert first["attn_out_w"].shape == (8 * 32, 32)
    assert "attn_sink" not in first and second["attn_sink"].shape == (8,)
    assert second["attn_sink"].dtype == jnp.float32
    assert float(jnp.abs(second["attn_sink"]).max()) > 0.1      # drawn
    sparse = params["period"][1]
    assert not [k for k in sparse if k.startswith("shared_")]
    assert "q_norm_scale" not in first


def test_a_key_of_a_tile_and_a_half_is_kept_in_two_leaves():
    """192-wide keys beside 128-wide values: the first 64 columns of two KV
    heads side by side in `kr`, every leaf whole lane tiles, an entry stored
    at the model's 320 values a head; other widths keep `k` / `v`."""
    assert kv_pool.kv_leaf_shapes(4, 192, 128) == {
        "k": (4, 128), "kr": (2, 128), "v": (4, 128)}
    assert kv_pool.kv_leaf_shapes(8, 128, 128) == {"k": (8, 128),
                                                   "v": (8, 128)}
    assert kv_pool.kv_leaf_shapes(3, 192, 128) == {"k": (3, 192),
                                                   "v": (3, 128)}
    assert kv_pool.kv_leaf_shapes(4, 48, 32) == {"k": (4, 48), "v": (4, 32)}
    cfg = _cfg(attn_head_dim=192, attn_value_dim=128, n_head=4)
    full, window = em.cache_kinds(cfg, 16)
    assert full.leaves == ("k", "kr", "v")
    assert window.leaves == ("wk", "wkr", "wv")
    pool = mm.make_mimo_v2_flash_decode_model(
        cfg, params={}).init_paged_pool(5, 16, jnp.bfloat16, window_blocks=7)
    assert {k: v.shape for k, v in pool.items()} == {
        "k": (2, 5, 2, 16, 128), "kr": (2, 5, 1, 16, 128),
        "v": (2, 5, 2, 16, 128), "wk": (9, 7, 4, 8, 128),
        "wkr": (9, 7, 2, 8, 128), "wv": (9, 7, 4, 8, 128)}
    stored = sum(v.nbytes // (v.shape[0] * v.shape[1] * v.shape[3])
                 for k, v in pool.items() if not k.startswith("w"))
    assert stored == full.entry_values * 2 == 2 * 320 * 2


def test_the_other_families_pools_are_what_they_were():
    """A K-EXAONE and a GLM engine beside a MiMo one: their pools' leaves,
    shapes and types are the ones they had with one entry for both kinds."""
    from tests import exaone_cases, glm_cases
    mimo = mm.make_mimo_v2_flash_decode_model(_cfg(), params={})
    assert set(mimo.init_paged_pool(6, 16, window_blocks=5)) \
        == {"k", "v", "wk", "wv"}
    exa = em.make_exaone_moe_decode_model(exaone_cases._cfg(), params={})
    pool = exa.init_paged_pool(6, 16, jnp.bfloat16, window_blocks=5)
    assert {k: (v.shape, str(v.dtype)) for k, v in pool.items()} == {
        "k": ((1, 6, 2, 16, 16), "bfloat16"),
        "v": ((1, 6, 2, 16, 16), "bfloat16"),
        "wk": ((4, 5, 2, 8, 16), "bfloat16"),
        "wv": ((4, 5, 2, 8, 16), "bfloat16")}
    kinds = em.cache_kinds(exaone_cases._cfg(), 16)
    assert [(k.name, k.leaves) for k in kinds] == [
        ("full", ("k", "v")), ("window", ("wk", "wv"))]
    tree = exaone_cases._params(exaone_cases._cfg())["prologue"][0]
    assert {"q_norm_scale", "k_norm_scale", "shared_gate_w"} - set(tree) \
        == {"shared_gate_w"}                    # the dense layer has none
    assert "shared_gate_w" in exaone_cases._params(
        exaone_cases._cfg())["period"][0]
    from deepspeed_tpu.models import glm4_moe_lite as gm
    gcfg = glm_cases._cfg()
    glm = gm.make_glm4_moe_lite_decode_model(gcfg, params={})
    pool = glm.init_paged_pool(6, 16, jnp.bfloat16)
    width = -(-(gcfg.kv_lora_rank + gcfg.qk_rope_head_dim) // 128) * 128
    assert {k: v.shape for k, v in pool.items()} == {
        "ckv": (gcfg.n_layer, 6, 1, 16, width)}


# ----------------------------------------------------------------------
# program against reference: the forward, then through the scheduler
# ----------------------------------------------------------------------

# float32: the program and the reference differ by summation order alone
# (and the sink as an initial state against a concatenated column).
# bfloat16: 8 bits of mantissa through eleven layers of width 32 with scores
# of order 1; the CPU gives ~1.5% rms at this size.
_TOLERANCE = {"float32": (2e-4, 2e-4), "bfloat16": (0.05, 0.08)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_then_chunks_and_decoding_give_the_references_logits(dtype):
    """The whole-sequence forward, then prompts longer than the ring (4
    blocks of 8 = 32 positions: ring blocks are reused, the windowed walks
    start above block 0) prefilled in chunks and decoded through the rings
    and the pool, every position's LOGITS against the reference's."""
    jdtype = jnp.dtype(dtype)
    cfg = _cfg(jdtype, held=(4, 8))
    params = _params(cfg, seed=3, dtype=jdtype)
    engine, srv = _serving(cfg, params, dtype)
    assert srv.ring == 4
    rng = np.random.default_rng(11)
    reqs = [Request(uid=i, tokens=rng.integers(0, 128, (n,), np.int32),
                    max_new_tokens=m, stop_on_eos=False)
            for i, (n, m) in enumerate([(45, 9), (7, 47)])]
    done = srv.run(reqs)
    arch = _arch(cfg)
    rms_tol, max_tol = _TOLERANCE[dtype]
    dense = jax.jit(lambda p, t: mm.mimo_v2_flash_forward(p, t, cfg))
    for r in reqs:
        seq = np.concatenate([r.tokens, done[r.uid].tokens])
        want = np.asarray(ref.logits(params, jnp.asarray(seq), arch),
                          np.float32)
        got = np.asarray(dense(params, jnp.asarray(seq[None]))[0],
                         np.float32)
        assert _rms(got, want) <= rms_tol, (r.uid, _rms(got, want))
        assert np.abs(got - want).max() <= max_tol * np.abs(want).max()
        if dtype == "float32":
            # ... and the PAGED programs emitted the reference's tokens
            greedy = want.argmax(-1)[len(r.tokens) - 1:-1]
            np.testing.assert_array_equal(done[r.uid].tokens, greedy)
    stats = srv.stats()
    assert stats["compiles"] == {"decode_step": 1, "prefill_step": 1}
    kinds = stats["kv_pool_kinds"]
    assert (kinds["full"]["layers"], kinds["window"]["layers"]) == (2, 9)
    item = jdtype.itemsize
    assert kinds["full"]["bytes_per_token"] \
        == kinds["full"]["model_bytes_per_token"] == 2 * 2 * 80 * item
    assert kinds["window"]["bytes_per_token"] \
        == kinds["window"]["model_bytes_per_token"] == 9 * 4 * 80 * item
    assert kinds["window"]["ring_blocks_per_slot"] == 4


def test_chunks_riding_decode_calls_emit_the_same_tokens():
    """`make_mixed_paged_fn` on the two-kind pool: a chunk and the slots'
    decode rows as one tensor; the two-call path is the oracle."""
    cfg = _cfg(held=(0, 8))
    params = _params(cfg, seed=5)
    rng = np.random.default_rng(12)
    reqs = [Request(uid=i, tokens=rng.integers(0, 128, (n,), np.int32),
                    max_new_tokens=m, stop_on_eos=False)
            for i, (n, m) in enumerate([(9, 30), (50, 6), (21, 12)])]
    _, srv = _serving(cfg, params, one_device=True)
    fused = srv.run(reqs)
    assert srv.stats()["fused_chunks"] > 0
    assert srv.stats()["compiles"]["mixed_step"] == 1
    _, apart = _serving(cfg, params, one_device=True)
    apart._chunks_riding = lambda due, decoding: 0
    want = apart.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(fused[r.uid].tokens,
                                      want[r.uid].tokens)
    arch = _arch(cfg)
    for r in reqs[:2]:
        seq = np.concatenate([r.tokens, fused[r.uid].tokens])
        greedy = np.asarray(ref.logits(params, jnp.asarray(seq),
                                       arch)).argmax(-1)
        np.testing.assert_array_equal(fused[r.uid].tokens,
                                      greedy[len(r.tokens) - 1:-1])


@pytest.mark.parametrize("without", ["sink", "value_scale", "kind_theta",
                                     "kind_heads"])
def test_each_departure_of_the_family_is_visible(without):
    """The reference with ONE mechanism left out — the sink, the value
    scale, the full layers' own rotary base, the kinds' own head grouping —
    differs from the program by more than forty times float32's tolerance."""
    cfg = _cfg()
    params = _params(cfg, seed=1)
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 128, (40,)),
                         jnp.int32)
    got = np.asarray(mm.mimo_v2_flash_forward(params, tokens[None], cfg)[0])
    arch = _arch(cfg)
    assert _rms(got, np.asarray(ref.logits(params, tokens, arch))) <= 2e-4
    control = dataclasses.replace(arch, without=frozenset([without]))
    assert _rms(got, np.asarray(ref.logits(params, tokens, control))) \
        > 40 * 2e-4


def test_the_reference_in_eight_bits_is_scaled_and_does_not_overflow():
    """The control the benchmark's limits are set against: every weight and
    product input through float8_e4m3's bits with a scale a row — finite,
    and several times further off than bfloat16 (at real widths and depth
    the chip reads it an order of magnitude over the limits)."""
    cfg = _cfg()
    params = _params(cfg, seed=4)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 128, (40,)),
                         jnp.int32)
    want = np.asarray(ref.logits(params, tokens, _arch(cfg)))
    errors = {}
    for name, dtype in (("float8", jnp.float8_e4m3fn),
                        ("bfloat16", jnp.bfloat16)):
        got = np.asarray(ref.logits(params, tokens,
                                    _arch(cfg, round_to=dtype)))
        assert np.isfinite(got).all(), name
        errors[name] = _rms(got, want)
    assert 4 * errors["bfloat16"] < errors["float8"] > 0.02


def test_four_shares_add_up_to_the_whole_layer_with_no_shared_expert():
    """The routed parts four shares compute (`held` = 0-3, 4-7, ... of 16
    experts), summed, equal the reference's whole sparse layer."""
    cfg = _cfg()
    p = _params(cfg, seed=7)["prologue"][1]
    assert "shared_gate_w" not in p
    h = jnp.asarray(np.random.default_rng(3).normal(size=(24, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.routed_sum(h, p, _arch(cfg, held=None))
        top_p, top_e = topk_routing(h, p["moe_gate_w"], cfg.top_k, True,
                                    scoring="sigmoid",
                                    bias=p["moe_gate_bias"], scale=1.0)
        total, elsewhere = jnp.zeros_like(h), 0
        for first in range(0, 16, 4):
            stacks = {"w_gate_up": p["moe_w_gate_up"][first:first + 4],
                      "w_down": p["moe_w_down"][first:first + 4]}
            part, counters = routed_experts(h, top_p, top_e, stacks,
                                            held=(first, 4))
            total = total + part
            elsewhere += int(counters[4])
            # a share's own program computes its part and nothing more
            share = dataclasses.replace(cfg, experts_held=(first, 4))
            out, _, _ = em._sparse_mlp(h[None], {**p, **{
                "moe_" + k: v for k, v in stacks.items()}}, share)
            np.testing.assert_allclose(np.asarray(out[0]), np.asarray(part),
                                       rtol=2e-5, atol=2e-5)
    assert elsewhere == 3 * h.shape[0] * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------------
# the walks and the writer at the published widths, Pallas interpreter,
# against the gather oracle (`_paged_attend` over the merged leaves)
# ----------------------------------------------------------------------

_B, _H, _BLK, _NB, _W = 2, 16, 128, 4, 128


def _wide_pool(kv_heads, seed, tables=None, blocks=None):
    """A pool of `kv_leaf_shapes(kv_heads, 192, 128)` written through the
    REFERENCE scatter with every position of `_NB` blocks a row -> (pool,
    tables, the keys and values it holds in position order)."""
    rng = np.random.default_rng(seed)
    if tables is None:
        tables = 1 + np.arange(_B)[:, None] * _NB + np.arange(_NB)[None]
    tables = jnp.asarray(tables, jnp.int32)
    k = jnp.asarray(rng.normal(size=(_B, _NB * _BLK, kv_heads, 192)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(_B, _NB * _BLK, kv_heads, 128)),
                    jnp.float32)
    pool = {name: jnp.zeros((blocks or 1 + _B * _NB, heads, _BLK, width),
                            jnp.float32)
            for name, (heads, width) in kv_pool.kv_leaf_shapes(
                kv_heads, 192, 128).items()}
    return pool, tables, k, v, rng


def _oracle(q, pool, tables, positions, kv_heads, window, sink):
    ctx = {leaf: gather_block_leaf(rows, tables)
           for leaf, rows in pool.items()}
    values = ctx.pop("v")
    cfg = GPTConfig(n_head=_H, n_kv_head=kv_heads, d_model=64,
                    attn_head_dim=192, attn_value_dim=128,
                    sliding_window=window, attn_sink=sink is not None)
    return _paged_attend(q, kv_pool.merge_keys(ctx), values, positions, cfg,
                         sink=sink)


def test_the_writer_lays_a_wide_key_in_its_two_leaves():
    pool, tables, k, v, _ = _wide_pool(4, seed=20)
    start = jnp.asarray([0, 0], jnp.int32)
    rows = kv_pool.pool_rows(k, v, pool)
    assert {n: r.shape[2:] for n, r in rows.items()} == {
        "k": (4, 128), "kr": (2, 128), "v": (4, 128)}
    for leaf in pool:
        want = kv_pool.kv_pool_write_reference(pool[leaf], rows[leaf], start,
                                               tables)
        got = kv_pool.kv_pool_write(pool[leaf], rows[leaf], start, tables,
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        pool[leaf] = got
    ctx = {leaf: gather_block_leaf(pool[leaf], tables) for leaf in pool}
    ctx.pop("v")
    np.testing.assert_array_equal(np.asarray(kv_pool.merge_keys(ctx)),
                                  np.asarray(jnp.moveaxis(k, 1, 2)))
    assert attn_dispatch.kv_pool_writer(
        {n: x.astype(jnp.bfloat16) for n, x in pool.items()}) \
        == attn_dispatch.KV_POOL_WRITE_SCATTER       # the CPU: the rule
    assert all(kv_pool.pool_in_place_supported(jnp.bfloat16, _BLK,
                                               x.shape[-1])
               for x in pool.values())


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("kv_heads, window", [(4, None), (8, _W)],
                         ids=["full-4-heads", "window-8-heads"])
@pytest.mark.parametrize("chunk, start", [(1, (300, 77)), (128, (256, 40))],
                         ids=["decode", "chunk-from-mid-block"])
def test_the_walks_take_wide_keys_narrow_values_and_a_sink(chunk, start,
                                                           kv_heads, window,
                                                           sink):
    pool, tables, k, v, rng = _wide_pool(kv_heads, seed=21)
    rows = kv_pool.pool_rows(k, v, pool)
    zero = jnp.zeros((_B,), jnp.int32)
    pool = {leaf: kv_pool.kv_pool_write_reference(pool[leaf], rows[leaf],
                                                  zero, tables)
            for leaf in pool}
    q = jnp.asarray(rng.normal(size=(_B, chunk, _H, 192)), jnp.float32)
    start = jnp.asarray(start, jnp.int32)
    logit = jnp.asarray(rng.normal(size=(_H,)), jnp.float32) if sink \
        else None
    program = "paged_kernel" if chunk == 1 else "paged_prefill_kernel"
    got = attn_dispatch.get_program(program).runner(
        q, pool, tables, start, sm_scale=None, window=window, work=None,
        **({} if logit is None else dict(sink=logit)))
    want = _oracle(q, pool, tables, start[:, None] + jnp.arange(chunk)[None],
                   kv_heads, window, logit)
    assert got.shape == (_B, chunk, _H * 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    if sink:        # the sink takes weight: a row's result is smaller
        plain = _oracle(q, pool, tables,
                        start[:, None] + jnp.arange(chunk)[None], kv_heads,
                        window, None)
        assert np.abs(np.asarray(plain) - np.asarray(want)).max() > 1e-3


def test_a_wrapped_ring_of_wide_keys_reads_what_a_full_table_would():
    """A context of more blocks than the ring holds, written chunk by chunk
    through a ring table and through an ordinary one (the in-place writer,
    interpreted): every chunk's windowed walk with the sink, and a decode
    token after it, read the same."""
    chunk, nb, heads = 128, 6, 8
    ring = ring_blocks(_W, _BLK, chunk, 1)
    assert ring == 3 < nb
    rng = np.random.default_rng(22)
    shapes = kv_pool.kv_leaf_shapes(heads, 192, 128)
    full = {n: jnp.zeros((1 + nb, h, _BLK, w), jnp.float32)
            for n, (h, w) in shapes.items()}
    rings = {n: jnp.zeros((1 + ring, h, _BLK, w), jnp.float32)
             for n, (h, w) in shapes.items()}
    full_t = jnp.asarray(1 + np.arange(nb)[None], jnp.int32)
    ring_t = jnp.asarray(ring_tables(1, nb, ring))
    logit = jnp.asarray(rng.normal(size=(_H,)), jnp.float32)
    prefill = attn_dispatch.get_program("paged_prefill_kernel").runner
    decode = attn_dispatch.get_program("paged_kernel").runner
    for start in range(0, 5 * chunk, chunk):
        k = jnp.asarray(rng.normal(size=(1, chunk, heads, 192)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, chunk, heads, 128)), jnp.float32)
        at = jnp.asarray([start], jnp.int32)
        for pool, table in ((full, full_t), (rings, ring_t)):
            for leaf, rows in kv_pool.pool_rows(k, v, pool).items():
                pool[leaf] = kv_pool.kv_pool_write(pool[leaf], rows, at,
                                                   table, interpret=True)
        q = jnp.asarray(rng.normal(size=(1, chunk, _H, 192)), jnp.float32)
        a, b = (prefill(q, pool, table, at, sm_scale=None, window=_W,
                        work=None, sink=logit)
                for pool, table in ((full, full_t), (rings, ring_t)))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        last = jnp.asarray([start + chunk - 1], jnp.int32)
        a, b = (decode(q[:, -1:], pool, table, last, sm_scale=None,
                       window=_W, work=None, sink=logit)
                for pool, table in ((full, full_t), (rings, ring_t)))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_kernels_serve_the_references_tokens_at_the_published_widths(
        monkeypatch):
    """The served path steered onto the in-place form (on the CPU the rule
    declines; the kernels run in the interpreter): heads of 192 / 128, the
    keys in two leaves, the writer, both walks of both kinds with the sink,
    through the scheduler — the reference's greedy tokens."""
    monkeypatch.setattr(attn_dispatch, "kv_pool_writer",
                        lambda pool: attn_dispatch.KV_POOL_WRITE_KERNEL)
    cfg = _cfg(pattern=(0, 1, 1, 1, 1, 1), pattern_period=6, n_head=4,
               attn_head_dim=192, attn_value_dim=128, sliding_window=128,
               window_block=128, use_flash_attention=True)
    params = _params(cfg, seed=9)
    _, srv = _serving(cfg, params, one_device=True, block=128, max_slots=2,
                      max_context=512, num_kv_blocks=8,
                      decode_steps_per_sync=2)
    assert set(srv.pool) == {"k", "kr", "v", "wk", "wkr", "wv"}
    rng = np.random.default_rng(13)
    reqs = [Request(uid=i, tokens=rng.integers(0, 128, (n,), np.int32),
                    max_new_tokens=m, stop_on_eos=False)
            for i, (n, m) in enumerate([(300, 4), (40, 5)])]
    done = srv.run(reqs)
    arch = _arch(cfg)
    for r in reqs:
        seq = np.concatenate([r.tokens, done[r.uid].tokens])
        greedy = np.asarray(ref.logits(params, jnp.asarray(seq),
                                       arch)).argmax(-1)
        np.testing.assert_array_equal(done[r.uid].tokens,
                                      greedy[len(r.tokens) - 1:-1])
    stats = srv.stats()
    assert set(stats["kv_pool_writer"].values()) \
        == {attn_dispatch.KV_POOL_WRITE_KERNEL}
    programs = stats["attention_program"]
    assert programs["decode_step"] == "paged_kernel"
    assert programs["prefill_step"] == "paged_prefill_kernel"
    assert programs["mixed_step"] == "paged_prefill_kernel+paged_kernel"
    # the step ring's window fields are filled for this pool: 300 tokens in
    # chunks of 128 through a ring of 3 blocks
    records = srv.steptrace.records()
    assert sum(r.decode_window_live_blocks for r in records) > 0
    assert 0 < sum(r.prefill_window_live_blocks for r in records) \
        < sum(r.prefill_window_table_blocks for r in records)
    # ... and the (query, position) pairs the masks keep: chunks of 128 at
    # 0, 128, 256 and 0; a window layer's first chunk sees 1 .. 128 positions
    assert sum(r.prefill_kept_pairs for r in records) \
        == 128 * (0 + 128 + 256 + 0) + 4 * 128 * 129 // 2
    assert sum(r.prefill_window_kept_pairs for r in records) \
        == 2 * (127 * 128 // 2 + 128) + 2 * 128 * 128


# ----------------------------------------------------------------------
# what is refused, and the benchmark's files
# ----------------------------------------------------------------------


@pytest.mark.parametrize("knobs, match", [
    (dict(enable_prefix_caching=True), "enable_prefix_caching is not built"),
    (dict(quantization={"kv_cache_dtype": "int8"}), "int8"),
])
def test_serving_refuses_what_is_not_built_for_two_kinds(knobs, match):
    cfg = _cfg()
    with pytest.raises(ValueError, match=match):
        _serving(cfg, _params(cfg), **knobs)


def test_the_training_flash_program_declines_a_sink_and_a_narrow_value():
    from deepspeed_tpu.models.gpt import _train_attn_site
    plain = GPTConfig(n_head=4, d_model=512, use_flash_attention=True)
    assert attn_dispatch.select(
        _train_attn_site(plain, 1024, 1024, False, None)) == "flash"
    for over in (dict(attn_sink=True), dict(attn_head_dim=192,
                                            attn_value_dim=128)):
        cfg = GPTConfig(n_head=4, d_model=512, use_flash_attention=True,
                        **over)
        assert attn_dispatch.select(
            _train_attn_site(cfg, 1024, 1024, False, None)) == "dense"


def _benchmark_module(*parts):
    import importlib.util
    import sys
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "bench_" + parts[-1][:-3], os.path.join(BENCH, *parts))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH)
    return module


def test_the_walks_roofline_counts_the_models_entry_a_kind():
    """`benchmark/roofline_walk_kinds.py` by hand at the served widths: a
    full layer's block of 512 rows is 4 x 320 values a row, a window layer's
    block of 128 rows 8 x 320; the chunk's operations are the kept pairs'."""
    walk = _benchmark_module("roofline_walk_kinds.py")
    full, window = (2, 512, 64, 4, 192, 128), (9, 128, 64, 8, 192, 128)
    flops, nbytes = walk.decode_walk([(full, 10), (window, 3)])
    rows_full, rows_window = 2 * 512 * 10, 9 * 128 * 3
    assert nbytes == 2 * (rows_full * 4 * 320 + rows_window * 8 * 320)
    assert flops == 2 * (rows_full + rows_window) * 64 * 320
    flops, nbytes = walk.chunk_walk(
        [(full, 1000, 5), (window, 300, 6)], chunks=2, chunk=256)
    assert flops == 2 * (2 * 1000 + 9 * 300) * 64 * 320
    q_and_out = 2 * 256 * 64 * 320
    assert nbytes == 2 * (2 * (5 * 512 * 4 * 320 + q_and_out)
                          + 9 * (6 * 128 * 8 * 320 + q_and_out))
    # the reader finds nothing to read without a trace: no value, no raise
    reader = _benchmark_module("readers", "paged_walk_roofline_kinds.py")
    assert reader.read({"traced": (None, None)}, None, {}) is None


def test_benchmark_holds_the_cells_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mimo-v2-flash-11l-ep32", "agent_longctx_backlog", 1)
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    published = _published("mimo-v2-flash-11l-ep32.json")
    assert config["reduced"] == published["reduced"] == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"]
    assert published["reduced_from"]["num_hidden_layers"] == 48
    assert published["reduced_from"]["n_routed_experts"] == 256
    assert published["reduced_from"]["vocab_size"] == 152576
    # every width as published
    for key, value in {
            "hidden_size": 4096, "intermediate_size": 16384,
            "moe_intermediate_size": 2048, "num_attention_heads": 64,
            "head_dim": 192, "v_head_dim": 128, "num_key_value_heads": 4,
            "swa_num_key_value_heads": 8, "swa_head_dim": 192,
            "swa_v_head_dim": 128, "sliding_window": 128,
            "rope_theta": 5000000, "swa_rope_theta": 10000,
            "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
            "num_experts_per_tok": 8, "published_n_routed_experts": 256,
            "n_routed_experts": 8, "experts_held_range": [0, 8],
            "vocab_size": 19072, "num_hidden_layers": 11,
            "hybrid_layer_pattern": list(PATTERN),
            "moe_layer_freq": [0] + [1] * 10}.items():
        assert published[key] == value, key
    assert published["parameters_held"]["by_the_issue"] == 3409017920
    for kind, name in (("drivers", published["driver"] + ".py"),
                       ("references", published["reference"] + ".py"),
                       ("traffic", cell["traffic"] + ".json"),
                       ("checks", "rehearsal_mimov2flash.json")):
        assert os.path.exists(os.path.join(BENCH, kind, name)), name
    for key in ("assumed", "why_reduced", "why_serving", "check_limits",
                "deployment"):
        assert published[key], key
    reported = [m["name"] for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    assert sorted(reported) == ["serve_tokens_per_s", "setup_s"]
    own = [m for m in bench["per_layer"]
           if m.get("workloads") == [cell["name"]]]
    assert sorted(m["name"] for m in own) == [
        "kv_pool_copy_time_share.agent", "moe_dispatch_time_share.agent",
        "paged_prefill_roofline.kindwise", "paged_walk_roofline.kindwise"]
    assert len(bench["per_layer"]) <= 128
