"""Pallas kernel numerics vs XLA reference (reference pattern: tests/unit/ops/*
golden-numerics tests). Run in interpret mode on the CPU harness."""

import functools
import glob
import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _ref_attention(q, k, v, causal=True):
    # [B,H,T,D]
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p, v.astype(jnp.float32)).astype(q.dtype)


# (B, H, T, D, block_q, block_k, causal): "small" is the historical case; the
# others walk what the flat grid, the sub-block predicate and the transposed
# dk/dv scores can get wrong — a diagonal that crosses several tiles
# (block_q != block_k, both ways), T of exactly one tile, rows of blocks with
# interior AND diagonal tiles, no mask at all, a head narrower than a lane
# tile, and the default 1024 tiles worked in 512 sub-blocks (one of the four
# a diagonal tile holds is wholly hidden and skipped)
_FLASH_CASES = {
    "small": (2, 2, 128, 32, 64, 64, True),
    "small-full": (2, 2, 128, 32, 64, 64, False),
    "bq128-bk256": (1, 2, 512, 128, 128, 256, True),
    "bq256-bk128": (1, 2, 512, 128, 256, 128, True),
    "one-tile": (1, 2, 128, 128, 128, 128, True),
    "interior-and-diagonal": (1, 2, 512, 128, 128, 128, True),
    "full": (1, 2, 256, 128, 128, 128, False),
    "head-64": (1, 2, 256, 64, 128, 128, True),
    "default-tiles": (1, 1, 2048, 128, None, None, True),
}


def _flash_case(case, seed, dtype=jnp.float32):
    B, H, T, D, block_q, block_k, causal = _FLASH_CASES[case]
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, H, T, D)), dtype)
               for _ in range(3))
    return q, k, v, dict(causal=causal, block_q=block_q, block_k=block_k), rng


class TestFlashAttention:
    @pytest.mark.parametrize("case", list(_FLASH_CASES))
    def test_forward_matches(self, case):
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
        q, k, v, kw, _ = _flash_case(case, 0)
        out = flash_attention(q, k, v, layout="BHTD", **kw)
        ref = _ref_attention(q, k, v, causal=kw["causal"])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)

    # bf16 exercises the native-dtype MXU dot path (p/ds narrowed to bf16
    # inside the kernels — fp32 inputs make those casts no-ops); tolerances
    # widen to the bf16 rounding band
    @pytest.mark.parametrize("case,dtype,rtol,atol", [
        ("small", jnp.bfloat16, 4e-2, 4e-2),
    ] + [(case, jnp.float32, 5e-3, 5e-3) for case in _FLASH_CASES])
    def test_backward_matches(self, case, dtype, rtol, atol):
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
        q, k, v, kw, _ = _flash_case(case, 1, dtype)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, layout="BHTD", **kw)
                           .astype(jnp.float32) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(_ref_attention(q, k, v, causal=kw["causal"])
                           .astype(jnp.float32) ** 2)

        g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                       rtol=rtol, atol=atol, err_msg=f"d{name}")

    @pytest.mark.parametrize("case", list(_FLASH_CASES))
    def test_with_lse_values_and_grads(self, case):
        """flash_attention_with_lse: lse matches logsumexp of the score rows,
        and an lse-DEPENDENT loss backprops correctly (the dlse cotangent
        folds into the kernels as delta - dlse — ring attention relies on
        this to differentiate its partial-merge weights)."""
        from deepspeed_tpu.ops.pallas.flash_attention import \
            flash_attention_with_lse
        q, k, v, kw, rng = _flash_case(case, 7)
        B, H, T, D = q.shape
        sm = 1.0 / np.sqrt(D)

        def ref(q, k, v):
            s = jnp.einsum("bhtd,bhsd->bhts", q, k) * sm
            if kw["causal"]:
                mask = jnp.tril(jnp.ones((T, T), bool))[None, None]
                s = jnp.where(mask, s, -jnp.inf)
            lse = jax.scipy.special.logsumexp(s, axis=-1)
            o = jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, -1), v)
            return o, lse

        o, lse = flash_attention_with_lse(q, k, v, **kw)
        o_ref, lse_ref = ref(q, k, v)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-3, atol=2e-3)

        # loss touching BOTH outputs (the lse term exercises the dlse path)
        wl = jnp.asarray(rng.normal(0, 1, (B, H, T)), jnp.float32)

        def loss(fn):
            def f(q, k, v):
                o, lse = fn(q, k, v)
                return jnp.sum(o ** 2) + jnp.sum(lse * wl)
            return jax.grad(f, argnums=(0, 1, 2))

        g = loss(lambda q, k, v: flash_attention_with_lse(q, k, v, **kw))(q, k, v)
        g_ref = loss(ref)(q, k, v)
        for a, b, name in zip(g, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-3, err_msg=f"d{name}")

    # hand counts: (diagonal, interior, dead) tiles of one (batch x head)
    @pytest.mark.parametrize("T,block_q,block_k,causal,want", [
        (2048, 512, 512, True, (4, 6, 6)),       # the training cell's sub-blocks
        (2048, 512, 512, False, (0, 16, 0)),
        (2048, 1024, 1024, True, (2, 1, 1)),     # ... and its tiles
        (512, 128, 256, True, (4, 2, 2)),
        (512, 256, 128, True, (4, 2, 2)),
        (128, 128, 128, True, (1, 0, 0)),
    ])
    def test_live_tiles_against_a_hand_count(self, T, block_q, block_k,
                                             causal, want):
        from deepspeed_tpu.ops.pallas.flash_attention import (
            _tile_tables, flash_live_tiles)
        assert flash_live_tiles(T, block_q, block_k, causal) == want
        # the grid of each kernel is exactly the live tiles, every one once
        for k_major in (False, True):
            qi, ki = _tile_tables(T, block_q, block_k, causal, k_major)
            assert len(qi) == want[0] + want[1]
            assert len({(int(a), int(b)) for a, b in zip(qi, ki)}) == len(qi)
            outer = np.asarray(ki if k_major else qi)
            assert (np.diff(outer) >= 0).all()       # a block's tiles together

    def test_streaming_parity_beyond_legacy_cap(self):
        """Numerics + grads at a T strictly past the retired whole-slab VMEM
        cap ((14 MiB)/(4*D*itemsize) — 1792 tokens at head_dim 512 fp32):
        the KV-grid streaming kernel must match dense attention where the
        old kernel refused to run."""
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
        D, T = 512, 2048
        legacy_cap = (14 * 2**20) // (4 * D * 4)
        assert T > legacy_cap, (T, legacy_cap)
        rng = np.random.default_rng(5)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (1, 1, T, D)), jnp.float32)
                   for _ in range(3))
        out = flash_attention(q, k, v, causal=True, layout="BHTD",
                              block_q=256, block_k=256)
        ref = _ref_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True, layout="BHTD",
                                           block_q=256, block_k=256) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(_ref_attention(q, k, v, causal=True) ** 2)

        g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_flash, g_ref, "qkv"):
            scale = float(jnp.abs(b).max())
            assert float(jnp.abs(a - b).max()) < 1e-4 * scale, \
                f"d{name} diverges beyond the legacy cap"

    def test_auto_dispatch_by_seq_len(self):
        """use_flash_attention=None auto-dispatches: XLA below FLASH_MIN_SEQ,
        the Pallas kernel at/above it (measured crossover ~1k on v5e); the
        decode path's own auto-dispatch is pinned in TestDecodeStreaming."""
        import dataclasses
        from deepspeed_tpu.models.gpt import (FLASH_MIN_SEQ, GPTConfig,
                                              gpt_forward, init_gpt_params)
        cfg = GPTConfig(n_layer=1, n_head=2, d_model=64,
                        max_seq_len=FLASH_MIN_SEQ, vocab_size=256,
                        dtype=jnp.float32, remat=False)
        params = init_gpt_params(cfg, seed=0)

        def uses_pallas(cfg, T):
            toks = jnp.zeros((1, T), jnp.int32)
            jaxpr = jax.make_jaxpr(lambda p, t: gpt_forward(p, t, cfg))(params, toks)
            return "pallas_call" in str(jaxpr)

        assert cfg.use_flash_attention is None            # auto is the default
        assert not uses_pallas(cfg, 256)                  # short: XLA
        assert uses_pallas(cfg, FLASH_MIN_SEQ)            # long: kernel
        forced_off = dataclasses.replace(cfg, use_flash_attention=False)
        assert not uses_pallas(forced_off, FLASH_MIN_SEQ)
        forced_on = dataclasses.replace(cfg, use_flash_attention=True,
                                        max_seq_len=256)
        assert uses_pallas(forced_on, 256)

    def test_bthd_layout(self):
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
        rng = np.random.default_rng(2)
        q, k, v = (jnp.asarray(rng.normal(0, 1, (2, 128, 4, 16)), jnp.float32) for _ in range(3))
        out = flash_attention(q, k, v, causal=True, layout="BTHD", block_q=64, block_k=64)
        ref = jnp.swapaxes(_ref_attention(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v))), 1, 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)

    # (T, H, D, causal, merged v): a head 128 lanes wide is a column block of
    # `[B, T, H*D]` and the kernels address v, o, dO and dv there; at 64 the
    # copies stay
    @pytest.mark.parametrize("T,H,D,causal,merged", [
        (256, 2, 128, True, False),
        (256, 2, 128, False, False),
        (2048, 16, 128, True, False),
        (2048, 16, 128, False, True),
        (256, 2, 256, True, True),
        (256, 2, 64, True, False),
    ])
    def test_heads_in_place_equal_the_head_major_copies(self, T, H, D, causal,
                                                        merged):
        """`flash_attention(layout="BTHD")` where `flash_heads_in_place(D)`:
        the forward BIT FOR BIT what the `[B, H, T, D]` path gives on the
        same inputs, the three gradients within the kernels' own tolerance
        (`delta`'s sum runs in another order); v and the result may cross
        with their heads merged; the kernels' operands are the projections'
        own `[B, T, H*D]` arrays exactly where a head is whole lane tiles."""
        from deepspeed_tpu.ops.pallas.flash_attention import (
            flash_attention, flash_heads_in_place)
        rng = np.random.default_rng(55)
        q, k, v, w = (jnp.asarray(rng.normal(0, 1, (1, T, H, D)), jnp.float32)
                      for _ in range(4))
        assert flash_heads_in_place(D) == (D % 128 == 0)

        def in_place(q, k, v):
            if merged:
                return flash_attention(q, k, v.reshape(1, T, H * D),
                                       causal=causal).reshape(1, T, H, D)
            return flash_attention(q, k, v, causal=causal)

        def head_major(q, k, v):
            return jnp.swapaxes(flash_attention(
                *(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), causal=causal,
                layout="BHTD"), 1, 2)

        np.testing.assert_array_equal(np.asarray(in_place(q, k, v)),
                                      np.asarray(head_major(q, k, v)))
        grads = [jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) * w),
                          argnums=(0, 1, 2))(q, k, v)
                 for f in (in_place, head_major)]
        for a, b, name in zip(*grads, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-3, err_msg=f"d{name}")

        # what the three kernels are handed: (shape, ...) of each call
        handed = [[tuple(x.aval.shape) for x in eqn.invars]
                  for eqn in _pallas_calls(jax.make_jaxpr(jax.grad(
                      lambda q, k, v: jnp.sum(in_place(q, k, v) * w),
                      argnums=(0, 1, 2)))(q, k, v).jaxpr)]
        assert len(handed) == 3
        for shapes in handed:
            # q and k head-major (the rotation writes that for nothing); v —
            # and dO behind it in the backward — as the columns they are
            assert shapes[2:4] == [(H, T, D)] * 2
            want = (1, T, H * D) if D % 128 == 0 else (H, T, D)
            assert shapes[4] == want and (len(shapes) == 5
                                          or shapes[5] == want)
        if D % 128:
            with pytest.raises(ValueError, match="whole lane tiles"):
                flash_attention(q, k, v.reshape(1, T, H * D))


def _pallas_calls(jaxpr):
    """Every `pallas_call` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


class TestDecodeStreaming:
    """Blocked HBM-streaming decode attention (`ops/pallas/decode_attention`):
    the cache is walked one [block_m, hd] tile per grid step with the block
    index clamped to each row's live prefix — context length is HBM-bound."""

    def test_blocked_decode_parity_ragged(self):
        """Parity vs the jnp oracle on a ragged batch whose live prefixes
        span <1 block, mid-cache, and the last slot — the clamped index map
        must not skip or double-count frontier blocks. GQA layout."""
        from deepspeed_tpu.ops.pallas.decode_attention import (
            decode_attention, decode_attention_reference)
        B, H, Hkv, M, hd = 4, 8, 2, 1024, 32
        rng = np.random.default_rng(9)
        q = jnp.asarray(rng.normal(0, 1, (B, H, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(0, 1, (B, Hkv, M, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(0, 1, (B, Hkv, M, hd)), jnp.float32)
        pos = jnp.asarray([3, 127, 600, M - 1], jnp.int32)
        out = decode_attention(q, k, v, pos, block_m=128)
        ref = decode_attention_reference(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    def test_blocked_decode_beyond_legacy_cap_length(self):
        """A cache LONGER than the old whole-slab VMEM domain (~14k at
        head_dim 128 bf16; scaled here via head_dim 512 fp32 → 1792) streams
        correctly — the shape the old kernel could not serve at all."""
        from deepspeed_tpu.ops.pallas.decode_attention import (
            decode_attention, decode_attention_reference)
        B, H, M, hd = 2, 1, 2048, 512
        assert M > (14 * 2**20) // (4 * hd * 4)
        rng = np.random.default_rng(10)
        q = jnp.asarray(rng.normal(0, 1, (B, H, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(0, 1, (B, H, M, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(0, 1, (B, H, M, hd)), jnp.float32)
        pos = jnp.asarray([M - 1, 42], jnp.int32)
        out = decode_attention(q, k, v, pos, block_m=512)
        ref = decode_attention_reference(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    def test_decode_auto_dispatch_by_context(self):
        """The decode kernel auto-engages from DECODE_KERNEL_MIN_CTX (the
        blocked kernel reads only the live prefix; XLA reads the whole
        allocated cache); short caches stay XLA; True/False still force."""
        import dataclasses

        from deepspeed_tpu.models.gpt import (DECODE_KERNEL_MIN_CTX,
                                              GPTConfig,
                                              make_gpt_decode_model)
        cfg = GPTConfig(n_layer=1, n_head=2, d_model=64, max_seq_len=256,
                        vocab_size=128, dtype=jnp.float32, remat=False)

        def uses_pallas(cfg, M):
            spec = make_gpt_decode_model(cfg=cfg)
            cache = spec.init_cache(1, M, jnp.float32)
            tok = jnp.zeros((1,), jnp.int32)
            pos = jnp.zeros((1,), jnp.int32)
            jaxpr = jax.make_jaxpr(
                lambda p, t, s, c: spec.decode_fn(p, t, s, c))(
                    spec.params, tok, pos, cache)
            return "pallas_call" in str(jaxpr)

        assert cfg.use_flash_attention is None
        assert not uses_pallas(cfg, 1024)                        # short: XLA
        assert uses_pallas(cfg, DECODE_KERNEL_MIN_CTX)           # long: kernel
        forced_off = dataclasses.replace(cfg, use_flash_attention=False)
        assert not uses_pallas(forced_off, DECODE_KERNEL_MIN_CTX)
        forced_on = dataclasses.replace(cfg, use_flash_attention=True)
        assert uses_pallas(forced_on, 1024)


class TestNorms:
    def test_layer_norm(self):
        from deepspeed_tpu.ops.pallas.norms import fused_layer_norm
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(0, 2, (4, 33, 256)), jnp.float32)
        scale = jnp.asarray(rng.normal(1, 0.1, (256,)), jnp.float32)
        bias = jnp.asarray(rng.normal(0, 0.1, (256,)), jnp.float32)
        out = fused_layer_norm(x, scale, bias)
        mean = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        ref = (x - mean) / jnp.sqrt(var + 1e-5) * scale + bias
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)

    def test_rms_norm_with_residual(self):
        from deepspeed_tpu.ops.pallas.norms import fused_rms_norm
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(0, 1, (8, 128)), jnp.float32)
        r = jnp.asarray(rng.normal(0, 1, (8, 128)), jnp.float32)
        scale = jnp.ones((128,), jnp.float32)
        out = fused_rms_norm(x, scale, residual=r)
        xr = x + r
        ref = xr / jnp.sqrt(jnp.mean(xr**2, -1, keepdims=True) + 1e-5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


class TestQuant:
    def test_roundtrip_error_small(self):
        from deepspeed_tpu.ops.pallas.quant import quantize_int8, dequantize_int8
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(0, 1, (16, 256)), jnp.float32)
        q, s = quantize_int8(x, group_size=64)
        assert q.dtype == jnp.int8 and s.shape == (16, 4)
        y = dequantize_int8(q, s, dtype=jnp.float32, group_size=64)
        err = np.abs(np.asarray(y) - np.asarray(x)).max()
        scale_max = np.asarray(s).max()
        assert err <= scale_max * 0.51 + 1e-6, (err, scale_max)

    def test_quantized_allgather_path(self):
        """int8 payload + scales survive an all_gather round (qwZ building block)."""
        from deepspeed_tpu.ops.pallas.quant import quantize_int8, dequantize_int8
        from deepspeed_tpu.comm import mesh as mesh_mod
        from deepspeed_tpu.config.core import MeshConfig
        mesh_mod._CURRENT_MESH = None
        mesh_mod._CURRENT_SPEC = None
        mesh_mod.init_mesh(MeshConfig(data=8))
        import deepspeed_tpu.comm as comm
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(0, 1, (8, 128)), jnp.float32)
        q, s = quantize_int8(x, group_size=128)
        qg = comm.all_gather(q, axis="data")
        sg = comm.all_gather(s, axis="data")
        y = dequantize_int8(qg[:8], sg[:8], dtype=jnp.float32, group_size=128)
        err = np.abs(np.asarray(y) - np.asarray(x)).max()
        assert err <= np.asarray(s).max() * 0.51 + 1e-6


class TestTheOnlineSoftmaxHasOneForm:
    """`decode_attention.py::_online_softmax_update` carries the row
    statistics lane-replicated (PR 44); the column form it replaced is
    frozen in `tests/softmax_oracle.py`. Same float32 operations on the same
    values in the same order: every kernel that shares the update gives the
    column form's BITS."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("case", ["head-64", "bq128-bk256",
                                      "bq256-bk128", "full"])
    def test_flash_forward(self, case, dtype, monkeypatch):
        from deepspeed_tpu.ops.pallas.flash_attention import \
            flash_attention_with_lse
        from tests.softmax_oracle import assert_same_bits_as_the_column_form
        q, k, v, kw, _ = _flash_case(case, 5, dtype)
        assert_same_bits_as_the_column_form(
            monkeypatch, lambda *a: flash_attention_with_lse(*a, **kw),
            q, k, v)

    # (Hkv, G) by head width: the rows a decode tile carries at the served
    # models (MHA 1, Mistral 4, K-EXAONE 8) and the latent walk's twenty,
    # which is not a sublane multiple
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("hd", [64, 128])
    @pytest.mark.parametrize("heads", [(2, 1), (2, 4), (1, 8), (1, 20)],
                             ids=["g1", "g4", "g8", "g20"])
    @pytest.mark.parametrize("kernel", ["contiguous", "paged", "paged-int8"])
    def test_decode_walks(self, kernel, heads, hd, dtype, monkeypatch):
        from deepspeed_tpu.inference.quantization import quantize_kv
        from deepspeed_tpu.ops.pallas import decode_attention as da
        from tests.softmax_oracle import assert_same_bits_as_the_column_form
        Hkv, G = heads
        block, nb = 128, 3
        rng = np.random.default_rng(31)
        pos = jnp.asarray([0, 127, 128, 300], jnp.int32)
        q = jnp.asarray(rng.normal(size=(4, Hkv * G, hd)), dtype)
        if kernel == "contiguous":
            k, v = (jnp.asarray(rng.normal(size=(4, Hkv, nb * block, hd)),
                                dtype) for _ in range(2))
            assert_same_bits_as_the_column_form(
                monkeypatch,
                lambda *a: da.decode_attention(*a, block_m=block,
                                               interpret=True), q, k, v, pos)
            return
        k, v = (jnp.asarray(rng.normal(size=(1 + 4 * nb, Hkv, block, hd)),
                            dtype) for _ in range(2))
        # row 2 is a dead slot: its table is the trash block throughout
        tables = jnp.asarray([[5, 0, 0], [7, 0, 0], [0, 0, 0], [2, 9, 4]],
                             jnp.int32)
        if kernel == "paged":
            # a window that begins inside the walk's first block, too
            for window in (None, 200):
                assert_same_bits_as_the_column_form(
                    monkeypatch,
                    lambda *a: da.paged_decode_attention(
                        *a, interpret=True, window=window),
                    q, k, v, tables, pos)
            return
        kq, ks = quantize_kv(k, 32)
        vq, vs = quantize_kv(v, 32)
        assert_same_bits_as_the_column_form(
            monkeypatch,
            lambda *a: da.paged_decode_attention_quant(*a, interpret=True),
            q, kq, vq, ks, vs, tables, pos)


# ----------------------------------------------------------------------
# the decode walk's frontier cut (PR 62): a short table's frontier block
# moves by row tiles, a long table's walk is the call it was
# ----------------------------------------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CUT_BLOCK, _CUT_NB, _CUT_TILE = 512, 3, 128
# where the cut changes what is copied: both ends of a tile and of a block
CUT_POSITIONS = [0, _CUT_TILE - 1, _CUT_TILE, _CUT_BLOCK - 1, _CUT_BLOCK,
                 _CUT_NB * _CUT_BLOCK - 1]
# (Hkv, G, what is special about the pool or the walk)
CUT_KINDS = {"g1": (4, 1, None), "g32": (1, 32, None), "int8": (2, 4, "int8"),
             "kr_pool": (2, 2, "kr_pool"), "sink": (2, 4, "sink"),
             "head-groups": (4, 2, "groups")}


def _dense_walk(q, keys, values, tables, pos, sm_scale, sink=None):
    """The walk's result by a dense softmax over the gathered blocks,
    float32: q [B, H, dk]; keys / values [N, Hkv, block, dk | dv]; `sink`
    [H] a logit a head that joins the denominator and has no value."""
    B, H, _ = q.shape
    Hkv = keys.shape[1]

    def gathered(pool):
        x = jnp.moveaxis(pool[tables], 2, 1)        # [B, Hkv, nb, block, w]
        return x.reshape(B, Hkv, -1, x.shape[-1]).astype(jnp.float32)
    k, v = gathered(keys), gathered(values)
    s = jnp.einsum("bkgd,bkmd->bkgm", q.reshape(B, Hkv, H // Hkv, -1)
                   .astype(jnp.float32), k) * sm_scale
    s = jnp.where(jnp.arange(k.shape[2]) <= pos[:, None, None, None], s,
                  -1e30)
    if sink is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(
            sink.reshape(1, Hkv, -1, 1), s.shape[:3] + (1,))], axis=-1)
        v = jnp.concatenate([v, jnp.zeros_like(v[:, :, :1])], axis=2)
    out = jnp.einsum("bkgm,bkmd->bkgd", jax.nn.softmax(s, axis=-1), v)
    return out.reshape(B, H, -1)


@pytest.mark.parametrize("pos", CUT_POSITIONS)
@pytest.mark.parametrize("kind", list(CUT_KINDS))
def test_a_short_tables_walk_reads_its_frontier_block_by_row_tiles(
        kind, pos, monkeypatch):
    """`_paged_cut_kernel` (interpreted) against the gather oracle with the
    frontier at both ends of a row tile and of a block and at the table's
    last position, a dead slot between the live ones, one query row a KV
    head and 32, the keys' half tile apart, a sink — and the int8 pool,
    whose scale columns no row slice of can be copied: the rule keeps it on
    the whole block, and the call says which walk was built. "head-groups":
    two KV heads a step of four, so a head group's last step starts the
    next group's first copies."""
    from deepspeed_tpu.inference.quantization import quantize_kv
    from deepspeed_tpu.ops.pallas import decode_attention as da
    Hkv, G, special = CUT_KINDS[kind]
    hd, block, nb = 128, _CUT_BLOCK, _CUT_NB
    rng = np.random.default_rng(62 + pos)
    N = 1 + 2 * nb
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    k, v = normal(N, Hkv, block, hd), normal(N, Hkv, block, hd)
    # slot 1 is dead: its table is the trash block throughout
    tables = np.zeros((3, nb), np.int32)
    tables[[0, 2]] = rng.permutation(np.arange(1, N)).reshape(2, nb)
    tables = jnp.asarray(tables)
    at = jnp.asarray([pos, 300, 700], jnp.int32)
    scale = 1.0 / math.sqrt(hd)
    if special == "int8":
        (kq, ks), (vq, vs) = quantize_kv(k, 32), quantize_kv(v, 32)
        q = normal(3, Hkv * G, hd)
        walk = lambda: da.paged_decode_attention_quant(
            q, kq, vq, ks, vs, tables, at, interpret=True)
        want = da.paged_decode_attention_quant_reference(
            q, {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}, tables, at)
    elif special == "kr_pool":
        kr = normal(N, Hkv // 2, block, 128)
        q = normal(3, Hkv * G, hd + 128)
        scale = 1.0 / math.sqrt(hd + 128)
        walk = lambda: da.paged_decode_attention(
            q, k, v, tables, at, sm_scale=scale, kr_pool=kr, interpret=True)
        want = _dense_walk(q, jnp.concatenate(
            [k, jnp.repeat(kr, 2, axis=1)], axis=-1), v, tables, at, scale)
    elif special == "sink":
        q, logit = normal(3, Hkv * G, hd), normal(Hkv * G)
        walk = lambda: da.paged_decode_attention(
            q, k, v, tables, at, sink=logit, interpret=True)
        want = _dense_walk(q, k, v, tables, at, scale, logit)
    else:
        if special == "groups":
            monkeypatch.setattr(da, "_WALK_TILE_BYTES",
                                2 * 2 * 2 * block * hd * 4)
        q = normal(3, Hkv * G, hd)
        walk = lambda: da.paged_decode_attention(q, k, v, tables, at,
                                                 interpret=True)
        want = da.paged_decode_attention_reference(q, k, v, tables, at)
    # the kernel's own copies, or the pipeline's whole blocks
    cut = special != "int8"
    assert ("dma_start" in str(jax.make_jaxpr(walk)())) == cut
    got = np.asarray(walk())
    np.testing.assert_allclose(got[[0, 2]], np.asarray(want)[[0, 2]],
                               rtol=2e-5, atol=2e-5)
    assert not got[1].any()


def _served_walk_shapes(config):
    """(q, pool leaf, tables, pos) of a benchmark configuration's decode
    walk as `ShapeDtypeStruct`s, from its file: slots, heads, block and
    table as served."""
    with open(os.path.join(ROOT, "benchmark", "configs", config)) as f:
        cfg = json.load(f)
    s = cfg["serving"]
    B, block = s["max_slots"], s["kv_block_size"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    sds = jax.ShapeDtypeStruct
    return (sds((B, H, hd), jnp.bfloat16),
            sds((s["num_kv_blocks"], Hkv, block, hd), jnp.bfloat16),
            sds((B, s["max_context"] // block), jnp.int32),
            sds((B,), jnp.int32))


def _long_table_walk_hashes():
    """The jaxpr text of `paged_decode_attention` at Granite's and
    Qwen3-Next's served table shapes (10 blocks: past the cut), hashed:
    written to `tests/step_program_hashes.json` (`long_table_walks`) by this
    function in the tree PR 62 started from."""
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention
    out = {}
    for config in ("granite-4.0-h-small-10l-ep4.json",
                   "qwen3-next-80b-a3b-12l-ep8.json"):
        q, pool, tables, pos = _served_walk_shapes(config)
        text = str(jax.make_jaxpr(functools.partial(
            paged_decode_attention, interpret=False))(
                q, pool, pool, tables, pos))
        out[config[:-5]] = hashlib.sha256(text.encode()).hexdigest()[:16]
    return out


def test_a_long_tables_walk_is_the_call_it_was_before_the_cut():
    with open(os.path.join(ROOT, "tests", "step_program_hashes.json")) as f:
        assert _long_table_walk_hashes() == json.load(f)["long_table_walks"]


def test_the_rule_cuts_the_short_tables_of_the_benchmark_and_no_other():
    """Every configuration the benchmark serves, by its own file: the eight
    tables of ten blocks and more keep the whole block (Granite's and
    Qwen3-Next's walks would read past 100% of a count made of whole
    blocks), OLMoE's three blocks and SDAR's five move their frontier block
    in tiles of 128 rows; a window or a sparse layer's selection keeps the
    whole block whatever the table."""
    from deepspeed_tpu.ops.pallas.decode_attention import _frontier_rows
    tiles = {}
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark", "configs",
                                              "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if "serving" not in cfg:
            continue
        s = cfg["serving"]
        tiles[os.path.basename(path).split("-")[0]] = _frontier_rows(
            s["max_context"] // s["kv_block_size"], s["kv_block_size"],
            (128, 128))
    assert tiles == {"olmoe": 128, "sdar": 128, **dict.fromkeys(
        ["glm", "granite", "k", "keye", "mimo", "mistral", "nemotron",
         "qwen3"], 512)}
    assert _frontier_rows(3, 512, (128, 128), window=128) == 512
    assert _frontier_rows(3, 512, (128, 128), selected=True) == 512
    assert _frontier_rows(3, 512, (128, 4)) == 512      # int8 scale columns
    assert _frontier_rows(3, 128, (128, 128)) == 128    # a block of one tile
    assert _frontier_rows(8, 512, (640,)) == 128 \
        and _frontier_rows(9, 512, (640,)) == 512
