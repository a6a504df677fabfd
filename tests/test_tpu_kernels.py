"""Real-TPU Pallas kernel lane: compile (NOT interpret) every kernel on the
chip and check numerics against its jnp oracle.

Run: DSTPU_RUN_TPU_TESTS=1 python -m pytest tests/ -m tpu -q -p no:xdist

The CPU suite routes all Pallas code through interpret mode
(`platform/device.py::pallas_interpret`), so a regression in the Mosaic
lowering would pass CI without this lane. With the variable set and no TPU
present the lane FAILS (tests/conftest.py) — it never skips its way green.

Recorded state of every kernel in `ops/pallas/` on TPU v5 lite, jax 0.9.0 /
libtpu 0.0.34 (PR 21): compiles and matches — decode (contiguous, paged,
int8 paged at one scale per vector), quantize/dequantize int8, norms,
evoformer (head dims 128 and 32), block-sparse; repaired — flash fwd/bwd/lse
(row-statistics block layout), int8 paged decode at sub-vector scale groups
(in-kernel reshape), token sort (cumsum); refuses on TPU with a named error —
quantize/dequantize int4 (stride-2 lane gather).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tpu


def _xla_attention(q, k, v, causal, sm_scale):
    # [B, T, H, D] reference in fp32
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * sm_scale
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, vf)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_compiled_numerics(causal, dtype):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    B, T, H, D = 2, 512, 4, 128
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, T, H, D)), dtype)
               for _ in range(3))
    sm = 1.0 / np.sqrt(D)
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, interpret=False))(q, k, v)
    ref = _xla_attention(q, k, v, causal, sm)
    # MXU multiplies are bf16 at DEFAULT precision even for fp32 inputs: XLA's
    # own default-vs-highest delta on this shape is ~9e-3, and the kernel must
    # sit in the same band (measured 8.6e-3), not at fp32 epsilon.
    tol = 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=tol, rtol=tol)


def test_longctx_generate_on_chip():
    """Long-context SERVING capability pin: a 4096-token prompt through the
    compiled prefill + decode programs on the real chip (the r5 measured
    datum: ~0.8 s for generate(64) at B=4; here a smaller/faster shape —
    the pin is that the path compiles and produces sane tokens, not the
    latency)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model
    cfg = GPTConfig(n_layer=4, n_head=4, d_model=256, max_seq_len=4096 + 16,
                    vocab_size=50304, dtype=jnp.bfloat16)
    model = make_gpt_decode_model(cfg=cfg, name="longserve-pin")
    eng = deepspeed_tpu.init_inference(model, config={"dtype": "bf16"})
    prompt = np.random.default_rng(0).integers(0, 50000, (2, 4096)).astype(np.int32)
    out = np.asarray(eng.generate(prompt, max_new_tokens=16))
    assert out.shape == (2, 16)
    # greedy decode is deterministic — a NaN/garbage-logits regression breaks
    # this reproducibility pin even though argmax indices stay in-range
    out2 = np.asarray(eng.generate(prompt, max_new_tokens=16))
    np.testing.assert_array_equal(out, out2)
    assert len(np.unique(out)) > 1, "degenerate constant output"


def test_flash_streaming_16k_compiled():
    """The tentpole pin: seq 16384 at head_dim 128 bf16 — PAST the retired
    whole-slab VMEM cap — compiles and matches a blockwise fp32 oracle
    IN-KERNEL on the chip (the old kernel raised 'VMEM domain' here and the
    shape fell to the ~2.8x-slower chunked XLA fallback)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    B, T, H, D = 1, 16384, 1, 128
    assert T > (14 * 2**20) // (4 * D * 2)      # strictly beyond the old cap
    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, T, H, D)), jnp.bfloat16)
               for _ in range(3))
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False))(q, k, v)
    assert out.shape == (B, T, H, D)
    o = np.asarray(out, np.float32)
    assert np.isfinite(o).all()
    # spot-check rows against an exact fp32 oracle (full-T reference would
    # materialize 16k x 16k scores; rows are enough to catch streaming bugs)
    qf, kf, vf = (np.asarray(x, np.float32)[0, :, 0] for x in (q, k, v))
    for t in (0, 511, 512, 8191, T - 1):        # block edges + extremes
        s = (qf[t] @ kf[: t + 1].T) / np.sqrt(D)
        p = np.exp(s - s.max()); p /= p.sum()
        np.testing.assert_allclose(o[0, t, 0], p @ vf[: t + 1],
                                   atol=3e-2, rtol=3e-2)


def test_decode_streaming_long_cache_compiled():
    """Blocked decode at a 32k cache (past the old whole-[M, hd]-slab cap):
    compiles on-chip, matches the jnp oracle, with ragged live prefixes."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        decode_attention, decode_attention_reference)
    B, H, Hkv, M, D = 4, 16, 4, 32768, 128
    assert M > (14 * 2**20) // (4 * D * 2)
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.normal(0, 1, (B, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(0, 1, (B, Hkv, M, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (B, Hkv, M, D)), jnp.bfloat16)
    pos = jnp.asarray([100, 8191, 20000, M - 1], jnp.int32)
    out = jax.jit(lambda q, k, v, p: decode_attention(
        q, k, v, p, interpret=False))(q, k, v, pos)
    ref = decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=4e-2, rtol=4e-2)


def test_flash_attention_compiled_grads():
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    B, T, H, D = 1, 256, 2, 128
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, T, H, D)), jnp.float32)
               for _ in range(3))
    sm = 1.0 / np.sqrt(D)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=False)**2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, True, sm)**2)

    g1 = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        a, b = np.asarray(a), np.asarray(b)
        scale = np.abs(b).max()
        # relative to grad magnitude: MXU default-precision band (~0.7%)
        assert np.abs(a - b).max() < 2e-2 * scale, \
            f"d{name}: {np.abs(a - b).max():.4f} vs scale {scale:.2f}"


def test_decode_attention_compiled():
    from deepspeed_tpu.ops.pallas.decode_attention import (
        decode_attention, decode_attention_reference)
    B, H, M, D = 4, 8, 1024, 128
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(0, 1, (B, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, H, M, D)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, H, M, D)), jnp.float32)
    pos = jnp.asarray([5, 100, 700, 1023], jnp.int32)
    out = jax.jit(lambda q, k, v, p: decode_attention(
        q, k, v, p, interpret=False))(q, k, v, pos)
    ref = decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-2, rtol=2e-2)  # MXU default precision


def test_quant_kernels_compiled():
    from deepspeed_tpu.ops.pallas.quant import quantize_int8, dequantize_int8
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 2, (256, 512)), jnp.float32)
    q, s = jax.jit(lambda x: quantize_int8(x, interpret=False))(x)
    assert q.dtype == jnp.int8
    back = jax.jit(lambda q, s: dequantize_int8(
        q, s, dtype=jnp.float32, interpret=False))(q, s)
    # int8 groupwise round-trip error bounded by scale/2 per group
    err = np.abs(np.asarray(back) - np.asarray(x))
    bound = np.repeat(np.asarray(s), 128, axis=-1)[:, :512] * 0.51 + 1e-6
    assert (err <= bound).mean() > 0.999


def test_norms_compiled():
    from deepspeed_tpu.ops.pallas.norms import fused_layer_norm, fused_rms_norm
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 1, (128, 512)), jnp.float32)
    scale = jnp.asarray(rng.normal(1, 0.1, (512,)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.1, (512,)), jnp.float32)
    out = jax.jit(lambda x, s, b: fused_layer_norm(
        x, s, b, interpret=False))(x, scale, bias)
    mu = np.asarray(x).mean(-1, keepdims=True)
    var = np.asarray(x).var(-1, keepdims=True)
    ref = (np.asarray(x) - mu) / np.sqrt(var + 1e-5) * np.asarray(scale) + np.asarray(bias)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-4)

    out_r = jax.jit(lambda x, s: fused_rms_norm(x, s, interpret=False))(x, scale)
    ref_r = np.asarray(x) / np.sqrt((np.asarray(x)**2).mean(-1, keepdims=True)
                                    + 1e-5) * np.asarray(scale)
    np.testing.assert_allclose(np.asarray(out_r), ref_r, atol=2e-5, rtol=2e-4)


def test_evoformer_attention_compiled():
    from deepspeed_tpu.ops.pallas.evoformer_attn import evoformer_attention
    B, N, S, H, D = 1, 4, 64, 2, 128
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, N, S, H, D)), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(rng.integers(0, 2, (B, N, 1, 1, S)) * -1e9, jnp.float32)
    pair = jnp.asarray(rng.normal(0, 1, (B, 1, H, S, S)), jnp.float32)
    out = jax.jit(lambda q, k, v, m, p: evoformer_attention(
        q, k, v, biases=(m, p), interpret=False))(q, k, v, mask, pair)
    ref = jax.jit(lambda q, k, v, m, p: evoformer_attention(
        q, k, v, biases=(m, p), block_q=7))(q, k, v, mask, pair)  # jnp fallback
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)  # MXU default precision


def test_evoformer_attention_compiled_head_dim_32():
    """AlphaFold's own head width: the kernel is selected by one shape rule
    on every platform, so a sub-lane head dim must compile too (it does: the
    hardware-only detour to XLA this replaced was never needed)."""
    from deepspeed_tpu.ops.pallas.evoformer_attn import evoformer_attention
    B, N, S, H, D = 1, 4, 128, 2, 32
    rng = np.random.default_rng(15)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, N, S, H, D)), jnp.float32)
               for _ in range(3))
    pair = jnp.asarray(rng.normal(0, 1, (B, 1, H, S, S)), jnp.float32)
    out = jax.jit(lambda q, k, v, p: evoformer_attention(
        q, k, v, biases=(None, p), interpret=False))(q, k, v, pair)
    ref = jax.jit(lambda q, k, v, p: evoformer_attention(
        q, k, v, biases=(None, p), block_q=7))(q, k, v, pair)  # jnp path
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)  # MXU default precision


# (B, H, T, D, block_q, block_k, causal), tiles of whole lane tiles: the
# shapes `tests/test_kernels.py::_FLASH_CASES` walks in the interpreter,
# through Mosaic — "tb-2" is the multi-q-block shape (Tb > 1) whose
# row-statistics tile jax 0.9 refused before PR 21
_FLASH_COMPILED_CASES = {
    "tb-2": (1, 4, 1024, 128, 512, 512, True),
    "bq128-bk256": (1, 2, 512, 128, 128, 256, True),
    "bq256-bk128": (1, 2, 512, 128, 256, 128, True),
    "one-tile": (1, 2, 128, 128, 128, 128, True),
    "interior-and-diagonal": (1, 2, 512, 128, 128, 128, True),
    "full": (1, 2, 256, 128, 128, 128, False),
    "head-64": (1, 2, 256, 64, 128, 128, True),
    "default-tiles": (1, 2, 2048, 128, None, None, True),
}


@pytest.mark.parametrize("case", list(_FLASH_COMPILED_CASES))
def test_flash_attention_with_lse_compiled(case):
    """The ring programs' building block: (o, lse) forward and the lse
    cotangent through the backward, compiled, over the shapes that walk
    every branch of the tile walk (unequal tiles both ways, one tile, rows
    of blocks with interior and diagonal tiles, no mask, a 64-wide head,
    the default 1024 tiles worked in 512 sub-blocks)."""
    from deepspeed_tpu.ops.pallas.flash_attention import \
        flash_attention_with_lse
    B, H, T, D, block_q, block_k, causal = _FLASH_COMPILED_CASES[case]
    rng = np.random.default_rng(16)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, H, T, D)), jnp.bfloat16)
               for _ in range(3))

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / np.sqrt(D)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                       v.astype(jnp.float32))
        return o, lse

    def kernel(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                          block_q=block_q, block_k=block_k,
                                          interpret=False)
        return o.astype(jnp.float32), lse

    o, lse = jax.jit(kernel)(q, k, v)
    o_ref, lse_ref = jax.jit(dense)(q, k, v)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-2, rtol=2e-2)

    def loss(f):      # a loss that reads BOTH outputs -> lse cotangent live
        return lambda q, k, v: sum(jnp.sum(x ** 2) for x in f(q, k, v))

    g = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() < 3e-2 * np.abs(b).max(), \
            f"d{name}: {np.abs(a - b).max():.4f} vs {np.abs(b).max():.2f}"


def _paged_case(rng, B=4, H=16, Hkv=16, hd=128, block=512, nb=16, N=65):
    q = jnp.asarray(rng.normal(0, 1, (B, H, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(0, 1, (N, Hkv, block, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (N, Hkv, block, hd)), jnp.bfloat16)
    # distinct physical blocks per row, none the trash block 0
    tables = jnp.asarray(rng.permutation(N - 1)[:B * nb].reshape(B, nb) + 1,
                         jnp.int32)
    pos = jnp.asarray([5, 700, 3000, block * nb - 1], jnp.int32)
    return q, k, v, tables, pos


@pytest.mark.parametrize("Hkv", [16, 4])    # MHA (q tile [1, 128]) and GQA
def test_paged_decode_attention_compiled(Hkv):
    """The serving decode hot op at gpt2-1.3b's geometry: 16 heads of 128,
    512-token pool blocks, an 8k table — ragged live prefixes resolved
    through a scalar-prefetched 2-D block table."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention, paged_decode_attention_reference)
    q, k, v, tables, pos = _paged_case(np.random.default_rng(17), Hkv=Hkv)
    out = jax.jit(lambda *a: paged_decode_attention(
        *a, interpret=False))(q, k, v, tables, pos)
    ref = paged_decode_attention_reference(q, k, v, tables, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("cell", ["mistral", "olmoe"])
def test_paged_decode_attention_compiled_at_the_served_shapes(cell):
    """The benchmark's two serving shapes — 32 slots x 32 query heads over 8
    KV heads and a 32-block table; 64 slots x 16 heads (MHA) over a 3-block
    table; 512-token blocks of 128 — with live rows of ragged lengths among
    dead ones: the walk's every-KV-head tile at both widths, the dynamic
    grid bound, zeros for the dead rows."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention, paged_decode_attention_reference)
    B, H, Hkv, nb, N, contexts = {
        "mistral": (32, 32, 8, 32, 200,
                    [1, 512, 513, 700, 2048, 5000, 9000, 16384, 12345, 33]),
        "olmoe": (64, 16, 16, 3, 130,
                  [1, 511, 512, 513, 1024, 1025, 1536] * 8),
    }[cell]
    rng = np.random.default_rng(28)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(28), 3)
    q = jax.random.normal(kq, (B, H, 128), jnp.bfloat16)
    k = jax.random.normal(kk, (N, Hkv, 512, 128), jnp.bfloat16)
    v = jax.random.normal(kv, (N, Hkv, 512, 128), jnp.bfloat16)
    tables = np.zeros((B, nb), np.int32)
    pos = np.zeros((B,), np.int32)
    physical = iter(1 + rng.permutation(N - 1))
    rows = rng.permutation(B)[:len(contexts)]
    for b, context in zip(rows, contexts):
        pos[b] = context - 1
        for j in range(pos[b] // 512 + 1):
            tables[b, j] = next(physical)
    live = np.zeros((B,), bool)
    live[rows] = True
    out = np.asarray(jax.jit(lambda *a: paged_decode_attention(
        *a, interpret=False))(q, k, v, jnp.asarray(tables),
                              jnp.asarray(pos)), np.float32)
    ref = np.asarray(paged_decode_attention_reference(
        q, k, v, jnp.asarray(tables), jnp.asarray(pos)), np.float32)
    np.testing.assert_allclose(out[live], ref[live], atol=3e-2, rtol=3e-2)
    assert not out[~live].any()


@pytest.mark.parametrize("cell,start", [
    ("mistral", 0), ("mistral", 3584), ("mistral", 15872),
    ("olmoe", 0), ("olmoe", 256), ("olmoe", 1280)])
def test_paged_prefill_attention_compiled_at_the_served_shapes(cell, start):
    """`dstpu_paged_prefill` at the benchmark's two chunk shapes — 512 rows
    of 32 query heads over 8 KV heads, a 32-block table; 256 rows of 16
    heads (MHA), a 3-block table, so a chunk can start inside a block — at
    the first chunk, a middle one and the table's last, against the gather
    and the dense attend it replaces; the table past the frontier holds the
    trash block."""
    from deepspeed_tpu.models.gpt import GPTConfig, _paged_attend
    from deepspeed_tpu.ops.pallas.kv_pool import kv_pool_gather
    from deepspeed_tpu.ops.pallas.prefill_attention import (
        paged_prefill_attention, paged_prefill_live_blocks)
    C, H, Hkv, nb, N = {"mistral": (512, 32, 8, 32, 200),
                        "olmoe": (256, 16, 16, 3, 130)}[cell]
    rng = np.random.default_rng(30)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(30), 3)
    q = jax.random.normal(kq, (1, C, H, 128), jnp.bfloat16)
    k = jax.random.normal(kk, (N, Hkv, 512, 128), jnp.bfloat16)
    v = jax.random.normal(kv, (N, Hkv, 512, 128), jnp.bfloat16)
    tables = np.zeros((1, nb), np.int32)
    live = paged_prefill_live_blocks(start, C, 512, nb)
    tables[0, :live] = 1 + rng.permutation(N - 1)[:live]
    tables, starts = jnp.asarray(tables), jnp.asarray([start], jnp.int32)
    out = jax.jit(lambda *a: paged_prefill_attention(
        *a, interpret=False))(q, k, v, tables, starts)
    cfg = GPTConfig(vocab_size=64, n_layer=1, n_head=H, n_kv_head=Hkv,
                    d_model=H * 128, d_ff=64, max_seq_len=nb * 512)
    ref = _paged_attend(q, kv_pool_gather(k, tables, interpret=False),
                        kv_pool_gather(v, tables, interpret=False),
                        starts[:, None] + jnp.arange(C)[None], cfg)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("group", [128, 32])
def test_paged_decode_attention_quant_compiled(group):
    """int8 pool, dequantized in-kernel: one scale per K/V vector (the
    default, scale blocks one lane wide) and four per vector (the group
    count whose in-kernel reshape Mosaic refused before PR 21)."""
    from deepspeed_tpu.inference.quantization import quantize_kv
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention_quant, paged_decode_attention_quant_reference)
    q, k, v, tables, pos = _paged_case(np.random.default_rng(18), nb=8, N=33)
    pos = jnp.minimum(pos, 8 * 512 - 1)
    qk, sk = quantize_kv(k, group)
    qv, sv = quantize_kv(v, group)
    out = jax.jit(lambda *a: paged_decode_attention_quant(
        *a, interpret=False))(q, qk, qv, sk, sv, tables, pos)
    ref = paged_decode_attention_quant_reference(
        q, {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}, tables, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kv_pool_write_and_gather_compiled(dtype):
    """The in-place pool kernels at the served tile widths, bit for bit
    against the XLA scatter and gather they replace: a 32-slot decode step
    with inactive slots in the trash block, a 512-token chunk that starts
    off a tile boundary and crosses a block, a verify chunk."""
    from deepspeed_tpu.inference.kv_cache import gather_block_kv
    from deepspeed_tpu.ops.pallas.kv_pool import (kv_pool_gather,
                                                  kv_pool_write,
                                                  kv_pool_write_reference)
    rng = np.random.default_rng(25)
    N, Hkv, block, hd, nb = 104, 8, 512, 128, 4
    pool = jnp.asarray(rng.standard_normal((N, Hkv, block, hd)), dtype)
    write = jax.jit(lambda *a: kv_pool_write(*a, interpret=False))
    for B, C, live in ((32, 1, 24), (1, 512, 1), (8, 5, 6)):
        tables = np.zeros((B, nb), np.int32)
        tables[:live] = 1 + rng.permutation(N - 1)[:live * nb].reshape(
            live, nb)
        start = np.zeros((B,), np.int32)
        start[:live] = rng.integers(0, nb * block - C, live)
        if C == 512:
            start[0] = 293                   # off a tile, across block 0 -> 1
        rows = jnp.asarray(rng.standard_normal((B, C, Hkv, hd)), dtype)
        want = np.asarray(kv_pool_write_reference(
            pool, rows, jnp.asarray(start), jnp.asarray(tables)), np.float32)
        got = np.array(write(pool, rows, jnp.asarray(start),
                             jnp.asarray(tables)), np.float32)
        # inactive slots collide at block 0, positions 0..C-1: any one wins
        np.testing.assert_array_equal(got[1:], want[1:])
        np.testing.assert_array_equal(got[0, :, C:], want[0, :, C:])
    tables = jnp.asarray(rng.integers(0, N, (3, nb)), jnp.int32)
    want, _ = gather_block_kv(pool, pool, tables)
    got = jax.jit(lambda *a: kv_pool_gather(*a, interpret=False))(pool, tables)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_in_place_paged_programs_match_the_scatter_programs_on_chip(
        monkeypatch):
    """On the chip the rule admits a bfloat16 pool of 512x128 tiles: the
    programs built in place (pool carried, Mosaic writes and reads) leave
    the same pool and the same logits as the xs/ys + scatter form, which
    declining the rule builds."""
    from deepspeed_tpu.models.gpt import GPTConfig, make_gpt_decode_model
    from deepspeed_tpu.ops import attention_dispatch

    def run(in_place):
        if not in_place:
            monkeypatch.setattr(
                attention_dispatch, "kv_pool_writer",
                lambda pool: attention_dispatch.KV_POOL_WRITE_SCATTER)
        cfg = GPTConfig(vocab_size=512, n_layer=3, n_head=8, n_kv_head=4,
                        d_model=1024, d_ff=1024, max_seq_len=8192,
                        use_rotary=True, use_rmsnorm=True, dtype=jnp.bfloat16,
                        remat=False)
        spec = make_gpt_decode_model(cfg, name="chip", seed=0)
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                        spec.params)
        pool = spec.init_paged_pool(40, 512, jnp.bfloat16)
        tables = np.zeros((4, 16), np.int32)
        tables[:3] = 1 + np.arange(3 * 13).reshape(3, 13)[:, :1] + \
            np.arange(16)[None] % 13                      # 3 live slots
        tables = jnp.asarray(tables)
        toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, (1, 512)),
                           jnp.int32)
        logits, pool = jax.jit(spec.prefill_paged_fn, donate_argnums=(4,))(
            params, toks, jnp.asarray([293], jnp.int32),
            jnp.asarray([511], jnp.int32), pool, tables[:1])
        outs = [logits]
        tok = jnp.asarray([3, 5, 7, 0], jnp.int32)
        pos = jnp.asarray([805, 17, 4000, 0], jnp.int32)
        decode = jax.jit(spec.decode_paged_fn, donate_argnums=(3,))
        for _ in range(3):
            logits, pool = decode(params, tok, pos, pool, tables)
            outs.append(logits[:3])
            tok = (tok * 7 + 1) % 512          # not the argmax: a last-bit
            pos = pos + jnp.asarray([1, 1, 1, 0], jnp.int32)  # tie would fork
        return outs, pool, dict(spec.kv_pool_writers)

    got, got_pool, writers = run(True)
    assert set(writers.values()) == {attention_dispatch.KV_POOL_WRITE_KERNEL}
    want, want_pool, writers = run(False)
    assert set(writers.values()) == {attention_dispatch.KV_POOL_WRITE_SCATTER}
    # two different XLA programs around the same kernels: a row in the wrong
    # place is a difference of order 1, a fusion's other rounding point is
    # one bfloat16 ulp (the kernels alone are held bit for bit above)
    def close(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, rtol=2e-2,
                                   atol=2e-2 * float(np.abs(b).max()))

    for leaf in ("k", "v"):
        close(got_pool[leaf][:, 1:], want_pool[leaf][:, 1:])
        assert float(jnp.abs(got_pool[leaf][:, 1:]).max()) > 0
    for a, b in zip(got, want):
        close(a, b)


@pytest.mark.parametrize("n,experts", [(1024, 4), (4096, 64)])
def test_token_sort_compiled(n, experts):
    """All-int32 counting sort: bit-equal to the jnp oracle, compiled."""
    from deepspeed_tpu.ops.pallas.token_sort import (token_sort,
                                                     token_sort_oracle)
    idx = jnp.asarray(np.random.default_rng(19).integers(0, experts, (n,)),
                      jnp.int32)
    pos, counts = jax.jit(lambda i: token_sort(
        i, experts, interpret=False))(idx)
    pos_ref, counts_ref = token_sort_oracle(idx, experts)
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(pos_ref))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_ref))


def _group_sizes(kind, M, E, rng):
    if kind == "even":
        return np.full(E, M // E)
    if kind == "one":                       # one group holds every row
        sizes = np.zeros(E, np.int64)
        sizes[E // 3] = M
        return sizes
    if kind == "sparse":                    # most experts idle, uneven runs
        sizes = np.zeros(E, np.int64)
        live = rng.choice(E, max(2, E // 8), replace=False)
        sizes[live] = rng.multinomial(M, np.ones(len(live)) / len(live))
        return sizes
    return rng.multinomial(M, np.ones(E) / E)        # "random"


@pytest.mark.parametrize("kind", ["even", "one", "sparse", "random"])
@pytest.mark.parametrize("M,K,N", [(512, 2048, 2048), (512, 1024, 2048),
                                   (4096, 2048, 2048), (64, 2048, 2048)])
def test_moe_gmm_compiled_matches_the_segment_loop(M, K, N, kind):
    """`dstpu_moe_gmm` at OLMoE's widths (64 experts; a 64-slot decode step,
    a 512-token chunk, an 8-row step) against the plain loop over groups.
    bfloat16 in, float32 accumulation over the whole K in one dot on both
    sides: the results are equal to the bit, straddled tiles, idle experts
    and the one-group case included."""
    from deepspeed_tpu.ops.pallas.moe_gmm import moe_gmm, moe_gmm_reference
    E = 64
    rng = np.random.default_rng([M, K, len(kind)])
    lhs = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(0, 0.05, (E, K, N)), jnp.bfloat16)
    sizes = jnp.asarray(_group_sizes(kind, M, E, rng), jnp.int32)
    assert int(sizes.sum()) == M
    got = jax.jit(lambda a, b, s: moe_gmm(a, b, s, interpret=False))(
        lhs, rhs, sizes)
    want = jax.jit(moe_gmm_reference)(lhs, rhs, sizes)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    # the second half of the groups as a layer of its own in the stack
    half = sizes.at[:E // 2].set(0)
    half = half.at[E // 2].add(M - half.sum())
    got = jax.jit(lambda a, b, s, o: moe_gmm(a, b, s[E // 2:], o,
                                             interpret=False))(
        lhs, rhs, half, jnp.int32(E // 2))
    want = jax.jit(moe_gmm_reference)(lhs, rhs, half)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_routed_moe_paged_programs_on_chip():
    """The routed top-k layer inside `scan_paged` on the chip: the paged
    programs take the in-place pool form and the Mosaic grouped matmul, and
    serving through the scheduler (window 1 and 4) emits the tokens of the
    greedy full forward, teacher-free, at a size whose logits have wide
    margins (float32)."""
    import deepspeed_tpu
    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.config.core import MeshConfig
    from deepspeed_tpu.inference.scheduler import Request
    from deepspeed_tpu.models.moe_gpt import (MoEGPTConfig,
                                              init_moe_gpt_params,
                                              make_moe_gpt_decode_model,
                                              moe_gpt_forward)
    cfg = MoEGPTConfig(n_layer=2, n_head=2, d_model=256, d_ff=128,
                       vocab_size=512, max_seq_len=512, num_experts=8,
                       top_k=4, moe_freq=1, use_rotary=True, use_swiglu=True,
                       use_rmsnorm=True, qk_norm=True, tie_embeddings=False,
                       use_flash_attention=True, dtype=jnp.float32)
    params = init_moe_gpt_params(cfg, seed=5)
    mesh_mod.clear_mesh()
    mesh_mod.init_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    engine = deepspeed_tpu.init_inference(
        make_moe_gpt_decode_model(cfg, params=params, name="routed"),
        config={"dtype": "float32", "kv_cache_dtype": "float32",
                "greedy": True, "kv_block_size": 128, "max_out_tokens": 512})
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 512, (n,), np.int32) for n in (200, 37)]
    want = []
    forward = jax.jit(lambda p, t: moe_gpt_forward(p, t, cfg,
                                                   training=False)[0])
    with jax.default_matmul_precision("highest"):
        for prompt in prompts:
            seq = list(prompt)
            for _ in range(8):
                padded = np.zeros((1, 256), np.int32)
                padded[0, :len(seq)] = seq
                seq.append(int(forward(params, padded)[0, len(seq) - 1]
                               .argmax()))
            want.append(seq[len(prompt):])
        for window in (1, 4):
            serving = engine.serving(max_slots=8, max_context=512,
                                     prefill_chunk=128, num_kv_blocks=24,
                                     decode_steps_per_sync=window)
            done = serving.run([Request(uid=i, tokens=p, max_new_tokens=8,
                                        stop_on_eos=False)
                                for i, p in enumerate(prompts)])
            stats = serving.stats()
            assert set(stats["kv_pool_writer"].values()) == {
                "dstpu_kv_pool_write"}
            assert stats["step_counters"]["moe_assignments"] > 0
            for i, tokens in enumerate(want):
                assert list(done[i].tokens) == tokens, (window, i)


@pytest.mark.parametrize("cell,group", [("mistral", 1), ("olmoe", 1),
                                        ("mistral", 3), ("olmoe", 2)])
def test_mixed_call_matches_the_two_calls_at_the_served_shapes(cell, group):
    """A chunk riding the decode call (`mixed_paged_fn`) at the served widths
    — Mistral's 512-row chunk beside 32 slots over 32-block tables, OLMoE's
    256-row chunk beside 64 slots through 64 experts (2560 assignments,
    whole 128-row tiles) — two layers deep: its logits and the pool it
    leaves against the chunk program followed by the decode program, on a
    shared state (free-running tokens of two programs are not comparable at
    real widths). The in-place pool and both walks are what the chip's rule
    builds. `group` > 1: a GROUP of consecutive chunks of the one prompt
    rides (every chunk's logits against the chunk program's, run in turn)."""
    from deepspeed_tpu.models.gpt import (GPTConfig, gpt_init_fn,
                                          make_gpt_decode_model)
    from deepspeed_tpu.models.moe_gpt import (MoEGPTConfig,
                                              make_moe_gpt_decode_model,
                                              moe_gpt_init_fn)
    if cell == "mistral":
        cfg = GPTConfig(vocab_size=32000, n_layer=2, n_head=32, n_kv_head=8,
                        d_model=4096, d_ff=14336, max_seq_len=16384,
                        use_rotary=True, rope_theta=1e6, use_rmsnorm=True,
                        use_swiglu=True, tie_embeddings=False,
                        dtype=jnp.bfloat16, remat=False)
        params = jax.jit(gpt_init_fn(cfg, dtype=jnp.bfloat16))(
            jax.random.PRNGKey(3))
        spec = make_gpt_decode_model(cfg, name="chip", params=params)
        slots, nb, chunk, blocks = 32, 32, 512, 100
    else:
        cfg = MoEGPTConfig(vocab_size=50304, n_layer=2, n_head=16,
                           n_kv_head=16, d_model=2048, d_ff=1024,
                           max_seq_len=1536, use_rotary=True,
                           use_rmsnorm=True, use_swiglu=True, qk_norm=True,
                           tie_embeddings=False, num_experts=64, top_k=8,
                           norm_topk_prob=False, moe_freq=1,
                           use_flash_attention=True, dtype=jnp.bfloat16,
                           remat=False)
        params = jax.jit(moe_gpt_init_fn(cfg, dtype=jnp.bfloat16))(
            jax.random.PRNGKey(3))
        spec = make_moe_gpt_decode_model(cfg, params=params, name="chip")
        slots, nb, chunk, blocks = 64, 3, 256, 130
    rng = np.random.default_rng(7)
    # the slots hold contexts of 1..nb-1 blocks; the chunk's slot is the last
    # row's, prefilled to `start` already (zeros there: K/V of padding)
    tables = np.zeros((slots, nb), np.int32)
    free = iter(range(1, blocks))
    live = min(slots - 1, (blocks - 1 - nb) // 2)
    pos = np.zeros((slots,), np.int32)
    for row in range(live):
        need = 1 + row % 2
        tables[row, :need] = [next(free) for _ in range(need)]
        pos[row] = rng.integers(1, need * 512 - 1)
    G = group
    chunk_table = np.zeros((1, nb), np.int32)
    start = 512 if cell == "mistral" else 0
    under = (start + G * chunk - 1) // 512 + 1
    chunk_table[0, :under] = [next(free) for _ in range(under)]
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (slots,)), jnp.int32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (G, chunk)), jnp.int32)
    pos, tables = jnp.asarray(pos), jnp.asarray(tables)
    starts = jnp.asarray(start + chunk * np.arange(G), jnp.int32)
    lasts = jnp.asarray([chunk - 7] * G, jnp.int32)
    chunk_table = jnp.asarray(chunk_table)

    def pool():
        fresh = spec.init_paged_pool(blocks, 512, jnp.bfloat16)
        key = jax.random.PRNGKey(11)
        return {name: (jax.random.normal(key, leaf.shape, jnp.bfloat16) * 0.5)
                .at[:, 0].set(0) for name, leaf in fresh.items()}

    prefill = jax.jit(spec.prefill_paged_fn, donate_argnums=(4,))
    decode = jax.jit(spec.decode_paged_fn, donate_argnums=(3,))
    mixed = jax.jit(spec.mixed_paged_fn, donate_argnums=(7,))
    firsts, two = [], pool()
    for j in range(G):
        first, two, *_ = prefill(params, toks[j:j + 1], starts[j:j + 1],
                                 lasts[j:j + 1], two, chunk_table)
        firsts.append(first[0])
    rows, two, *_ = decode(params, tok, pos, two, tables)
    both, one, *_ = mixed(params, toks, starts, lasts,
                          jnp.tile(chunk_table, (G, 1)), tok, pos, pool(),
                          tables, *([jnp.int32(G)] if G > 1 else []))
    assert spec.kv_pool_writers["mixed"] == "dstpu_kv_pool_write"
    assert spec.paged_attn_programs["mixed/prefill_chunk"] \
        == "paged_prefill_kernel"
    assert spec.paged_attn_programs["mixed/paged_decode"] == "paged_kernel"

    def close(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, rtol=2e-2,
                                   atol=2e-2 * float(np.abs(b).max()))

    close(both[:G], jnp.stack(firsts))
    close(both[G:G + live], rows[:live])
    for leaf in one:
        close(one[leaf][:, 1:], two[leaf][:, 1:])


def test_grouped_mixed_call_on_two_kinds_matches_the_calls_of_its_own():
    """A GROUP of three chunks of one prompt riding a decode token on the
    pool of two kinds, MiMo-V2-Flash's published widths three layers deep
    (full, window, window; keys 192 wide beside values of 128, 4 and 8 KV
    heads, the window layers' learned sink, 8 of 256 experts held): the
    group's 768 positions wrap the window kind's ring of four 128-token
    blocks, one chunk in flight. Its logits and both kinds' leaves against
    three chunk programs in turn followed by the decode program, on a shared
    state."""
    from deepspeed_tpu.inference.kv_cache import ring_blocks, ring_tables
    from deepspeed_tpu.models import mimo_v2_flash as mm
    pattern = (0, 1, 1)
    cfg = mm.MiMoV2FlashConfig(
        vocab_size=4096, n_layer=3, n_head=64, d_model=4096,
        attn_head_dim=192, attn_value_dim=128, attn_value_scale=0.707,
        rotary_pct=0.334, n_kv_head=4, swa_n_kv_head=8, rope_theta=5e6,
        swa_rope_theta=1e4, swa_sink=True, full_sink=False,
        sliding_window=128, window_block=128,
        layer_types=mm.layer_types(pattern),
        mlp_layer_types=mm.mlp_layer_types((0, 1, 1)), d_ff=2048,
        d_ff_dense=16384, max_seq_len=4096, norm_eps=1e-5,
        tie_embeddings=False, num_experts=256, experts_held=(0, 8), top_k=8,
        norm_topk_prob=True, router_scoring="sigmoid",
        routed_scaling_factor=1.0, use_flash_attention=True,
        dtype=jnp.bfloat16)
    params = jax.jit(mm.mimo_v2_flash_init_fn(
        cfg, dtype=jnp.bfloat16, embedding_std=1.0))(jax.random.PRNGKey(3))
    spec = mm.make_mimo_v2_flash_decode_model(cfg, params=params, name="chip")
    slots, chunk, G, block, blocks = 16, 256, 3, 512, 40
    nb, nbw = 4096 // block, 4096 // 128
    ring = ring_blocks(128, 128, chunk, 1)
    assert ring == 4
    rings = ring_tables(slots, nbw, ring)
    rng = np.random.default_rng(7)
    tables = np.zeros((slots, nb), np.int32)
    free = iter(range(1, blocks))
    pos = np.zeros((slots,), np.int32)
    live = slots - 1            # the last slot is the prompt's
    for row in range(live):
        need = 1 + row % 2
        tables[row, :need] = [next(free) for _ in range(need)]
        pos[row] = rng.integers(1, need * block - 1)
    start = 512
    chunk_table = np.zeros((1, nb), np.int32)
    under = (start + G * chunk - 1) // block + 1
    chunk_table[0, :under] = [next(free) for _ in range(under)]
    chunk_tables = (jnp.asarray(chunk_table), jnp.asarray(rings[live:]))
    slot_tables = (jnp.asarray(tables), jnp.asarray(
        np.where(np.arange(slots)[:, None] < live, rings, 0).astype(np.int32)))
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (slots,)), jnp.int32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (G, chunk)), jnp.int32)
    starts = jnp.asarray(start + chunk * np.arange(G), jnp.int32)
    lasts = jnp.asarray([chunk - 1, chunk - 7, chunk - 30], jnp.int32)
    pos = jnp.asarray(pos)

    def pool():
        fresh = spec.init_paged_pool(blocks, block, jnp.bfloat16,
                                     window_blocks=1 + slots * ring)
        key = jax.random.PRNGKey(11)
        return {name: (jax.random.normal(key, leaf.shape, jnp.bfloat16) * 0.5)
                .at[:, 0].set(0) for name, leaf in fresh.items()}

    prefill = jax.jit(spec.prefill_paged_fn, donate_argnums=(4,))
    decode = jax.jit(spec.decode_paged_fn, donate_argnums=(3,))
    mixed = jax.jit(spec.mixed_paged_fn, donate_argnums=(7,))
    firsts, two = [], pool()
    for j in range(G):
        first, two, *_ = prefill(params, toks[j:j + 1], starts[j:j + 1],
                                 lasts[j:j + 1], two, chunk_tables)
        firsts.append(first[0])
    rows, two, *_ = decode(params, tok, pos, two, slot_tables)
    both, one, *_ = mixed(
        params, toks, starts, lasts,
        tuple(jnp.tile(t, (G, 1)) for t in chunk_tables), tok, pos, pool(),
        slot_tables, jnp.int32(G))
    assert spec.kv_pool_writers["mixed"] == "dstpu_kv_pool_write"
    assert spec.paged_attn_programs["mixed/prefill_chunk"] \
        == "paged_prefill_kernel"
    assert spec.paged_attn_programs["mixed/paged_decode"] == "paged_kernel"

    def close(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, rtol=2e-2,
                                   atol=2e-2 * float(np.abs(b).max()))

    close(both[:G], jnp.stack(firsts))
    close(both[G:G + live], rows[:live])
    for leaf in one:
        close(one[leaf][:, 1:], two[leaf][:, 1:])
    # a partial group: the third chunk absent, its rows padding — the first
    # two chunks' logits and the slots' as before, its positions unwritten
    part, _, *_ = mixed(
        params, toks, starts, lasts,
        tuple(jnp.tile(t, (G, 1)) for t in chunk_tables), tok, pos, pool(),
        slot_tables, jnp.int32(G - 1))
    close(part[:G - 1], jnp.stack(firsts[:G - 1]))


@pytest.mark.parametrize("cell", ["olmoe", "sdar"])
def test_the_frontier_cut_compiled_at_the_served_shapes(cell, monkeypatch):
    """`_paged_cut_kernel` at the two served shapes whose tables are short
    (OLMoE: 64 slots, 16 KV heads of one query row, 3 blocks; SDAR: 128
    slots, 4 KV heads of 32 rows — 8 query heads x a block of 4 positions —
    5 blocks, `pos | 3`): frontiers at both ends of every row tile of the
    first block, of a later block and at the table's last position, dead
    slots between the live ones — against the gather oracle, and against the
    whole-block walk the rule is steered back to."""
    from deepspeed_tpu.ops.pallas import decode_attention as da
    B, Hkv, G, nb, N = {"olmoe": (64, 16, 1, 3, 131),
                        "sdar": (128, 4, 32, 5, 449)}[cell]
    assert da._frontier_rows(nb, 512, (128, 128)) == 128
    edges = [t * 128 + d for t in range(4) for d in (0, 127)]
    contexts = edges + [512 + e for e in edges] + [nb * 512 - 1, 300, 700]
    rng = np.random.default_rng(62)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(62), 3)
    q = jax.random.normal(kq, (B, Hkv * G, 128), jnp.bfloat16)
    k = jax.random.normal(kk, (N, Hkv, 512, 128), jnp.bfloat16)
    v = jax.random.normal(kv, (N, Hkv, 512, 128), jnp.bfloat16)
    tables = np.zeros((B, nb), np.int32)
    pos = np.zeros((B,), np.int32)
    physical = iter(1 + rng.permutation(N - 1))
    rows = rng.permutation(B)[:len(contexts)]
    for b, at in zip(rows, contexts):
        pos[b] = at | 3 if cell == "sdar" else at
        for j in range(pos[b] // 512 + 1):
            tables[b, j] = next(physical)
    live = np.zeros((B,), bool)
    live[rows] = True
    operands = (q, k, v, jnp.asarray(tables), jnp.asarray(pos))
    walk = lambda: np.asarray(jax.jit(lambda *a: da.paged_decode_attention(
        *a, interpret=False))(*operands), np.float32)
    out = walk()
    ref = np.asarray(da.paged_decode_attention_reference(*operands),
                     np.float32)
    np.testing.assert_allclose(out[live], ref[live], atol=3e-2, rtol=3e-2)
    assert not out[~live].any()
    monkeypatch.setattr(da, "_frontier_rows",
                        lambda nb, block_m, *a, **kw: block_m)
    np.testing.assert_allclose(out, walk(), atol=1e-2, rtol=1e-2)


def test_sparse_index_kernels_compiled_at_the_served_shapes():
    """`ops/pallas/sparse_index.py` at Keye-VL-2.0's served shapes (16 index
    heads of 64, blocks of 512, a table of 132, chunks of 1024, `topk` 2048):
    the score walks against their `jax.numpy` twin on the gathered keys, the
    selection EXACTLY its twin's set (planted ties, a row of zeros of both
    signs), and both masked walks against the dense attend under the same
    selection."""
    from deepspeed_tpu.models.gpt import GPTConfig
    from deepspeed_tpu.models.sparse_attn import _attend_selected
    from deepspeed_tpu.ops.pallas import sparse_index as si
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention
    from deepspeed_tpu.ops.pallas.prefill_attention import \
        paged_prefill_attention
    rng = np.random.default_rng(60)
    C, Hi, d, block, nb, M, topk = 1024, 16, 64, 512, 132, 200, 2048
    H, Hkv, hd, S = 32, 4, 128, 16
    keys = jnp.asarray(rng.normal(size=(M, 1, block, 128)),
                       jnp.bfloat16).at[..., d:].set(0)
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(M, Hkv, block, hd)),
                                  jnp.bfloat16) for _ in range(2))
    live = 41                       # a chunk from position 19,700 to 20,723
    table = np.zeros((1, nb), np.int32)
    table[0, :live] = rng.permutation(np.arange(1, M))[:live]
    start = jnp.asarray([19700], jnp.int32)
    qi = jnp.asarray(rng.normal(size=(1, C, Hi, d)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(1, C, Hi)), jnp.float32)
    scores = jax.jit(lambda *a: si.paged_index_scores(*a, interpret=False))(
        qi, w, keys, jnp.asarray(table), start)
    ctx = keys[table[0, :live], 0].reshape(1, live * block, 128)[..., :d]
    want = si.index_scores(qi, w, ctx)
    got = np.asarray(scores)[:, :live].transpose(0, 2, 1, 3).reshape(
        1, C, live * block)
    # past a row's own position: whatever the memory held
    limit = start[:, None] + jnp.arange(C)[None] + 1
    seen = np.arange(live * block)[None, None] < np.asarray(limit)[..., None]
    np.testing.assert_allclose(np.where(seen, got, 0),
                               np.where(seen, np.asarray(want), 0),
                               rtol=2e-2, atol=2e-2)
    # the selection of the kernel's OWN scores, ties planted
    planted = scores.at[0, 3, :, 7].set(0.25).at[0, 9, :, 100].set(0.25) \
        .at[0, :live, 5, :].set(0.0).at[0, :live, 5, ::2].set(-0.0)
    select = jax.jit(lambda a, b: si.sparse_select(a, b, topk,
                                                   interpret=False))

    def selected(major, limit, live):
        """-> (the kernel's mask, the twin's set, the kernel's counters),
        within the rows' own positions."""
        chosen, counts = select(major, limit)
        seen = np.arange(live * block)[None, None] \
            < np.asarray(limit)[..., None]
        flat = np.asarray(major)[:, :live].transpose(0, 2, 1, 3).reshape(
            1, C, live * block)
        twin = np.asarray(si.select_topk(
            jnp.asarray(np.where(seen, flat, 0)), limit, topk))
        mine = np.asarray(chosen)[:, :live].transpose(0, 2, 1, 3).reshape(
            1, C, live * block) > 0
        differ = ((mine & seen) != twin)[0].sum(-1)
        assert not differ.any(), (
            np.flatnonzero(differ)[:12], differ[differ > 0][:12],
            (mine & seen)[0].sum(-1)[differ > 0][:12])
        assert (twin.sum(-1) == topk).all()
        return chosen, twin, np.asarray(counts)

    chosen, twin, counts = selected(planted, limit, live)
    # 16 row tiles; no bracket refuted, and far fewer sweeps than the 36 a
    # tile that a search over the key's 32 bits makes (PR 61)
    assert counts[0] == C // 64 and counts[2] == 0
    assert counts[1] < 28 * counts[0]
    # ... an ADVERSARIAL layout: every key the sample holds small (lane l of
    # block j, lane tile g, where l % 32 == (j % 8) * 4 + g at this `topk`),
    # every other large — every bracket lies under the row's k-th key, the
    # proving sweep says so, and the set is still the twin's
    j, _, l = np.ogrid[:nb, :1, :block]
    sampled = (l % 128) % 32 == (j % 8) * 4 + l // 128
    hostile = jnp.where(jnp.asarray(sampled)[None], -jnp.abs(scores) - 1.0,
                        jnp.abs(scores))
    _, _, counts = selected(hostile, limit, live)
    assert counts[2] == counts[0] == C // 64
    # ... and the table's last chunk: a frontier of 66,560 positions
    deep = jnp.asarray(rng.normal(size=(1, nb, C, block)), jnp.float32)
    far = jnp.asarray([130 * block - C], jnp.int32)[:, None] \
        + jnp.arange(C)[None] + 1
    _, _, counts = selected(deep, far, 130)
    assert counts[2] == 0 and counts[1] < 28 * counts[0]
    # the chunk walk under the selection
    cfg = GPTConfig(n_head=H, n_kv_head=Hkv, d_model=H * hd)
    q = jnp.asarray(rng.normal(size=(1, C, H, hd)), jnp.bfloat16)
    out = jax.jit(lambda *a: paged_prefill_attention(
        *a, selected=chosen, interpret=False))(
        q, k_pool, v_pool, jnp.asarray(table), start)
    gather = lambda pool: jnp.moveaxis(pool[table[0, :live]], 1, 0).reshape(
        1, Hkv, live * block, hd)
    ref = _attend_selected(q, gather(k_pool), gather(v_pool),
                           jnp.asarray(twin), cfg)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2,
                               rtol=3e-2)
    # the slots' rows: scores over the work list, the bias, the decode walk
    pos = np.asarray(rng.integers(3000, live * block, S), np.int32)
    pos[5] = 0
    tables = np.zeros((S, nb), np.int32)
    for b in range(S):
        n = pos[b] // block + 1
        tables[b, :n] = rng.permutation(np.arange(1, M))[:n]
    tables[5] = 0                   # a dead slot
    qd = jnp.asarray(rng.normal(size=(S, Hi, d)), jnp.bfloat16)
    wd = jnp.asarray(rng.normal(size=(S, Hi)), jnp.float32)
    sd = jax.jit(lambda *a: si.paged_index_scores_decode(
        *a, interpret=False))(qd, wd, keys, jnp.asarray(tables),
                              jnp.asarray(pos))
    bias, _ = jax.jit(lambda a, b: si.sparse_select(
        a, b, topk, bias=True, interpret=False))(
        jnp.swapaxes(sd[:, :, 0], 0, 1)[None], jnp.asarray(pos + 1)[None])
    qq = jnp.asarray(rng.normal(size=(S, H, hd)), jnp.bfloat16)
    out = jax.jit(lambda *a: paged_decode_attention(
        *a, selected=bias[0][:, :, None], interpret=False))(
        qq, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(pos))
    for b in (0, 7, 15):
        n = pos[b] // block + 1
        ctx = keys[tables[b, :n], 0].reshape(1, n * block, 128)[..., :d]
        want = np.asarray(si.index_scores(qd[b][None, None], wd[b][None, None],
                                          ctx))[0, 0]
        got = np.asarray(sd)[b, :n, 0].reshape(-1)
        np.testing.assert_allclose(got[:pos[b] + 1], want[:pos[b] + 1],
                                   rtol=2e-2, atol=2e-2)
        twin = np.asarray(si.select_topk(jnp.asarray(got)[None],
                                         jnp.asarray([pos[b] + 1]), topk))
        mine = np.asarray(bias)[0, :n, b].reshape(-1) == 0
        np.testing.assert_array_equal(mine, twin[0])
        gather = lambda pool: jnp.moveaxis(pool[tables[b, :n]], 1, 0).reshape(
            1, Hkv, n * block, hd)
        ref = _attend_selected(qq[b][None, None], gather(k_pool),
                               gather(v_pool), jnp.asarray(twin)[None], cfg)
        np.testing.assert_allclose(np.asarray(out[b], np.float32).ravel(),
                                   np.asarray(ref, np.float32).ravel(),
                                   atol=3e-2, rtol=3e-2)
    assert not np.asarray(out[5]).any()


def test_quant_int4_kernels_refuse_on_tpu():
    """Recorded state, not a TODO: the packed-nibble kernels need stride-2
    lane indexing, which the Pallas TPU lowering refuses; the wrappers say
    so by name instead of surfacing the compiler's traceback — and never
    take another path silently."""
    from deepspeed_tpu.ops.pallas.quant import dequantize_int4, quantize_int4
    x = jnp.ones((256, 512), jnp.float32)
    with pytest.raises(NotImplementedError, match="stride-2 lane"):
        quantize_int4(x)
    with pytest.raises(NotImplementedError, match="stride-2 lane"):
        dequantize_int4(jnp.ones((256, 256), jnp.int8),
                        jnp.ones((256, 4), jnp.float32))


def _bench(fn, *args, iters=10, batches=5):
    """Best-of-N batched timing."""
    out = fn(*args)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))  # hard fence
    best = float("inf")
    for _ in range(batches):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def test_flash_beats_xla_at_long_seq():
    """The kernel's raison d'être: at seq >= 4k causal, streaming-softmax
    flash must beat materialized XLA attention (VERDICT r1 weak #3)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    B, T, H, D = 1, 4096, 8, 128
    rng = np.random.default_rng(6)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, T, H, D)), jnp.bfloat16)
               for _ in range(3))
    sm = 1.0 / np.sqrt(D)
    flash = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False))
    xla = jax.jit(lambda q, k, v: _xla_attention(q, k, v, True, sm)
                  .astype(jnp.bfloat16))
    # INTERLEAVE the two variants' timing batches (drift over the run then
    # lands on both sides)
    for f in (flash, xla):
        float(jnp.sum(f(q, k, v).astype(jnp.float32)))
    best = {"flash": float("inf"), "xla": float("inf")}
    for _ in range(5):
        for name, f in (("flash", flash), ("xla", xla)):
            t0 = time.perf_counter()
            for _ in range(10):
                out = f(q, k, v)
            float(jnp.sum(out.astype(jnp.float32)))
            best[name] = min(best[name], (time.perf_counter() - t0) / 10)
    t_flash, t_xla = best["flash"], best["xla"]
    print(f"\nseq {T}: flash {t_flash*1e3:.2f}ms vs XLA {t_xla*1e3:.2f}ms "
          f"({t_xla/t_flash:.2f}x)")
    assert t_flash < t_xla, \
        f"flash ({t_flash*1e3:.2f}ms) slower than XLA ({t_xla*1e3:.2f}ms) at seq {T}"


def test_serving_throughput_decode_paths():
    """Serving-throughput proof (VERDICT r3 #7): batched generation (prefill
    + N decode steps) measured as tokens/s for BOTH decode paths at 2k
    context; the DEFAULT (auto) path must not lose badly to the alternative.
    Measured on the chip in PR 21 (TPU v5 lite, this shape): XLA decode
    1632 tok/s vs Pallas 1851 — the wall-clock bound below is kept as found
    (no assertion is tuned here; S0's per-cell bound replaces it, ROADMAP
    D12)."""
    import dataclasses
    from deepspeed_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                          make_gpt_decode_model)
    B, M, ctx = 4, 2048, 2048 - 64
    base = GPTConfig(n_layer=8, n_head=8, d_model=1024, max_seq_len=M,
                     vocab_size=50304, remat=False)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), init_gpt_params(base, seed=0))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, 1000, (B, 128)), jnp.int32)

    runners = {}
    for name, flag in (("xla", None), ("pallas", True)):
        cfg = dataclasses.replace(base, use_flash_attention=flag)
        spec = make_gpt_decode_model(cfg=cfg, params=params)
        cache = spec.init_cache(B, M, jnp.bfloat16)
        # pre-filled long context: decode cost is dominated by cache reads
        cache = {"k": jax.random.normal(jax.random.PRNGKey(0),
                                        cache["k"].shape, jnp.bfloat16),
                 "v": jax.random.normal(jax.random.PRNGKey(1),
                                        cache["v"].shape, jnp.bfloat16),
                 "length": jnp.full((B,), ctx, jnp.int32)}

        def mk(reps, spec=spec):
            @jax.jit
            def run(params, tok, cache):
                def step(carry, _):
                    tok, pos, cache = carry
                    logits, cache = spec.decode_fn(params, tok, pos, cache)
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return (nxt, pos + 1, cache), logits.mean()
                pos = jnp.full((B,), ctx, jnp.int32)
                (tok, _, _), outs = jax.lax.scan(step, (tok, pos, cache),
                                                 None, length=reps)
                return outs.sum()
            return run

        tok = jnp.zeros((B,), jnp.int32)
        lo, hi = mk(8), mk(32)
        float(lo(params, tok, cache)); float(hi(params, tok, cache))
        runners[name] = (lo, hi, cache, tok)

    # INTERLEAVE the two paths' rounds
    best = {"xla": float("inf"), "pallas": float("inf")}
    for _ in range(4):
        for name, (lo, hi, cache, tok) in runners.items():
            t0 = time.perf_counter(); float(lo(params, tok, cache))
            a = time.perf_counter() - t0
            t0 = time.perf_counter(); float(hi(params, tok, cache))
            b = time.perf_counter() - t0
            if b > a:   # timer noise can invert the pair; a negative
                best[name] = min(best[name], (b - a) / 24)  # per-step time
    assert all(v < float("inf") for v in best.values()),         f"every timing round inverted (extreme contention): {best}"
    results = {k: B / v for k, v in best.items()}
    print(f"\ndecode tokens/s at ctx {ctx}: xla {results['xla']:.0f} "
          f"pallas {results['pallas']:.0f}")
    # the shipped default (auto = XLA decode at this context) must not be a
    # bad call
    assert results["xla"] > 0.75 * results["pallas"], results
