"""Block-sparse flash kernel (reference `ops/sparse_attention/matmul.py:17`
Triton SDD/DSD analog): numerics vs the dense masked path for every layout
family, gradients, the SparseSelfAttention fast-path routing, and a real-TPU
timing lane."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.sparse_attention import (BigBirdSparsityConfig,
                                                BSLongformerSparsityConfig,
                                                FixedSparsityConfig,
                                                SparseSelfAttention,
                                                VariableSparsityConfig)
from deepspeed_tpu.ops.pallas.block_sparse_attention import (
    BLOCK_K, block_sparse_attention, _build)

B, H, T, D = 2, 4, 512, 64


def _qkv(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(0, 1, (B, H, T, D)), dtype)
                 for _ in range(3))


def _dense_reference(cfg, q, k, v):
    """The dense masked fp32 path, bypassing the kernel fast path."""
    attn = SparseSelfAttention(cfg)
    mask = attn._mask(T)
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(D)
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p, v.astype(jnp.float32)).astype(q.dtype)


LAYOUT_FAMILIES = [
    ("fixed", FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=4,
                                  num_global_blocks=1,
                                  attention="unidirectional")),
    ("bigbird", BigBirdSparsityConfig(num_heads=H, block=16,
                                      num_random_blocks=2,
                                      num_sliding_window_blocks=3,
                                      num_global_blocks=1)),
    ("bslongformer", BSLongformerSparsityConfig(num_heads=H, block=16,
                                                num_sliding_window_blocks=5,
                                                global_block_indices=(0, 7))),
    ("variable", VariableSparsityConfig(num_heads=H, block=16,
                                        num_random_blocks=1,
                                        local_window_blocks=(2, 4, 8),
                                        global_block_indices=(0,),
                                        different_layout_per_head=True)),
]


@pytest.mark.parametrize("name,cfg", LAYOUT_FAMILIES, ids=[n for n, _ in LAYOUT_FAMILIES])
def test_kernel_matches_dense_masked(name, cfg):
    q, k, v = _qkv()
    layout = cfg.make_layout(T)
    ref = _dense_reference(cfg, q, k, v)
    out = block_sparse_attention(q, k, v, layout, block=cfg.block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block_q", [128, 256])
def test_kernel_gradients_match_dense(block_q):
    cfg = LAYOUT_FAMILIES[0][1]
    q, k, v = _qkv(1)
    layout = cfg.make_layout(T)

    def f_sparse(q, k, v):
        return jnp.sum(block_sparse_attention(q, k, v, layout, block=16,
                                              block_q=block_q) ** 2)

    def f_dense(q, k, v):
        return jnp.sum(_dense_reference(cfg, q, k, v) ** 2)

    gs = jax.grad(f_sparse, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_sparse_self_attention_routes_to_kernel():
    """T % 128 == 0 + no extra masks -> the kernel path; outputs match the
    dense fallback (which extra-mask calls still take)."""
    cfg = FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=4,
                              num_global_blocks=1, attention="unidirectional")
    q, k, v = _qkv(2)
    attn = SparseSelfAttention(cfg)
    out = attn(q, k, v)
    ref = _dense_reference(cfg, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # non-128-multiple T falls back to the dense path and still works
    q2, k2, v2 = (x[:, :, :320] for x in (q, k, v))
    out2 = attn(q2, k2, v2)
    assert out2.shape == (B, H, 320, D)


def _dense_with_masks(attn, q, k, v, rpe=None, attn_mask=None, kpm=None):
    """The dense fallback math (mirrors SparseSelfAttention.__call__'s tail),
    used as the reference for the in-kernel mask streaming."""
    Tl = q.shape[2]
    mask = attn._mask(Tl)
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(q.shape[-1])
    if rpe is not None:
        r = jnp.asarray(rpe)
        s = s + (r if r.ndim == 4 else r[None] if r.ndim == 3 else r[None, None])
    s = jnp.where(mask[None], s, -1e30)
    if attn_mask is not None:
        m = jnp.asarray(attn_mask)
        while m.ndim < 4:
            m = m[None]
        if attn.attn_mask_mode == "mul":
            s = jnp.where(m != 0, s, -1e30)
        else:
            s = s + m.astype(s.dtype)
    if kpm is not None:
        s = jnp.where(kpm[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)


def _masked_case(T2=2048, H2=2, B2=2, seed=5):
    """Fixed layout at T=2k + rpe + keep-style attn_mask + key padding, built
    so no query row goes fully dead (diagonal kept; early global keys never
    padded)."""
    cfg2 = FixedSparsityConfig(num_heads=H2, block=16, num_local_blocks=8,
                               num_global_blocks=1)
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B2, H2, T2, D)), jnp.float32)
               for _ in range(3))
    rpe = jnp.asarray(rng.normal(0, 0.5, (T2, T2)), jnp.float32)
    keep = rng.random((T2, T2)) > 0.1
    np.fill_diagonal(keep, True)
    attn_mask = jnp.asarray(keep.astype(np.float32))
    kpm_np = np.ones((B2, T2), bool)
    kpm_np[:, -100:] = False          # pad the tail; global cols stay live
    return cfg2, q, k, v, rpe, attn_mask, jnp.asarray(kpm_np)


def test_kernel_masks_parity_2k():
    """VERDICT r4 item 2: rpe + attn_mask + key_padding_mask at T=2k route
    THROUGH the kernel (no dense fallback) and match the dense masked math."""
    cfg2, q, k, v, rpe, attn_mask, kpm = _masked_case()
    attn = SparseSelfAttention(cfg2)
    out = attn(q, k, v, rpe=rpe, attn_mask=attn_mask, key_padding_mask=kpm)
    ref = _dense_with_masks(attn, q, k, v, rpe=rpe, attn_mask=attn_mask,
                            kpm=kpm)
    valid = np.asarray(kpm)[:, None, :, None]  # padded-out QUERY rows excluded
    np.testing.assert_allclose(np.asarray(out) * valid, np.asarray(ref) * valid,
                               rtol=3e-5, atol=3e-5)


def test_kernel_mask_grads_match_dense_incl_rpe():
    """The in-kernel dbias accumulation must reproduce the dense path's rpe
    gradient (rpe can be a LEARNED relative-position table), along with
    dq/dk/dv under all three mask operands."""
    cfg2, q, k, v, rpe, attn_mask, kpm = _masked_case(T2=1024, seed=6)
    attn = SparseSelfAttention(cfg2)

    def f_kernel(q, k, v, rpe):
        return jnp.sum(attn(q, k, v, rpe=rpe, attn_mask=attn_mask,
                            key_padding_mask=kpm) ** 2)

    def f_dense(q, k, v, rpe):
        return jnp.sum(_dense_with_masks(attn, q, k, v, rpe=rpe,
                                         attn_mask=attn_mask, kpm=kpm) ** 2)

    gs = jax.grad(f_kernel, argnums=(0, 1, 2, 3))(q, k, v, rpe)
    gd = jax.grad(f_dense, argnums=(0, 1, 2, 3))(q, k, v, rpe)
    for a, b in zip(gs, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_kernel_per_head_bias_and_add_mode():
    """[H, T, T] per-head rpe (per-head dbias blocks) + additive attn_mask
    mode, forward and rpe-grad parity."""
    T2, H2, B2 = 1024, 2, 1
    cfg2 = FixedSparsityConfig(num_heads=H2, block=16, num_local_blocks=8,
                               num_global_blocks=1)
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B2, H2, T2, D)), jnp.float32)
               for _ in range(3))
    rpe = jnp.asarray(rng.normal(0, 0.5, (H2, T2, T2)), jnp.float32)
    add_mask = jnp.asarray(rng.normal(0, 0.3, (T2, T2)), jnp.float32)
    attn = SparseSelfAttention(cfg2, attn_mask_mode="add")

    def loss_k(q, rpe):
        return jnp.sum(attn(q, k, v, rpe=rpe, attn_mask=add_mask) ** 2)

    def loss_d(q, rpe):
        return jnp.sum(_dense_with_masks(attn, q, k, v, rpe=rpe,
                                         attn_mask=add_mask) ** 2)

    np.testing.assert_allclose(
        np.asarray(attn(q, k, v, rpe=rpe, attn_mask=add_mask)),
        np.asarray(_dense_with_masks(attn, q, k, v, rpe=rpe,
                                     attn_mask=add_mask)),
        rtol=3e-5, atol=3e-5)
    gk = jax.grad(loss_k, argnums=(0, 1))(q, rpe)
    gd = jax.grad(loss_d, argnums=(0, 1))(q, rpe)
    for a, b in zip(gk, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_mask_only_grads_skip_dbias_but_stay_correct():
    """attn_mask WITHOUT rpe routes with bias_needs_grad=False: the backward
    must not materialize the dense [B, Hb, T, T] dbias tensor (review r5
    finding), while dq/dk/dv still reflect the mask exactly."""
    cfg2, q, k, v, _, attn_mask, kpm = _masked_case(T2=1024, seed=11)
    attn = SparseSelfAttention(cfg2)

    def f_kernel(q, k, v):
        return jnp.sum(attn(q, k, v, attn_mask=attn_mask,
                            key_padding_mask=kpm) ** 2)

    def f_dense(q, k, v):
        return jnp.sum(_dense_with_masks(attn, q, k, v, attn_mask=attn_mask,
                                         kpm=kpm) ** 2)

    gs = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)
    # structural pin: the blocked dbias_raw output [B, Hb, nbq, nbk, bq, bk]
    # must be absent from the mask-only backward (and present when an rpe IS
    # learned — positive control proving the probe string is right)
    B2, T2 = q.shape[0], q.shape[2]
    bq = 128
    nb = T2 // bq
    dbias_shape = f"f32[{B2},1,{nb},{nb},{bq},{BLOCK_K}]"
    assert dbias_shape not in str(
        jax.make_jaxpr(jax.grad(f_kernel))(q, k, v)), \
        "mask-only backward materializes the dense dbias tensor"
    rpe = jnp.zeros((T2, T2), jnp.float32)

    def f_rpe(q, rpe):
        return jnp.sum(attn(q, k, v, rpe=rpe, attn_mask=attn_mask,
                            key_padding_mask=kpm) ** 2)

    assert dbias_shape in str(
        jax.make_jaxpr(jax.grad(f_rpe, argnums=(0, 1)))(q, rpe)), \
        "positive control failed: learned-rpe backward should emit dbias"

    # ADD-mode masks WERE differentiable on the dense path — the kernel
    # routing must keep that (r5 review regression finding: a learned
    # additive bias passed via attn_mask silently froze)
    attn_add = SparseSelfAttention(cfg2, attn_mask_mode="add")
    am = jnp.asarray(np.random.default_rng(12).normal(0, 0.3, (T2, T2)),
                     jnp.float32)
    gk = jax.grad(lambda m: jnp.sum(attn_add(q, k, v, attn_mask=m) ** 2))(am)
    gd = jax.grad(lambda m: jnp.sum(
        _dense_with_masks(attn_add, q, k, v, attn_mask=m) ** 2))(am)
    assert float(jnp.abs(gk).max()) > 0, "add-mode mask gradient is zero"
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gd),
                               rtol=3e-4, atol=3e-4)


def test_frozen_rpe_skips_dbias():
    """rpe_requires_grad=False (ADVICE r5 #1): a frozen rpe table must not
    materialize the dense [B, Hb, nbq, nbk, bq, bk] fp32 dbias in backward,
    and dq/dk/dv must still reflect the rpe exactly."""
    cfg2, q, k, v, rpe, _, _ = _masked_case(T2=1024, seed=13)
    frozen = SparseSelfAttention(cfg2, rpe_requires_grad=False)
    learned = SparseSelfAttention(cfg2)

    def f(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v, rpe=rpe) ** 2)

    gs = jax.grad(f(frozen), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(
        _dense_with_masks(frozen, q, k, v, rpe=rpe) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)
    B2, T2 = q.shape[0], q.shape[2]
    bq = 128
    nb = T2 // bq
    dbias_shape = f"f32[{B2},1,{nb},{nb},{bq},{BLOCK_K}]"
    assert dbias_shape not in str(
        jax.make_jaxpr(jax.grad(f(frozen)))(q, k, v)), \
        "frozen-rpe backward materializes the dense dbias tensor"
    # positive control: the default (learned) rpe still emits it
    assert dbias_shape in str(
        jax.make_jaxpr(jax.grad(f(learned)))(q, k, v)), \
        "positive control failed: learned-rpe backward should emit dbias"


@pytest.mark.parametrize("lead", [(1,), (1, 1)])
def test_batch_shared_attn_mask_takes_kernel(lead):
    """[1, T, T] / [1, 1, T, T] batch-shared masks (ADVICE r5 #2) squeeze to
    the kernel's (T, T) gate instead of silently falling to the dense
    O(T^2) path — pinned structurally (pallas_call in the jaxpr) and
    numerically against the explicitly-2D call."""
    cfg2, q, k, v, _, attn_mask, _ = _masked_case(T2=1024, seed=14)
    attn = SparseSelfAttention(cfg2)
    shaped = attn_mask.reshape(lead + attn_mask.shape)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda q, k, v, m: attn(q, k, v, attn_mask=m))(q, k, v, shaped)), \
        f"{shaped.shape} mask fell off the kernel path"
    out = attn(q, k, v, attn_mask=shaped)
    ref = attn(q, k, v, attn_mask=attn_mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_batched_attn_mask_falls_back_with_warning():
    """A [B, T, T] batched attn_mask doesn't fit the head-slab streaming: the
    dense path still serves it, and LOUDLY (VERDICT r4: the silent fallback
    was the bug). The repo logger binds the real stdout (propagate=False), so
    the test hooks a handler onto it instead of using caplog/capfd."""
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger
    T2, H2, B2 = 256, 2, 2
    cfg2 = FixedSparsityConfig(num_heads=H2, block=16, num_local_blocks=4)
    rng = np.random.default_rng(8)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B2, H2, T2, D)), jnp.float32)
               for _ in range(3))
    batched = jnp.ones((B2, T2, T2), jnp.float32)
    attn = SparseSelfAttention(cfg2)

    messages = []
    handler = logging.Handler()
    handler.emit = lambda r: messages.append(r.getMessage())
    ds_logger.addHandler(handler)
    try:
        out = attn(q, k, v, attn_mask=batched)
        assert out.shape == (B2, H2, T2, D)
        assert any("dense" in m.lower() for m in messages), messages
        # mask-free 128-multiple calls stay on the kernel: no new warning
        messages.clear()
        attn(q, k, v)
        assert not any("dense" in m.lower() for m in messages), messages
    finally:
        ds_logger.removeHandler(handler)


def test_visit_lists_skip_dead_blocks():
    """The kernel's whole point: visited k-blocks per row track the layout,
    not T — at ~19% density the mean visit count is a fraction of nb."""
    cfg = FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=4,
                              num_global_blocks=1, attention="unidirectional")
    layout = cfg.make_layout(T)
    counts, idx, *_ = _build(layout, T, 16, 128)
    nb = T // 128
    assert counts.mean() < 0.75 * nb, (counts.mean(), nb)
    assert counts.min() >= 1


def test_dead_query_row_rejected():
    """A q row dead at KERNEL granularity (a full 128-token stripe with no
    live k-block) has an empty visit set -> undefined softmax; the build
    refuses. (A dead 16-granular row inside a live kernel row degrades to the
    dense path's uniform-softmax behavior instead — consistent, not fatal.)"""
    layout = np.zeros((1, T // 16, T // 16), bool)
    layout[:, :, 0] = True
    layout[0, 8:16, :] = False  # fine rows 8..15 = kernel q-block 1, all dead
    q, k, v = (x[:, :1] for x in _qkv(3))
    with pytest.raises(AssertionError, match="fully-masked"):
        block_sparse_attention(q, k, v, layout, block=16, block_q=128)


def test_causal_dead_row_rejected():
    """causal=True: a q row whose only visited blocks are strictly in the
    future dies after the token-granular causal intersection even though the
    layout-only check passes; _build must reject the combination."""
    n = T // 16
    layout = np.zeros((1, n, n), bool)
    layout[:, :, -1] = True          # every row visits only the LAST k-block
    layout[0, -1, 0] = True          # keep the final kernel row layout-alive
    q, k, v = (x[:, :1] for x in _qkv(4))
    # non-causal: legal (every row has a live block)
    block_sparse_attention(q, k, v, layout, block=16, block_q=128)
    with pytest.raises(AssertionError, match="causal"):
        block_sparse_attention(q, k, v, layout, block=16, block_q=128,
                               causal=True)


@pytest.mark.tpu
def test_tpu_masked_kernel_compiled():
    """Compile (not interpret) the mask-streaming paths on the real chip:
    Mosaic must accept the dynamic leading-index bias loads and the dbias
    read-modify-write, and numerics must sit in the MXU default-precision
    band vs the dense math."""
    cfg2, q, k, v, rpe, attn_mask, kpm = _masked_case(T2=1024, seed=9)
    attn = SparseSelfAttention(cfg2)

    out = jax.jit(lambda q, k, v, rpe: attn(
        q, k, v, rpe=rpe, attn_mask=attn_mask,
        key_padding_mask=kpm))(q, k, v, rpe)
    ref = _dense_with_masks(attn, q, k, v, rpe=rpe, attn_mask=attn_mask,
                            kpm=kpm)
    valid = np.asarray(kpm)[:, None, :, None]
    np.testing.assert_allclose(np.asarray(out) * valid,
                               np.asarray(ref) * valid, rtol=2e-2, atol=2e-2)

    def f_kernel(q, rpe):
        return jnp.sum(attn(q, k, v, rpe=rpe, attn_mask=attn_mask,
                            key_padding_mask=kpm) ** 2)

    def f_dense(q, rpe):
        return jnp.sum(_dense_with_masks(attn, q, k, v, rpe=rpe,
                                         attn_mask=attn_mask, kpm=kpm) ** 2)

    gk = jax.jit(jax.grad(f_kernel, argnums=(0, 1)))(q, rpe)
    gd = jax.grad(f_dense, argnums=(0, 1))(q, rpe)
    for a, b in zip(gk, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-2, atol=3e-2)


@pytest.mark.tpu
def test_tpu_sparse_speedup_at_8k():
    """Real-chip lane: at T=8k / ~26% density the kernel must beat the dense
    masked path (the bound below only asserts that it WINS). Reference
    capability: compute savings are WHY `ops/sparse_attention` exists."""
    import time
    Tl, Hl = 8192, 4
    cfg = FixedSparsityConfig(num_heads=Hl, block=16, num_local_blocks=256,
                              num_global_blocks=8, attention="unidirectional")
    layout = cfg.make_layout(Tl)
    assert 0.2 < layout.mean() < 0.3
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (1, Hl, Tl, D)), jnp.bfloat16)
               for _ in range(3))
    attn = SparseSelfAttention(cfg)
    mask = attn._mask(Tl)

    def dense_fn(a):
        s = jnp.einsum("bhtd,bhsd->bhts", a.astype(jnp.float32),
                       k.astype(jnp.float32)) / np.sqrt(D)
        s = jnp.where(mask[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhts,bhsd->bhtd", p, v.astype(jnp.float32))

    N = 20

    def bench(fn):
        @jax.jit
        def run(a):
            def body(c, _):
                o = fn(c)
                return (o / (1 + jnp.max(jnp.abs(o)))).astype(c.dtype), None
            return jax.lax.scan(body, a, None, length=N)[0]
        float(jnp.sum(run(q).astype(jnp.float32)))
        best = float("inf")
        for _ in range(3):  # best-of-3
            t0 = time.perf_counter()
            float(jnp.sum(run(q).astype(jnp.float32)))
            best = min(best, (time.perf_counter() - t0) / N)
        return best

    t_sparse = bench(lambda a: block_sparse_attention(a, k, v, layout, block=16))
    t_dense = bench(lambda a: dense_fn(a).astype(a.dtype))
    # the ratio has moved between toolchains (2.3x and 1.23x were both
    # recorded for the identical kernel), so the bound asserts only that the
    # kernel WINS; S0's per-cell bound replaces this (ROADMAP D12)
    assert t_dense / t_sparse >= 1.1, (t_sparse, t_dense)


def test_sparse_attn_fn_is_token_causal():
    """The unidirectional layouts tril only at BLOCK granularity — a diagonal
    block is fully open. sparse_attn_fn must therefore be token-causal via
    the kernel's causal flag: perturbing a FUTURE token must not change any
    earlier output (the direct leak probe), and full-density causal must
    match plain causal attention per-op tight."""
    from deepspeed_tpu.models.gpt import _attention
    from deepspeed_tpu.ops.sparse_attention import (DenseSparsityConfig,
                                                    sparse_attn_fn)

    class CausalDense(DenseSparsityConfig):
        attention = "unidirectional"

        def make_layout(self, seq_len):
            lay = super().make_layout(seq_len)
            return lay & np.tril(np.ones(lay.shape[1:], bool))[None]

    fn = sparse_attn_fn(CausalDense(num_heads=4, block=16))
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (2, 128, 4, 16)), jnp.float32)
               for _ in range(3))  # zoo layout [B, T, H, hd]
    out = np.asarray(fn(q, k, v))
    # leak probe: change token 5's key+value; outputs at positions < 5 of a
    # causal attention are untouched (position 5 is INSIDE the first 16-token
    # block, so block-granular masking alone would leak it)
    k2 = k.at[:, 5].set(k[:, 5] + 100.0)
    v2 = v.at[:, 5].set(v[:, 5] - 100.0)
    out2 = np.asarray(fn(q, k2, v2))
    np.testing.assert_array_equal(out[:, :5], out2[:, :5])
    assert np.abs(out[:, 5:] - out2[:, 5:]).max() > 1e-3  # probe is live

    # per-op parity vs the zoo's dense causal attention
    T = 128
    causal_mask = np.tril(np.ones((T, T), bool))[None]
    from deepspeed_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(n_layer=1, n_head=4, d_model=64, dtype=jnp.float32)
    ref = np.asarray(_attention(q, k, v, jnp.asarray(causal_mask), cfg))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_gpt_trains_with_sparse_attention():
    """The reference trains BERT with SparseSelfAttention swapped in; here the
    GPT zoo takes the sparse kernel through the attn_fn slot and trains —
    and the spec's apply_fn (eval/inference forward) uses the SAME sparse
    attention, not a silent dense fallback."""
    import deepspeed_tpu
    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.models.gpt import (GPTConfig, init_gpt_params,
                                          make_gpt_model)
    from deepspeed_tpu.ops.sparse_attention import sparse_attn_fn
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    cfg = GPTConfig(n_layer=2, n_head=4, d_model=64, max_seq_len=256,
                    vocab_size=256, dtype=jnp.float32, remat=False)
    toks = np.random.default_rng(0).integers(0, 256, (2, 128)).astype(np.int32)
    batch = {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}
    sparse = sparse_attn_fn(FixedSparsityConfig(
        num_heads=4, block=16, num_local_blocks=4, num_global_blocks=1,
        attention="unidirectional"))
    model = make_gpt_model(cfg=cfg, name="sparse-gpt", attn_fn=sparse)
    # apply_fn carries the sparse attention too (not the dense default)
    assert model.apply_fn.keywords.get("attn_fn") is sparse
    eng, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "mesh": {"data": 1}, "steps_per_print": 10**9})
    losses = [float(eng.train_batch(batch)) for _ in range(4)]
    assert losses[-1] < losses[0], losses
