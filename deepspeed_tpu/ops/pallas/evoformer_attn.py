"""Evoformer attention (DS4Science analog) — biased attention for
AlphaFold-style models, fused on TPU with Pallas.

Reference: `csrc/deepspeed4science/evoformer_attn/` (CUTLASS fused MHA with two
bias operands) exposed as `DS4Sci_EvoformerAttention(Q, K, V, [bias1, bias2])`:
  - Q/K/V: [B, N, S, H, D]  (batch, MSA rows / residue groups, seq, heads, dim)
  - bias1: [B, N, 1, 1, S]  mask bias (per-row key mask, broadcast over H and q)
  - bias2: [B, 1, H, S, S]  pair bias (shared across rows, per-head)
covering MSA row/column attention and triangle attention (start/end node).

TPU formulation: one streaming-softmax Pallas kernel with the two bias
operands read blockwise (the [B, N, H, S, S] logits tensor is never
materialized in the forward). Backward recomputes per-row (scan over N) so
its peak extra memory is [B, H, S, S] rather than N× that; pair-bias and
mask-bias gradients are produced like the reference kernel's dbias outputs.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.platform.device import pallas_interpret

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


# ----------------------------------------------------------------------
# forward kernel
# ----------------------------------------------------------------------


def _evo_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, pair_ref, o_ref,
                    *, sm_scale, block_k, has_mask, has_pair):
    # q_ref: [block_q, D]; k/v_ref: [S, D]; mask_ref: [1, S] additive;
    # pair_ref: [block_q, S] additive; o_ref: [block_q, D]
    block_q, D = q_ref.shape
    S = k_ref.shape[0]
    q = q_ref[:, :].astype(jnp.float32) * sm_scale

    def body(j, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq, bk]
        if has_mask:
            s = s + mask_ref[0, pl.ds(j * block_k, block_k)].astype(jnp.float32)[None, :]
        if has_pair:
            s = s + pair_ref[:, pl.ds(j * block_k, block_k)].astype(jnp.float32)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, D), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, S // block_k, body, (acc0, m0, l0))
    o_ref[:, :] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _evo_fwd_pallas(q, k, v, mask, pair, sm_scale, block_q, block_k, interpret):
    """q,k,v: [B, N, H, S, D]; mask: [B, N, 1, S] or None; pair: [B, H, S, S]
    or None → out [B, N, H, S, D]."""
    B, N, H, S, D = q.shape
    grid = (B, N, H, S // block_q)
    has_mask = mask is not None
    has_pair = pair is not None

    in_specs = [
        pl.BlockSpec((None, None, None, block_q, D), lambda b, n, h, qi: (b, n, h, qi, 0)),
        pl.BlockSpec((None, None, None, S, D), lambda b, n, h, qi: (b, n, h, 0, 0)),
        pl.BlockSpec((None, None, None, S, D), lambda b, n, h, qi: (b, n, h, 0, 0)),
    ]
    operands = [q, k, v]
    if has_mask:
        in_specs.append(pl.BlockSpec((None, None, 1, S), lambda b, n, h, qi: (b, n, 0, 0)))
        operands.append(mask)
    else:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(jnp.zeros((1, 1), q.dtype))
    if has_pair:
        in_specs.append(pl.BlockSpec((None, None, block_q, S), lambda b, n, h, qi: (b, h, qi, 0)))
        operands.append(pair)
    else:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(jnp.zeros((1, 1), q.dtype))

    out = pl.pallas_call(
        functools.partial(_evo_fwd_kernel, sm_scale=sm_scale, block_k=block_k,
                          has_mask=has_mask, has_pair=has_pair),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, None, block_q, D),
                               lambda b, n, h, qi: (b, n, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N, H, S, D), q.dtype),
        interpret=interpret,
    )(*operands)
    return out


# ----------------------------------------------------------------------
# reference math (jnp) — also the backward
# ----------------------------------------------------------------------


def _evo_attn_math(q, k, v, mask, pair, sm_scale):
    """Naive fp32-softmax attention on [B, N, H, S, D] internals."""
    s = jnp.einsum("bnhqd,bnhkd->bnhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if mask is not None:
        s = s + mask.astype(jnp.float32)[:, :, :, None, :]     # [B,N,1,1,S]
    if pair is not None:
        s = s + pair.astype(jnp.float32)[:, None]              # [B,1,H,S,S]
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnhqk,bnhkd->bnhqd", p, v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _evo_core(q, k, v, mask, pair, sm_scale, block_q, block_k, interpret):
    if interpret == "jnp":
        return _evo_attn_math(q, k, v, mask, pair, sm_scale)
    return _evo_fwd_pallas(q, k, v, mask, pair, sm_scale, block_q, block_k, interpret)


def _evo_core_fwd(q, k, v, mask, pair, sm_scale, block_q, block_k, interpret):
    out = _evo_core(q, k, v, mask, pair, sm_scale, block_q, block_k, interpret)
    return out, (q, k, v, mask, pair)


def _evo_core_bwd(sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, mask, pair = res
    B, N, H, S, D = q.shape

    def per_row(carry, inputs):
        dpair_acc = carry
        qn, kn, vn, maskn, gn = inputs        # [B, H, S, D] / [B, 1, S] / ...
        s = jnp.einsum("bhqd,bhkd->bhqk", qn.astype(jnp.float32),
                       kn.astype(jnp.float32)) * sm_scale
        if mask is not None:
            s = s + maskn.astype(jnp.float32)[:, :, None, :]
        if pair is not None:
            s = s + pair.astype(jnp.float32)
        p = jax.nn.softmax(s, axis=-1)
        do = gn.astype(jnp.float32)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, do)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, vn.astype(jnp.float32))
        ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
        dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kn.astype(jnp.float32)) * sm_scale
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qn.astype(jnp.float32)) * sm_scale
        dmask = jnp.sum(ds, axis=(1, 2))[:, None, :]          # [B, 1, S]
        if pair is not None:
            dpair_acc = dpair_acc + ds
        return dpair_acc, (dq, dk, dv, dmask)

    dpair0 = jnp.zeros((B, H, S, S), jnp.float32)
    maskN = (jnp.moveaxis(mask, 1, 0) if mask is not None
             else jnp.zeros((N, B, 1, S), q.dtype))
    dpair, (dq, dk, dv, dmask) = jax.lax.scan(
        per_row, dpair0,
        (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0),
         maskN, jnp.moveaxis(g, 1, 0)))
    dq = jnp.moveaxis(dq, 0, 1).astype(q.dtype)
    dk = jnp.moveaxis(dk, 0, 1).astype(k.dtype)
    dv = jnp.moveaxis(dv, 0, 1).astype(v.dtype)
    dmask_out = (jnp.moveaxis(dmask, 0, 1).astype(mask.dtype)
                 if mask is not None else None)
    dpair_out = dpair.astype(pair.dtype) if pair is not None else None
    return dq, dk, dv, dmask_out, dpair_out


_evo_core.defvjp(_evo_core_fwd, _evo_core_bwd)


# ----------------------------------------------------------------------
# public op (reference DS4Sci_EvoformerAttention signature)
# ----------------------------------------------------------------------


def evoformer_attention(q, k, v, biases=(), sm_scale=None,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                        interpret=None):
    """Biased attention for Evoformer-style models.

    q, k, v: [B, N, S, H, D] (the reference kernel's layout). `biases` is a
    sequence of additive bias arrays in the two patterns the reference accepts
    (`evoformer_attn` op: bias1 mask [B, N, 1, 1, S], bias2 pair
    [B, 1, H, S, S]); each may appear at most once. Returns [B, N, S, H, D].
    Differentiable in q/k/v and both biases.
    """
    B, N, S, H, D = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    mask = None
    pair = None
    for b in biases:
        if b is None:
            continue
        if b.ndim != 5:
            raise ValueError(f"bias must be 5-D, got shape {b.shape}")
        if b.shape[2] == 1 and b.shape[3] == 1:        # [B, N, 1, 1, S] mask
            if mask is not None:
                raise ValueError("duplicate mask bias")
            mask = b.reshape(b.shape[0], b.shape[1], 1, b.shape[4])
            mask = jnp.broadcast_to(mask, (B, N, 1, S))
        elif b.shape[1] == 1:                          # [B, 1, H, S, S] pair
            if pair is not None:
                raise ValueError("duplicate pair bias")
            pair = jnp.broadcast_to(b[:, 0], (B, H, S, S))
        else:
            raise ValueError(
                f"unsupported bias shape {b.shape}: expected [B,N,1,1,S] "
                "(mask) or [B,1,H,S,S] (pair)")

    qi = jnp.moveaxis(q, 3, 2)   # [B, N, H, S, D]
    ki = jnp.moveaxis(k, 3, 2)
    vi = jnp.moveaxis(v, 3, 2)

    if interpret is None:
        interpret = pallas_interpret()
    bq, bk = min(block_q, S), min(block_k, S)
    # ONE shape rule on every platform: a block-tileable S takes the kernel,
    # which on a TPU compiles through Mosaic or raises the compiler's error
    # — never a second, quieter path that only hardware runs take
    use_pallas = S % bq == 0 and S % bk == 0 and S >= 8
    mode = (bq, bk, interpret) if use_pallas else None
    if mode is None:
        out = _evo_core(qi, ki, vi, mask, pair, float(sm_scale), 0, 0, "jnp")
    else:
        out = _evo_core(qi, ki, vi, mask, pair, float(sm_scale),
                        int(mode[0]), int(mode[1]), mode[2])
    return jnp.moveaxis(out, 2, 3)
