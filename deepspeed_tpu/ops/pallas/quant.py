"""Groupwise int8 quantization (Pallas).

Analog of the reference's `csrc/quantization/` suite (quantize.cu, swizzled
quant, quant_reduce) powering ZeRO++ qwZ/qgZ and weight-only inference quant.
Symmetric per-group int8: scale = max|x| / 127 per group of `group_size`
contiguous elements along the last dim.

These ops are the building blocks for quantized collectives: all-gather/reduce
run over the int8 payload + f32 scales, dequantize after (runtime path in
runtime/quantized_collectives.py).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.platform.device import pallas_interpret


def _quant_kernel(x_ref, q_ref, s_ref, *, group_size):
    x = x_ref[:, :].astype(jnp.float32)            # [rows, D]
    rows, D = x.shape
    g = D // group_size
    xg = x.reshape(rows, g, group_size)
    amax = jnp.max(jnp.abs(xg), axis=-1)           # [rows, g]
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xg / scale[..., None]), -127, 127).astype(jnp.int8)
    q_ref[:, :] = q.reshape(rows, D)
    s_ref[:, :] = scale


def _dequant_kernel(q_ref, s_ref, o_ref, *, group_size):
    q = q_ref[:, :].astype(jnp.float32)
    rows, D = q.shape
    g = D // group_size
    s = s_ref[:, :]
    x = q.reshape(rows, g, group_size) * s[..., None]
    o_ref[:, :] = x.reshape(rows, D).astype(o_ref.dtype)


def _block_rows(n):
    for b in (128, 64, 32, 16, 8, 4, 2, 1):
        if n % b == 0:
            return b
    return 1


def quantize_int8(x, group_size=128, interpret=None):
    """x: [..., D] → (q int8 [..., D], scales f32 [..., D//group_size])."""
    if interpret is None:
        interpret = pallas_interpret()
    orig = x.shape
    D = orig[-1]
    assert D % group_size == 0, f"last dim {D} not divisible by group_size {group_size}"
    x2 = x.reshape(-1, D)
    N = x2.shape[0]
    bn = _block_rows(N)
    g = D // group_size
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, group_size=group_size),
        grid=(N // bn,),
        in_specs=[pl.BlockSpec((bn, D), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((bn, g), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, D), jnp.int8),
            jax.ShapeDtypeStruct((N, g), jnp.float32),
        ],
        interpret=interpret,
    )(x2)
    return q.reshape(orig), s.reshape(orig[:-1] + (g,))


def _quant4_kernel(x_ref, q_ref, s_ref, *, group_size):
    # same scale/clip rule as the int8 kernel at qmax 7, then two values
    # packed per byte as biased [1, 15] nibbles (lo = even index, hi = odd)
    # — byte-identical to inference/quantization.quantize_tensor(bits=4)
    x = x_ref[:, :].astype(jnp.float32)            # [rows, D]
    rows, D = x.shape
    g = D // group_size
    xg = x.reshape(rows, g, group_size)
    amax = jnp.max(jnp.abs(xg), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(xg / scale[..., None]), -7, 7)
    qu = (q.reshape(rows, D).astype(jnp.int32) + 8).astype(jnp.uint8)
    packed = (qu[:, 0::2] | (qu[:, 1::2] << 4)).astype(jnp.uint8)
    q_ref[:, :] = jax.lax.bitcast_convert_type(packed, jnp.int8)
    s_ref[:, :] = scale


def _dequant4_kernel(q_ref, s_ref, o_ref, *, group_size):
    packed = jax.lax.bitcast_convert_type(q_ref[:, :], jnp.uint8)
    rows = packed.shape[0]
    D = packed.shape[1] * 2
    lo = (packed & 0xF).astype(jnp.int32) - 8
    hi = (packed >> 4).astype(jnp.int32) - 8
    q = jnp.stack([lo, hi], axis=-1).reshape(rows, D).astype(jnp.float32)
    g = D // group_size
    s = s_ref[:, :]
    x = q.reshape(rows, g, group_size) * s[..., None]
    o_ref[:, :] = x.reshape(rows, D).astype(o_ref.dtype)


def _refuse_int4_on_tpu(interpret):
    """The int4 kernels run in the interpreter only. Recorded state on the
    chip (TPU v5 lite, jax 0.9.0 / libtpu 0.0.34, PR 21): the nibble
    (un)packing indexes lanes with stride 2 (`qu[:, 0::2]`), which Pallas
    lowers to a gather Mosaic refuses — say so by name instead of handing
    the caller the compiler's traceback."""
    if not interpret:
        raise NotImplementedError(
            "ops/pallas/quant int4 kernels do not compile for TPU: the "
            "packed-nibble layout needs stride-2 lane indexing and the "
            "Pallas TPU gather lowering refuses it (\"Shape mismatch in "
            "input, indices and output\"). On a TPU use "
            "inference.quantization.quantize_tensor(bits=4) / "
            "dequantize_tensor — plain XLA, byte-identical packing")


def quantize_int4(x, group_size=128, interpret=None):
    """x: [..., D] → (packed int8 [..., D//2], scales f32 [..., D//g]).

    Two int4 values per byte (the ZeRO++ qgZ / WOQ storage form); packing
    layout and scale semantics are pinned against the pure-jnp
    `inference/quantization.quantize_tensor(bits=4)` by the parity tests."""
    if interpret is None:
        interpret = pallas_interpret()
    _refuse_int4_on_tpu(interpret)
    orig = x.shape
    D = orig[-1]
    assert D % group_size == 0, \
        f"last dim {D} not divisible by group_size {group_size}"
    assert D % 2 == 0, f"int4 packs two values per byte: last dim {D} odd"
    x2 = x.reshape(-1, D)
    N = x2.shape[0]
    bn = _block_rows(N)
    g = D // group_size
    q, s = pl.pallas_call(
        functools.partial(_quant4_kernel, group_size=group_size),
        grid=(N // bn,),
        in_specs=[pl.BlockSpec((bn, D), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bn, D // 2), lambda i: (i, 0)),
            pl.BlockSpec((bn, g), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, D // 2), jnp.int8),
            jax.ShapeDtypeStruct((N, g), jnp.float32),
        ],
        interpret=interpret,
    )(x2)
    return q.reshape(orig[:-1] + (D // 2,)), s.reshape(orig[:-1] + (g,))


def dequantize_int4(q, scales, dtype=jnp.bfloat16, group_size=128,
                    interpret=None):
    """Inverse of `quantize_int4`: packed [..., D//2] int8 + scales → [..., D]."""
    if interpret is None:
        interpret = pallas_interpret()
    _refuse_int4_on_tpu(interpret)
    orig = q.shape
    D = orig[-1] * 2
    q2 = q.reshape(-1, orig[-1])
    s2 = scales.reshape(-1, D // group_size)
    N = q2.shape[0]
    bn = _block_rows(N)
    out = pl.pallas_call(
        functools.partial(_dequant4_kernel, group_size=group_size),
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, orig[-1]), lambda i: (i, 0)),
            pl.BlockSpec((bn, D // group_size), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), dtype),
        interpret=interpret,
    )(q2, s2)
    return out.reshape(orig[:-1] + (D,))


def dequantize_int8(q, scales, dtype=jnp.bfloat16, group_size=128, interpret=None):
    if interpret is None:
        interpret = pallas_interpret()
    orig = q.shape
    D = orig[-1]
    q2 = q.reshape(-1, D)
    s2 = scales.reshape(-1, D // group_size)
    N = q2.shape[0]
    bn = _block_rows(N)
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, group_size=group_size),
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((bn, D // group_size), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), dtype),
        interpret=interpret,
    )(q2, s2)
    return out.reshape(orig)
