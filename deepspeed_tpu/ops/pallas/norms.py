"""Fused layer/RMS norm (Pallas).

Analog of the reference's `normalize_kernels.cu` / `rms_norm.cu`
(`csrc/transformer/`, `csrc/transformer/inference/csrc/rms_norm.cu`): one pass over
the row in VMEM, fp32 statistics, optional residual-add fusion (the
`residual_add` + norm fusion the inference kernels do).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.platform.device import pallas_interpret


def _ln_kernel(x_ref, scale_ref, bias_ref, o_ref, *, eps):
    x = x_ref[:, :].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    y = y * scale_ref[:].astype(jnp.float32) + bias_ref[:].astype(jnp.float32)
    o_ref[:, :] = y.astype(o_ref.dtype)


def _rms_kernel(x_ref, scale_ref, o_ref, *, eps):
    x = x_ref[:, :].astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    o_ref[:, :] = (y * scale_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rows_blocks(n_rows):
    for b in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if n_rows % b == 0:
            return b
    return 1


def fused_layer_norm(x, scale, bias, eps=1e-5, residual=None, interpret=None):
    """LayerNorm over the last dim; optional fused residual add (x+residual first)."""
    if interpret is None:
        interpret = pallas_interpret()
    if residual is not None:
        x = x + residual
    orig_shape = x.shape
    D = orig_shape[-1]
    x2 = x.reshape(-1, D)
    N = x2.shape[0]
    bn = _rows_blocks(N)
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bn, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        interpret=interpret,
    )(x2, scale, bias)
    return out.reshape(orig_shape)


def fused_rms_norm(x, scale, eps=1e-5, residual=None, interpret=None):
    if interpret is None:
        interpret = pallas_interpret()
    if residual is not None:
        x = x + residual
    orig_shape = x.shape
    D = orig_shape[-1]
    x2 = x.reshape(-1, D)
    N = x2.shape[0]
    bn = _rows_blocks(N)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bn, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        interpret=interpret,
    )(x2, scale)
    return out.reshape(orig_shape)
