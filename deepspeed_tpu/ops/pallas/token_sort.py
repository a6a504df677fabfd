"""Stable token sort by expert (Pallas) — the MoE dropless-dispatch primitive.

Analog of the reference's `csrc/random_ltd/token_sort.cu`: rank every token
within its expert's queue (a stable counting sort over expert ids) so tokens
can scatter into per-expert buffers without capacity drops. `parallel/moe.py`'s
`dropless_moe` scatters with `buf.at[expert_idx, pos].set(x)` — `pos` from this
kernel, capacity = N, so no assignment can ever overflow.

Kernel shape: tokens along sublanes in `bn`-row blocks, experts along lanes.
The grid walks token blocks sequentially (TPU grids are sequential by
default); running per-expert counts accumulate in the revisited `counts`
output block — the standard Pallas accumulator pattern — so each block's
local cumsum offsets by everything already seen. All math is int32, which is
why the gather-oracle parity tests can demand bit-equality.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.platform.device import pallas_interpret


def _block_rows(n):
    for b in (128, 64, 32, 16, 8, 4, 2, 1):
        if n % b == 0:
            return b
    return 1


def _token_sort_kernel(idx_ref, pos_ref, counts_ref, *, num_experts):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        counts_ref[:, :] = jnp.zeros_like(counts_ref)

    idx = idx_ref[:, :]                                        # [bn, 1] int32
    bn = idx.shape[0]
    e_iota = jax.lax.broadcasted_iota(jnp.int32, (bn, num_experts), 1)
    onehot = (idx == e_iota).astype(jnp.int32)                 # [bn, E]
    base = counts_ref[:, :]                                    # [1, E] seen so far
    # in-block inclusive prefix count as a lower-triangular matmul: Mosaic
    # has no cumsum lowering ("Unimplemented primitive in Pallas TPU
    # lowering: cumsum", jax 0.9.0). Operands are 0/1 and a column sums to
    # at most bn <= 128, so the f32 accumulation is exact in any MXU
    # precision and the int32 result equals jnp.cumsum's bit for bit
    r = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
    csum = jnp.dot((c <= r).astype(jnp.float32), onehot.astype(jnp.float32),
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    rank = csum - 1 + base                                     # 0-based global
    pos_ref[:, :] = jnp.sum(rank * onehot, axis=1, keepdims=True)
    counts_ref[:, :] = base + csum[-1:, :]


def token_sort(expert_idx, num_experts, interpret=None):
    """expert_idx: [N] int → (pos [N] int32, counts [E] int32).

    `pos[i]` is token i's 0-based stable rank within expert `expert_idx[i]`'s
    queue; `counts[e]` the number of tokens routed to expert e (callers route
    only valid ids — an out-of-range id matches no expert lane, so it counts
    nowhere and its rank degenerates to 0).
    """
    if interpret is None:
        interpret = pallas_interpret()
    N = expert_idx.shape[0]
    idx2 = expert_idx.astype(jnp.int32).reshape(N, 1)
    bn = _block_rows(N)
    pos, counts = pl.pallas_call(
        functools.partial(_token_sort_kernel, num_experts=num_experts),
        grid=(N // bn,),
        in_specs=[pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, num_experts), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, num_experts), jnp.int32),
        ],
        interpret=interpret,
    )(idx2)
    return pos.reshape(N), counts.reshape(num_experts)


def token_sort_oracle(expert_idx, num_experts):
    """Pure-jnp gather oracle for `token_sort` (bit-parity pinned by tests)."""
    idx = expert_idx.astype(jnp.int32)
    onehot = (idx[:, None]
              == jnp.arange(num_experts, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    csum = jnp.cumsum(onehot, axis=0)
    pos = jnp.sum((csum - 1) * onehot, axis=1)
    return pos.astype(jnp.int32), csum[-1].astype(jnp.int32)
