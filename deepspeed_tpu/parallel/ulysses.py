"""DeepSpeed-Ulysses sequence parallelism, TPU-native.

Reference: `deepspeed/sequence/layer.py:15-85` — `_SeqAllToAll` (all-to-all that
re-shards [B, T/sp, H, hd] → [B, T, H/sp, hd]) and `DistributedAttention` (the
all-to-all sandwich around any local attention), with seq groups from
`utils/groups.py:420-466`.

TPU-native formulation: under SPMD the two all-to-alls are *sharding constraints* —
activations arrive sequence-sharded, we constrain q/k/v to head-sharded before the
attention and constrain the output back to sequence-sharded; XLA emits exactly the
two all-to-alls of the reference over the `sequence` ICI axis. An explicit
`shard_map` variant is provided for when manual scheduling is needed.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.comm.mesh import BATCH_AXES, SEQ_AXIS, TENSOR_AXIS, shard_constraint


def ulysses_attention(attn_fn):
    """Wrap a local attention fn ([B,T,H,hd]×3 → [B,T,H,hd]) with the Ulysses
    sequence↔head re-sharding sandwich (SPMD-constraint formulation)."""

    def wrapped(q, k, v, *args, **kwargs):
        # incoming: sequence-sharded on T (and possibly TP-sharded on H)
        # before attention: all heads local per (sequence,tensor) shard of H; full T
        q = shard_constraint(q, BATCH_AXES, None, (SEQ_AXIS, TENSOR_AXIS), None)
        k = shard_constraint(k, BATCH_AXES, None, (SEQ_AXIS, TENSOR_AXIS), None)
        v = shard_constraint(v, BATCH_AXES, None, (SEQ_AXIS, TENSOR_AXIS), None)
        out = attn_fn(q, k, v, *args, **kwargs)
        # back to sequence-sharded layout
        return shard_constraint(out, BATCH_AXES, SEQ_AXIS, TENSOR_AXIS, None)

    return wrapped


class DistributedAttention:
    """API-parity class (reference `sequence/layer.py:37`): construct with a local
    attention callable; call with q,k,v shaped [B, T, H, hd]."""

    def __init__(self, local_attention, sequence_process_group=None,
                 scatter_idx=2, gather_idx=1):
        self.local_attn = local_attention
        self.scatter_idx = scatter_idx
        self.gather_idx = gather_idx
        self._wrapped = ulysses_attention(local_attention)

    def __call__(self, query, key, value, *args, **kwargs):
        return self._wrapped(query, key, value, *args, **kwargs)


def seq_all_to_all(x, scatter_axis, gather_axis, axis_name=SEQ_AXIS):
    """Explicit in-shard_map all-to-all (reference `_SeqAllToAll.forward`):
    scatters `scatter_axis` over the sequence ranks and gathers `gather_axis`."""
    return jax.lax.all_to_all(x, axis_name, split_axis=scatter_axis,
                              concat_axis=gather_axis, tiled=True)


def ulysses_shard_map_attention(attn_fn, mesh=None):
    """Explicit shard_map Ulysses for manual control: q,k,v are global arrays
    sharded [B@data, T@sequence, H@tensor, hd]; inside, each sequence rank trades
    its sequence shard for a head shard, runs local attention on the full sequence,
    then trades back.

    The head-scatter all-to-all hands each of the sp sequence ranks a whole
    number of heads, so the per-tensor-shard head count must divide by sp —
    validated eagerly per call with a clear ValueError (the alternative is a
    shape-mismatch error deep inside XLA's all-to-all lowering)."""
    mesh = mesh or mesh_mod.get_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    sp = sizes.get(SEQ_AXIS, 1)
    tp = sizes.get(TENSOR_AXIS, 1)

    spec = P(BATCH_AXES, SEQ_AXIS, TENSOR_AXIS, None)

    def local(q, k, v):
        # local shapes: [b, t/sp, h/tp, hd]
        q = seq_all_to_all(q, scatter_axis=2, gather_axis=1)  # → [b, t, h/(tp·sp), hd]
        k = seq_all_to_all(k, scatter_axis=2, gather_axis=1)
        v = seq_all_to_all(v, scatter_axis=2, gather_axis=1)
        o = attn_fn(q, k, v)
        return seq_all_to_all(o, scatter_axis=1, gather_axis=2)

    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                   check_vma=False)

    def validated(q, k, v):
        for name, x in (("q", q), ("k", k), ("v", v)):
            h_local = x.shape[2] // tp if x.shape[2] % tp == 0 else None
            if h_local is None or h_local % sp != 0:
                raise ValueError(
                    f"ulysses_shard_map_attention: {name} has {x.shape[2]} "
                    f"heads — after the {tp}-way tensor split, the per-shard "
                    f"head count must divide by the {sp}-way `sequence` axis "
                    f"(the all-to-all scatters whole heads per rank). Use a "
                    f"head count divisible by tp*sp={tp * sp}, lower the "
                    f"sequence axis, or compose with ring context "
                    f"parallelism (parallel/ring.py ring_ulysses_attention: "
                    f"the non-dividing factor of sp moves to the K/V ring)")
        return fn(q, k, v)

    return validated
