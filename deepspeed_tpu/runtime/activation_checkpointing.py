"""Activation checkpointing.

Reference: `runtime/activation_checkpointing/checkpointing.py` (1,248 LoC) —
Megatron-style `CheckpointFunction` with partitioned activations across MP ranks,
CPU checkpointing, contiguous buffers, and a CUDA RNG tracker.

On TPU the mechanism collapses into `jax.checkpoint` policies:
  * `checkpoint(fn)`                → recompute in backward (same semantics)
  * partition_activations          → `save_and_offload_only_these_names` /
                                     sharding constraints on residuals (XLA keeps
                                     saved activations sharded already under SPMD)
  * cpu_checkpointing              → `jax.checkpoint` + host offload policy
                                     (`offload_dot_with_no_batch_dims` family)
  * RNG tracker                    → explicit PRNG keys (pure functional already)

`configure()`/`is_configured()` keep the reference's module-level API so ported
client code (Megatron-style) runs unchanged.
"""

from functools import partial

import jax

from deepspeed_tpu.utils.logging import logger

_CONFIG = {
    "partition_activations": False,
    "contiguous_memory_optimization": False,
    "cpu_checkpointing": False,
    "num_checkpoints": None,
    "synchronize": False,
    "profile": False,
    "policy": None,
}
_CONFIGURED = False

POLICIES = {
    "full": None,  # save nothing, recompute everything
    "nothing_saveable": None,
    "dots": "dots_saveable",
    "dots_saveable": "dots_saveable",
    "dots_with_no_batch_dims": "dots_with_no_batch_dims_saveable",
    "dots_with_no_batch_dims_saveable": "dots_with_no_batch_dims_saveable",
    "offload_dots": "save_and_offload_dot_with_no_batch_dims",
}


def configure(mpu_=None,
              deepspeed_config=None,
              partition_activations=None,
              contiguous_checkpointing=None,
              num_checkpoints=None,
              checkpoint_in_cpu=None,
              synchronize=None,
              profile=None,
              policy=None):
    """Reference `configure` (`checkpointing.py:1057`) signature."""
    global _CONFIGURED
    if deepspeed_config is not None:
        ac = getattr(deepspeed_config, "activation_checkpointing", None)
        if ac is not None:
            _CONFIG.update(partition_activations=ac.partition_activations,
                           cpu_checkpointing=ac.cpu_checkpointing,
                           contiguous_memory_optimization=ac.contiguous_memory_optimization,
                           num_checkpoints=ac.number_checkpoints,
                           policy=ac.policy)
    for key, val in (("partition_activations", partition_activations),
                     ("contiguous_memory_optimization", contiguous_checkpointing),
                     ("num_checkpoints", num_checkpoints),
                     ("cpu_checkpointing", checkpoint_in_cpu),
                     ("synchronize", synchronize),
                     ("profile", profile),
                     ("policy", policy)):
        if val is not None:
            _CONFIG[key] = val
    _CONFIGURED = True


def is_configured():
    return _CONFIGURED


def _resolve_policy(name):
    if name is None:
        name = _CONFIG.get("policy") or "full"
    mapped = POLICIES.get(name, name)
    if mapped is None:
        return None
    pol = getattr(jax.checkpoint_policies, mapped, None)
    if pol is None:
        logger.warning(f"unknown remat policy '{name}', defaulting to full recompute")
    return pol


def checkpoint(function, *args, policy=None, prevent_cse=True):
    """Reference `CheckpointFunction.apply` style entry: runs `function(*args)`
    under remat. Also usable as a decorator factory via `checkpoint_wrapper`."""
    fn = jax.checkpoint(function, policy=_resolve_policy(policy),
                        prevent_cse=prevent_cse)
    return fn(*args)


def checkpoint_wrapper(function, policy=None, prevent_cse=True):
    """Decorator form: `block = checkpoint_wrapper(block_fn)`.

    Pass `prevent_cse=False` when the wrapped fn is applied inside
    `lax.scan`/`lax.while_loop` — the loop boundary already blocks the CSE
    that prevent_cse guards against, and the relaxed form lets XLA schedule
    the recompute more freely."""
    return jax.checkpoint(function, policy=_resolve_policy(policy),
                          prevent_cse=prevent_cse)


class CheckpointFunction:
    """Name-parity shim (reference `checkpointing.py:477`)."""

    @staticmethod
    def apply(run_function, *args):
        return checkpoint(run_function, *args)


# RNG-tracker parity: functional keys make this a bookkeeping no-op, but Megatron
# imports these names.
class CudaRNGStatesTracker:
    def __init__(self):
        self.states_ = {}

    def add(self, name, seed):
        self.states_[name] = jax.random.PRNGKey(seed)

    def get_states(self):
        return dict(self.states_)

    def set_states(self, states):
        self.states_ = dict(states)

    def fork(self, name="model-parallel-rng"):
        import contextlib
        return contextlib.nullcontext()


_RNG_TRACKER = CudaRNGStatesTracker()


def get_cuda_rng_tracker():
    return _RNG_TRACKER


def model_parallel_cuda_manual_seed(seed):
    _RNG_TRACKER.add("model-parallel-rng", seed)
