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

The zoo's blocks do not take a policy by name by default: they HOLD what their
backward reads of the forward, as many of those results as the device's free
memory allows (`fit_held`, `held_policy` below; docs/activation_checkpointing.md).
"""

import contextlib
import dataclasses
from typing import (Callable, Dict, Mapping, Optional, Sequence, Tuple,
                    Union)

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


# ----------------------------------------------------------------------
# Held residuals: the remat policy of a scanned block, derived from what fits
# ----------------------------------------------------------------------

# Of the device's limit, what the fit leaves alone: the runtime's own 258 MiB,
# what the arithmetic below misses of a step's true peak (it came within 0.03
# GiB of `memory_analysis().peak_memory_in_bytes` on both of the v5e's
# training cells), and room for the compiler's packing, which wastes a
# quarter of a full program's temporaries before it repacks (PERF.md
# section 7, PR 49).
HELD_MARGIN_SHARE = 1 / 16


@dataclasses.dataclass(frozen=True)
class HeldPlan:
    """What the blocks hold for their backward, and why no more: the names
    taken (a prefix of `bytes_per_layer`'s order), every candidate's bytes a
    block on one device (a block is a layer where `layers` is a number), the
    blocks that carry a name (`layers`: one number for every name, or a
    number a name), how many of them hold it (`blocks`: all of them but for
    the last name taken, which may stop short), the bytes that were free for
    them (`margin_bytes` already taken off) and the first name of which a
    block did not fit (None: every block of every name did)."""
    names: Tuple[str, ...]
    bytes_per_layer: Dict[str, int]
    layers: Union[int, Dict[str, int]]
    free_bytes: int
    margin_bytes: int
    first_unfit: Optional[str]
    blocks: Dict[str, int]

    @property
    def held_bytes(self) -> int:
        return sum(n * self.bytes_per_layer[name]
                   for name, n in self.blocks.items())

    def to_dict(self):
        return {**dataclasses.asdict(self), "names": list(self.names),
                "held_bytes": self.held_bytes}

    def render(self) -> str:
        from deepspeed_tpu.telemetry.memscope import fmt_bytes
        size = lambda name, n: fmt_bytes(n * self.bytes_per_layer[name])
        unfit = self.first_unfit
        if isinstance(self.layers, int):
            over, taken = f" over {self.layers} layers", lambda name, n: ""
            missing = f"{unfit} ({size(unfit, self.layers)})" if unfit else ""
        else:
            over = ""
            taken = lambda name, n: f" in {n} of {self.layers[name]} blocks"
            missing = "" if not unfit else \
                f"{'one more block of ' if unfit in self.blocks else ''}" \
                f"{unfit} ({size(unfit, 1)} a block)"
        held = ", ".join(f"{name}{taken(name, n)} {size(name, n)}"
                         for name, n in self.blocks.items()) or "nothing"
        why = f"{missing} does not fit" if unfit else "every candidate fits"
        return (f"held for the backward{over}: {held} = "
                f"{fmt_bytes(self.held_bytes)} of "
                f"{fmt_bytes(self.free_bytes)} free (margin "
                f"{fmt_bytes(self.margin_bytes)} kept); {why}")


def fit_held(free_bytes: int, bytes_per_layer: Mapping[str, int],
             layers: Union[int, Mapping[str, Sequence[int]]],
             margin_bytes: int = 0) -> HeldPlan:
    """The names to hold: `bytes_per_layer`'s names in ITS order (the caller
    ranks them by the recompute a held byte saves) while their sum over the
    blocks that carry them fits under `free_bytes - margin_bytes`; the first
    that does not fit ends the list, so the sets are nested as the free
    bytes grow. `layers` a number: a name is held by every layer or by none
    (one scanned block, one policy). A name's GROUPS of blocks, in the
    caller's order (`{name: (3, 3, 1)}`: a group is what one policy covers,
    a scanned block's repeats): of the name that ends the list the leading
    groups that fit are held. Pure arithmetic: nothing is compiled or
    allocated."""
    room = max(0, int(free_bytes) - int(margin_bytes))
    whole = isinstance(layers, int)
    blocks, used, unfit = {}, 0, None
    for name, nbytes in bytes_per_layer.items():
        fits = 0
        for size in (layers,) if whole else layers[name]:
            if used + size * nbytes > room:
                unfit = name
                break
            used, fits = used + size * nbytes, fits + size
        if fits:
            blocks[name] = fits
        if unfit:
            break
    carrying = int(layers) if whole else \
        {name: sum(groups) for name, groups in layers.items()}
    return HeldPlan(tuple(blocks), dict(bytes_per_layer), carrying, room,
                    int(margin_bytes), unfit, blocks)


_BUDGET = None      # (free bytes, gradient bytes, margin, report)


@contextlib.contextmanager
def held_budget(free_bytes: int, grad_bytes: int = 0, margin_bytes: int = 0,
                report: Optional[Callable[[HeldPlan], None]] = None):
    """While a step is TRACED inside this, a block that derives its policy
    (`held_policy`) may spend `free_bytes` of one device — its limit less
    the state it holds — on the step's temporaries, of which `grad_bytes`
    are the gradients', live through the backward; `report` hears the plan
    it made. The training engine enters it around the model's loss
    (`Engine._budgeted_loss`); with no budget installed a block holds
    nothing."""
    global _BUDGET
    before = _BUDGET
    _BUDGET = (int(free_bytes), int(grad_bytes), int(margin_bytes), report)
    try:
        yield
    finally:
        _BUDGET = before


def held_plan(bytes_per_layer: Mapping[str, int],
              layers: Union[int, Mapping[str, Sequence[int]]],
              working_sets: Sequence[Mapping[str, float]]) -> HeldPlan:
    """What fits of `bytes_per_layer` (`fit_held`) in the installed budget
    beside what the step keeps with NOTHING held, at the moment that keeps
    the most. A moment of `working_sets`: the inputs of the blocks whose
    backward is still to come (`carried_bytes`) and the larger of two
    working sets, the loss's (`loss_bytes`) or one block's backward
    (`backward_bytes`) beside the gradients made so far (`grads_share` of
    them; all of them where it is left out). The plan is said to the
    budget's `report`. No budget: no room."""
    free, grads, margin, report = _BUDGET or (0, 0, 0, None)
    floor = max(
        at.get("carried_bytes", 0) + max(
            at.get("loss_bytes", 0),
            int(at.get("grads_share", 1.0) * grads)
            + at.get("backward_bytes", 0))
        for at in working_sets)
    plan = fit_held(free - floor, bytes_per_layer, layers, margin)
    if report is not None:
        report(plan)
    return plan


def policy_holding(names):
    """The `jax.checkpoint` policy of a block that holds `names`; none:
    `nothing_saveable`, the program a block has always lowered to."""
    if not names:
        return jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint_policies.save_only_these_names(*names)


def held_policy(bytes_per_layer: Mapping[str, int], layers: int,
                carried_bytes: int = 0, loss_bytes: int = 0,
                backward_bytes: int = 0):
    """The `jax.checkpoint` policy of ONE block scanned over `layers`: every
    layer holds the names that fit (`held_plan`) beside every layer's input
    (`carried_bytes`), and the loss's working set or one block's backward
    with all the gradients."""
    plan = held_plan(bytes_per_layer, layers, [dict(
        carried_bytes=carried_bytes, loss_bytes=loss_bytes,
        backward_bytes=backward_bytes)])
    return policy_holding(plan.names)
