"""Device mesh & logical topology.

TPU-native replacement for the reference's process-group machinery
(`deepspeed/utils/groups.py:64,113,207,473` — DP/MP/EP/SP group creation — and
`runtime/pipe/topology.py:12,251` ProcessTopology/PipelineParallelGrid): instead of
rank-list group objects, a single `jax.sharding.Mesh` with named axes. Every
"group" query becomes an axis (or tuple of axes) name; every cartesian-rank
computation is the mesh's coordinate system.

Axis order outer→inner = ('pipe', 'data', 'expert', 'sequence', 'tensor') so that
slow/DCN-spanning axes are outermost and bandwidth-hungry axes (tensor) sit on
adjacent ICI neighbors — the standard megascale layout.

ZeRO sharding uses the combined ('data','sequence') axes as its partition domain,
mirroring the reference's seq_data_parallel_group
(`runtime/engine.py:1116-1122` wires seq×DP as the ZeRO dp_process_group).
"""

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.utils.logging import logger

# Canonical axis names, outermost first.
PIPE_AXIS = "pipe"
DATA_AXIS = "data"
ZERO_INNER_AXIS = "zero"     # inner factor of the data domain (MiCS/hpZ sub-groups)
EXPERT_AXIS = "expert"
SEQ_AXIS = "sequence"
TENSOR_AXIS = "tensor"

ALL_AXES: Tuple[str, ...] = (PIPE_AXIS, DATA_AXIS, ZERO_INNER_AXIS, EXPERT_AXIS,
                             SEQ_AXIS, TENSOR_AXIS)

# ZeRO partitions over data×zero×sequence (see module docstring). The `zero`
# axis is 1 unless MiCS (`mics_shard_size`) or hpZ (`zero_hpz_partition_size`)
# confine (part of) the sharding to an inner sub-group that rides ICI
# (reference: `zero/mics.py:55` sub-group sharding, `zero/config.py:256` hpZ).
ZERO_AXES: Tuple[str, ...] = (DATA_AXIS, ZERO_INNER_AXIS, SEQ_AXIS)

# Batch dims of activations shard over the full data domain.
BATCH_AXES: Tuple[str, ...] = (DATA_AXIS, ZERO_INNER_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Resolved logical topology (analog of PipelineParallelGrid, `topology.py:251`)."""
    pipe: int = 1
    data: int = 1
    zero: int = 1
    expert: int = 1
    sequence: int = 1
    tensor: int = 1

    @property
    def world_size(self):
        return (self.pipe * self.data * self.zero * self.expert * self.sequence
                * self.tensor)

    def axis_sizes(self):
        return {
            PIPE_AXIS: self.pipe,
            DATA_AXIS: self.data,
            ZERO_INNER_AXIS: self.zero,
            EXPERT_AXIS: self.expert,
            SEQ_AXIS: self.sequence,
            TENSOR_AXIS: self.tensor,
        }

    @classmethod
    def resolve(cls, mesh_config, n_devices: Optional[int] = None):
        """Fill the -1 ("absorb remaining devices") axis from the device count."""
        n = n_devices or (mesh_config.devices if getattr(mesh_config, "devices", None) else jax.device_count())
        sizes = {
            "pipe": mesh_config.pipe,
            "data": mesh_config.data,
            "zero": getattr(mesh_config, "zero", 1),
            "expert": mesh_config.expert,
            "sequence": mesh_config.sequence,
            "tensor": mesh_config.tensor,
        }
        unknown = [k for k, v in sizes.items() if v == -1]
        assert len(unknown) <= 1, f"at most one mesh axis may be -1, got {unknown}"
        fixed = int(np.prod([v for v in sizes.values() if v != -1]))
        if unknown:
            assert n % fixed == 0, f"{n} devices not divisible by fixed axes product {fixed}"
            sizes[unknown[0]] = n // fixed
        spec = cls(**sizes)
        # A spec smaller than the device count is allowed — tests and
        # partial-slice runs use it — but never quietly: init_mesh names
        # the devices it leaves idle.
        assert spec.world_size <= n, (
            f"mesh {spec} needs {spec.world_size} devices but only {n} are present")
        return spec


def build_mesh(spec: MeshSpec, devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    assert len(devices) == spec.world_size, (
        f"need {spec.world_size} devices for {spec}, have {len(devices)}")
    arr = np.asarray(devices).reshape(spec.pipe, spec.data, spec.zero,
                                      spec.expert, spec.sequence, spec.tensor)
    return Mesh(arr, ALL_AXES)


# -------------------- global current mesh (the "cdb" analog) --------------------
# Reference keeps a module-global backend `cdb` (`deepspeed/comm/comm.py:41`); we keep
# the active Mesh + spec the same way.

_CURRENT_MESH: Optional[Mesh] = None
_CURRENT_SPEC: Optional[MeshSpec] = None


def set_mesh(mesh: Mesh, spec: Optional[MeshSpec] = None):
    global _CURRENT_MESH, _CURRENT_SPEC
    _CURRENT_MESH = mesh
    if spec is None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        spec = MeshSpec(
            pipe=sizes.get(PIPE_AXIS, 1),
            data=sizes.get(DATA_AXIS, 1),
            zero=sizes.get(ZERO_INNER_AXIS, 1),
            expert=sizes.get(EXPERT_AXIS, 1),
            sequence=sizes.get(SEQ_AXIS, 1),
            tensor=sizes.get(TENSOR_AXIS, 1),
        )
    _CURRENT_SPEC = spec


def get_mesh() -> Mesh:
    assert _CURRENT_MESH is not None, "no mesh initialized — call comm.init_distributed()/init_mesh first"
    return _CURRENT_MESH


def get_spec() -> MeshSpec:
    assert _CURRENT_SPEC is not None, "no mesh initialized"
    return _CURRENT_SPEC


def has_mesh() -> bool:
    return _CURRENT_MESH is not None


def clear_mesh():
    """Uninstall the global mesh (engine teardown / test isolation)."""
    global _CURRENT_MESH, _CURRENT_SPEC
    _CURRENT_MESH = None
    _CURRENT_SPEC = None


def init_mesh(mesh_config=None, devices=None, n_devices=None) -> Mesh:
    """Build + install the global mesh from a MeshConfig (or default: all-data)."""
    from deepspeed_tpu.config.core import MeshConfig
    mesh_config = mesh_config or MeshConfig()
    spec = MeshSpec.resolve(mesh_config, n_devices=n_devices or (len(devices) if devices else None))
    present = list(devices if devices is not None else jax.devices())
    devices, unused = present[:spec.world_size], present[spec.world_size:]
    mesh = build_mesh(spec, devices)
    set_mesh(mesh, spec)
    logger.info(f"mesh initialized: {spec} over {spec.world_size} devices")
    if unused:
        logger.warning(
            f"mesh {spec} uses {len(devices)} of {len(present)} devices in "
            f"jax.devices() list order; {len(unused)} left idle: "
            f"{[str(d) for d in unused]}")
    return mesh


# -------------------- group-query parity (utils/groups.py analog) --------------------


def axis_size(axis) -> int:
    sizes = get_spec().axis_sizes()
    if isinstance(axis, (tuple, list)):
        return int(np.prod([sizes[a] for a in axis]))
    return sizes[axis]


def get_world_size() -> int:
    return get_spec().world_size if has_mesh() else jax.device_count()


def get_data_parallel_world_size() -> int:
    # ZeRO/data domain = data × sequence (see module docstring)
    return axis_size(ZERO_AXES) if has_mesh() else jax.device_count()


def get_model_parallel_world_size() -> int:
    return axis_size(TENSOR_AXIS) if has_mesh() else 1


def get_pipe_parallel_world_size() -> int:
    return axis_size(PIPE_AXIS) if has_mesh() else 1


def get_expert_parallel_world_size() -> int:
    return axis_size(EXPERT_AXIS) if has_mesh() else 1


def get_sequence_parallel_world_size() -> int:
    return axis_size(SEQ_AXIS) if has_mesh() else 1


def data_parallel_sharding(*per_axis) -> NamedSharding:
    """NamedSharding helper: shard leading dim over the ZeRO domain."""
    return NamedSharding(get_mesh(), P(ZERO_AXES, *per_axis))


def replicated_sharding() -> NamedSharding:
    return NamedSharding(get_mesh(), P())


_CONSTRAINTS_DISABLED = False


class constraints_disabled:
    """Context manager: make shard_constraint a no-op while tracing code that runs
    inside a shard_map body (where outer-mesh constraints are not applicable)."""

    def __enter__(self):
        global _CONSTRAINTS_DISABLED
        self._prev = _CONSTRAINTS_DISABLED
        _CONSTRAINTS_DISABLED = True

    def __exit__(self, *exc):
        global _CONSTRAINTS_DISABLED
        _CONSTRAINTS_DISABLED = self._prev


def shard_constraint(x, *spec_entries):
    """`with_sharding_constraint` against the current global mesh; no-op when no
    mesh is installed (lets model code run standalone) or inside
    `constraints_disabled()` (shard_map bodies)."""
    if not has_mesh() or _CONSTRAINTS_DISABLED:
        return x
    spec = P(*spec_entries)
    return jax.lax.with_sharding_constraint(x, NamedSharding(get_mesh(), spec))
