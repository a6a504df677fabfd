"""Typed configuration system.

TPU-native analog of the reference's `runtime/config.py:686` (`DeepSpeedConfig`) and
`runtime/config_utils.py:16` (`DeepSpeedConfigModel`, the pydantic base with "auto"
fields). We use plain dataclass-style models (no pydantic dependency) with:

  * JSON file or dict input,
  * `"auto"` sentinel resolution,
  * unknown-key warnings (matching the reference's strict-ish behavior),
  * the micro/GAS/global batch-size triad arithmetic
    (reference `runtime/config.py` `_batch_assertion`/`_set_batch_related_parameters`).

Config keys intentionally mirror the reference's JSON schema (`train_batch_size`,
`zero_optimization.stage`, `fp16.enabled`, ...) so reference configs load unchanged;
TPU-specific extensions live under the `"mesh"` block.
"""

import copy
import dataclasses
import json
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Dict, List, Optional, Union

from deepspeed_tpu.utils.logging import logger

AUTO = "auto"


class OffloadDeviceEnum(str, Enum):
    """Reference: `runtime/zero/offload_config.py` OffloadDeviceEnum."""
    none = "none"
    cpu = "cpu"
    nvme = "nvme"


def _is_auto(v):
    return isinstance(v, str) and v == AUTO


@dataclass
class ConfigModel:
    """Base for config blocks: dict construction with unknown-key warnings and
    recursive nesting, mirroring `DeepSpeedConfigModel`."""

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]], path=""):
        d = dict(d or {})
        kwargs = {}
        field_map = {f.name: f for f in dataclasses.fields(cls)}
        for key, value in d.items():
            if key not in field_map:
                logger.warning(f"Config: unknown key '{path}{key}' ignored")
                continue
            f = field_map[key]
            ftype = f.type
            if isinstance(value, dict) and isinstance(ftype, type) and issubclass_safe(ftype, ConfigModel):
                value = ftype.from_dict(value, path=f"{path}{key}.")
            kwargs[key] = value
        return cls(**kwargs)

    def to_dict(self):
        out = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, ConfigModel):
                v = v.to_dict()
            elif isinstance(v, Enum):
                v = v.value
            out[f.name] = v
        return out

    def resolve_auto(self, **defaults):
        for name, value in defaults.items():
            if _is_auto(getattr(self, name, None)):
                setattr(self, name, value)


def issubclass_safe(t, parent):
    try:
        return issubclass(t, parent)
    except TypeError:
        return False


def maybe_unwrap_tuned(d):
    """A dstpu_tune artifact (autotuning/session.py) handed where a config
    dict is expected unwraps to its winner's full merged config — so
    `initialize(config="tuned_config.json")` / `init_inference(config=...)`
    consume the tuner's output directly. Anything else passes through."""
    if isinstance(d, dict) and "dstpu_tune" in d:
        winner = d.get("winner") or {}
        cfg = winner.get("config")
        if not isinstance(cfg, dict):
            raise ValueError(
                "dstpu_tune artifact has no winner config to load (a "
                "--dry-run artifact holds only the prune ledger) — run the "
                "measured stage, or extract a config by hand")
        return copy.deepcopy(cfg)
    return d


# --------------------------------------------------------------------------------------
# Feature blocks
# --------------------------------------------------------------------------------------


@dataclass
class OffloadParamConfig(ConfigModel):
    """Reference: `DeepSpeedZeroOffloadParamConfig` (`runtime/zero/offload_config.py`)."""
    device: str = "none"          # none | cpu | nvme
    nvme_path: Optional[str] = None
    buffer_count: int = 5
    buffer_size: int = 10**8
    max_in_cpu: int = 10**9
    pin_memory: bool = False
    # async staging-pool depth (runtime/param_swap.LayerStreamer): layers
    # of weights kept in flight ahead of compute; 0 = blocking baseline,
    # 1 = classic double buffering (docs/offload.md "Staging depth")
    lookahead: int = 1


@dataclass
class OffloadOptimizerConfig(ConfigModel):
    device: str = "none"
    nvme_path: Optional[str] = None
    buffer_count: int = 4
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False
    ratio: float = 1.0


@dataclass
class ZeroConfig(ConfigModel):
    """Reference: `DeepSpeedZeroConfig` (`runtime/zero/config.py:81`).

    On TPU, stages are realized as sharding policies over the mesh's combined
    data axes rather than hook-driven partitioning:
      stage 0: params+grads+opt replicated (DP allreduce)
      stage 1: optimizer state sharded
      stage 2: + gradients reduce-scattered into the shard
      stage 3: + parameters sharded (XLA gathers before use)
    """
    stage: int = 0
    contiguous_gradients: bool = True           # accepted; XLA manages layout
    reduce_scatter: bool = True
    reduce_bucket_size: int = 5 * 10**8         # accepted; XLA buckets internally
    allgather_partitions: bool = True
    allgather_bucket_size: int = 5 * 10**8
    overlap_comm: bool = True                   # XLA latency-hiding scheduler
    offload_param: Optional[OffloadParamConfig] = None
    offload_optimizer: Optional[OffloadOptimizerConfig] = None
    sub_group_size: int = 10**9
    stage3_max_live_parameters: int = 10**9
    stage3_max_reuse_distance: int = 10**9
    stage3_prefetch_bucket_size: int = 5 * 10**7
    stage3_param_persistence_threshold: int = 10**5
    stage3_gather_16bit_weights_on_model_save: bool = False
    round_robin_gradients: bool = False
    zero_hpz_partition_size: int = 1            # ZeRO++ hpZ: secondary shard group size
    zero_quantized_weights: bool = False        # ZeRO++ qwZ: int8 weight all-gather
    zero_quantized_gradients: bool = False      # ZeRO++ qgZ: int8 grad reduce
    # explicit grad-reduce through the comm facade: one hierarchical
    # reduce per step — plain psum over the fast (ICI) axes, then a
    # transform-compressed 2-hop reduce over the declared slow axis
    # (compressed_comm_axis, default: the outermost data-domain axis).
    # With zero_quantized_gradients the slow hop runs the int8 qgZ wire.
    explicit_grad_reduce: bool = False
    # 1-bit Adam wire: error-feedback sign+scale compression on the slow-axis
    # grad reduce (pairs with the OneBit* optimizers, whose in-optimizer
    # compression is simulated — this knob shrinks the actual wire). Implies
    # explicit_grad_reduce.
    onebit_gradients: bool = False
    compressed_comm_axis: Optional[str] = None  # slow-tier mesh axis for the wire
    mics_shard_size: int = -1                   # MiCS: shard group size (<=0 disabled)
    mics_hierarchical_params_gather: bool = False
    ignore_unused_parameters: bool = True
    param_persistence_threshold: Optional[int] = None

    def __post_init__(self):
        if isinstance(self.offload_param, dict):
            self.offload_param = OffloadParamConfig.from_dict(self.offload_param)
        if isinstance(self.offload_optimizer, dict):
            self.offload_optimizer = OffloadOptimizerConfig.from_dict(self.offload_optimizer)
        assert 0 <= self.stage <= 3, f"zero_optimization.stage must be 0-3, got {self.stage}"


@dataclass
class Fp16Config(ConfigModel):
    """Reference: fp16 block (`runtime/config.py`, loss scaler `runtime/fp16/loss_scaler.py`)."""
    enabled: Union[bool, str] = False
    auto_cast: bool = False
    loss_scale: float = 0.0          # 0 => dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0

    @property
    def dynamic(self):
        return self.loss_scale == 0


@dataclass
class Bf16Config(ConfigModel):
    enabled: Union[bool, str] = False
    # Keep fp32 master weights + fp32 grad accumulation (reference BF16_Optimizer role).
    master_weights: bool = True


@dataclass
class OptimizerConfig(ConfigModel):
    """Reference: optimizer block — {"type": "AdamW", "params": {...}}."""
    type: str = "AdamW"
    params: Dict[str, Any] = field(default_factory=dict)
    legacy_fusion: bool = False


@dataclass
class SchedulerConfig(ConfigModel):
    type: Optional[str] = None
    params: Dict[str, Any] = field(default_factory=dict)


@dataclass
class MeshConfig(ConfigModel):
    """TPU-native extension: logical mesh axis sizes.

    Replaces the reference's process-group plumbing (`deepspeed/utils/groups.py`,
    `runtime/pipe/topology.py`): DP/TP/PP/SP/EP group objects collapse into named mesh
    axes. Sizes of -1 mean "absorb remaining devices" (at most one axis may be -1;
    default: data).
    Axis order is outer→inner = DCN→ICI friendly: pipe, data, zero, expert,
    sequence, tensor.
    """
    data: int = -1
    zero: int = 1     # inner factor of the data domain (MiCS/hpZ sub-group size)
    tensor: int = 1
    pipe: int = 1
    sequence: int = 1
    expert: int = 1
    # devices: total device count override (defaults to jax.device_count())
    devices: Optional[int] = None


@dataclass
class ActivationCheckpointingConfig(ConfigModel):
    """Reference: `runtime/activation_checkpointing/checkpointing.py` config block.
    On TPU this maps to `jax.checkpoint` policies; partitioning/cpu offload map to
    remat policies + host offload of residuals."""
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU extension: which remat policy to use ("full", "dots", "dots_with_no_batch_dims", "none")
    policy: str = "full"


@dataclass
class FlopsProfilerConfig(ConfigModel):
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


@dataclass
class TelemetryConfig(ConfigModel):
    """Unified telemetry (`deepspeed_tpu/telemetry/`): metrics registry +
    exporters + spans. Opt-in: when disabled (default) the instrumented
    subsystems record nothing in the registry and NO files are written (the
    in-memory step timeline, `telemetry/steptrace.py`, is the one always-on
    part and has no setting). Shared by the train
    config and `TpuInferenceConfig` — the serving scheduler reads the same
    block."""
    enabled: bool = False
    output_path: str = "telemetry"   # dir for <subsystem>.prom/.jsonl/.trace.json
    export_interval: int = 20        # steps between exports (scheduler
                                     # iterations for serving, optimizer steps
                                     # for training)
    prometheus: bool = True          # text-exposition file (atomic rewrite)
    jsonl: bool = True               # append-only log (bin/dstpu_metrics)
    monitor_bridge: bool = True      # flatten snapshots into MonitorMaster
                                     # scalars so TB/WandB/CSV keep working
    chrome_trace: bool = False       # host-side span timeline (Perfetto)
    peak_tflops: float = 0.0         # per-chip peak override for MFU (TFLOPs);
                                     # 0 = the live device_kind's published
                                     # peak (platform/device.py); an unknown
                                     # device then publishes no MFU gauge
    measure_program_flops: bool = True  # MFU numerator: cost-analyze the
                                     # compiled step once at first step (XLA's
                                     # exact program flops — an extra one-time
                                     # compile); False = analytic 6N model
                                     # flops (the PaLM MFU convention, free)
    tracing: bool = False            # request-scoped span trees:
                                     # <subsystem>.trace.jsonl (dstpu_trace)
                                     # + a flow-linked chrome trace (Perfetto)
    flight_recorder: bool = False    # bounded ring of scheduling events,
                                     # dumped to <subsystem>.flightrec.*.json
                                     # on replica failure / sentinel trip /
                                     # dump signal
    flight_recorder_events: int = 256  # ring capacity (last-N events kept)
    memscope: bool = False           # HBM memory ledger + OOM forensics
                                     # (telemetry/memscope.py): per-subsystem
                                     # mem/* byte-attribution gauges, a pre-
                                     # flight capacity check at engine build,
                                     # and a ledger+planner+flight dump on
                                     # RESOURCE_EXHAUSTED at the dispatch
                                     # boundaries
    memscope_programs: bool = True   # ledger includes per-program temp/arg
                                     # bytes from XLA memory_analysis() of
                                     # the persistent jitted programs — one
                                     # extra AOT compile per program, lazily
                                     # at first export (the jit CALL caches,
                                     # and so compile_stats(), are untouched)
    memscope_capacity_bytes: int = 0  # per-device HBM capacity override for
                                     # headroom/preflight math; 0 = read
                                     # device.memory_stats()["bytes_limit"]
                                     # (absent on the CPU harness)
    memscope_preflight: str = "warn"  # capacity-planner verdict at engine
                                     # build: "off" | "warn" | "refuse"
                                     # (refuse raises PredictedOOMError
                                     # before anything compiles)


@dataclass
class EigenvalueConfig(ConfigModel):
    """Reference: eigenvalue block (`runtime/config.py:545`) — curvature
    estimation driving the MoQ quantization schedule."""
    enabled: bool = False
    verbose: bool = False
    max_iter: int = 100
    tol: float = 1e-2
    stability: float = 1e-6
    gas_boundary_resolution: int = 1
    layer_name: str = "blocks"
    layer_num: int = 0


@dataclass
class TensorBoardConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJob"


@dataclass
class WandbConfig(ConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed_tpu"


@dataclass
class CsvConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJob"


@dataclass
class CommsLoggerConfig(ConfigModel):
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = field(default_factory=list)


@dataclass
class PipelineConfig(ConfigModel):
    """Pipeline-parallel engine knobs (reference: `runtime/pipe/` + engine config)."""
    stages: Union[int, str] = AUTO
    partition_method: str = "parameters"   # parameters | uniform | type:<regex>
    seed_layers: bool = False
    activation_checkpoint_interval: int = 0
    pipe_schedule: str = "1f1b"            # 1f1b | gpipe | interleaved


@dataclass
class GradientCompressionConfig(ConfigModel):
    """1-bit/compressed-optimizer analog (reference `runtime/fp16/onebit/`).
    TPU realization: error-feedback + int8/1-bit quantized collectives."""
    enabled: bool = False
    bits: int = 8
    error_feedback: bool = True
    warmup_steps: int = 100


@dataclass
class AutotuningConfig(ConfigModel):
    enabled: bool = False
    fast: bool = True
    results_dir: str = "autotuning_results"
    exps_dir: str = "autotuning_exps"
    metric: str = "throughput"
    start_profile_step: int = 3
    end_profile_step: int = 5
    max_train_micro_batch_size_per_gpu: int = 1024
    tuner_type: str = "gridsearch"
    tuner_early_stopping: int = 5
    tuner_num_trials: int = 50


@dataclass
class ElasticityConfig(ConfigModel):
    """Reference: `elasticity/config.py` — admissible world sizes from batch divisibility."""
    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: List[int] = field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    prefer_larger_batch: bool = True
    ignore_non_elastic_batch_info: bool = False
    version: float = 0.1


@dataclass
class DataEfficiencyConfig(ConfigModel):
    enabled: bool = False
    seed: int = 1234
    data_sampling: Dict[str, Any] = field(default_factory=dict)
    data_routing: Dict[str, Any] = field(default_factory=dict)


@dataclass
class DataTypesConfig(ConfigModel):
    """Reference: `runtime/config.py:876` data_types block — the gradient
    ACCUMULATOR dtype for gas > 1. Default fp32 (exact accumulation across
    micro-batches); "bf16" halves the accumulator's HBM footprint and RMW
    traffic at ~3-decimal-digit accumulation precision — the knob that makes
    gas viable when fp32 accumulators do not fit next to the model state."""
    grad_accum_dtype: Optional[str] = None   # None/"fp32" | "bf16" | "fp16"


@dataclass
class ProgressiveLayerDropConfig(ConfigModel):
    """Reference: `runtime/config.py` progressive_layer_drop block +
    `runtime/progressive_layer_drop.py` (theta schedule)."""
    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001


@dataclass
class CheckpointConfig(ConfigModel):
    """Reference: checkpoint block + `runtime/checkpoint_engine/`."""
    tag_validation: str = "Warn"     # Ignore | Warn | Fail
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write: Dict[str, Any] = field(default_factory=dict)
    # TPU extension: engine = "orbax" (async, default) or "numpy" (simple .npz files)
    engine: str = "orbax"
    async_save: bool = False
    # crash-safety knobs (docs/fault_tolerance.md):
    # keep_last_n: retention — committed tags beyond the newest N are GC'd
    # after each successful commit (0 = keep everything); uncommitted/legacy
    # dirs are never retention-deleted
    keep_last_n: int = 0
    # verify_checksums: load-time deep (crc32) verification of every file the
    # manifest records; False checks existence+size only (large checkpoints)
    verify_checksums: bool = True


@dataclass
class FaultToleranceConfig(ConfigModel):
    """Training-loop bad-state sentinels + in-process rollback
    (`runtime/sentinel.py`, docs/fault_tolerance.md). Opt-in: the sentinel
    reads the loss on the host every step, which costs a device sync."""
    enabled: bool = False
    nonfinite_budget: int = 3        # consecutive non-finite losses tolerated
    overflow_budget: int = 50        # consecutive fp16 overflow skip-steps
    loss_spike_window: int = 0       # rolling-median window (0 = disabled)
    loss_spike_factor: float = 10.0
    loss_spike_patience: int = 3
    # rollback to the last good checkpoint in-process instead of raising
    # BadStateError (requires a prior save_checkpoint/load_checkpoint so the
    # engine knows the checkpoint root)
    auto_rollback: bool = True
    max_rollbacks: int = 3           # per-process budget before raising anyway


@dataclass
class MoEConfig(ConfigModel):
    """Expert-parallel knobs; layer-level options live on the MoE layer itself
    (reference `deepspeed/moe/layer.py:16`)."""
    enabled: bool = False
    ep_size: int = 1
    moe_param_groups: bool = True
    use_residual: bool = False


@dataclass
class CompressionConfig(ConfigModel):
    """Reference: `deepspeed/compression/config.py` — accepted and dispatched to
    deepspeed_tpu.compression."""
    weight_quantization: Dict[str, Any] = field(default_factory=dict)
    activation_quantization: Dict[str, Any] = field(default_factory=dict)
    sparse_pruning: Dict[str, Any] = field(default_factory=dict)
    row_pruning: Dict[str, Any] = field(default_factory=dict)
    head_pruning: Dict[str, Any] = field(default_factory=dict)
    channel_pruning: Dict[str, Any] = field(default_factory=dict)
    layer_reduction: Dict[str, Any] = field(default_factory=dict)


# --------------------------------------------------------------------------------------
# Root config
# --------------------------------------------------------------------------------------


@dataclass
class TpuTrainConfig(ConfigModel):
    """Root training config — analog of `DeepSpeedConfig` (`runtime/config.py:686`)."""

    train_batch_size: Union[int, str, None] = None
    train_micro_batch_size_per_gpu: Union[int, str, None] = None
    gradient_accumulation_steps: Union[int, str, None] = None

    optimizer: Optional[OptimizerConfig] = None
    scheduler: Optional[SchedulerConfig] = None

    fp16: Fp16Config = field(default_factory=Fp16Config)
    bf16: Bf16Config = field(default_factory=Bf16Config)
    zero_optimization: ZeroConfig = field(default_factory=ZeroConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    activation_checkpointing: ActivationCheckpointingConfig = field(default_factory=ActivationCheckpointingConfig)
    flops_profiler: FlopsProfilerConfig = field(default_factory=FlopsProfilerConfig)
    eigenvalue: EigenvalueConfig = field(default_factory=EigenvalueConfig)
    tensorboard: TensorBoardConfig = field(default_factory=TensorBoardConfig)
    wandb: WandbConfig = field(default_factory=WandbConfig)
    csv_monitor: CsvConfig = field(default_factory=CsvConfig)
    comms_logger: CommsLoggerConfig = field(default_factory=CommsLoggerConfig)
    pipeline: PipelineConfig = field(default_factory=PipelineConfig)
    compression_training: CompressionConfig = field(default_factory=CompressionConfig)
    gradient_compression: GradientCompressionConfig = field(default_factory=GradientCompressionConfig)
    autotuning: AutotuningConfig = field(default_factory=AutotuningConfig)
    elasticity: ElasticityConfig = field(default_factory=ElasticityConfig)
    data_efficiency: DataEfficiencyConfig = field(default_factory=DataEfficiencyConfig)
    progressive_layer_drop: ProgressiveLayerDropConfig = field(
        default_factory=ProgressiveLayerDropConfig)
    data_types: DataTypesConfig = field(default_factory=DataTypesConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    fault_tolerance: FaultToleranceConfig = field(default_factory=FaultToleranceConfig)
    moe: MoEConfig = field(default_factory=MoEConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)

    gradient_clipping: float = 0.0
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    communication_data_type: Optional[str] = None
    sparse_gradients: bool = False
    steps_per_print: int = 10
    wall_clock_breakdown: bool = False
    memory_breakdown: bool = False
    dump_state: bool = False
    zero_allow_untested_optimizer: bool = True
    zero_force_ds_cpu_optimizer: bool = False
    disable_allgather: bool = False
    seed: int = 1234

    # TPU extensions
    param_dtype: str = AUTO          # resolved from fp16/bf16 blocks
    matmul_precision: str = "default"  # jax.default_matmul_precision
    remat: bool = False              # shorthand: activation_checkpointing.policy applied to blocks

    def __post_init__(self):
        for name, cls_ in (("optimizer", OptimizerConfig), ("scheduler", SchedulerConfig)):
            v = getattr(self, name)
            if isinstance(v, dict):
                setattr(self, name, cls_.from_dict(v, path=name + "."))
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, dict) and issubclass_safe(f.type, ConfigModel):
                setattr(self, f.name, f.type.from_dict(v, path=f.name + "."))

    # ---------------- batch triad ----------------

    def resolve_batch_sizes(self, dp_world_size: int):
        """Resolve the (global, micro, GAS) triad given the data-parallel world size.

        Mirrors the reference's `_set_batch_related_parameters` / `_batch_assertion`
        (`runtime/config.py`): any two determine the third; one given assumes the
        others are 1; none given defaults micro=1, gas=1.
        """
        tb = self.train_batch_size if not _is_auto(self.train_batch_size) else None
        mb = self.train_micro_batch_size_per_gpu if not _is_auto(self.train_micro_batch_size_per_gpu) else None
        gas = self.gradient_accumulation_steps if not _is_auto(self.gradient_accumulation_steps) else None

        if tb is not None and mb is not None and gas is not None:
            pass
        elif tb is not None and mb is not None:
            gas = tb // (mb * dp_world_size)
        elif tb is not None and gas is not None:
            mb = tb // (gas * dp_world_size)
        elif mb is not None and gas is not None:
            tb = mb * gas * dp_world_size
        elif tb is not None:
            gas = 1
            mb = tb // dp_world_size
        elif mb is not None:
            gas = 1
            tb = mb * dp_world_size
        else:
            mb, gas = 1, 1
            tb = dp_world_size

        assert tb == mb * gas * dp_world_size, (
            f"batch size triad inconsistent: train_batch_size={tb} != "
            f"micro({mb}) * gas({gas}) * dp_world({dp_world_size})")
        assert tb > 0 and mb > 0 and gas > 0, "batch sizes must be positive"

        self.train_batch_size = int(tb)
        self.train_micro_batch_size_per_gpu = int(mb)
        self.gradient_accumulation_steps = int(gas)
        return tb, mb, gas

    # ---------------- precision ----------------

    @property
    def fp16_enabled(self):
        return bool(self.fp16.enabled) and self.fp16.enabled != AUTO

    @property
    def bf16_enabled(self):
        return bool(self.bf16.enabled) and self.bf16.enabled != AUTO

    def compute_dtype(self):
        import jax.numpy as jnp
        if self.fp16_enabled:
            return jnp.float16
        if self.bf16_enabled:
            return jnp.bfloat16
        if self.param_dtype not in (AUTO, None):
            return jnp.dtype(self.param_dtype)
        return jnp.float32

    # ---------------- construction ----------------

    @classmethod
    def load(cls, config: Union[str, Dict[str, Any], "TpuTrainConfig", None]):
        if config is None:
            config = {}
        if isinstance(config, TpuTrainConfig):
            return config
        if isinstance(config, str):
            with open(config) as f:
                config = json.load(f)
        assert isinstance(config, dict), f"config must be dict/path/TpuTrainConfig, got {type(config)}"
        config = copy.deepcopy(maybe_unwrap_tuned(config))
        return cls.from_dict(config)

    def dump(self):
        return json.dumps(self.to_dict(), indent=2, default=str)
