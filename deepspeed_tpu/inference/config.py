"""Inference config — analog of `DeepSpeedInferenceConfig` (`inference/config.py`)."""

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from deepspeed_tpu.config.core import ConfigModel, TelemetryConfig


@dataclass
class QuantConfig(ConfigModel):
    enabled: bool = False
    bits: int = 8
    group_size: int = 64


@dataclass
class TensorParallelConfig(ConfigModel):
    tp_size: int = 1
    enabled: bool = True


@dataclass
class SpecDecodeConfig(ConfigModel):
    """Speculative decoding over the paged pool (`inference/spec_decode.py`).

    When enabled, the serving scheduler replaces the per-token decode step
    (and the decode window) with a draft+verify loop: a DRAFTER proposes
    `draft_k` tokens per active slot, one fixed-shape jitted VERIFY call
    scores all of them for all `max_slots` at once (the chunked-prefill
    machinery at positions pos..pos+k), and the longest agreeing prefix is
    accepted plus one bonus token from the first disagreeing logit row —
    1..k+1 tokens per model step instead of exactly 1. Rejection is an O(1)
    rewind of the slot's length cursor: blocks past it are overwritten by
    later writes, never freed or reallocated, and the block table is
    untouched. Greedy output is token-identical to non-speculative serving.
    """
    drafter: str = "off"          # "off" | "ngram" | "model". "ngram" is the
                                  # model-free prompt-lookup drafter (match
                                  # the newest generated tokens against the
                                  # slot's own prompt+output history, propose
                                  # the continuation — ideal for the cache-
                                  # heavy shared-prefix workloads prefix
                                  # caching serves); "model" drives a second,
                                  # smaller DecodeModelSpec passed to
                                  # `engine.serving(draft_spec=...)`
    draft_k: int = 4              # draft tokens proposed+verified per step —
                                  # a compile-stability knob: pins the verify
                                  # program's [max_slots, draft_k+1] shape.
                                  # Size against the measured acceptance
                                  # rate: the verify step always pays k+1
                                  # positions of compute, accepted or not
    ngram_max: int = 4            # longest suffix n-gram the prompt-lookup
    ngram_min: int = 1            # drafter tries to match (tried max..min)


@dataclass
class ServingQuantizationConfig(ConfigModel):
    """Quantized serving (`inference/quantization.py`, the int8 paged pool).

    Decode is HBM-bandwidth-bound at serving batch sizes: every step reads
    the whole weight set plus the live KV prefix. Quantizing the RESIDENT
    bytes therefore buys two things at once — capacity (an int8 pool holds
    ~2x the blocks per HBM byte: more concurrent users, a bigger prefix
    cache; int8/int4 weights let one chip hold a 2-4x-over-bf16 model, the
    ZeRO-Inference direction) and tokens/s (the decode step streams half
    the bytes). Both knobs change ONLY what is stored: K/V quantize at
    cache-write time and dequantize inside the paged kernel's KV-grid walk
    (or the gather fallback), weights dequantize inside the jitted step
    where XLA fuses the dequant into the consuming matmul — program shapes,
    and therefore the one-compile-per-program contract, are untouched.
    """
    kv_cache_dtype: str = ""      # "" = inherit the engine's kv_cache_dtype;
                                  # "bf16"/"bfloat16" | "int8". int8 stores
                                  # the pool as symmetric per-group int8 with
                                  # f32 scales riding the same physical-block
                                  # axis (scales travel with blocks through
                                  # prefix sharing / handoff / transplant)
    kv_group_size: int = 0        # elements per K/V scale group along
                                  # head_dim; 0 = head_dim (one scale per
                                  # written vector per head). Must divide
                                  # head_dim; smaller = tighter quant, more
                                  # scale overhead (4/g bytes per element)
    weights: str = "off"          # "off" | "int8" | "int4": pytree-wide
                                  # weight-only quantization at serving-
                                  # engine build (dequantize-on-use view;
                                  # int4 packs two values per byte). Applies
                                  # to the ENGINE's resident params — the
                                  # dense copy is dropped, generate() serves
                                  # the quantized tree too
    weight_group_size: int = 64   # elements per weight scale group (last
                                  # dim); leaves it does not tile stay dense


@dataclass
class DegradationConfig(ConfigModel):
    """Graceful-degradation ladder (`serving/degradation.py`).

    When enabled, a `PressureController` evaluates pool pressure every
    `eval_interval` scheduler syncs — free-block fraction, queue depth,
    and (when telemetry is on) TTFT p99 — and walks an ORDERED ladder of
    service-degrading levels, one rung per evaluation, escalating while
    any signal is over its high watermark and de-escalating one rung only
    after `hold_steps` consecutive calm evaluations (hysteresis: separate
    high/low watermarks + the hold count prevent flapping):

      0 normal · 1 cap draft_k to 1 (spec decode keeps its compiled shape,
      the drafter just proposes less) · 2 disable spec decode (fall back
      to a single-step decode program) · 3 force the 1-step decode window
      (finer retirement granularity frees blocks sooner) · 4 aggressively
      flush the reclaimable prefix-cache blocks (zeroes the replica's
      prefix-affinity pull so the router routes shared-prefix traffic
      elsewhere, and moves demand-eviction work off the admission path) ·
      5 shed queued requests whose priority is below `shed_below_priority`.

    Disabled (default) the controller is never constructed: the hot path,
    the compiled programs, and `compile_stats()` are untouched.
    """
    enabled: bool = False
    eval_interval: int = 4        # scheduler syncs between evaluations
    free_block_low: float = 0.10  # available/capacity below this => pressure
    free_block_high: float = 0.30 # ...and above this counts as calm
    queue_high: int = 16          # engine queue depth over this => pressure
    queue_low: int = 2            # ...and at/below this counts as calm
    ttft_p99_ms: float = 0.0      # TTFT p99 over this => pressure (0 = off;
                                  # needs telemetry for the histogram)
    hold_steps: int = 3           # consecutive calm evals per de-escalation
    shed_below_priority: int = 0  # level 5 sheds queued requests with
                                  # Request.priority strictly below this
    headroom_low: float = 0.0     # mem/headroom_frac (telemetry/memscope.py
                                  # ledger) below this => pressure (0 = off;
                                  # needs telemetry.memscope + a known HBM
                                  # capacity — the signal is omitted when
                                  # either is missing)
    headroom_high: float = 0.0    # ...and at/above this counts as calm
                                  # (clamped up to headroom_low)


@dataclass
class ServingConfig(ConfigModel):
    """Continuous-batching serving engine (`inference/scheduler.py`).

    The serving layer runs a FIXED-shape decode step over `max_slots`
    sequence slots against one engine-owned paged KV pool; requests are
    admitted into freed slots every step and retire (freeing their blocks)
    the moment they emit EOS. All shape knobs here are compile-stability
    knobs: each one pins a jitted program's shape for the engine's lifetime.
    """
    max_slots: int = 8            # decode batch slots — THE decode step shape
    max_context: int = 0          # per-sequence cap (prompt + generated);
                                  # 0 = the engine's max_out_tokens. Sets the
                                  # block-table width nb = ceil(max_context /
                                  # kv_block_size)
    num_kv_blocks: int = 0        # physical pool blocks (incl. the reserved
                                  # trash block 0); 0 = worst case:
                                  # max_slots * nb + 1 (no admission can ever
                                  # starve); smaller values oversubscribe the
                                  # pool and lean on admission backpressure
    prefill_chunk: int = 0        # chunked-prefill bucket: prompts process in
                                  # fixed [1, chunk] slices (one compile
                                  # total); 0 = kv_block_size
    prefill_chunks_per_step: int = 1  # prefill work interleaved per decode
                                  # step — bounds how long an arriving prompt
                                  # can stall the running batch. Over
                                  # decode_steps_per_sync it is also how many
                                  # chunks ONE token of a mixed call carries
                                  # (their ceil ratio; docs/inference.md)
    decode_steps_per_sync: int = 1  # decode WINDOW: tokens decoded per
                                  # scheduler sync, inside one jitted
                                  # lax.scan (vLLM's multi-step scheduling).
                                  # >1 amortizes per-call dispatch + the
                                  # host roundtrip over K tokens — the lever
                                  # on dispatch-latency-bound backends — at
                                  # the cost of K-step retirement/admission
                                  # granularity (a sequence finishing
                                  # mid-window wastes the window's tail)
    blocks_per_call: int = 1      # a model that generates by diffusion over
                                  # blocks (`DecodeModelSpec.generator`): the
                                  # blocks of `block_length` tokens a decode
                                  # call commits a slot — its window, in
                                  # blocks (`decode_steps_per_sync` is the
                                  # other generators')
    enable_prefix_caching: bool = False  # automatic prefix caching
                                  # (inference/prefix_cache.py): full prompt
                                  # blocks are content-hashed and reused
                                  # across requests — a shared system prompt
                                  # prefills once. Token-identical greedy
                                  # output, zero new compiles; costs only
                                  # host-side hashing at submit
    spec_decode: SpecDecodeConfig = field(default_factory=SpecDecodeConfig)
                                  # speculative decoding (drafter/draft_k —
                                  # see SpecDecodeConfig); replaces the
                                  # decode window when on
    audit_interval: int = 0       # run the KV-pool invariant auditor
                                  # (inference/audit.py) every N scheduler
                                  # syncs (0 = on-demand/shutdown only).
                                  # Host-side reads only — never touches the
                                  # compiled programs
    audit_action: str = "repair"  # on a failed audit, after the flight-
                                  # recorder dump: "repair" rebuilds the
                                  # free list/refcounts from the slot tables
                                  # (ground truth) and keeps serving;
                                  # "raise" raises PoolCorruptionError out
                                  # of step() so the serving router
                                  # quarantines the replica (PR 6 failover)
    degradation: DegradationConfig = field(default_factory=DegradationConfig)
                                  # graceful-degradation ladder under
                                  # sustained pressure (see
                                  # DegradationConfig); off by default
    quantization: ServingQuantizationConfig = field(
        default_factory=ServingQuantizationConfig)
                                  # quantized serving: int8 KV pool +
                                  # weight-only int8/int4 (see
                                  # ServingQuantizationConfig); off by
                                  # default — bf16 pool, dense weights
    prefix_cache_policy: str = "lru"  # what happens to a cached block when
                                  # its last reader retires: "lru" parks it
                                  # on the reclaimable list (evicted oldest-
                                  # first only when an alloc would fail —
                                  # caching never reduces usable capacity);
                                  # "none" frees + unregisters immediately
                                  # (only concurrently-active sharing)


@dataclass
class TpuInferenceConfig(ConfigModel):
    dtype: str = "bfloat16"
    tensor_parallel: TensorParallelConfig = field(default_factory=TensorParallelConfig)
    max_out_tokens: int = 1024
    max_tokens: Optional[int] = None
    min_out_tokens: int = 1
    replace_with_kernel_inject: bool = True   # on TPU: use pallas decode kernels
    quant: QuantConfig = field(default_factory=QuantConfig)
    checkpoint: Optional[str] = None
    max_batch_size: int = 8
    # decoding
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    greedy: bool = True
    eos_token_id: Optional[int] = None
    # moe inference
    moe: Dict[str, Any] = field(default_factory=dict)
    # kv cache
    kv_cache_dtype: str = "bfloat16"
    # blocked KV-cache layout: cache length is rounded up to a whole number
    # of kv_block_size-token blocks, the unit the streaming decode kernel
    # (`ops/pallas/decode_attention.py`) DMAs from HBM — per decode step it
    # touches only the blocks covering each row's live prefix, so serving
    # contexts are bounded by HBM, not VMEM. 512 is the measured
    # bandwidth-floor block on v5e; 0 disables the rounding (legacy exact-
    # length caches; the kernel then pays a runtime pad-to-block copy).
    kv_block_size: int = 512
    # continuous-batching serving engine knobs (InferenceEngine.serving())
    serving: ServingConfig = field(default_factory=ServingConfig)
    # unified telemetry (deepspeed_tpu/telemetry/): TTFT/TPOT/queue-wait
    # histograms + pool gauges on the serving scheduler; disabled by default
    # (zero overhead, no files written)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    # ZeRO-Inference parameter spill (reference ds_config "zero_optimization"
    # with stage-3 param offload): {"offload_param": {"device": "cpu"|"nvme",
    # "nvme_path": ..., "lookahead": 1, "staging": 3}}
    zero: Dict[str, Any] = field(default_factory=dict)

    _LEGACY_DTYPES = {"fp16": "float16", "half": "float16", "bf16": "bfloat16",
                      "fp32": "float32", "float": "float32",
                      "torch.float16": "float16", "torch.bfloat16": "bfloat16",
                      "torch.float32": "float32"}

    @classmethod
    def from_dict(cls, d, path=""):
        """Accept the reference's legacy kwargs (`inference/config.py`
        validators): `mp_size` is the deprecated tensor_parallel degree —
        silently ignoring it would serve tp=1 — plus torch-style dtype
        spellings and the retired `replace_method` knob."""
        from deepspeed_tpu.config.core import maybe_unwrap_tuned
        d = dict(maybe_unwrap_tuned(d or {}))
        if "mp_size" in d:
            tp = d.pop("mp_size")
            tpc = d.setdefault("tensor_parallel", {})
            if isinstance(tpc, dict):
                tpc.setdefault("tp_size", int(tp))
        d.pop("replace_method", None)  # deprecated no-op in the reference too
        dt = d.get("dtype")
        if dt is not None and not isinstance(dt, str):
            dt = str(dt)
        if isinstance(dt, str):
            d["dtype"] = cls._LEGACY_DTYPES.get(dt, dt)
        return super().from_dict(d, path=path)
