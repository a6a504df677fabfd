"""Platform (accelerator) abstraction.

TPU-native analog of the reference's `accelerator/abstract_accelerator.py:10`
(`DeepSpeedAccelerator` ABC, ~80 methods) + `accelerator/real_accelerator.py:45`
(env/auto probe). In JAX most of that surface collapses: streams/events are XLA's
async dispatch, memory mgmt is the runtime's; what remains useful is device query,
HBM stats, dtype support, platform naming, and the communication-backend name.

Selection: `jax.default_backend()`. `DSTPU_ACCELERATOR` may narrow a hardware
host to "cpu" (the host platform is always present); it can never name an
accelerator JAX did not find.
"""

import os
import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.utils.logging import logger


class BaseAccelerator:
    """Shared implementation over jax.devices()."""

    _name = "base"
    _communication_backend = "xla"

    # ---- identity ----
    def device_name(self, device_index=None):
        if device_index is None:
            return self._name
        return f"{self._name}:{device_index}"

    def is_available(self):
        try:
            return len(self.devices()) > 0
        except RuntimeError:
            return False

    def device_count(self):
        return len(self.devices())

    def devices(self):
        return [d for d in jax.devices() if self._matches(d)]

    def _matches(self, d):
        return True

    def current_device(self):
        return self.devices()[0]

    def current_device_name(self):
        return self.device_name(0)

    def communication_backend_name(self):
        # Reference: accelerator.communication_backend_name() picks nccl/ccl/hccl
        # (`accelerator/cuda_accelerator.py`); on TPU there is a single answer: XLA
        # collectives over ICI/DCN.
        return self._communication_backend

    # ---- memory ----
    def memory_stats(self, device=None):
        d = device or self.current_device()
        try:
            return d.memory_stats() or {}
        except Exception:
            return {}

    def memory_allocated(self, device=None):
        return self.memory_stats(device).get("bytes_in_use", 0)

    def max_memory_allocated(self, device=None):
        return self.memory_stats(device).get("peak_bytes_in_use", 0)

    def total_memory(self, device=None):
        return self.memory_stats(device).get("bytes_limit", 0)

    def available_memory(self, device=None):
        s = self.memory_stats(device)
        return max(s.get("bytes_limit", 0) - s.get("bytes_in_use", 0), 0)

    def empty_cache(self):
        # XLA owns allocation; provide GC-style hook for API parity.
        import gc
        gc.collect()

    def reset_peak_memory_stats(self, device=None):
        pass  # not exposed by the TPU runtime; kept for API parity

    # ---- synchronization (streams/events collapse to dispatch barriers) ----
    def synchronize(self, device=None):
        jax.effects_barrier()

    # ---- dtype support ----
    def is_bf16_supported(self):
        return True

    def is_fp16_supported(self):
        return True

    def supported_dtypes(self):
        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8]

    def preferred_dtype(self):
        return jnp.bfloat16

    # ---- profiling ranges (nvtx analog) ----
    def range_push(self, msg):
        self._trace = jax.profiler.TraceAnnotation(msg)
        self._trace.__enter__()

    def range_pop(self):
        if getattr(self, "_trace", None) is not None:
            self._trace.__exit__(None, None, None)
            self._trace = None

    # ---- misc parity ----
    def lazy_call(self, callback):
        callback()

    def op_builder_dir(self):
        return "deepspeed_tpu.ops"

    def on_accelerator(self, tensor):
        return hasattr(tensor, "devices") or hasattr(tensor, "device")


class TpuAccelerator(BaseAccelerator):
    _name = "tpu"
    _communication_backend = "xla-ici"

    def _matches(self, d):
        return d.platform == "tpu"

    def preferred_dtype(self):
        return jnp.bfloat16


class CpuAccelerator(BaseAccelerator):
    _name = "cpu"
    _communication_backend = "xla-host"

    def devices(self):
        # asked for by name: on a hardware host the CPU is a secondary
        # backend and jax.devices() would list only the accelerator
        return jax.devices("cpu")


class GpuAccelerator(BaseAccelerator):
    _name = "gpu"
    _communication_backend = "xla-nccl"

    def _matches(self, d):
        return d.platform in ("gpu", "cuda", "rocm")


_ACCELERATOR = None


def set_accelerator(accel):
    global _ACCELERATOR
    _ACCELERATOR = accel


@functools.lru_cache(None)
def _probe():
    backend = jax.default_backend()
    want = os.environ.get("DSTPU_ACCELERATOR") or backend
    if want in ("cuda", "rocm"):
        want = "gpu"
    if want not in (backend, "cpu"):
        raise ValueError(
            f"DSTPU_ACCELERATOR={want!r} contradicts jax.default_backend()="
            f"{backend!r}: the variable may narrow a run to 'cpu', it cannot "
            f"name hardware JAX did not find (a CPU run called 'tpu' would "
            f"report TPU memory, dtypes and collectives it does not have)")
    if want == "tpu":
        return TpuAccelerator()
    if want == "gpu":
        return GpuAccelerator()
    return CpuAccelerator()


def get_accelerator():
    global _ACCELERATOR
    if _ACCELERATOR is None:
        _ACCELERATOR = _probe()
    return _ACCELERATOR
