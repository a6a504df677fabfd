"""What this process can know about the device it runs on — one home.

Every answer here is read from JAX, never assumed: which backend is live
(and therefore whether a Pallas kernel compiles through Mosaic or runs in
the interpreter), the published peaks of the exact `device_kind` JAX reports,
how much device memory there is, where compiled programs are cached, and
whether this process already owns the accelerator (a chip belongs to one
process at a time, so a parent that holds it must not spawn a child that
needs it).
"""

import dataclasses
import os
from typing import Optional

import jax

# <repo>/.jax_cache — derived from the package location so a parent and every
# child it spawns (utils/subproc.child_env copies the environment) agree on
# the path without passing it. The path is part of the cache key's
# provenance: a directory that moves between runs never hits.
_IN_TREE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def ensure_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere stable; return the
    directory. Idempotent; `initialize()` / `init_inference()` call it before
    their first compile.

    `JAX_COMPILATION_CACHE_DIR` set: do nothing at all — JAX reads the
    variable itself, and whoever set it owns the placement. Unset: the fixed
    in-tree path (never a tempfile, pid or timestamp)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if jax.config.jax_compilation_cache_dir != _IN_TREE_CACHE:
        jax.config.update("jax_compilation_cache_dir", _IN_TREE_CACHE)
    return _IN_TREE_CACHE


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """THE interpret-mode rule for every `pallas_call` in the package: on a
    TPU a kernel compiles through Mosaic (or raises the compiler's error);
    everywhere else it runs in the Pallas interpreter."""
    return not on_tpu()


def holds_accelerator() -> bool:
    """True when THIS process has already initialised a TPU backend — i.e. a
    child spawned now that needs the chip would fail at start-up ("The TPU
    is already in use"). Never initialises a backend itself."""
    # jax has no public "is a backend up?" query; asking for the backend is
    # what brings it up. This is the same predicate jax.distributed uses.
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized() and on_tpu()


def refuse_spawn_if_holding_accelerator(what: str, child_env=None) -> None:
    """Spawner guard: a process that owns the TPU cannot hand it to a child.

    Measured on the chip (TPU v5 lite, libtpu 0.0.34, PR 21): a second
    process fails at backend start-up in ~3 s with "ABORTED: The TPU is
    already in use by process with pid N" — no fallback to the CPU, since
    the machine lists the platform explicitly (JAX_PLATFORMS=tpu,cpu).
    Refusing here puts that explanation at the spawn instead of in a dead
    child's stderr. `what` names the spawner; `child_env` is the
    environment the child would get (default: this process's) — a child
    pinned to JAX_PLATFORMS=cpu needs no chip and may start."""
    env = os.environ if child_env is None else child_env
    if holds_accelerator() and env.get("JAX_PLATFORMS", "") != "cpu":
        raise RuntimeError(
            f"{what}: this process has already initialised the TPU backend, "
            f"and a chip belongs to one process at a time — the child would "
            f"die at start-up (\"The TPU is already in use by process with "
            f"pid {os.getpid()}\"). Spawn before the first JAX device call, "
            f"give the child JAX_PLATFORMS=cpu, or run the work in this "
            f"process")


# ----------------------------------------------------------------------
# published peaks, keyed by the exact `device_kind` string JAX reports
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_tflops: float      # dense bf16 matmul peak, TFLOP/s per chip
    hbm_gbps: float         # HBM bandwidth, GB/s per chip
    source: str


# An entry is added only after its key was READ on that chip (substring
# matching on marketing names is how a v5e came to be looked up as "v5e"
# while reporting "TPU v5 lite"). A device that is not here is an error
# for a benchmark and "no MFU gauge" for telemetry — never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_tflops=197.0, hbm_gbps=819.0,
        source="Google Cloud documentation, 'TPU v5e' (197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s); device_kind read on the chip, PR 21"),
}


def device_kind() -> str:
    return jax.devices()[0].device_kind


def device_peaks(kind: Optional[str] = None) -> DevicePeaks:
    """Peaks of `kind` (default: the live device). KeyError-style failure
    for a device nobody has measured on: an MFU against somebody else's
    peak is worse than no MFU."""
    kind = device_kind() if kind is None else kind
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise LookupError(
            f"no published peaks on file for device_kind {kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}). Add it to "
            f"deepspeed_tpu/platform/device.py::DEVICE_PEAKS with its "
            f"source, or pass telemetry.peak_tflops explicitly") from None


# Device memory the CPU harness pretends to have when a sizing decision
# needs a number (the CPU client reports no allocator stats). One v5e's
# worth, so CPU tests walk the same branches a chip run does.
CPU_TEST_HBM_BYTES = 16 * 2**30


def device_memory_bytes() -> int:
    """Per-device memory limit from the allocator's own stats. On a TPU an
    empty answer is an error (a sizing decision would otherwise rest on a
    guess); the CPU harness gets the stated test value."""
    from deepspeed_tpu.platform.accelerator import get_accelerator
    limit = int(get_accelerator().total_memory() or 0)
    if limit:
        return limit
    if on_tpu():
        raise RuntimeError(
            "the TPU runtime reported no memory_stats()['bytes_limit'] — "
            "refusing to size the offload tier against an assumed HBM")
    return CPU_TEST_HBM_BYTES
