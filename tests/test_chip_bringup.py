"""What PR 21's bring-up promises, checked where a CPU can check it: nothing
on the main path hides the device, one process per chip, a placeable compile
cache. (What only a chip can check lives in `chip_smoke.py` and the `-m tpu`
lane.)"""

import logging
import os
import subprocess
import sys

import jax
import pytest

from deepspeed_tpu.platform import device as dev

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, **env):
    full = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run(argv, cwd=REPO, env=full, capture_output=True,
                          text=True, timeout=300)


# ----------------------------------------------------------------------
# compile cache
# ----------------------------------------------------------------------


@pytest.fixture
def _restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_placed_from_outside_is_left_alone(
        monkeypatch, _restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert dev.ensure_compile_cache() == "/some/dir"
    # JAX reads the variable itself (at import); the function sets nothing
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_defaults_to_fixed_in_tree_path(
        monkeypatch, _restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert dev.ensure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert dev.ensure_compile_cache() == want          # idempotent


# ----------------------------------------------------------------------
# no assumed device
# ----------------------------------------------------------------------


def test_peaks_are_keyed_by_exact_device_kind():
    v5e = dev.device_peaks("TPU v5 lite")
    assert (v5e.bf16_tflops, v5e.hbm_gbps) == (197.0, 819.0) and v5e.source
    for unknown in ("cpu", "TPU v5e", "v5e", ""):     # no substring matching
        with pytest.raises(LookupError, match="no published peaks"):
            dev.device_peaks(unknown)
    with pytest.raises(LookupError):                  # the live CPU device
        dev.device_peaks()


def test_interpret_rule_and_memory_on_the_cpu_harness():
    assert dev.pallas_interpret() and not dev.on_tpu()
    assert dev.device_memory_bytes() == dev.CPU_TEST_HBM_BYTES


def test_accelerator_env_may_narrow_never_contradict(monkeypatch):
    from deepspeed_tpu.platform import accelerator
    monkeypatch.setenv("DSTPU_ACCELERATOR", "tpu")
    accelerator._probe.cache_clear()
    try:
        with pytest.raises(ValueError, match="contradicts"):
            accelerator._probe()
        monkeypatch.setenv("DSTPU_ACCELERATOR", "cpu")
        assert accelerator._probe().device_name() == "cpu"
    finally:
        accelerator._probe.cache_clear()


def test_init_mesh_names_the_devices_it_leaves_idle(devices8):
    from deepspeed_tpu.comm import mesh as mesh_mod
    from deepspeed_tpu.config.core import MeshConfig
    seen = []
    handler = logging.Handler()
    handler.emit = lambda rec: seen.append(rec)
    log = logging.getLogger("deepspeed_tpu")    # propagate=False: hook it
    log.addHandler(handler)
    try:
        mesh_mod.init_mesh(MeshConfig(data=2))
    finally:
        log.removeHandler(handler)
    idle = [r.getMessage() for r in seen if r.levelno == logging.WARNING]
    assert len(idle) == 1 and "uses 2 of 8 devices" in idle[0] \
        and "6 left idle" in idle[0]


# ----------------------------------------------------------------------
# one process per chip
# ----------------------------------------------------------------------


def test_spawners_refuse_when_the_parent_holds_the_chip(monkeypatch):
    assert not dev.holds_accelerator()                # CPU harness: never
    dev.refuse_spawn_if_holding_accelerator("test")   # -> no-op
    monkeypatch.setattr(dev, "holds_accelerator", lambda: True)
    with pytest.raises(RuntimeError, match="one process at a time"):
        dev.refuse_spawn_if_holding_accelerator("test", {})
    # a child pinned to the CPU needs no chip
    dev.refuse_spawn_if_holding_accelerator("test", {"JAX_PLATFORMS": "cpu"})

    from deepspeed_tpu.serving.remote_replica import ReplicaProcess
    proc = ReplicaProcess(factory="deepspeed_tpu.testing.fabric:"
                          "tiny_serving_engine", replica_id="r0",
                          env={"JAX_PLATFORMS": "tpu"})
    with pytest.raises(RuntimeError, match=r"ReplicaProcess\(r0\)"):
        proc.spawn()
    assert proc.proc is None


def test_chip_smoke_exits_nonzero_without_a_tpu():
    out = _run([sys.executable, "chip_smoke.py"])
    assert out.returncode == 1, (out.stdout[-500:], out.stderr[-2000:])
    assert "needs a TPU, JAX found platform 'cpu'" in out.stdout
    assert '"ok"' not in out.stdout                   # no result line


def test_tpu_lane_fails_instead_of_skipping_without_a_tpu():
    out = _run([sys.executable, "-m", "pytest", "tests/test_tpu_kernels.py",
                "-q", "-p", "no:cacheprovider", "-p", "no:xdist",
                "-k", "test_norms_compiled"], DSTPU_RUN_TPU_TESTS="1")
    assert out.returncode != 0
    assert "JAX found no TPU" in out.stdout and "skipped" not in out.stdout
