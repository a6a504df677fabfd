"""Native op build system.

Analog of the reference's `op_builder/builder.py:102` (`OpBuilder` ABC with JIT
build at `:448`): compiles the C++ host libraries on first use with g++ and
loads them via ctypes. No CUDA/torch-extension machinery — the TPU compute path
is Pallas/XLA; native code here is host-side (AIO swap, CPU optimizers).
"""

import ctypes
import hashlib
import os
import pathlib
import platform
import subprocess
import threading

from deepspeed_tpu.utils.logging import logger

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
_CSRC = _REPO_ROOT / "csrc"
_BUILD_DIR = pathlib.Path(__file__).resolve().parent / "_native"
_LOCK = threading.Lock()
_LOADED = {}


def _host_cpu():
    """Identity of the CPU `-march=native` compiles for: architecture, model
    name and feature flags (Linux /proc/cpuinfo; elsewhere what `platform`
    knows)."""
    parts = [platform.machine(), platform.processor()]
    try:
        with open("/proc/cpuinfo") as f:
            seen = set()
            for line in f:
                field = line.split(":", 1)[0].strip()
                if field in ("model name", "flags", "Features") \
                        and field not in seen:
                    seen.add(field)
                    parts.append(line.strip())
    except OSError:
        pass
    return "|".join(parts)


class OpBuilder:
    """Base: named native library, lazily JIT-built and ctypes-loaded."""

    NAME = None
    SOURCES = ()

    def lib_path(self):
        return _BUILD_DIR / f"lib{self.NAME}.so"

    def is_compatible(self):
        return os.name == "posix"

    def sources(self):
        return [str(_CSRC / s) for s in self.SOURCES]

    FLAGS = ("-O3", "-march=native", "-fopenmp", "-fPIC", "-std=c++17")

    def build_key(self):
        """What a built library is a function of: the sources' bytes, the
        compiler flags, and — because of `-march=native` — the CPU that
        compiled it. A library found in the tree is reused only when its
        recorded key matches: the `.so` files are git-ignored but travel
        with a copied working tree, and one built for another host's CPU
        must be rebuilt, not loaded (mtime said "fresh" for those)."""
        h = hashlib.sha256(" ".join(self.FLAGS).encode())
        for src in self.sources():
            h.update(pathlib.Path(src).read_bytes())
        h.update(_host_cpu().encode())
        return h.hexdigest()

    def build(self, verbose=False):
        out = self.lib_path()
        stamp = out.with_suffix(".so.key")
        key = self.build_key()
        if out.exists() and stamp.exists() and stamp.read_text() == key:
            return out
        _BUILD_DIR.mkdir(parents=True, exist_ok=True)
        tmp = out.with_suffix(f".so.tmp{os.getpid()}")
        cmd = ["g++", *self.FLAGS, *self.sources(), "-shared", "-lpthread",
               "-o", str(tmp)]
        logger.info(f"building native op {self.NAME}: {' '.join(cmd)}")
        subprocess.run(cmd, check=True, capture_output=not verbose)
        os.replace(tmp, out)            # atomic: concurrent builders race safely
        stamp.write_text(key)
        return out

    def load(self, verbose=False):
        with _LOCK:
            if self.NAME in _LOADED:
                return _LOADED[self.NAME]
            path = self.build(verbose=verbose)
            lib = ctypes.CDLL(str(path))
            self.annotate(lib)
            _LOADED[self.NAME] = lib
            return lib

    def annotate(self, lib):
        pass


class AsyncIOBuilder(OpBuilder):
    """Reference `op_builder/async_io.py` role."""

    NAME = "dstpu_aio"
    SOURCES = ("aio/dstpu_aio.cpp",)

    def annotate(self, lib):
        lib.dstpu_aio_create.restype = ctypes.c_void_p
        lib.dstpu_aio_create.argtypes = [ctypes.c_int, ctypes.c_int]
        lib.dstpu_aio_create_ex.restype = ctypes.c_void_p
        lib.dstpu_aio_create_ex.argtypes = [ctypes.c_int, ctypes.c_int,
                                            ctypes.c_int, ctypes.c_int]
        lib.dstpu_aio_destroy.argtypes = [ctypes.c_void_p]
        for fn in (lib.dstpu_aio_pread, lib.dstpu_aio_pwrite):
            fn.restype = ctypes.c_int64
            fn.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,
                           ctypes.c_int64, ctypes.c_int64]
        lib.dstpu_aio_wait.restype = ctypes.c_int64
        lib.dstpu_aio_wait.argtypes = [ctypes.c_void_p]
        lib.dstpu_aio_pending.restype = ctypes.c_int64
        lib.dstpu_aio_pending.argtypes = [ctypes.c_void_p]


class CPUAdamBuilder(OpBuilder):
    """Reference `op_builder/cpu_adam.py` role (also carries Lion/Adagrad)."""

    NAME = "dstpu_cpu_optim"
    SOURCES = ("cpu_optim/dstpu_cpu_adam.cpp",)

    def annotate(self, lib):
        lib.dstpu_cpu_adam_step.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_float, ctypes.c_float, ctypes.c_int, ctypes.c_int64,
            ctypes.c_int]
        lib.dstpu_cpu_lion_step.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float]
        lib.dstpu_cpu_adagrad_step.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_float]
        lib.dstpu_fp32_to_bf16.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                           ctypes.c_int64]


class DataLoaderBuilder(OpBuilder):
    """Native prefetching token-dataset loader (the torch-DataLoader-worker
    role of the reference's `runtime/dataloader.py`)."""

    NAME = "dstpu_dataloader"
    SOURCES = ("dataloader/dstpu_dataloader.cpp",)

    def annotate(self, lib):
        lib.dstpu_dl_create.restype = ctypes.c_void_p
        lib.dstpu_dl_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
            ctypes.c_int, ctypes.c_uint64, ctypes.c_int]
        lib.dstpu_dl_num_tokens.restype = ctypes.c_int64
        lib.dstpu_dl_num_tokens.argtypes = [ctypes.c_void_p]
        lib.dstpu_dl_next.restype = ctypes.c_int64
        lib.dstpu_dl_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.dstpu_dl_destroy.argtypes = [ctypes.c_void_p]


ALL_OPS = {b.NAME: b for b in (AsyncIOBuilder(), CPUAdamBuilder(),
                               DataLoaderBuilder())}


def get_op_builder(name):
    return ALL_OPS[name]
