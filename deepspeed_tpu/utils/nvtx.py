"""Profiler range annotations — analog of the reference's nvtx shim
(`deepspeed/utils/nvtx.py` `instrument_w_nvtx`, accelerator
`range_push/range_pop`). On TPU these map to `jax.profiler` trace
annotations, which show up in xprof/TensorBoard traces.

Import-guarded: when `jax.profiler.TraceAnnotation` is unavailable (minimal
environments, stripped jax builds) every entry point is a hard no-op, so the
telemetry span layer (`telemetry/spans.py`) stays safe to call anywhere."""

import contextlib
import functools

try:
    import jax
    _TraceAnnotation = jax.profiler.TraceAnnotation
except Exception:          # pragma: no cover - depends on the environment
    _TraceAnnotation = None

# LIFO of open ranges so range_pop() matches the reference accelerator API
# (`accelerator/abstract_accelerator.py` range_pop takes no arguments).
_RANGE_STACK = []


def range_push(msg):
    """Start a named range (reference accelerator.range_push)."""
    if _TraceAnnotation is None:
        return None
    t = _TraceAnnotation(msg)
    t.__enter__()
    _RANGE_STACK.append(t)
    return t


def range_pop(t=None):
    """End a range started with range_push. With no argument, pops the most
    recently pushed range (reference API); a handle may also be passed."""
    if t is None:
        if not _RANGE_STACK:
            return
        t = _RANGE_STACK.pop()
    else:
        # remove the handle wherever it sits so a later argless pop never
        # exits it a second time
        try:
            _RANGE_STACK.remove(t)
        except ValueError:
            pass
    t.__exit__(None, None, None)


def instrument_w_nvtx(func):
    """Decorator: wrap `func` in a named profiler range (reference
    `utils/nvtx.py:instrument_w_nvtx`); returns `func` unchanged when the
    profiler is unavailable."""
    if _TraceAnnotation is None:
        return func

    @functools.wraps(func)
    def wrapped(*args, **kwargs):
        with _TraceAnnotation(func.__qualname__):
            return func(*args, **kwargs)

    return wrapped


def annotate(name, **attrs):
    """Context manager for a named trace range (null when unavailable).
    `attrs` are appended to the name as `#key=value#` by the profiler, and
    only while a session runs: outside one they cost nothing."""
    if _TraceAnnotation is None:
        return contextlib.nullcontext()
    return _TraceAnnotation(name, **attrs)
