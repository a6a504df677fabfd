"""Pluggable collective layer — the comm spine.

One registry of the five collective primitives (all_reduce / all_gather /
reduce_scatter / all_to_all / ppermute), each usable two ways:

  * **eagerly** over global arrays — `run(op, x, ...)` dispatches the eager
    implementation registered by `comm/comm.py` (its jitted `shard_map`
    wrappers), which carries measured wall-time into the stats;
  * **inside `shard_map` bodies** — the instrumented in-jit wrappers below
    (`psum`, `pmean`, `all_gather`, `reduce_scatter`, `all_to_all`,
    `ppermute`) call straight into `jax.lax` and record *trace-time payload
    bytes*: the bytes one participant hands to the wire per execution of the
    traced program at that call site. Re-running an already-compiled program
    records nothing new — `stats.reset()` then retrace (``jit(...).lower``)
    to re-measure, which is what `Engine.lower_train_step` and
    tests/test_comm_volume.py do. Collectives inside `lax.scan` bodies trace
    once but execute every iteration; pass ``repeats=n_iters`` so the
    accounting matches (parallel/pipeline.py does this for its per-tick
    ppermute handoffs).

Byte convention (kept deliberately simple so ratios are exact): recorded
bytes = payload bytes of the arrays a single participant hands to the
underlying lax op, times ``repeats``; axis size 1 records 0 (no wire). No
hop-count or (n-1)/n algorithm factors are applied — absolute numbers are
payload-proportional, and compressed-vs-fp ratios are exact.

Per-op stats mirror into the telemetry registry once a `Telemetry` object is
bound (`comm/<op>_bytes` + `comm/<op>_calls` counters, `comm/<op>_ms`
histograms — catalog rows in docs/profiling.md; the training engine binds
its telemetry at construction).

**Transform hooks** let compression plug in under every consumer once: a
`WireTransform` is an encode/decode pair over f32 payloads. Registered
transforms:

  * ``"none"``   — identity (fp32 wire);
  * ``"int8"``   — ZeRO++ qwZ/qgZ groupwise symmetric int8 (scale =
    max|x|/127 per group), the same single-definition quant whose on-chip
    form lives in `ops/pallas/quant.py` and whose collective use lives in
    `runtime/quantized_collectives.py` (that module now imports these
    definitions);
  * ``"onebit"`` — 1-bit sign+mean-magnitude compression (the 1-bit Adam
    wire format, `runtime/compressed_grads.py`'s `_sign_compress` rule),
    packed 8 signs/byte; used with error feedback via
    `compressed_all_reduce(..., transform="onebit", err=...)`.
"""

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


OP_NAMES = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
            "ppermute")

TRANSFORM_NAMES = ("none", "int8", "onebit")

DEFAULT_GROUP_SIZE = 256  # qwZ/qgZ quantization group (reference default)


# ------------------------------------------------------------------
# Per-op stats, mirrored into telemetry when bound
# ------------------------------------------------------------------


class CommStats:
    """Per-op {calls, bytes, seconds} accumulator.

    `record` is called from two places: the eager facade (comm/comm.py)
    with measured wall-time, and the in-jit wrappers below at trace time
    with `seconds=None` (compiled collectives have no per-op host timer).
    When a `Telemetry` object is bound the same records flow into its
    registry as `comm/<op>_bytes` / `comm/<op>_calls` counters and
    `comm/<op>_ms` histograms.
    """

    def __init__(self):
        self._records: Dict[str, Dict[str, float]] = {}
        self._telemetry = None

    def bind_telemetry(self, telemetry):
        """Mirror subsequent records into `telemetry`'s registry."""
        self._telemetry = telemetry

    def record(self, op_name, nbytes, seconds=None, calls=1):
        rec = self._records.setdefault(
            op_name, {"calls": 0, "bytes": 0, "seconds": 0.0})
        rec["calls"] += int(calls)
        rec["bytes"] += int(nbytes)
        if seconds is not None:
            rec["seconds"] += float(seconds)
        t = self._telemetry
        if t is not None:
            t.inc(f"comm/{op_name}_bytes", int(nbytes))
            t.inc(f"comm/{op_name}_calls", int(calls))
            if seconds is not None:
                t.observe(f"comm/{op_name}_ms", float(seconds) * 1e3)

    def bytes_of(self, op_name):
        return int(self._records.get(op_name, {}).get("bytes", 0))

    def calls_of(self, op_name):
        return int(self._records.get(op_name, {}).get("calls", 0))

    def total_bytes(self):
        return sum(int(r["bytes"]) for r in self._records.values())

    def snapshot(self):
        return {op: dict(rec) for op, rec in self._records.items()}

    def reset(self):
        self._records.clear()


stats = CommStats()


def _payload_bytes(tree):
    """Static payload bytes of a pytree of (possibly traced) arrays."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", ())
        size = 1
        for d in shape:
            size *= int(d)
        total += size * jnp.dtype(getattr(leaf, "dtype", jnp.float32)).itemsize
    return total


def _axis_size(axis_name):
    """Size of a named axis (or tuple of axes) inside a shard_map trace."""
    return int(jax.lax.psum(1, axis_name))


# ------------------------------------------------------------------
# Op registry: one name → eager + in-jit implementations
# ------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    name: str
    lax: Callable        # in-shard_map implementation (instrumented)
    eager: Optional[Callable] = None   # global-array facade implementation


_OPS: Dict[str, CollectiveOp] = {}


def register_op(name, *, lax, eager=None):
    """Register (or re-register) a collective under `name`.

    `lax` is the in-shard_map form; `eager` the global-array facade form
    (comm/comm.py registers its timed wrappers at import). Re-registration
    replaces the entry — transform/logging wrappers plug in under every
    consumer by wrapping here once.
    """
    op = CollectiveOp(name=name, lax=lax, eager=eager)
    _OPS[name] = op
    return op


def get_op(name):
    if name not in _OPS:
        raise ValueError(
            f"unknown collective op {name!r}; registered ops: "
            f"{sorted(_OPS)}")
    return _OPS[name]


def op_names():
    return tuple(sorted(_OPS))


def collective(name, *args, **kwargs):
    """In-jit dispatch through the registry (use inside shard_map bodies)."""
    return get_op(name).lax(*args, **kwargs)


def run(name, *args, **kwargs):
    """Eager dispatch through the registry (global arrays in, global out)."""
    op = get_op(name)
    if op.eager is None:
        raise ValueError(
            f"collective op {name!r} has no eager implementation; "
            "use it inside a shard_map body via collective()")
    return op.eager(*args, **kwargs)


# ------------------------------------------------------------------
# Instrumented in-jit primitives (use these inside shard_map bodies)
# ------------------------------------------------------------------


def psum(x, axis_name, *, repeats=1):
    if _axis_size(axis_name) > 1:
        stats.record("all_reduce", _payload_bytes(x) * repeats, calls=repeats)
    return jax.lax.psum(x, axis_name)


def pmean(x, axis_name, *, repeats=1):
    if _axis_size(axis_name) > 1:
        stats.record("all_reduce", _payload_bytes(x) * repeats, calls=repeats)
    return jax.lax.pmean(x, axis_name)


def all_gather(x, axis_name, *, axis=0, tiled=False, repeats=1):
    if _axis_size(axis_name) > 1:
        stats.record("all_gather", _payload_bytes(x) * repeats, calls=repeats)
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, *, scatter_dimension=0, tiled=True,
                   repeats=1):
    if _axis_size(axis_name) > 1:
        stats.record("reduce_scatter", _payload_bytes(x) * repeats,
                     calls=repeats)
    return jax.lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=tiled)


def all_to_all(x, axis_name, *, split_axis, concat_axis, tiled=False,
               repeats=1):
    if _axis_size(axis_name) > 1:
        stats.record("all_to_all", _payload_bytes(x) * repeats, calls=repeats)
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=tiled)


def ppermute(x, axis_name, perm, *, repeats=1):
    if _axis_size(axis_name) > 1:
        stats.record("ppermute", _payload_bytes(x) * repeats, calls=repeats)
    return jax.lax.ppermute(x, axis_name, perm)


register_op("all_reduce", lax=psum)
register_op("all_gather", lax=all_gather)
register_op("reduce_scatter", lax=reduce_scatter)
register_op("all_to_all", lax=all_to_all)
register_op("ppermute", lax=ppermute)


# ------------------------------------------------------------------
# Wire transforms (compression hooks)
# ------------------------------------------------------------------


def group_quant_int8(x, group_size=DEFAULT_GROUP_SIZE):
    """x: [..., D] → (int8 [..., D], f32 scales [..., D//group_size]).

    Groupwise symmetric quant, scale = max|group|/127 — the ZeRO++ qwZ/qgZ
    rule and the same semantics `ops/pallas/quant.py` implements on-chip.
    This is the single definition; `runtime/quantized_collectives.py`
    imports it.
    """
    D = x.shape[-1]
    g = max(1, D // group_size) if D % group_size == 0 else 1
    gs = D // g
    xg = x.astype(jnp.float32).reshape(x.shape[:-1] + (g, gs))
    amax = jnp.max(jnp.abs(xg), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xg / scale[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(x.shape), scale


def group_dequant_int8(q, scale, dtype):
    """Inverse of `group_quant_int8` (reduction happens in f32 downstream)."""
    D = q.shape[-1]
    g = scale.shape[-1]
    gs = D // g
    x = q.astype(jnp.float32).reshape(q.shape[:-1] + (g, gs)) * scale[..., None]
    return x.reshape(q.shape).astype(dtype)


def _pack_signs(bits):
    """bool [..., M] with M % 8 == 0 → uint8 [..., M//8]."""
    b = bits.reshape(bits.shape[:-1] + (-1, 8)).astype(jnp.int32)
    weights = (1 << jnp.arange(8, dtype=jnp.int32))
    return jnp.sum(b * weights, axis=-1).astype(jnp.uint8)


def _unpack_signs(packed, numel):
    """uint8 [..., P] → f32 [..., numel] of ±1 (bit set → +1)."""
    bits = (packed[..., :, None].astype(jnp.int32)
            >> jnp.arange(8, dtype=jnp.int32)) & 1
    flat = bits.reshape(packed.shape[:-1] + (-1,))[..., :numel]
    return (flat * 2 - 1).astype(jnp.float32)


def onebit_encode(x):
    """Flat f32 [N] → (packed signs uint8 [ceil(N/8)], scale f32 [1]).

    sign(x) * mean|x| — the 1-bit Adam compression rule
    (`runtime/compressed_grads.py`'s `_sign_compress`), with sign(0) → +1 so
    every value packs to exactly one bit.
    """
    numel = x.shape[0]
    scale = jnp.mean(jnp.abs(x))[None]
    pad = (-numel) % 8
    bits = x >= 0
    if pad:
        bits = jnp.concatenate([bits, jnp.zeros((pad,), bool)])
    return _pack_signs(bits), scale


def onebit_decode(packed, scale, numel):
    """Inverse of `onebit_encode`: ±scale values, f32 [..., numel]."""
    return _unpack_signs(packed, numel) * scale[..., :1]


@dataclasses.dataclass(frozen=True)
class WireTransform:
    """Encode/decode pair over f32 payloads.

    encode: f32 [..., D] → (payloads: tuple of arrays, meta: dict of static
    values); every payload keeps the input's leading dims so the collective
    moves them uniformly. decode: (payloads, meta) → f32 [..., D].
    """
    name: str
    encode: Callable[[jnp.ndarray], Tuple[Tuple[jnp.ndarray, ...], dict]]
    decode: Callable[[Tuple[jnp.ndarray, ...], dict], jnp.ndarray]


def _none_encode(x):
    return (x.astype(jnp.float32),), {}


def _none_decode(payloads, meta):
    return payloads[0]


def _int8_encode(x, group_size=DEFAULT_GROUP_SIZE):
    q, scale = group_quant_int8(x, group_size)
    return (q, scale), {}


def _int8_decode(payloads, meta):
    q, scale = payloads
    return group_dequant_int8(q, scale, jnp.float32)


def _onebit_encode_t(x):
    packed, scale = onebit_encode(x.reshape(-1))
    return (packed, scale), {"numel": int(x.shape[-1])}


def _onebit_decode_t(payloads, meta):
    packed, scale = payloads
    return onebit_decode(packed, scale, meta["numel"])


_TRANSFORMS: Dict[str, WireTransform] = {}


def register_transform(transform):
    _TRANSFORMS[transform.name] = transform
    return transform


def get_transform(name, group_size=DEFAULT_GROUP_SIZE):
    if name == "int8" and group_size != DEFAULT_GROUP_SIZE:
        return WireTransform(
            name="int8",
            encode=lambda x: _int8_encode(x, group_size),
            decode=_int8_decode)
    if name not in _TRANSFORMS:
        raise ValueError(
            f"unknown wire transform {name!r}; registered transforms: "
            f"{sorted(_TRANSFORMS)}")
    return _TRANSFORMS[name]


def transform_names():
    return tuple(sorted(_TRANSFORMS))


register_transform(WireTransform("none", _none_encode, _none_decode))
register_transform(WireTransform("int8", _int8_encode, _int8_decode))
register_transform(WireTransform("onebit", _onebit_encode_t,
                                 _onebit_decode_t))


# ------------------------------------------------------------------
# Composite compressed collectives (built on the instrumented primitives,
# inside shard_map bodies)
# ------------------------------------------------------------------


def transform_all_gather(x, axis_name, transform="int8",
                         group_size=DEFAULT_GROUP_SIZE, out_dtype=None):
    """All-gather with an encoded wire: local [...] → stacked [n, ...].

    The payloads (e.g. int8 values + f32 group scales) cross the wire;
    decode happens on the receiver. ``transform="none"`` degenerates to a
    plain instrumented all_gather.
    """
    out_dtype = out_dtype or x.dtype
    if transform == "none":
        return all_gather(x.astype(out_dtype), axis_name)
    t = get_transform(transform, group_size)
    flat = x.reshape(-1)
    payloads, meta = t.encode(flat)
    gathered = tuple(all_gather(p, axis_name) for p in payloads)
    deq = t.decode(gathered, meta)                    # [n, numel] f32
    n = deq.shape[0]
    return deq.reshape((n,) + x.shape).astype(out_dtype)


def transform_reduce_scatter(x, axis_name, transform="int8",
                             group_size=DEFAULT_GROUP_SIZE):
    """Reduce-scatter with an encoded wire: flat [N] (N % n == 0) → [N/n] f32
    sum. Encoded chunks move via all_to_all; receivers decode and reduce in
    f32 (the qgZ dequant-reduce). Supported transforms: none, int8 — onebit
    has no scatter form (use `compressed_all_reduce` with error feedback).
    """
    if transform not in ("none", "int8"):
        raise ValueError(
            f"transform_reduce_scatter supports transforms ('none', 'int8'); "
            f"got {transform!r}")
    n = _axis_size(axis_name)
    N = x.shape[0]
    if N % n != 0:
        raise ValueError(
            f"transform_reduce_scatter: leading dim {N} not divisible by "
            f"axis size {n}")
    if transform == "none":
        return reduce_scatter(x.astype(jnp.float32), axis_name)
    t = get_transform(transform, group_size)
    chunks = x.astype(jnp.float32).reshape(n, N // n)
    payloads, meta = t.encode(chunks)
    received = tuple(
        all_to_all(p, axis_name, split_axis=0, concat_axis=0)
        for p in payloads)
    deq = t.decode(received, meta)                    # [n, N//n] f32
    return jnp.sum(deq, axis=0)


def transform_all_to_all(x, axis_name, *, split_axis, concat_axis,
                         tiled=True, transform="none",
                         group_size=DEFAULT_GROUP_SIZE, out_dtype=None):
    """All-to-all with an encoded wire — the MoE expert-dispatch primitive.

    ``transform="none"`` degenerates to the plain instrumented all_to_all.
    With ``"int8"`` the groupwise-quantized payload (int8 values + f32 group
    scales, both keeping the input's leading dims) crosses the wire and the
    receiver dequantizes — the ZeRO++ qgZ rule applied to activation dispatch.
    ``"onebit"`` is rejected: sign+mean-magnitude destroys routed activations
    (it is a gradient wire with error feedback, not an activation codec).
    """
    if transform == "onebit":
        raise ValueError(
            "transform_all_to_all does not support 'onebit' — the 1-bit wire "
            "is an error-feedback gradient codec, not an activation codec; "
            "use transform='int8' for compressed expert dispatch")
    out_dtype = out_dtype or x.dtype
    if transform == "none":
        return all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)
    t = get_transform(transform, group_size)
    payloads, meta = t.encode(x)
    moved = tuple(
        all_to_all(p, axis_name, split_axis=split_axis,
                   concat_axis=concat_axis, tiled=tiled)
        for p in payloads)
    return t.decode(moved, meta).astype(out_dtype)


def compressed_all_reduce(x, axis_name, transform="none",
                          group_size=DEFAULT_GROUP_SIZE, err=None):
    """SUM over `axis_name` with a compressed wire (inside shard_map).

    ``"none"``/``"int8"`` run the 2-hop reduce-scatter + all-gather scheme
    (the qgZ structure); ``"onebit"`` runs the 1-bit Adam error-feedback
    reduce — requires ``err`` (the per-rank f32 compression residual, same
    shape as ``x``) and returns ``(sum, new_err)`` instead of the bare sum.

    Axis size 1 is the identity (onebit still returns its residual pair).
    """
    if transform not in TRANSFORM_NAMES:
        raise ValueError(
            f"compressed_all_reduce supports transforms {TRANSFORM_NAMES}; "
            f"got {transform!r}")
    if transform == "onebit":
        if err is None:
            raise ValueError(
                "compressed_all_reduce(transform='onebit') needs `err`, the "
                "error-feedback residual carried between steps (init zeros)")
        return _onebit_allreduce(x, axis_name, err)
    n = _axis_size(axis_name)
    if n == 1:
        return x.astype(jnp.float32)
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    numel = flat.shape[0]
    pad = (-numel) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    mine = transform_reduce_scatter(flat, axis_name, transform, group_size)
    full = transform_all_gather(mine, axis_name, transform, group_size,
                                out_dtype=jnp.float32)
    return full.reshape(-1)[:numel].reshape(shape)


def _onebit_allreduce(x, axis_name, err):
    """1-bit error-feedback allreduce: compensate → sign+scale → gather →
    decode+sum. The residual (what compression lost this step) feeds back
    next step, keeping the long-run mean unbiased — the 1-bit Adam scheme.
    Wire payload: 1 bit per element + one f32 scale per rank.
    """
    c = x.astype(jnp.float32) + err.astype(jnp.float32)
    shape = c.shape
    flat = c.reshape(-1)
    numel = flat.shape[0]
    packed, scale = onebit_encode(flat)
    decoded_self = onebit_decode(packed, scale, numel)
    new_err = (flat - decoded_self).reshape(shape)
    if _axis_size(axis_name) == 1:
        return decoded_self.reshape(shape), new_err
    p_all = all_gather(packed, axis_name)             # [n, P] uint8
    s_all = all_gather(scale, axis_name)              # [n, 1] f32
    vals = onebit_decode(p_all, s_all, numel)         # [n, numel] f32
    return jnp.sum(vals, axis=0).reshape(shape), new_err


def onebit_error_init(tree):
    """Zero error-feedback residuals matching a grad pytree (f32)."""
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32), tree)
