"""Unit tests for the pluggable collective layer (`comm/collectives.py`).

The comm spine's contracts, each pinned here: one op registry serving
eager AND in-shard_map callers, trace-time byte accounting (with
`repeats` for scan bodies), telemetry mirroring (both from in-jit
records and from the eager `CommsLogger`), the wire transforms
(none/int8/onebit) with their error properties, and the composite
`compressed_all_reduce` used by the engine's explicit grad-reduce.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm import collectives as coll
from deepspeed_tpu.comm import comm
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.config.core import MeshConfig
from jax import shard_map


def _mk_mesh(**axes):
    mesh_mod._CURRENT_MESH = None
    mesh_mod._CURRENT_SPEC = None
    return mesh_mod.init_mesh(MeshConfig(data=axes.get("data", 1),
                                         tensor=axes.get("tensor", 1),
                                         sequence=axes.get("sequence", 1),
                                         expert=axes.get("expert", 1),
                                         pipe=axes.get("pipe", 1)))


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------


def test_registry_covers_op_set_and_errors_list_supported():
    assert set(coll.OP_NAMES) <= set(coll.op_names())
    with pytest.raises(ValueError, match="registered ops"):
        coll.get_op("broadcast")
    # ppermute has no eager (global-array) form: run() must say so
    with pytest.raises(ValueError, match="no eager implementation"):
        coll.run("ppermute", jnp.zeros((4,)), "data", [(0, 1)])
    with pytest.raises(ValueError, match="registered transforms"):
        coll.get_transform("fp4")
    assert set(coll.TRANSFORM_NAMES) <= set(coll.transform_names())


def test_eager_run_dispatches_to_comm_facade():
    _mk_mesh(data=8)
    x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    coll.stats.reset()
    out = coll.run("all_reduce", x)
    ref = comm.all_reduce(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert coll.stats.bytes_of("all_reduce") > 0


# ----------------------------------------------------------------------
# stats: trace-time accounting, repeats, telemetry mirror
# ----------------------------------------------------------------------


class _TelemetryStub:
    """CommStats only needs inc/observe; record what flows through."""

    def __init__(self):
        self.counters, self.observations = {}, {}

    def inc(self, name, n=1.0):
        self.counters[name] = self.counters.get(name, 0) + n

    def observe(self, name, value):
        self.observations.setdefault(name, []).append(value)


def test_stats_accumulate_snapshot_reset_and_mirror():
    s = coll.CommStats()
    t = _TelemetryStub()
    s.bind_telemetry(t)
    s.record("all_reduce", 1000)
    s.record("all_reduce", 500, seconds=0.002, calls=2)
    s.record("ppermute", 64)
    assert s.bytes_of("all_reduce") == 1500
    assert s.calls_of("all_reduce") == 3
    assert s.total_bytes() == 1564
    snap = s.snapshot()
    assert snap["all_reduce"]["seconds"] == pytest.approx(0.002)
    assert t.counters["comm/all_reduce_bytes"] == 1500
    assert t.counters["comm/ppermute_calls"] == 1
    # only timed (eager) records land in the ms histogram
    assert t.observations["comm/all_reduce_ms"] == [pytest.approx(2.0)]
    s.reset()
    assert s.snapshot() == {} and s.total_bytes() == 0


def test_trace_time_bytes_with_repeats_and_no_double_count():
    mesh = _mk_mesh(data=8)

    def body(x):
        return coll.psum(x, "data", repeats=3)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("data"),
                           out_specs=P(), check_vma=False))
    x = jnp.ones((8, 16), jnp.float32)
    coll.stats.reset()
    lowered = fn.lower(x)           # trace → 3 repeats of [16] f32
    assert coll.stats.bytes_of("all_reduce") == 16 * 4 * 3
    assert coll.stats.calls_of("all_reduce") == 3
    lowered.compile()(x)            # executing records nothing new
    fn(x)
    assert coll.stats.bytes_of("all_reduce") == 16 * 4 * 3


def test_axis_size_one_records_no_wire_bytes():
    mesh = _mk_mesh(data=1)
    fn = jax.jit(shard_map(lambda x: coll.psum(x, "data"), mesh=mesh,
                           in_specs=P("data"), out_specs=P(),
                           check_vma=False))
    coll.stats.reset()
    fn.lower(jnp.ones((1, 8), jnp.float32))
    assert coll.stats.bytes_of("all_reduce") == 0


def test_comms_logger_append_mirrors_into_facade_stats():
    t = _TelemetryStub()
    coll.stats.reset()
    coll.stats.bind_telemetry(t)
    try:
        comm.comms_logger.append("all_gather", 4096, 0.003)
    finally:
        coll.stats.bind_telemetry(None)
    assert coll.stats.bytes_of("all_gather") == 4096
    assert coll.stats.snapshot()["all_gather"]["seconds"] == \
        pytest.approx(0.003)
    assert t.counters["comm/all_gather_bytes"] == 4096
    assert t.observations["comm/all_gather_ms"] == [pytest.approx(3.0)]


# ----------------------------------------------------------------------
# wire transforms
# ----------------------------------------------------------------------


def test_group_quant_int8_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3, (4, 512)), jnp.float32)
    q, scale = coll.group_quant_int8(x, group_size=256)
    assert q.dtype == jnp.int8 and scale.shape == (4, 2)
    deq = coll.group_dequant_int8(q, scale, jnp.float32)
    # symmetric rounding: per-element error <= scale/2 = max|group|/254
    bound = float(jnp.max(scale)) / 2 + 1e-7
    assert float(jnp.max(jnp.abs(deq - x))) <= bound


def test_onebit_encode_decode_roundtrip():
    x = jnp.asarray([1.5, -0.5, 2.0, -3.0, 0.0, 4.0], jnp.float32)
    packed, scale = coll.onebit_encode(x)
    assert packed.dtype == jnp.uint8 and packed.shape == (1,)  # 6 bits → 1B
    decoded = coll.onebit_decode(packed, scale, 6)
    mean_mag = float(jnp.mean(jnp.abs(x)))
    signs = np.asarray([1, -1, 1, -1, 1, 1], np.float32)  # sign(0) → +1
    np.testing.assert_allclose(np.asarray(decoded), signs * mean_mag,
                               rtol=1e-6)


def test_register_transform_plugs_in_under_every_consumer():
    mesh = _mk_mesh(data=4)
    # a custom wire: fp16 truncation — registered once, usable by name
    t = coll.WireTransform(
        "fp16-test",
        encode=lambda x: ((x.astype(jnp.float16),), {}),
        decode=lambda p, m: p[0].astype(jnp.float32))
    coll.register_transform(t)
    try:
        fn = jax.jit(shard_map(
            lambda x: coll.transform_all_gather(x, "data", "fp16-test"),
            mesh=mesh, in_specs=P(None, "data"), out_specs=P(None, None),
            check_vma=False))
        x = jnp.arange(16, dtype=jnp.float32).reshape(1, 16) / 8
        out = fn(x)
        assert out.shape == (4, 1, 4)
        np.testing.assert_allclose(np.asarray(out).reshape(-1),
                                   np.asarray(x).reshape(-1), rtol=1e-3)
    finally:
        coll._TRANSFORMS.pop("fp16-test", None)


# ----------------------------------------------------------------------
# composite compressed collectives (inside shard_map)
# ----------------------------------------------------------------------


def test_transform_reduce_scatter_matches_psum_scatter():
    mesh = _mk_mesh(data=8)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 1, (8, 1024)), jnp.float32)

    def body(transform):
        def run(v):
            return coll.transform_reduce_scatter(v.reshape(-1), "data",
                                                 transform)
        return jax.jit(shard_map(run, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data"), check_vma=False))

    ref = np.asarray(body("none")(x))
    got = np.asarray(body("int8")(x))
    assert ref.shape == got.shape == (1024,)
    exact = np.asarray(x).sum(0).reshape(-1)[:128 * 8]
    np.testing.assert_allclose(ref[:exact.size], exact, rtol=1e-5, atol=1e-5)
    # int8 wire: error bounded by one quant step per contribution
    np.testing.assert_allclose(got, ref, atol=8 * 0.02, rtol=0.05)
    with pytest.raises(ValueError, match="supports transforms"):
        coll.transform_reduce_scatter(jnp.zeros((8,)), "data", "onebit")


def test_compressed_all_reduce_matches_psum():
    mesh = _mk_mesh(data=8)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(0, 1, (8, 37)), jnp.float32)  # odd numel → pad

    def build(transform):
        def run(v):
            return coll.compressed_all_reduce(v[0], "data", transform)
        return jax.jit(shard_map(run, mesh=mesh, in_specs=P("data"),
                                 out_specs=P(), check_vma=False))

    ref = np.asarray(x).sum(0)
    none = np.asarray(build("none")(x))
    int8 = np.asarray(build("int8")(x))
    np.testing.assert_allclose(none, ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(int8, ref, atol=8 * 0.02, rtol=0.05)


def test_onebit_allreduce_error_feedback_and_exact_case():
    mesh = _mk_mesh(data=8)

    def run(v, e):
        return coll.compressed_all_reduce(v[0], "data", "onebit", err=e[0])

    fn = jax.jit(shard_map(run, mesh=mesh, in_specs=(P("data"), P("data")),
                           out_specs=(P(), P("data")), check_vma=False))
    # constant positive input: sign=+1, scale=mean|x|=c → exact sum, zero
    # residual
    c = jnp.full((8, 16), 0.25, jnp.float32)
    e0 = jnp.zeros((8, 16), jnp.float32)
    total, err = fn(c, e0)
    np.testing.assert_allclose(np.asarray(total), 8 * 0.25, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(err), 0.0, atol=1e-7)
    # general input: residual carries exactly what compression lost
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1, (8, 16)), jnp.float32)
    total, err = fn(x, e0)
    packed, scale = coll.onebit_encode(jnp.asarray(np.asarray(x)[0]))
    decoded0 = coll.onebit_decode(packed, scale, 16)
    # err comes back under P("data"): rank 0's residual is the first 16
    np.testing.assert_allclose(np.asarray(err).reshape(-1)[:16],
                               np.asarray(x)[0] - np.asarray(decoded0),
                               rtol=1e-5, atol=1e-6)


def test_compressed_all_reduce_validation():
    with pytest.raises(ValueError, match="supports transforms"):
        coll.compressed_all_reduce(jnp.zeros((4,)), "data", "fp4")
    with pytest.raises(ValueError, match="needs `err`"):
        coll.compressed_all_reduce(jnp.zeros((4,)), "data", "onebit")
