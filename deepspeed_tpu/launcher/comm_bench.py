"""Collective micro-benchmark CLI (`python -m deepspeed_tpu.launcher.comm_bench`).

Reference: `bin/ds_bench` → DeepSpeedExamples communication benchmarks (latency /
algbw / busbw tables per collective and message size).

Runs each collective over the local mesh's data axis across a size sweep and
prints the standard latency/algbw/busbw table. busbw factors follow the NCCL
conventions: allreduce 2(n-1)/n, allgather & reducescatter (n-1)/n, alltoall
(n-1)/n.
"""

import argparse
import time


def _busbw_factor(op, n):
    if n <= 1:
        return 1.0
    if op == "all_reduce":
        return 2.0 * (n - 1) / n
    return (n - 1) / n


def run_collective(op, size_bytes, trials, warmup, dtype_name="bfloat16"):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.comm import mesh as mesh_mod

    mesh = mesh_mod.get_mesh()
    n = mesh.devices.size
    dtype = jnp.dtype(dtype_name)
    elems = max(n, size_bytes // dtype.itemsize)
    elems -= elems % n  # divisible for scatter/alltoall
    axes = tuple(mesh.axis_names)

    x = jax.device_put(jnp.ones((elems,), dtype), NamedSharding(mesh, P(axes)))

    from jax import shard_map

    if op == "all_reduce":
        def body(v):
            return jax.lax.psum(v, axes)
        out_spec = P(axes)
    elif op == "all_gather":
        def body(v):
            return jax.lax.all_gather(v, axes, tiled=True)
        out_spec = P()
    elif op == "reduce_scatter":
        def body(v):
            return jax.lax.psum_scatter(v, axes, tiled=True)
        out_spec = P(axes)
    elif op == "all_to_all":
        def body(v):
            return jax.lax.all_to_all(v.reshape(n, -1), axes, 0, 0,
                                      tiled=False).reshape(-1)
        out_spec = P(axes)
    else:
        raise ValueError(op)

    # dstpu: ignore[DT004]: the bench compiles one program per measured collective by definition; compile time is excluded by the warmup
    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P(axes), out_specs=out_spec,
                           check_vma=False))
    for _ in range(warmup):
        # dstpu: ignore[DT001]: warmup fence — the timed region must start from a drained device
        fn(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(trials):
        out = fn(x)
    # dstpu: ignore[DT001]: bench timing fence — bandwidth math needs the last collective finished
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / trials

    nbytes = elems * dtype.itemsize
    algbw = nbytes / dt / 1e9
    busbw = algbw * _busbw_factor(op, n)
    return dt, algbw, busbw, nbytes


def main(argv=None):
    parser = argparse.ArgumentParser(description="deepspeed-tpu comm benchmark")
    parser.add_argument("--ops", type=str,
                        default="all_reduce,all_gather,reduce_scatter,all_to_all")
    parser.add_argument("--minsize", type=int, default=1 << 12)
    parser.add_argument("--maxsize", type=int, default=1 << 26)
    parser.add_argument("--trials", type=int, default=20)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--dtype", type=str, default="bfloat16")
    args = parser.parse_args(argv)

    from deepspeed_tpu import comm
    if not comm.is_initialized():
        comm.init_distributed()

    for op in args.ops.split(","):
        print(f"\n==== {op} ({args.dtype}) ====")
        print(f"{'bytes':>12} {'latency(us)':>12} {'algbw(GB/s)':>12} {'busbw(GB/s)':>12}")
        size = args.minsize
        while size <= args.maxsize:
            dt, algbw, busbw, nbytes = run_collective(
                op, size, args.trials, args.warmup, args.dtype)
            print(f"{nbytes:>12} {dt*1e6:>12.1f} {algbw:>12.2f} {busbw:>12.2f}")
            size *= 4
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
