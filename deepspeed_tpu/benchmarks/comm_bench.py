"""Collective micro-benchmarks (reference ``bin/ds_bench`` → comms
benchmarks): sweep message sizes over the mesh's collectives and report
algbw/busbw."""

import argparse
import time

import numpy as np
from jax import shard_map as _shard_map


def bench_collective(op_name, sizes_mb, iters=10):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.parallel.topology import get_topology, DP_AXES
    import deepspeed_tpu.comm as dist

    topo = get_topology()
    n = topo.dp
    results = []
    for size_mb in sizes_mb:
        elems = int(size_mb * 1e6 / 4)
        elems = max(n, (elems // n) * n)
        x = jnp.ones((elems,), jnp.float32)
        if op_name == "all_reduce":
            fn = jax.jit(_shard_map(
                lambda v: dist.all_reduce(v, group=DP_AXES),
                mesh=topo.mesh, in_specs=(P(DP_AXES),), out_specs=P(DP_AXES),
                check_vma=False))
        elif op_name == "all_gather":
            fn = jax.jit(_shard_map(
                lambda v: dist.all_gather_into_tensor(v, group=DP_AXES),
                mesh=topo.mesh, in_specs=(P(DP_AXES),), out_specs=P(None),
                check_vma=False))
        elif op_name == "reduce_scatter":
            fn = jax.jit(_shard_map(
                lambda v: dist.reduce_scatter_tensor(v, group=DP_AXES),
                mesh=topo.mesh, in_specs=(P(None),), out_specs=P(DP_AXES),
                check_vma=False))
        else:
            raise ValueError(op_name)
        out = fn(x)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(x)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        nbytes = elems * 4
        algbw = nbytes / dt / 1e9
        busbw = algbw * (2 * (n - 1) / n if op_name == "all_reduce" else (n - 1) / n)
        results.append((size_mb, dt * 1e3, algbw, busbw))
    return results


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--op", default="all_reduce",
                        choices=["all_reduce", "all_gather", "reduce_scatter"])
    parser.add_argument("--sizes", default="1,8,64", help="MB sizes, comma-sep")
    parser.add_argument("--iters", type=int, default=10)
    args = parser.parse_args()
    sizes = [float(s) for s in args.sizes.split(",")]
    print(f"{'size(MB)':>10}{'lat(ms)':>12}{'algbw(GB/s)':>14}{'busbw(GB/s)':>14}")
    for size_mb, lat, algbw, busbw in bench_collective(args.op, sizes, args.iters):
        print(f"{size_mb:>10.1f}{lat:>12.3f}{algbw:>14.2f}{busbw:>14.2f}")


if __name__ == "__main__":
    main()
