"""Collective micro-benchmark.

Reference analog: ``bin/ds_bench`` → DeepSpeed's comm benchmark — sweeps
message sizes through allreduce/allgather/etc. and reports busbw/algbw.
Here the collectives are the jax.lax set over the live mesh axes.
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _busbw(op, size_bytes, t, n):
    """Bus bandwidth correction factors (ring-algorithm accounting)."""
    alg = size_bytes / t
    if op == "all_reduce":
        return alg * 2 * (n - 1) / n
    if op in ("all_gather", "reduce_scatter"):
        return alg * (n - 1) / n
    return alg


def run_collective_bench(op="all_reduce", sizes=None, trials=10,
                         axis="data", mesh=None, out=sys.stdout):
    from ..parallel.topology import get_topology

    topo = get_topology()
    mesh = mesh or topo.mesh
    n = max(topo.axis_size(axis), 1)
    sizes = sizes or [2 ** p for p in range(12, 27, 2)]  # 4KB..64MB fp32

    from functools import partial
    from jax.sharding import PartitionSpec as P

    collectives = {
        "all_reduce": lambda x: jax.lax.psum(x, axis),
        "all_gather": lambda x: jax.lax.all_gather(x, axis),
        "reduce_scatter": lambda x: jax.lax.psum_scatter(x, axis,
                                                         tiled=True),
        "all_to_all": lambda x: jax.lax.all_to_all(
            x.reshape(n, -1), axis, 0, 0).reshape(-1),
    }
    if op not in collectives:
        raise ValueError(f"unknown op {op}; have {sorted(collectives)}")

    rows = []
    for numel in sizes:
        x = jnp.ones((numel,), jnp.float32)

        fn = jax.jit(partial(jax.shard_map, mesh=mesh,
                             axis_names={axis},
                             in_specs=P(axis) if op != "all_reduce" else P(),
                             out_specs=P() if op == "all_reduce" else P(axis),
                             check_vma=False)(collectives[op]))
        fn(x).block_until_ready()                      # compile
        t0 = time.perf_counter()
        for _ in range(trials):
            r = fn(x)
        np.asarray(r)                                  # host sync
        dt = (time.perf_counter() - t0) / trials
        size_bytes = numel * 4
        rows.append((numel, size_bytes, dt * 1e3,
                     _busbw(op, size_bytes, dt, n) / 1e9))
    print(f"collective={op} axis={axis} group_size={n}", file=out)
    print(f"{'numel':>12} {'bytes':>12} {'ms':>10} {'busbw GB/s':>12}",
          file=out)
    for numel, size_bytes, ms, bw in rows:
        print(f"{numel:>12} {size_bytes:>12} {ms:>10.3f} {bw:>12.2f}",
              file=out)
    return rows


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser(
        prog="hds_bench", description="collective micro-benchmark "
        "(reference: ds_bench)")
    p.add_argument("--op", default="all_reduce")
    p.add_argument("--axis", default="data")
    p.add_argument("--trials", type=int, default=10)
    p.add_argument("--maxpow", type=int, default=24,
                   help="max message size = 2^maxpow elements")
    args = p.parse_args(argv)
    sizes = [2 ** p_ for p_ in range(12, args.maxpow + 1, 2)]
    run_collective_bench(op=args.op, axis=args.axis, trials=args.trials,
                         sizes=sizes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
