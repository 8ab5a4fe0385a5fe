"""Collective micro-benchmark.

Reference analog: ``bin/ds_bench`` → DeepSpeed's comm benchmark — sweeps
message sizes through allreduce/allgather/etc. and reports busbw/algbw.
Here the collectives are the jax.lax set over the live mesh axes.

``calibrate_mesh_axes`` (ISSUE 15) is the MEASURED counterpart of the
per-axis wire-cost model's declared bandwidths: it times grouped
neighbor-``ppermute`` rounds along each axis of a ``HierMeshSpec``
(wall clock — this module is the explicit measurement entry point, the
one place outside the sim-determinism purity perimeter that may read
the clock) and emits calibrated per-axis GB/s with declared-vs-measured
divergence. ``profiling/hlo_audit.py wire_cost_seconds`` consumes the
result with ``calibration="measured"`` so an artifact row always says
where its bandwidths came from. On CPU the numbers are shape-valid but
physically meaningless (the harness self-validates structure); on chip
this is the ``bin/chip_overlap_campaign.sh`` calibration leg.
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


def calibrate_mesh_axes(spec, *, mesh=None, axis="data",
                        payload_bytes=(1 << 16, 1 << 20), trials=5,
                        rounds=None, seed=0):
    """Measured per-axis wire calibration: time grouped neighbor
    ``ppermute`` rounds along EACH axis of ``spec`` (a
    ``comm.hierarchical.HierMeshSpec``) at the given payload sizes and
    fit per-axis GB/s.

    Per axis ``j``: every device sends its payload to its ring
    neighbor within the dim-``j`` groups (``axis_groups`` — exactly
    the grouped transport the hierarchical collectives ride), chained
    ``rounds`` times (default ``size - 1``, one full ring revolution).
    Wall-clock per round / payload bytes = the measured per-device
    link bandwidth on that axis. Each timed iteration is synced
    (``block_until_ready``) — the conservative, launch-gap-free
    number.

    Returns ``{"rows": [per (axis, payload) rows], "gbytes_per_s":
    {axis: headline GB/s (largest payload)}, "divergence_vs_declared":
    {axis: measured/declared or None}, "calibration": "measured",
    "backend": ...}``. The declared bandwidths come from the spec's
    own ``gbytes_per_s`` fields; axes without one report divergence
    ``None`` (visible, not silently dropped).
    """
    from functools import partial

    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from .hierarchical import axis_groups

    n = spec.world
    if mesh is None:
        devs = jax.devices()
        if len(devs) < n:
            raise ValueError(
                f"calibrate_mesh_axes: mesh spec {list(spec.sizes)} "
                f"needs {n} devices, found {len(devs)}")
        mesh = Mesh(np.array(devs[:n]).reshape(n), (axis,))

    rows = []
    headline = {}
    divergence = {}
    rng = np.random.default_rng(seed)
    for dim, ax in enumerate(spec.axes):
        groups = axis_groups(spec.sizes, dim)
        m = ax.size
        perm = [(g[k], g[(k + 1) % m]) for g in groups for k in range(m)]
        n_rounds = int(rounds) if rounds else max(1, m - 1)

        def chain(xl, perm=perm, n_rounds=n_rounds):
            cur = xl[0]
            for _ in range(n_rounds):
                cur = jax.lax.ppermute(cur, axis, perm)
            return cur[None]

        for nbytes in payload_bytes:
            elems = max(1, int(nbytes) // 4)
            x = jnp.asarray(rng.standard_normal((n, elems)), jnp.float32)
            fn = jax.jit(partial(
                jax.shard_map, mesh=mesh, axis_names={axis},
                in_specs=P(axis), out_specs=P(axis),
                check_vma=False)(chain))
            jax.block_until_ready(fn(x))           # compile
            t0 = time.perf_counter()
            for _ in range(trials):
                jax.block_until_ready(fn(x))
            per_round = (time.perf_counter() - t0) / trials / n_rounds
            gbps = (elems * 4) / per_round / 1e9
            rows.append({
                "axis": ax.name, "axis_size": m, "rounds": n_rounds,
                "payload_bytes": elems * 4, "trials": trials,
                "seconds_per_round": per_round,
                "measured_gbytes_per_s": gbps,
                "declared_gbytes_per_s": ax.gbytes_per_s,
            })
            headline[ax.name] = gbps
        decl = ax.gbytes_per_s
        divergence[ax.name] = (headline[ax.name] / decl) if decl \
            else None
    return {"rows": rows, "gbytes_per_s": headline,
            "divergence_vs_declared": divergence,
            "calibration": "measured",
            "backend": jax.default_backend()}


def fused_vs_unfused_bench(payloads=((512, 256), (1024, 512),
                                     (2048, 1024)),
                           *, batch=64, trials=5, mesh=None,
                           axis="data", group_k=None, seed=0):
    """Wall-clock verdict leg for the fused gather-matmul (ISSUE 18):
    time the STREAMED fused schedule (``ops/fused_collective_matmul.
    streamed_fused_gather_matmul`` — per ring step, chunk ``r+1`` on
    the wire beside chunk ``r``'s dequant-dot) against the UNFUSED
    pipeline (native ``all_gather`` of the int8+scales shards, then one
    ``quantized_matmul``) per ``(K, N)`` payload, jit(shard_map),
    best-of-``trials`` with a sync per iteration. The unfused baseline
    deliberately rides the NATIVE gather — the strongest opponent, not
    the ring twin — so ``fused_le_unfused_largest`` is a real verdict.

    Returns ``{"rows": [{k, n, batch, group_k, fused_ms, unfused_ms,
    speedup, maxdiff}], "fused_le_unfused_largest", "qmm_fallbacks",
    "fused_fallbacks", "backend", "devices"}``. ``maxdiff`` is the
    fused-vs-unfused output divergence (chunked-K sum: value-equal,
    not bitwise — the bitwise contract belongs to the reference twin,
    gated elsewhere). The two fallback dicts are those ops' entries in
    ``ops.fallback_report()`` AFTER the runs: on chip a non-empty one
    means the Pallas kernel bailed and the row is timing the
    reference."""
    from functools import partial

    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from ..ops import fallback_report
    from ..ops.fused_collective_matmul import streamed_fused_gather_matmul
    from ..ops.quantized_matmul import quantize_for_matmul, quantized_matmul

    if mesh is None:
        devs = jax.devices()
        mesh = Mesh(np.array(devs).reshape(len(devs)), (axis,))
    n = int(mesh.devices.size)
    rng = np.random.default_rng(seed)
    rows = []
    for K, N in payloads:
        if K % n:
            raise ValueError(
                f"fused_vs_unfused_bench: K={K} not divisible by the "
                f"{n}-device gather axis")
        k_sh = K // n
        gk = group_k or max(1, k_sh // 2)
        if k_sh % gk:
            raise ValueError(
                f"fused_vs_unfused_bench: group_k={gk} must divide the "
                f"per-device K shard {k_sh}")
        w = rng.standard_normal((K, N)).astype(np.float32)
        q, s = quantize_for_matmul(jnp.asarray(w), gk)
        x = jnp.asarray(rng.standard_normal((batch, K)), jnp.float32)

        def fused(xl, ql, sl, gk=gk):
            return streamed_fused_gather_matmul(
                xl, ql, sl, group_k=gk, shard_dim=0, axis_name=axis)

        def unfused(xl, ql, sl, gk=gk):
            qa = jax.lax.all_gather(ql, axis)
            sa = jax.lax.all_gather(sl, axis)
            return quantized_matmul(xl, qa.reshape(-1, qa.shape[-1]),
                                    sa.reshape(-1, sa.shape[-1]),
                                    group_k=gk)

        def timed(f):
            fn = jax.jit(partial(
                jax.shard_map, mesh=mesh, axis_names={axis},
                in_specs=(P(), P(axis), P(axis)), out_specs=P(),
                check_vma=False)(f))
            y = fn(x, q, s)
            jax.block_until_ready(y)               # compile
            best = float("inf")
            for _ in range(trials):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x, q, s))
                best = min(best, time.perf_counter() - t0)
            return best, np.asarray(y)

        tf, yf = timed(fused)
        tu, yu = timed(unfused)
        rows.append({
            "k": K, "n": N, "batch": batch, "group_k": gk,
            "devices": n, "trials": trials,
            "fused_ms": tf * 1e3, "unfused_ms": tu * 1e3,
            "speedup": tu / tf if tf else None,
            "maxdiff": float(np.max(np.abs(yf - yu))),
        })
    largest = max(rows, key=lambda r: r["k"] * r["n"])
    return {"rows": rows,
            "fused_le_unfused_largest":
                bool(largest["fused_ms"] <= largest["unfused_ms"]),
            "qmm_fallbacks": fallback_report().get("quantized_matmul", {}),
            "fused_fallbacks":
                fallback_report().get("fused_gather_matmul", {}),
            "backend": jax.default_backend(), "devices": n}


#: child program for the 16-device factoring parity leg: 4x4 and 2x8
#: hierarchical collectives bitwise vs native (fp32 + bf16), the
#: unified hpZ tier at hpz=4 on 4x4, pipelined-gather parity, and the
#: fused gather-matmul / qrs-exchange twins bitwise at 16 devices —
#: run in its own interpreter because the parent harness pins the CPU
#: device count at 8. Shared by ``bench.py --zero-overlap``'s
#: hier-16dev phase and tests/unit/comm/test_hier_16dev.py, so the
#: committed artifact and the slow test exercise the same program.
SIXTEEN_DEV_CHILD = r"""
import json
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from hcache_deepspeed_tpu.comm.hierarchical import (
    hierarchical_all_gather, hierarchical_all_to_all_rows,
    hierarchical_reduce_scatter_sum, make_mesh_spec)

devs = jax.devices()
assert len(devs) >= 16, f"need 16 virtual devices, got {len(devs)}"
mesh = Mesh(np.array(devs[:16]).reshape(16), ("d",))


def shm(f, ins, outs):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=ins,
                                 out_specs=outs, check_vma=False))


facts = {"shapes": [], "parity": True}
rng = np.random.default_rng(0)
for shape in ((4, 4), (2, 8)):
    spec = make_mesh_spec(list(shape))
    for dtype in (jnp.float32, jnp.bfloat16):
        x = jnp.asarray(rng.normal(size=(16, 37)), dtype)
        wide = jnp.asarray(rng.normal(size=(16, 16, 11)), dtype)
        rows = jnp.asarray(rng.normal(size=(16, 16, 7)), dtype)

        def hag(xl):
            return hierarchical_all_gather(xl[0], "d", spec)[None]

        def nag(xl):
            return jax.lax.all_gather(xl[0], "d")[None]

        def hrs(w):
            return hierarchical_reduce_scatter_sum(w[0], "d", spec)

        def nrs(w):
            return jax.lax.psum_scatter(w[0], "d",
                                        scatter_dimension=0, tiled=True)

        def ha2a(r):
            return hierarchical_all_to_all_rows(r[0], "d", spec)[None]

        def na2a(r):
            return jax.lax.all_to_all(r[0], "d", 0, 0)[None]

        def piped(xl):
            return hierarchical_all_gather(
                xl[0], "d", spec, pipeline_chunks=2)[None]

        checks = {
            "all_gather": (hag, nag, x),
            "reduce_scatter": (hrs, nrs, wide),
            "all_to_all": (ha2a, na2a, rows),
            "pipelined_gather": (piped, nag, x),
        }
        ok = {}
        for name, (hf, nf, arg) in checks.items():
            a = np.asarray(shm(hf, (P("d"),), P("d"))(arg))
            b = np.asarray(shm(nf, (P("d"),), P("d"))(arg))
            ok[name] = bool(np.array_equal(a.astype(np.float32),
                                           b.astype(np.float32)))
            facts["parity"] = facts["parity"] and ok[name]
        facts["shapes"].append({"mesh": list(shape),
                                "dtype": jnp.dtype(dtype).name,
                                "bitwise": ok})

# unified hpZ tier at 16 devices: hpz=4 on 4x4 = one intra row
spec44 = make_mesh_spec([4, 4])
x = jnp.asarray(rng.normal(size=(16, 23)), jnp.float32)
groups = [list(range(g * 4, (g + 1) * 4)) for g in range(4)]


def tier(xl):
    return hierarchical_all_gather(xl[0], "d", spec44, hpz=4)[None]


def native_grouped(xl):
    return jax.lax.all_gather(xl[0], "d",
                              axis_index_groups=groups)[None]


a = np.asarray(shm(tier, (P("d"),), P("d"))(x))
b = np.asarray(shm(native_grouped, (P("d"),), P("d"))(x))
facts["hpz_tier_bitwise"] = bool(np.array_equal(a, b))
facts["parity"] = facts["parity"] and facts["hpz_tier_bitwise"]

# fused computation-collective parity at 16 devices (ISSUE 18): the
# reference gather-matmul twin vs the unfused native pipeline, and the
# fused reduce-scatter epilogue exchange vs the native all_to_all —
# both must be BITWISE at the 16-way factoring too
from hcache_deepspeed_tpu.ops.fused_collective_matmul import (
    fused_qrs_exchange, reference_fused_gather_matmul)
from hcache_deepspeed_tpu.ops.quantized_matmul import (
    quantize_for_matmul, quantized_matmul)

wq, ws = quantize_for_matmul(
    jnp.asarray(rng.normal(size=(64, 16)), jnp.float32), 4)
xb = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)


def fgm(ql, sl):
    return reference_fused_gather_matmul(
        xb, ql, sl, group_k=4, shard_dim=0, axis_name="d")


def ugm(ql, sl):
    qa = jax.lax.all_gather(ql, "d")
    sa = jax.lax.all_gather(sl, "d")
    return quantized_matmul(xb, qa.reshape(-1, 16),
                            sa.reshape(-1, 16), group_k=4)


a = np.asarray(shm(fgm, (P("d"), P("d")), P())(wq, ws))
b = np.asarray(shm(ugm, (P("d"), P("d")), P())(wq, ws))
gm_ok = bool(np.array_equal(a, b))

pay = jnp.asarray(rng.integers(-127, 128, size=(16, 16, 6)), jnp.int8)
sc = jnp.asarray(rng.normal(size=(16, 16, 2)), jnp.float32)


def fqrs(p, s):
    a, b = fused_qrs_exchange(p[0], s[0], axis_name="d")
    return a[None], b[None]


def nqrs(p, s):
    return (jax.lax.all_to_all(p[0], "d", 0, 0)[None],
            jax.lax.all_to_all(s[0], "d", 0, 0)[None])


fa = shm(fqrs, (P("d"), P("d")), (P("d"), P("d")))(pay, sc)
na = shm(nqrs, (P("d"), P("d")), (P("d"), P("d")))(pay, sc)
qrs_ok = bool(all(np.array_equal(np.asarray(u), np.asarray(v))
                  for u, v in zip(fa, na)))
facts["fused_bitwise"] = {"gather_matmul": gm_ok, "qrs_exchange": qrs_ok}
facts["parity"] = facts["parity"] and gm_ok and qrs_ok
print(json.dumps(facts))
"""


def run_16dev_parity(repo_root=None, timeout=900):
    """Run the 16-device factoring parity child (own interpreter with
    ``--xla_force_host_platform_device_count=16``) and return its JSON
    facts. Raises on a failed child — never a silent skip."""
    import json as _json
    import os
    import subprocess

    env = dict(os.environ)
    if repo_root:
        env["PYTHONPATH"] = repo_root
    env["JAX_PLATFORMS"] = "cpu"
    kept = [t for t in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in t]
    env["XLA_FLAGS"] = " ".join(
        kept + ["--xla_force_host_platform_device_count=16"])
    out = subprocess.run([sys.executable, "-c", SIXTEEN_DEV_CHILD],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(
            f"16-dev parity child failed: {out.stderr[-2000:]}")
    return _json.loads(out.stdout.strip().splitlines()[-1])


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
