"""Benchmark entry point: prints ONE JSON line {metric, value, unit,
vs_baseline, device}.

``python bench.py`` is one in-process run of one fixed configuration:
GPT-2 350M causal-LM training on one chip (``CONFIG`` below: 8 heads of
128 so the flash kernel's MXU contractions run full depth, vocabulary
padded to a 128 multiple, batch 8 x 1024), bf16 params + fp32 Adam,
fused train step, Pallas flash attention. It needs the chip: without a
TPU it prints why and exits non-zero, it never measures the CPU under a
device metric's name. The timed window ends in ``block_until_ready``.
``vs_baseline`` is measured MFU over the reference's published 54% MFU
(Ulysses blog headline, BASELINE.md).

``HDS_BENCH_TINY=1`` is the CPU path tier-1 uses: the same code path on
a two-layer toy, forced onto the host platform, labelled a smoke, with
no MFU (the host has no published peak).

The flags (``--zero-overlap``, ``--fleet``, ``--disagg``,
``--spec-serve``, ``--fabric``, ``--fabric-obs``, ``--request-trace``,
``--autoscale``) are CPU emitters of simulation and audit artifacts;
they measure no device.
"""

import json
import os
import sys
import time

import numpy as np

#: the one training configuration ``python bench.py`` measures
CONFIG = dict(name="350m-hd128-b8", batch=8, seq=1024, n_layer=24,
              n_embd=1024, n_head=8, vocab_size=50304)
#: HDS_BENCH_TINY=1: seconds-cheap shape through the identical path
TINY_CONFIG = dict(name="tiny", batch=2, seq=128, n_layer=2, n_embd=64,
                   n_head=4, vocab_size=256)


def build_model(spec):
    """(model, model_config) for ``CONFIG``/``TINY_CONFIG``. Shared with
    tests/unit/test_bench_configs.py so the trace test builds exactly
    the model the bench measures."""
    from hcache_deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    mcfg = GPT2Config(n_layer=spec["n_layer"], n_embd=spec["n_embd"],
                      n_head=spec["n_head"], n_positions=spec["seq"],
                      vocab_size=spec["vocab_size"], dtype="bfloat16",
                      remat=False)
    return GPT2LMHeadModel(mcfg), mcfg


def _metric_label():
    return ("gpt2-tiny SMOKE tokens/sec (not a benchmark)"
            if os.environ.get("HDS_BENCH_TINY") == "1" else
            "gpt2-350m train tokens/sec/chip (bf16, seq1024)")


def _error_payload(message):
    """The line printed when there is no result: ``value`` 0.0 and the
    reason, so no consumer can read a failed run as a measurement."""
    return {
        "metric": _metric_label(),
        "value": 0.0,
        "unit": "tokens/sec",
        "vs_baseline": 0.0,
        "error": message,
    }


def run_training():
    """Measure the training configuration; prints the result JSON line
    and returns the exit code."""
    import jax

    tiny = os.environ.get("HDS_BENCH_TINY") == "1"
    if tiny:
        # the smoke path is a CPU path by construction, wherever it runs
        jax.config.update("jax_platforms", "cpu")

    import hcache_deepspeed_tpu as hds
    from hcache_deepspeed_tpu import ops
    from hcache_deepspeed_tpu.platform import device_row, get_platform

    device = device_row()
    if not tiny and device["platform"] != "tpu":
        print(json.dumps(dict(
            _error_payload(
                f"backend is {device['platform']!r}, not a TPU: a CPU "
                "run is never recorded under a device metric's name"),
            device=device)), flush=True)
        return 1

    spec = TINY_CONFIG if tiny else CONFIG
    model, mcfg = build_model(spec)
    batch, seq = spec["batch"], spec["seq"]
    rng = np.random.default_rng(0)
    data = {"input_ids": rng.integers(
        0, min(mcfg.vocab_size, 50257), (batch, seq), dtype=np.int32)}

    cfg = {
        "train_batch_size": batch,
        "train_micro_batch_size_per_gpu": batch,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 0},
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = hds.initialize(model=model, config=cfg,
                                     example_batch=data)

    # warmup / compile
    for _ in range(3):
        jax.block_until_ready(engine.train_batch(batch=data))

    # Steps chain through engine.state on device: enqueue them all and
    # wait once at the end of the window. The window runs under the
    # span tracer so the row carries a per-step host-issue breakdown
    # (the device truth needs an XLA profile).
    from hcache_deepspeed_tpu.telemetry import bench_extra
    from hcache_deepspeed_tpu.telemetry.tracer import get_tracer
    tracer = get_tracer()
    tracer_was = tracer.enabled
    tracer.configure(enabled=True)
    tracer.clear()
    steps = 30
    t0 = time.perf_counter()
    for _ in range(steps):
        loss_dev = engine.train_batch(batch=data)
    jax.block_until_ready(loss_dev)
    dt = time.perf_counter() - t0
    tracer.configure(enabled=tracer_was)
    step_breakdown = bench_extra(tracer.events())

    tokens_per_sec = steps * batch * seq / dt
    n_params = sum(x.size for x in jax.tree.leaves(engine.state["params"]))
    # 6N (fwd+bwd) weight FLOPs + 12*L*S*d attention FLOPs per token
    flops_per_token = 6 * n_params + 12 * mcfg.n_layer * seq * mcfg.n_embd
    achieved_tflops = tokens_per_sec * flops_per_token / 1e12
    # MFU is a device metric: the host platform has no published peak
    # (peak_tflops raises there, and for an unknown device_kind)
    peak = mfu = vs_baseline = None
    if not tiny:
        peak = get_platform().peak_tflops("bfloat16")
        mfu = round(achieved_tflops / peak, 4)
        vs_baseline = round(mfu / 0.54, 4)

    print(json.dumps({
        "metric": _metric_label(),
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": vs_baseline,
        "device": device,
        "extra": {
            "config": spec["name"],
            "seq": seq,
            "mfu": mfu,
            "achieved_tflops": round(achieved_tflops, 2),
            "peak_tflops": peak,
            "loss": float(loss_dev),
            "n_params": int(n_params),
            "step_time_ms": round(dt / steps * 1000, 2),
            "step_breakdown": step_breakdown,
            # a number measured while a kernel ran its jnp reference
            # describes the wrong code
            "fallbacks": ops.fallback_report(),
        },
    }), flush=True)
    return 0


def _qmm_fallback_row():
    """The quantized matmul's entry in ``ops.fallback_report()``."""
    from hcache_deepspeed_tpu.ops import fallback_report
    return fallback_report().get("quantized_matmul", {})


def run_zero_overlap(out_path=None):
    """``--zero-overlap``: CPU-deterministic audit of the explicit
    ZeRO-3 comm/compute overlap pipeline (docs/zero_overlap.md).

    Builds the 2-layer toy ZeRO-3 (qwZ) step on an 8-virtual-device
    CPU mesh, audits the compiled HLO with ``profiling/hlo_audit.py``
    for prefetch on vs ``overlap_comm=False``, checks bitwise parity
    between the two schedules over 3 steps, repeats both audits on the
    QUANTIZED-WIRE config (bucketed int8 reduce-scatter + error
    feedback + fused qwZ matmul consumption) with wire-bytes-saved per
    collective op recorded from the comms logger AND the compiled
    module, audits the decomposed flat-ring AND hierarchical (2-D mesh,
    ``comm/hierarchical.py``) transports — bitwise parity vs native,
    per-mesh-axis wire bytes, inter-axis quantized fraction, and
    modeled pod-scale wire seconds from the declared per-axis
    bandwidths — re-runs the Domino half-batch all-reduce audit
    (full-width + int8-wire + decomposed + hierarchical) through the
    explicit async-issue helper, and emits one JSONL row per
    measurement plus a summary line. Runs entirely on
    CPU, so the artifact is reproducible
    anywhere (native async pairs are expected to be 0 here; the derived
    tier is the CPU-decidable evidence).

    Chip-truth mode (``HDS_ZERO_OVERLAP_PLATFORM=tpu``): the same
    phases run on real TPU devices and land in ``ZERO_OVERLAP_TPU.jsonl``
    — there the NATIVE tier is the verdict: either the scheduler
    finally emits async pairs for the monolithic collectives, or the
    decomposed permute chains carry the overlap structurally (ROADMAP
    item 5's either-outcome resolution)."""
    platform = os.environ.get("HDS_ZERO_OVERLAP_PLATFORM", "cpu")
    if out_path is None:
        out_path = "ZERO_OVERLAP.jsonl" if platform == "cpu" \
            else "ZERO_OVERLAP_TPU.jsonl"
    if platform == "cpu":
        # must run before jax initializes its backends
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
    import jax
    if platform == "cpu":
        try:
            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass
    elif len(jax.devices()) < 8:
        print(json.dumps(_error_payload(
            f"zero-overlap tpu mode: need >= 8 devices, found "
            f"{len(jax.devices())}")), flush=True)
        return 3
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    import hcache_deepspeed_tpu as hds
    from hcache_deepspeed_tpu.comm.comms_logging import get_comms_logger
    from hcache_deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_tiny
    from hcache_deepspeed_tpu.profiling.hlo_audit import audit_compiled
    from hcache_deepspeed_tpu.telemetry.tracer import get_tracer

    tracer = get_tracer()
    tracer.configure(enabled=True)
    comms = get_comms_logger()
    comms.configure(enabled=True)

    rng = np.random.default_rng(0)
    data = {"input_ids": rng.integers(0, 256, (8, 32), dtype=np.int32)}

    def build(overlap, **zero_extra):
        model = GPT2LMHeadModel(gpt2_tiny(
            n_layer=2, n_embd=64, n_head=4, use_flash=False))
        zero = {"stage": 3, "min_shard_size": 1,
                "zero_quantized_weights": True,
                "overlap_comm": overlap}
        zero.update(zero_extra)
        cfg = {
            "train_batch_size": 8,
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": zero,
            "comms_logger": {"enabled": True},
            "steps_per_print": 10 ** 9,
        }
        engine, _, _, _ = hds.initialize(model=model, config=cfg,
                                         example_batch=data)
        return engine

    rows, losses, params = [], {}, {}
    for overlap in (True, False):
        comms.reset()
        engine = build(overlap)
        report, row = engine.zero_overlap_report(data)
        losses[overlap] = [float(engine.train_batch(batch=data))
                           for _ in range(3)]
        params[overlap] = jax.tree.leaves(engine.state["params"])
        row.update({
            "phase": "zero3-audit", "overlap_comm": overlap,
            "comm_bytes": {op: {ax: tot for ax, (_, tot) in by.items()}
                           for op, by in comms.axis_summary().items()
                           if op.startswith(("zero_", "qwZ", "qgZ",
                                             "domino", "issue."))},
            "wire_savings": comms.wire_savings_summary(),
        })
        rows.append(row)

    bitwise = (losses[True] == losses[False] and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(params[True], params[False])))

    # ---- quantized wire: bucketed int8 reduce-scatter + error
    # feedback + fused qwZ matmul consumption, prefetch on. Gates:
    # wire <= ~35% of the fp32 full-width bytes, loss trajectory
    # within tolerance of the full-width run, depth-1-vs-0 bitwise
    # parity preserved UNDER quantization.
    q_losses, q_params = {}, {}
    qrs_row = None
    for overlap in (True, False):
        comms.reset()
        engine = build(overlap,
                       zero_quantized_reduce_scatter=True,
                       zero_reduce_scatter_error_feedback=True,
                       zero_quantized_weights_fused_matmul=True)
        report, row = engine.zero_overlap_report(data)
        q_losses[overlap] = [float(engine.train_batch(batch=data))
                             for _ in range(3)]
        q_params[overlap] = jax.tree.leaves(engine.state["params"])
        row.update({
            "phase": "zero3-audit-quantized-wire",
            "overlap_comm": overlap,
            "alltoall_overlap_ratio": round(
                report.overlap_ratio("all-to-all"), 4),
            "wire_savings": comms.wire_savings_summary(),
        })
        if overlap:
            qrs_row = row
        rows.append(row)
    q_bitwise = (q_losses[True] == q_losses[False] and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(q_params[True], q_params[False])))
    qrs_frac = qrs_row["wire_savings"].get(
        "zero_qrs_all_to_all", {}).get("fraction")
    traj_ok = bool(np.allclose(q_losses[True], losses[True], rtol=5e-2))
    rows.append({
        "phase": "quantized-wire-parity", "steps": 3,
        "bitwise_depth_parity": q_bitwise,
        "losses": q_losses[True],
        "fp_wire_losses": losses[True],
        "trajectory_within_tol": traj_ok,
        "qrs_wire_fraction_of_fp32": qrs_frac,
        "qmm_fallbacks": _qmm_fallback_row(),
    })
    on = next(r for r in rows if r["overlap_comm"])
    off = next(r for r in rows if not r["overlap_comm"])
    on_pairs = [p for p in on["pairs"]
                if p["kind"].startswith("all-gather")
                and p["interleaved"] >= 1]
    off_pairs = [p for p in off["pairs"]
                 if p["kind"].startswith("all-gather")
                 and p["interleaved"] >= 1]
    rows.append({"phase": "parity", "steps": 3, "bitwise": bitwise,
                 "losses": losses[True]})

    # ---- decomposed ring collectives (zero_collective_impl=
    # decomposed): the gather/reduce lanes ride chunked-ppermute
    # chains (comm/ring.py) so overlap is STRUCTURAL — scored by the
    # auditor's structural_overlap_ratio over collective-permute ops,
    # gated >= the native derived ratio for BOTH lanes, and
    # bitwise-equal to the native transport at depth 1 and 0.
    d_losses, d_params, d_rows = {}, {}, {}
    for prefetch in (True, False):
        comms.reset()
        extra = {"zero_collective_impl": "decomposed"}
        if not prefetch:
            extra["stage3_prefetch_bucket_size"] = 0
        engine = build(True, **extra)
        report, row = engine.zero_overlap_report(data)
        d_losses[prefetch] = [float(engine.train_batch(batch=data))
                              for _ in range(3)]
        d_params[prefetch] = jax.tree.leaves(engine.state["params"])
        row.update({
            "phase": "zero3-audit-decomposed", "prefetch": prefetch,
            "ring_permute_bytes": comms.permute_bytes_summary(),
            "wire_savings": comms.wire_savings_summary(),
        })
        d_rows[prefetch] = row
        rows.append(row)
    dec_bitwise = (
        d_losses[True] == d_losses[False] == losses[True]
        and all(np.array_equal(np.asarray(x), np.asarray(y))
                and np.array_equal(np.asarray(x), np.asarray(z))
                for x, y, z in zip(params[True], d_params[True],
                                   d_params[False])))
    structural = d_rows[True]["structural_overlap_ratio"]
    dec_chain_max = max(
        (c["length"] for c in d_rows[True]["permute_chains"]),
        default=0)

    # quantized wire over the ring transport: per-ring-chunk
    # quantization preserves EF residuals + bucket layout, so the
    # decomposed qwire run is bitwise-equal to the native qwire run
    comms.reset()
    engine = build(True, zero_collective_impl="decomposed",
                   zero_quantized_reduce_scatter=True,
                   zero_reduce_scatter_error_feedback=True,
                   zero_quantized_weights_fused_matmul=True)
    report, row = engine.zero_overlap_report(data)
    dq_losses = [float(engine.train_batch(batch=data)) for _ in range(3)]
    dq_params = jax.tree.leaves(engine.state["params"])
    dq_bitwise = (dq_losses == q_losses[True] and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(q_params[True], dq_params)))
    row.update({
        "phase": "zero3-audit-decomposed-qwire", "prefetch": True,
        "ring_permute_bytes": comms.permute_bytes_summary(),
        "wire_savings": comms.wire_savings_summary(),
    })
    dq_structural = row["structural_overlap_ratio"]
    rows.append(row)
    rows.append({
        "phase": "decomposed-parity", "steps": 3,
        "bitwise_vs_native": dec_bitwise,
        "bitwise_qwire_vs_native_qwire": dq_bitwise,
        "losses": d_losses[True],
        "structural_overlap_ratio": structural,
        "structural_ge_native_gather": bool(
            structural >= on["gather_overlap_ratio"]),
        "structural_ge_native_reduce": bool(
            structural >= on["reduce_overlap_ratio"]),
        "max_permute_chain_len": dec_chain_max,
    })

    # ---- hierarchical (2-D mesh) collectives, zero_collective_impl=
    # hierarchical: the flat data axis declared as a 2x4 mesh
    # (outer/long-haul "inter" axis of 2, fast "intra" axis of 4), the
    # gather/reduce lanes riding per-axis grouped ring phases
    # (comm/hierarchical.py). Gates: bitwise parity vs the native AND
    # flat-ring transports (plain + quantized wire), inter-axis wire
    # bytes of the quantized run <= 0.35x the all-full-width
    # hierarchical run, structural overlap >= the flat rings on at
    # least one lane, and modeled pod-scale wire seconds per axis.
    HIER = {"zero_collective_impl": "hierarchical",
            "zero_mesh_shape": [2, 4]}
    #: declared wire-cost model inputs (NOT measurements): the pod
    #: projection target (configurable via ``--pod-shape RxC``;
    #: default the v5e-256 as a 16x16 mesh), fast axis at ICI-class
    #: 45 GB/s per device, long-haul axis priced at DCN-class
    #: 6.75 GB/s — the EQuARX bandwidth asymmetry the axis-selective
    #: quantization spends its bits against
    HIER_TOY_SIZES = {"inter": 2, "intra": 4}
    pod_arg = "16x16"
    argv = sys.argv[1:]
    if "--pod-shape" in argv:
        pod_arg = argv[argv.index("--pod-shape") + 1]
    try:
        pod_inter, pod_intra = (int(t) for t in
                                pod_arg.lower().split("x"))
    except ValueError:
        print(json.dumps(_error_payload(
            f"--pod-shape {pod_arg!r}: expected RxC (e.g. 16x16)")),
            flush=True)
        return 3
    HIER_POD_SIZES = {"inter": pod_inter, "intra": pod_intra}
    HIER_GBPS = {"inter": 6.75, "intra": 45.0}

    def hier_run(phase, **extra):
        comms.reset()
        engine = build(True, **extra)
        report, row = engine.zero_overlap_report(data)
        losses = [float(engine.train_batch(batch=data))
                  for _ in range(3)]
        params = jax.tree.leaves(engine.state["params"])
        row.update({
            "phase": phase, "prefetch": True,
            "ring_permute_bytes": comms.permute_bytes_summary(),
            "ring_permute_axis_bytes": comms.permute_axis_bytes(),
            "axis_bytes": comms.total_axis_bytes(),
            "wire_savings": comms.wire_savings_summary(),
        })
        rows.append(row)
        return row, losses, params

    h_row, h_losses, h_params = hier_run("zero3-audit-hierarchical",
                                         **HIER)
    hier_bitwise_native = (h_losses == losses[True] and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(h_params, params[True])))
    hier_bitwise_flat = (h_losses == d_losses[True] and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(h_params, d_params[True])))

    # all-full-width hierarchical (qwZ off) — the inter-axis byte
    # DENOMINATOR, plus a full-width flat-ring twin for bitwise parity
    comms.reset()
    engine = build(True, zero_quantized_weights=False,
                   zero_collective_impl="decomposed")
    fwd_losses = [float(engine.train_batch(batch=data))
                  for _ in range(3)]
    fwd_params = jax.tree.leaves(engine.state["params"])
    fw_row, fw_losses, fw_params = hier_run(
        "zero3-audit-hierarchical-fullwidth",
        zero_quantized_weights=False, **HIER)
    hier_fw_bitwise_flat = (fw_losses == fwd_losses and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(fw_params, fwd_params)))

    # quantized wire over the hierarchical transport (qwZ gather +
    # bucketed int8 reduce-scatter + EF + fused matmul consumption):
    # every long-haul byte rides int8 — the inter-axis NUMERATOR
    hq_row, hq_losses, hq_params = hier_run(
        "zero3-audit-hierarchical-qwire",
        zero_quantized_reduce_scatter=True,
        zero_reduce_scatter_error_feedback=True,
        zero_quantized_weights_fused_matmul=True, **HIER)
    hier_qwire_bitwise = (hq_losses == q_losses[True] and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(hq_params, q_params[True])))

    # axis-selective long-haul quantization of the fp gather lane
    # (zero_longhaul_wire_bits): full width intra, int8 inter — values
    # change only for long-haul rows, gated on trajectory tolerance
    # like every lossy wire, plus the matched-pair wire fraction
    lh_row, lh_losses, _ = hier_run(
        "zero3-audit-hierarchical-longhaul",
        zero_quantized_weights=False, zero_longhaul_wire_bits=8, **HIER)
    lh_frac = lh_row["wire_savings"].get(
        "zero_hier_all_gather_longhaul", {}).get("fraction")
    lh_traj_ok = bool(np.allclose(lh_losses, fw_losses, rtol=5e-2))

    fw_inter = fw_row["axis_bytes"].get("inter", 0)
    hq_inter = hq_row["axis_bytes"].get("inter", 0)
    hier_interaxis_fraction = round(hq_inter / fw_inter, 4) \
        if fw_inter else None
    hier_structural = max(h_row["structural_overlap_ratio"],
                          hq_row["structural_overlap_ratio"])

    # modeled wire seconds: measured per-axis bytes of the quantized
    # run priced at the declared toy bandwidths, and projected to the
    # declared 16x16 pod mesh (assumption recorded in the row)
    from hcache_deepspeed_tpu.profiling.hlo_audit import (
        pod_scale_wire_seconds, wire_cost_seconds)
    hier_cost_toy = wire_cost_seconds(hq_row["axis_bytes"], HIER_GBPS)
    hier_cost_pod = pod_scale_wire_seconds(
        hq_row["axis_bytes"], HIER_TOY_SIZES, HIER_POD_SIZES, HIER_GBPS)
    fw_cost_pod = pod_scale_wire_seconds(
        fw_row["axis_bytes"], HIER_TOY_SIZES, HIER_POD_SIZES, HIER_GBPS)
    rows.append({
        "phase": "hierarchical-parity", "steps": 3,
        "mesh_spec": h_row.get("mesh_spec"),
        "bitwise_vs_native": hier_bitwise_native,
        "bitwise_vs_flat": hier_bitwise_flat,
        "fullwidth_bitwise_vs_flat": hier_fw_bitwise_flat,
        "qwire_bitwise_vs_native_qwire": hier_qwire_bitwise,
        "losses": h_losses,
        "structural_overlap_ratio": hier_structural,
        "structural_ge_flat": bool(hier_structural >= structural),
        "interaxis_wire_bytes_quantized": hq_inter,
        "interaxis_wire_bytes_fullwidth": fw_inter,
        "interaxis_wire_fraction": hier_interaxis_fraction,
        "longhaul_gather_wire_fraction": lh_frac,
        "longhaul_trajectory_within_tol": lh_traj_ok,
        "wire_cost_toy": hier_cost_toy,
        "wire_cost_pod_quantized": hier_cost_pod,
        "wire_cost_pod_fullwidth": fw_cost_pod,
        "pod_axis_sizes": HIER_POD_SIZES,
        "pod_shape": pod_arg,
        "link_gbytes_per_s": HIER_GBPS,
    })

    # ---- unified hpZ tiering on the mesh (ISSUE 15 tentpole):
    # zero_hpz_partition_size=4 maps onto the 2x4 mesh's intra axis —
    # per-micro gathers ride the fast tier's grouped rings, the
    # secondary refresh rides the full mesh. Gates: the transport swap
    # (hier-hpz vs native-hpz, everything else fixed) is BITWISE at
    # full width AND under qwZ, and the secondary refresh's bytes are
    # attributed per mesh axis (zero_hier_secondary) instead of
    # staying a native blind spot.
    comms.reset()
    engine = build(True, zero_quantized_weights=False,
                   zero_hpz_partition_size=4)
    nfwhpz_losses = [float(engine.train_batch(batch=data))
                     for _ in range(3)]
    nfwhpz_params = jax.tree.leaves(engine.state["params"])
    hz_row, hz_losses, hz_params = hier_run(
        "zero3-audit-hier-hpz-unified", zero_quantized_weights=False,
        zero_hpz_partition_size=4, **HIER)
    hpz_fw_bitwise = (hz_losses == nfwhpz_losses and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(hz_params, nfwhpz_params)))
    comms.reset()
    engine = build(True, zero_hpz_partition_size=4)
    nqhpz_losses = [float(engine.train_batch(batch=data))
                    for _ in range(3)]
    nqhpz_params = jax.tree.leaves(engine.state["params"])
    hzq_row, hzq_losses, hzq_params = hier_run(
        "zero3-audit-hier-hpz-qw", zero_hpz_partition_size=4, **HIER)
    hpz_qw_bitwise = (hzq_losses == nqhpz_losses and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(hzq_params, nqhpz_params)))
    hpz_secondary_axes = hz_row["ring_permute_axis_bytes"].get(
        "zero_hier_secondary", {})
    hpz_secondary_on_mesh = bool(
        hpz_secondary_axes.get("intra") and
        hpz_secondary_axes.get("inter"))
    hpz_unified_bitwise = bool(hpz_fw_bitwise and hpz_qw_bitwise)
    rows.append({
        "phase": "hier-hpz-unified-parity", "steps": 3,
        "hpz": 4, "hpz_tiers": [{"axis": "intra", "span": 4}],
        "bitwise_fullwidth_vs_native_hpz": hpz_fw_bitwise,
        "bitwise_qw_vs_native_hpz": hpz_qw_bitwise,
        "unified_hpz_bitwise": hpz_unified_bitwise,
        "secondary_refresh_on_mesh": hpz_secondary_on_mesh,
        "secondary_refresh_axis_bytes": hpz_secondary_axes,
        "losses": hz_losses,
    })

    # ---- phase-pipelined hierarchical collectives (ISSUE 15
    # tentpole): zero_mesh_pipeline_chunks=2 splits every gather/
    # exchange payload into column chunks riding independent full
    # phase chains — chunk k's long-haul phase structurally
    # independent of chunk k+1's intra phase, scored by the auditor's
    # NEW cross-axis permute-pair tier. Gates: bitwise vs the
    # unpipelined hierarchical engine, structural overlap >= the PR 12
    # number, primitive-level cross-axis pairs >= 1 pipelined and == 0
    # unpipelined.
    hp_row, hp_losses, hp_params = hier_run(
        "zero3-audit-hier-pipelined", zero_mesh_pipeline_chunks=2,
        **HIER)
    pipelined_bitwise = (hp_losses == h_losses and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(hp_params, h_params)))
    pipelined_structural = hp_row["structural_overlap_ratio"]
    # primitive cross-axis audit: the pipelined gather's long-haul
    # phase really is dependence-free of the next chunk's intra phase
    from hcache_deepspeed_tpu.comm.hierarchical import (
        hierarchical_all_gather, make_mesh_spec)
    prim_spec = make_mesh_spec([2, 4])
    prim_mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("d",))
    prim_x = jnp.ones((8, 64), jnp.float32)
    prim_cross = {}
    for pc in (1, 2):
        def prim(xl, pc=pc):
            return hierarchical_all_gather(
                xl[0], "d", prim_spec, pipeline_chunks=pc)[None]
        compiled = jax.jit(jax.shard_map(
            prim, mesh=prim_mesh, in_specs=(P("d"),),
            out_specs=P("d"), check_vma=False)).lower(prim_x).compile()
        prim_cross[pc] = audit_compiled(compiled).cross_axis
    rows.append({
        "phase": "hier-pipelined-parity", "steps": 3,
        "pipeline_chunks": 2,
        "bitwise_vs_unpipelined": pipelined_bitwise,
        "structural_overlap_ratio": pipelined_structural,
        "structural_ge_flat": bool(pipelined_structural >= structural),
        "engine_cross_axis_pairs": hp_row["cross_axis_pairs"],
        "primitive_cross_axis_unpipelined": prim_cross[1],
        "primitive_cross_axis_pipelined": prim_cross[2],
        "losses": hp_losses,
    })
    pipelined_cross_ok = (prim_cross[1]["pairs"] == 0
                          and prim_cross[2]["pairs"] >= 1)

    # ---- 16-device factorings (ISSUE 15): 4x4 and 2x8 parity in a
    # 16-virtual-device child interpreter (the same program the slow
    # test runs), so the grouped-ring machinery is proven past the
    # 8-device toy matrix in the committed artifact itself.
    from hcache_deepspeed_tpu.comm.benchmark import run_16dev_parity
    try:
        facts16 = run_16dev_parity(
            repo_root=os.path.dirname(os.path.abspath(__file__)))
        hier_16dev_parity = bool(facts16["parity"])
    except Exception as exc:  # noqa: BLE001 — recorded, gates fail
        facts16 = {"error": repr(exc)}
        hier_16dev_parity = False
    rows.append(dict(facts16, phase="hier-16dev",
                     parity=hier_16dev_parity))

    # ---- measured wire calibration (ISSUE 15): time per-axis grouped
    # ppermute rounds (wall clock — the one deliberately impure leg)
    # and re-price the pod projection with MEASURED bandwidths; the
    # declared-vs-measured divergence rides in the row. On CPU the
    # numbers are physically meaningless — the shape/contract is the
    # gate here; on chip this leg IS the calibration.
    from hcache_deepspeed_tpu.comm.benchmark import calibrate_mesh_axes
    cal_spec = make_mesh_spec(
        [2, 4], link_gbytes_per_s=[HIER_GBPS["inter"],
                                   HIER_GBPS["intra"]])
    cal = calibrate_mesh_axes(cal_spec, mesh=prim_mesh, axis="d",
                              payload_bytes=(1 << 14, 1 << 18),
                              trials=3)
    cal_pod = pod_scale_wire_seconds(
        hq_row["axis_bytes"], HIER_TOY_SIZES, HIER_POD_SIZES,
        cal["gbytes_per_s"], calibration="measured")
    wire_cal_shape_ok = bool(
        set(cal["gbytes_per_s"]) == {"inter", "intra"}
        and all(np.isfinite(v) and v > 0
                for v in cal["gbytes_per_s"].values())
        and all(r["seconds_per_round"] > 0 for r in cal["rows"])
        and cal_pod["calibration"] == "measured")
    rows.append({
        "phase": "wire-calibration",
        "calibration": cal["calibration"],
        "backend": cal["backend"],
        "measured_gbytes_per_s": cal["gbytes_per_s"],
        "declared_gbytes_per_s": HIER_GBPS,
        "divergence_vs_declared": cal["divergence_vs_declared"],
        "per_payload_rows": cal["rows"],
        "wire_cost_pod_measured": cal_pod,
        "pod_shape": pod_arg,
        "shape_ok": wire_cal_shape_ok,
    })

    # ---- fused computation-collective kernels (ISSUE 18 tentpole):
    # zero_collective_impl=fused rides the hierarchical transport
    # twins for bucket payloads and consumes qwZ matmul leaves
    # MID-GATHER (ops/fused_collective_matmul.py — on CPU the bitwise
    # reference twin; the streamed/Pallas schedules carry the audit
    # and wall-clock evidence). Gates: engine bitwise vs native on the
    # plain AND quantized wire, the auditor's in-kernel tier scoring
    # >= 1 subsumed permute+dot pair where the unfused program scores
    # 0, fused <= unfused wall clock at the largest rig payload, 3-D
    # mesh bookkeeping at the 16x16 pod factoring, and the 16-device
    # fused parity legs.
    FUSED = {"zero_collective_impl": "fused", "zero_mesh_shape": [2, 4],
             "zero_mesh_axis_roles": ["data", "data"]}

    # (a) plain wire: fused transports are the hierarchical twins —
    # bitwise vs the native AND hierarchical engines
    f_row, f_losses, f_params = hier_run("zero3-audit-fused", **FUSED)
    f_fused_bytes = comms.fused_bytes_summary()
    f_row["fused_permute_bytes"] = f_fused_bytes
    fused_parity_plain = (f_losses == losses[True] and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(f_params, params[True])))
    fused_bitwise_hier = (f_losses == h_losses and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(f_params, h_params)))

    # (b) quantized wire + mid-gather consumption: qwZ mm-leaves ship
    # as raw (int8, scales) shard pairs and fold through the fused
    # gather-matmul at the Dense; the cotangent bucket folds through
    # the fused quant-EF + qrs-exchange epilogue — still bitwise vs
    # the native quantized-wire engine
    fq_row, fq_losses, fq_params = hier_run(
        "zero3-audit-fused-qwire",
        zero_quantized_reduce_scatter=True,
        zero_reduce_scatter_error_feedback=True,
        zero_quantized_weights_fused_matmul=True, **FUSED)
    fq_fused_bytes = comms.fused_bytes_summary()
    fq_row["fused_permute_bytes"] = fq_fused_bytes
    fused_parity_qwire = (fq_losses == q_losses[True] and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(fq_params, q_params[True])))
    fused_mid_gather_leaves = fq_row.get("mid_gather_leaves", 0)
    rows.append({
        "phase": "fused-parity", "steps": 3,
        "bitwise_vs_native": fused_parity_plain,
        "bitwise_vs_hierarchical": fused_bitwise_hier,
        "qwire_bitwise_vs_native_qwire": fused_parity_qwire,
        "mid_gather_leaves": fused_mid_gather_leaves,
        "losses": f_losses,
        "fused_permute_bytes_qwire": fq_fused_bytes,
    })

    # (c) in-kernel audit tier: the STREAMED fused schedule (per ring
    # step, the next chunk's permute beside the resident chunk's
    # dequant-dot) compiled next to the unfused gather-then-matmul —
    # the fused module must score scoped subsumed pairs, the unfused
    # module must score zero
    from hcache_deepspeed_tpu.ops.fused_collective_matmul import (
        streamed_fused_gather_matmul)
    from hcache_deepspeed_tpu.ops.quantized_matmul import (
        quantize_for_matmul, quantized_matmul)
    fa_mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("d",))
    fwq, fws = quantize_for_matmul(
        jnp.asarray(rng.normal(size=(128, 64)), jnp.float32), 8)
    fx = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)

    def fgm_stream(xl, ql, sl):
        return streamed_fused_gather_matmul(xl, ql, sl, group_k=8,
                                            shard_dim=0, axis_name="d")

    def fgm_unfused(xl, ql, sl):
        qa = jax.lax.all_gather(ql, "d")
        sa = jax.lax.all_gather(sl, "d")
        return quantized_matmul(xl, qa.reshape(-1, 64),
                                sa.reshape(-1, 64), group_k=8)

    def _fused_audit(f):
        return audit_compiled(jax.jit(jax.shard_map(
            f, mesh=fa_mesh, in_specs=(P(), P("d"), P("d")),
            out_specs=P(), check_vma=False)).lower(fx, fwq,
                                                   fws).compile())

    aud_fused = _fused_audit(fgm_stream)
    aud_unfused = _fused_audit(fgm_unfused)
    fused_subsumed = aud_fused.fused_kernel["subsumed_pairs"]
    unfused_subsumed = aud_unfused.fused_kernel["subsumed_pairs"]
    fused_audit_gate = bool(fused_subsumed >= 1
                            and unfused_subsumed == 0)
    farow = aud_fused.to_row()
    farow.update({
        "phase": "fused-audit", "variant": "streamed",
        "fused_kernel": dict(aud_fused.fused_kernel),
        "unfused_subsumed_pairs": unfused_subsumed,
        "unfused_fused_wire_bytes":
            aud_unfused.fused_kernel["wire_bytes"],
        "audit_gate": fused_audit_gate,
    })
    rows.append(farow)

    # (d) wall-clock rig: streamed fused vs the native unfused
    # pipeline per payload (best-of-trials), with the qmm/fused
    # fallback counters snapshot riding in the row — on CPU the
    # counters record the deliberate reference dispatch
    from hcache_deepspeed_tpu.comm.benchmark import fused_vs_unfused_bench
    fb = fused_vs_unfused_bench(mesh=fa_mesh, axis="d", trials=3)
    fb_largest = max(fb["rows"], key=lambda r: r["k"] * r["n"])
    fused_wallclock_speedup = fb_largest["speedup"]
    fused_le_unfused_largest = fb["fused_le_unfused_largest"]
    rows.append(dict(fb, phase="fused-bench",
                     largest_payload=fb_largest))

    # (e) 3-D mesh composition: declared non-ZeRO axis roles — the
    # fused ring rides the data sub-box of a (data, model, pipe)
    # factoring; host-side bookkeeping gates at the 16x16 pod
    # factoring and a composed 3-D spec (rank/coord round-trips,
    # axis-group partitions, role sub-factoring)
    from hcache_deepspeed_tpu.comm.hierarchical import (
        mesh_bookkeeping_report)
    book_16x16 = mesh_bookkeeping_report(make_mesh_spec([16, 16]))
    book_3d = mesh_bookkeeping_report(make_mesh_spec(
        [4, 2, 2], ["data0", "model", "pipe"],
        axis_roles=["data", "model", "pipe"]))
    mesh3d_bookkeeping_ok = bool(book_16x16["ok"] and book_3d["ok"])
    fused_16dev = facts16.get("fused_bitwise", {}) \
        if isinstance(facts16, dict) else {}
    fused_16dev_parity = bool(fused_16dev.get("gather_matmul")
                              and fused_16dev.get("qrs_exchange"))
    rows.append({
        "phase": "fused-mesh3d",
        "bookkeeping_16x16": book_16x16,
        "bookkeeping_3d": book_3d,
        "bookkeeping_ok": mesh3d_bookkeeping_ok,
        "fused_16dev_bitwise": fused_16dev,
        "fused_16dev_parity": fused_16dev_parity,
    })

    # ---- Domino half-batch all-reduce, through the async-issue helper
    from hcache_deepspeed_tpu.runtime.domino import domino_split_async
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("tensor",))
    xd = jnp.asarray(rng.normal(size=(8, 16, 64)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)

    def domino_fn(overlap):
        def fn(x, a, b):
            return domino_split_async(
                lambda h: jax.nn.gelu(h @ a) @ b,
                lambda t: jax.lax.psum(t, "tensor"),
                x, overlap=overlap)
        return fn

    for overlap in (True, False):
        compiled = jax.jit(jax.shard_map(
            domino_fn(overlap), mesh=mesh,
            in_specs=(P(), P(None, "tensor"), P("tensor",)),
            out_specs=P(), check_vma=False)).lower(xd, w1, w2).compile()
        drep = audit_compiled(compiled)
        drow = drep.to_row()
        drow.update({"phase": "domino-audit", "overlap": overlap,
                     "helper": "domino_split_async"})
        rows.append(drow)

    # opt-in int8 wire for the Domino half-batch all-reduces: the
    # compiled module's collective buffers go s8/u8 (wire_bytes shows
    # the quantized portion) while the program stays overlappable
    def domino_q(x, a, b):
        y, _ = domino_split_async(
            lambda h: jax.nn.gelu(h @ a) @ b,
            lambda t: jax.lax.psum(t, "tensor"),
            x, overlap=True, wire_bits=8, axis="tensor")
        return y

    comms.reset()
    compiled = jax.jit(jax.shard_map(
        domino_q, mesh=mesh,
        in_specs=(P(), P(None, "tensor"), P("tensor",)),
        out_specs=P(), check_vma=False)).lower(xd, w1, w2).compile()
    drep = audit_compiled(compiled)
    drow = drep.to_row()
    drow.update({"phase": "domino-audit-int8", "overlap": True,
                 "helper": "domino_split_async",
                 "wire_savings": comms.wire_savings_summary()})
    rows.append(drow)

    # decomposed RS+AG rings for the half-batch all-reduces: the 2
    # derived-legal pairs overlap WITHOUT native async support — every
    # permute step of one half's ring is dependence-free of the other
    # half's dots by dataflow construction
    def domino_dec(x, a, b):
        return domino_split_async(
            lambda h: jax.nn.gelu(h @ a) @ b,
            lambda t: jax.lax.psum(t, "tensor"),
            x, overlap=True, collective_impl="decomposed",
            axis="tensor")

    comms.reset()
    compiled_dec = jax.jit(jax.shard_map(
        domino_dec, mesh=mesh,
        in_specs=(P(), P(None, "tensor"), P("tensor",)),
        out_specs=P(), check_vma=False)).lower(xd, w1, w2).compile()
    drep_dec = audit_compiled(compiled_dec)
    y_native = np.asarray(jax.jit(jax.shard_map(
        domino_fn(True), mesh=mesh,
        in_specs=(P(), P(None, "tensor"), P("tensor",)),
        out_specs=P(), check_vma=False))(xd, w1, w2))
    y_dec = np.asarray(compiled_dec(xd, w1, w2))
    domino_dec_pairs = len(drep_dec.pairs("collective-permute",
                                          min_interleaved=1))
    domino_dec_parity = bool(np.allclose(y_dec, y_native,
                                         rtol=1e-5, atol=1e-5))
    drow = drep_dec.to_row()
    drow.update({"phase": "domino-audit-decomposed", "overlap": True,
                 "helper": "domino_split_async",
                 "overlapped_pairs": domino_dec_pairs,
                 "value_parity_vs_native": domino_dec_parity,
                 "ring_permute_bytes": comms.permute_bytes_summary()})
    rows.append(drow)

    # hierarchical mesh rings for the half-batch all-reduces: the same
    # scheduler-independent overlap on the declared 2x4 factoring of
    # the tensor axis, with per-axis byte attribution
    from hcache_deepspeed_tpu.comm.hierarchical import make_mesh_spec
    domino_spec = make_mesh_spec([2, 4])

    def domino_hier(x, a, b):
        return domino_split_async(
            lambda h: jax.nn.gelu(h @ a) @ b,
            lambda t: jax.lax.psum(t, "tensor"),
            x, overlap=True, collective_impl="hierarchical",
            axis="tensor", mesh_spec=domino_spec)

    comms.reset()
    compiled_hier = jax.jit(jax.shard_map(
        domino_hier, mesh=mesh,
        in_specs=(P(), P(None, "tensor"), P("tensor",)),
        out_specs=P(), check_vma=False)).lower(xd, w1, w2).compile()
    drep_hier = audit_compiled(compiled_hier)
    y_hier = np.asarray(compiled_hier(xd, w1, w2))
    domino_hier_pairs = len(drep_hier.pairs("collective-permute",
                                            min_interleaved=1))
    domino_hier_parity = bool(np.allclose(y_hier, y_native,
                                          rtol=1e-5, atol=1e-5))
    domino_hier_bitwise_flat = bool(np.array_equal(y_hier, y_dec))
    drow = drep_hier.to_row()
    drow.update({"phase": "domino-audit-hierarchical", "overlap": True,
                 "helper": "domino_split_async",
                 "mesh_spec": domino_spec.describe(),
                 "overlapped_pairs": domino_hier_pairs,
                 "value_parity_vs_native": domino_hier_parity,
                 "bitwise_vs_flat_rings": domino_hier_bitwise_flat,
                 "ring_permute_axis_bytes": comms.permute_axis_bytes()})
    rows.append(drow)

    summary = {
        "phase": "summary",
        "metric": "zero3 2-layer toy: overlappable all-gather pairs "
                  "(prefetch on)",
        "value": len(on_pairs),
        "unit": "pairs",
        "prefetch_on_gather_pairs": len(on_pairs),
        "prefetch_off_gather_pairs": len(off_pairs),
        "gather_overlap_ratio_on": on["gather_overlap_ratio"],
        "gather_overlap_ratio_off": off["gather_overlap_ratio"],
        "reduce_overlap_ratio_on": on["reduce_overlap_ratio"],
        "reduce_overlap_ratio_off": off["reduce_overlap_ratio"],
        "native_async_pairs": on["native_async_pairs"],
        "bitwise_parity": bitwise,
        "qrs_wire_fraction_of_fp32": qrs_frac,
        "qrs_bitwise_depth_parity": q_bitwise,
        "qrs_trajectory_within_tol": traj_ok,
        "structural_overlap_ratio_decomposed": structural,
        "structural_overlap_ratio_decomposed_qwire": dq_structural,
        "decomposed_bitwise_vs_native": dec_bitwise,
        "decomposed_qwire_bitwise": dq_bitwise,
        "decomposed_structural_ge_native_gather": bool(
            structural >= on["gather_overlap_ratio"]),
        "decomposed_structural_ge_native_reduce": bool(
            structural >= on["reduce_overlap_ratio"]),
        "domino_decomposed_overlapped_pairs": domino_dec_pairs,
        "domino_decomposed_value_parity": domino_dec_parity,
        "hier_bitwise_vs_native": hier_bitwise_native,
        "hier_bitwise_vs_flat": hier_bitwise_flat,
        "hier_fullwidth_bitwise_vs_flat": hier_fw_bitwise_flat,
        "hier_qwire_bitwise": hier_qwire_bitwise,
        "hier_structural_overlap_ratio": hier_structural,
        "hier_structural_ge_flat": bool(hier_structural >= structural),
        "hier_interaxis_wire_fraction": hier_interaxis_fraction,
        "hier_longhaul_gather_fraction": lh_frac,
        "hier_longhaul_trajectory_within_tol": lh_traj_ok,
        "hier_pod_wire_seconds_inter": hier_cost_pod["per_axis"]
        .get("inter", {}).get("seconds"),
        "hier_pod_wire_seconds_intra": hier_cost_pod["per_axis"]
        .get("intra", {}).get("seconds"),
        "hier_pod_bottleneck_axis": hier_cost_pod["bottleneck_axis"],
        "domino_hier_overlapped_pairs": domino_hier_pairs,
        "domino_hier_value_parity": domino_hier_parity,
        # ISSUE 15: unified hpZ tiering, phase pipelining, 16-device
        # factorings, measured wire calibration
        "hier_hpz_unified_bitwise": hpz_unified_bitwise,
        "hier_hpz_fullwidth_bitwise": hpz_fw_bitwise,
        "hier_hpz_qw_bitwise": hpz_qw_bitwise,
        "hier_hpz_secondary_on_mesh": hpz_secondary_on_mesh,
        "hier_pipelined_bitwise": pipelined_bitwise,
        "hier_pipelined_structural_ratio": pipelined_structural,
        "hier_pipelined_cross_axis_pairs": prim_cross[2]["pairs"],
        "hier_unpipelined_cross_axis_pairs": prim_cross[1]["pairs"],
        "hier_16dev_parity": hier_16dev_parity,
        # ISSUE 18: fused computation-collective kernels + 3-D mesh
        "fused_parity_plain": fused_parity_plain,
        "fused_parity_qwire": fused_parity_qwire,
        "fused_bitwise_vs_hier": fused_bitwise_hier,
        "fused_mid_gather_leaves": fused_mid_gather_leaves,
        "fused_subsumed_pairs": fused_subsumed,
        "unfused_subsumed_pairs": unfused_subsumed,
        "fused_audit_gate": fused_audit_gate,
        "fused_wallclock_speedup": fused_wallclock_speedup,
        "fused_le_unfused_largest": fused_le_unfused_largest,
        "mesh3d_bookkeeping_ok": mesh3d_bookkeeping_ok,
        "fused_16dev_parity": fused_16dev_parity,
        "fused_fallbacks": fb["fused_fallbacks"],
        "wire_cal_shape_ok": wire_cal_shape_ok,
        "wire_cal_gbps_inter": cal["gbytes_per_s"].get("inter"),
        "wire_cal_gbps_intra": cal["gbytes_per_s"].get("intra"),
        "wire_cal_divergence_inter":
            cal["divergence_vs_declared"].get("inter"),
        "wire_cal_divergence_intra":
            cal["divergence_vs_declared"].get("intra"),
        "pod_shape": pod_arg,
        "wire_saved_bytes_per_op": {
            op: rec["saved_bytes"]
            for op, rec in qrs_row["wire_savings"].items()},
        "backend": jax.default_backend(),
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    rows.append(summary)
    # regression sentinel: self-compare against the committed
    # trajectory BEFORE writing — the verdicts ride in the artifact
    # (non-fatal here; `perf check` is the gate with an exit code)
    from hcache_deepspeed_tpu.perf import self_check_rows
    check_row = self_check_rows(out_path, rows)
    rows.append(check_row)
    if check_row.get("regressions"):
        print(f"[bench] perf-check: {len(check_row['regressions'])} "
              f"headline regression(s) vs committed trajectory: "
              + "; ".join(r["metric"]
                          for r in check_row["regressions"]),
              file=sys.stderr)
    with open(out_path, "w") as fh:
        for row in rows:
            fh.write(json.dumps(row) + "\n")
    print(json.dumps({
        "metric": summary["metric"], "value": summary["value"],
        "unit": "pairs",
        "vs_baseline": 0.0 if not bitwise else 1.0,
        "extra": {k: v for k, v in summary.items()
                  if k not in ("phase", "metric", "value", "unit")},
    }), flush=True)
    ok = (len(on_pairs) >= 1 and len(off_pairs) == 0 and bitwise
          and q_bitwise and traj_ok
          and qrs_frac is not None and qrs_frac <= 0.35
          and dec_bitwise and dq_bitwise
          and structural >= on["gather_overlap_ratio"]
          and structural >= on["reduce_overlap_ratio"]
          and domino_dec_pairs >= 2 and domino_dec_parity
          # hierarchical gates (ISSUE 12): bitwise vs native AND flat
          # for plain + quantized wire, inter-axis quantized bytes
          # <= 0.35x full width, structural >= the flat rings
          and hier_bitwise_native and hier_bitwise_flat
          and hier_fw_bitwise_flat and hier_qwire_bitwise
          and hier_interaxis_fraction is not None
          and hier_interaxis_fraction <= 0.35
          and hier_structural >= structural
          and lh_frac is not None and lh_frac <= 0.35 and lh_traj_ok
          and domino_hier_pairs >= 2 and domino_hier_parity
          and domino_hier_bitwise_flat
          # ISSUE 15 gates: unified hpZ bitwise (fullwidth + qwZ
          # transport swaps), secondary refresh attributed on the
          # mesh, pipelined bitwise + structural >= the PR 12 number
          # + cross-axis pairs only in the pipelined program, the
          # 16-device (4x4 / 2x8) parity leg, and a shape-valid
          # measured calibration row
          and hpz_unified_bitwise and hpz_secondary_on_mesh
          and pipelined_bitwise and pipelined_structural >= structural
          and pipelined_cross_ok
          and hier_16dev_parity and wire_cal_shape_ok
          # ISSUE 18 gates: fused engine bitwise on plain + quantized
          # wire with mid-gather leaves actually routed, the in-kernel
          # audit differential (fused >= 1 subsumed pair, unfused 0),
          # fused <= unfused at the largest rig payload, 3-D mesh
          # bookkeeping, and the 16-dev fused parity legs
          and fused_parity_plain and fused_parity_qwire
          and fused_mid_gather_leaves >= 1
          and fused_audit_gate and fused_le_unfused_largest
          and mesh3d_bookkeeping_ok and fused_16dev_parity)
    return 0 if ok else 4


def run_fleet(out_path="FLEET_SERVE.jsonl"):
    """``--fleet``: CPU-deterministic fleet-serving audit — the
    N-replica router + latent-based KV migration stack under seeded
    replica crash/hang/partition chaos on the shared virtual clock
    (docs/serving.md / docs/resilience.md). Emits per-replica
    occupancy, per-migration rows and a summary with the span-derived
    migration/decode overlap ratio; self-compares against the
    committed perf trajectory before writing, like the zero-overlap
    and serve_loop phases. Runs on the CPU."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from hcache_deepspeed_tpu.inference.benchmark import \
        run_fleet_serve
    try:
        results = run_fleet_serve(out=out_path)
    except RuntimeError as exc:
        print(json.dumps(_error_payload(f"fleet gate failed: {exc}")),
              flush=True)
        return 4
    summary = next(r for r in results
                   if r.get("phase") == "fleet-summary")
    print(json.dumps({
        "metric": "fleet chaos: latent migrations landed "
                  "(crash/hang/partition survived)",
        "value": summary["landings"] + summary["recompute_landings"],
        "unit": "migrations",
        "vs_baseline": 1.0 if summary["invariants_ok"] and
        summary["deterministic"] else 0.0,
        "extra": {k: summary[k] for k in
                  ("deterministic", "invariants_ok",
                   "migration_balance_ok", "evictions",
                   "migration_overlap_ratio", "span_overlap_ratio",
                   "replica_crashes", "replica_states")},
    }), flush=True)
    ok = (summary["invariants_ok"] and summary["deterministic"] and
          summary["migration_balance_ok"] and
          summary["span_counter_agreement"])
    return 0 if ok else 4


def run_disagg(out_path="DISAGG_SERVE.jsonl"):
    """``--disagg``: CPU-deterministic disaggregated-serving audit —
    the N-prefill + M-decode tier coordinator with latent-wire handoff
    vs an equal-replica colocated fleet on the shared virtual clock
    (docs/serving.md). Gates inline: decode-tier TPOT p99 strictly
    better than the colocated baseline, bitwise stream parity,
    span-derived handoff/decode overlap agreeing with the counters,
    byte-identical same-seed digests, int8-wire parity, chunked
    prefill accounting, and tier-scoped chaos invariants. Self-
    compares against the committed perf trajectory before writing.
    Runs on the CPU."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from hcache_deepspeed_tpu.inference.benchmark import \
        run_disagg_serve
    try:
        results = run_disagg_serve(out=out_path)
    except RuntimeError as exc:
        print(json.dumps(_error_payload(f"disagg gate failed: {exc}")),
              flush=True)
        return 4
    summary = next(r for r in results
                   if r.get("phase") == "disagg-summary")
    print(json.dumps({
        "metric": "disagg serving: decode-tier TPOT p99 vs "
                  "colocated baseline (equal replicas)",
        "value": round(summary["colocated_tpot_p99"] /
                       max(summary["decode_tier_tpot_p99"], 1e-12),
                       4),
        "unit": "x better",
        "vs_baseline": 1.0 if summary["invariants_ok"] and
        summary["deterministic"] else 0.0,
        "extra": {k: summary[k] for k in
                  ("deterministic", "stream_parity", "invariants_ok",
                   "handoffs", "colocated_decodes",
                   "handoff_overlap_ratio", "span_counter_agreement",
                   "decode_tier_tpot_p99", "colocated_tpot_p99")},
    }), flush=True)
    ok = (summary["invariants_ok"] and summary["deterministic"] and
          summary["stream_parity"] and
          summary["span_counter_agreement"] and
          summary["decode_tier_tpot_p99"] <
          summary["colocated_tpot_p99"])
    return 0 if ok else 4


def run_spec_serve(out_path="SPEC_SERVE.jsonl"):
    """``--spec-serve``: CPU-deterministic audit of scheduler-
    dispatched speculative decoding + fleet-wide radix prefix reuse
    with latent prefix broadcast (docs/serving.md). Gates inline:
    bitwise stream parity vs non-speculative greedy, accepted-tokens/
    step > 1.3 on the lookup-friendly trace, >= 1 latent prefix
    broadcast with positive re-prefill savings on the affinity-vs-
    load conflict trace, the SLO-aware ladder escalating under an
    unmeetable objective, and byte-identical two-run event digests.
    Self-compares against the committed perf trajectory before
    writing. Runs on the CPU."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from hcache_deepspeed_tpu.inference.benchmark import \
        run_spec_serve as run_ss
    try:
        results = run_ss(out=out_path)
    except RuntimeError as exc:
        print(json.dumps(_error_payload(
            f"spec-serve gate failed: {exc}")), flush=True)
        return 4
    summary = next(r for r in results
                   if r.get("phase") == "spec-serve-summary")
    print(json.dumps({
        "metric": "speculative serving: accepted tokens per "
                  "speculative lane-step (1.0 = non-speculative "
                  "floor)",
        "value": summary["accepted_tokens_per_step"],
        "unit": "tokens/step",
        "vs_baseline": 1.0 if summary["invariants_ok"] and
        summary["deterministic"] else 0.0,
        "extra": {k: summary[k] for k in
                  ("deterministic", "stream_parity",
                   "lookup_virtual_speedup", "mixed_virtual_speedup",
                   "reprefill_savings", "prefix_broadcasts",
                   "prefix_tokens_reused", "slo_final_level")},
    }), flush=True)
    ok = (summary["invariants_ok"] and summary["deterministic"] and
          summary["stream_parity"] and
          summary["accepted_tokens_per_step"] > 1.3 and
          summary["reprefill_savings"] > 0)
    return 0 if ok else 4


def run_fabric(out_path="FABRIC_SERVE.jsonl"):
    """``--fabric``: deployment-fabric audit — the seeded migration-
    heavy trace served through both replica transports (in-memory
    twin vs spawned worker processes shipping real bytes over real
    sockets; docs/fabric.md), plus the literal kill-a-process chaos
    leg. Gates inline: two-run digest determinism on the in-memory
    twin, digest invariance and bitwise token-stream parity across
    transports, >= 1 two-hop worker-to-worker crossing, measured wire
    throughput recorded beside the priced link, >= 2 trace hops
    across real process boundaries with a connected causal DAG, and
    crash recovery with never-dropped accounting. Self-compares
    against the committed perf trajectory before writing. Runs on the CPU."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from hcache_deepspeed_tpu.inference.benchmark import \
        run_fabric_serve
    try:
        results = run_fabric_serve(out=out_path)
    except RuntimeError as exc:
        print(json.dumps(_error_payload(
            f"fabric gate failed: {exc}")), flush=True)
        return 4
    summary = next(r for r in results
                   if r.get("phase") == "fabric-summary")
    print(json.dumps({
        "metric": "deployment fabric: real-wire deliveries with "
                  "digest/stream parity vs the in-memory twin",
        "value": summary["two_hop_deliveries"],
        "unit": "two-hop crossings",
        "vs_baseline": 1.0 if summary["invariants_ok"] and
        summary["deterministic"] else 0.0,
        "extra": {k: summary[k] for k in
                  ("deterministic", "digest_transport_invariant",
                   "stream_parity", "max_trace_hops",
                   "trace_connected", "measured_wire_bytes_per_s",
                   "priced_link_bytes_per_s", "chaos_ok",
                   "chaos_kills", "replica_crashes",
                   "bootstrap_mismatches")},
    }), flush=True)
    ok = (summary["invariants_ok"] and summary["deterministic"] and
          summary["stream_parity"] and
          summary["digest_transport_invariant"] and
          summary["chaos_ok"])
    return 0 if ok else 4


def run_fabric_obs(out_path="FABRIC_OBS.jsonl"):
    """``--fabric-obs``: cross-process telemetry-plane audit — worker
    span/metric harvest over the fabric control channel, assembled
    process-fleet timelines, SIGKILL postmortem telemetry, per-link
    wire percentiles (docs/observability.md). Gates inline: harvest
    on/off digest invariance against the in-memory twin, 2-run
    determinism, Perfetto-clean cross-process timeline with >= 1
    arrow spanning two real worker processes, the killed worker's
    last-harvested telemetry in the flight bundle, and harvest
    overhead <= 5% of the fabric leg. Self-compares against the
    committed perf trajectory before writing. Runs on the CPU."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from hcache_deepspeed_tpu.inference.benchmark import \
        run_fabric_obs as run_fo
    try:
        results = run_fo(out=out_path)
    except RuntimeError as exc:
        print(json.dumps(_error_payload(
            f"fabric-obs gate failed: {exc}")), flush=True)
        return 4
    summary = next(r for r in results
                   if r.get("phase") == "fabric-obs-summary")
    print(json.dumps({
        "metric": "cross-process telemetry plane: harvested worker "
                  "spans on a digest-invisible control channel",
        "value": summary["worker_spans"],
        "unit": "harvested spans",
        "vs_baseline": 1.0 if summary["invariants_ok"] and
        summary["harvest_digest_invariant"] else 0.0,
        "extra": {k: summary[k] for k in
                  ("deterministic", "harvest_digest_invariant",
                   "timeline_valid", "worker_rows",
                   "cross_worker_arrows",
                   "postmortem_has_telemetry",
                   "harvest_overhead_fraction", "harvests",
                   "chaos_ok", "busiest_link")},
    }), flush=True)
    ok = (summary["invariants_ok"] and summary["deterministic"] and
          summary["harvest_digest_invariant"] and
          summary["timeline_valid"] and
          summary["postmortem_has_telemetry"] and
          summary["chaos_ok"])
    return 0 if ok else 4


def run_request_trace(out_path="REQUEST_TRACE.jsonl"):
    """``--request-trace``: CPU-deterministic causal-tracing audit —
    replay the chaos/fleet/disagg workloads and gate connected
    cross-replica span DAGs, per-request attribution closure (sum ==
    measured E2E within 1%), same-seed digest determinism, and
    byte-identical flight-recorder bundle digests
    (docs/observability.md). Self-compares against the committed perf
    trajectory before writing. Runs on the CPU."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from hcache_deepspeed_tpu.inference.benchmark import \
        run_request_trace as run_rt
    try:
        results = run_rt(out=out_path)
    except RuntimeError as exc:
        print(json.dumps(_error_payload(
            f"request-trace gate failed: {exc}")), flush=True)
        return 4
    summary = next(r for r in results
                   if r.get("phase") == "request-trace-summary")
    print(json.dumps({
        "metric": "causal request tracing: traced requests with "
                  "connected DAGs + closed attribution",
        "value": summary["traced_requests"],
        "unit": "requests",
        "vs_baseline": 1.0 if summary["dag_connected"] and
        summary["closure_ok"] else 0.0,
        "extra": {k: summary[k] for k in
                  ("dag_connected", "closure_ok",
                   "closure_max_residual", "deterministic",
                   "flight_deterministic", "flight_bundles",
                   "crash_evacuations", "handoffs",
                   "ttft_attr_p99_s")},
    }), flush=True)
    ok = (summary["dag_connected"] and summary["closure_ok"] and
          summary["deterministic"] and
          summary["flight_deterministic"] and
          not summary["violations"])
    return 0 if ok else 4


def run_autoscale(out_path="AUTOSCALE_SERVE.jsonl"):
    """``--autoscale``: SLO-driven elastic autoscaling audit — the
    hysteresis control loop over the bursty diurnal multi-tenant
    trace, with scale events as a first-class failure domain
    (docs/serving.md). Gates inline: 2-run digest determinism with
    the autoscaler active, SLO attainment >= the best static fleet of
    equal peak size at strictly lower replica-step cost, every scale
    event span-verified through the causal trace DAG, scale-event
    chaos (aborted bootstrap / mid-drain crash / faulted pre-warm)
    with byte-identical replays, and a process-mode leg where a real
    worker is spawned by scale-up (first spawn killed and recovered)
    and reaped on drain-retirement. Self-compares against the
    committed perf trajectory before writing. Runs on the CPU."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from hcache_deepspeed_tpu.inference.benchmark import \
        run_autoscale_serve
    try:
        results = run_autoscale_serve(out=out_path)
    except RuntimeError as exc:
        print(json.dumps(_error_payload(
            f"autoscale gate failed: {exc}")), flush=True)
        return 4
    summary = next(r for r in results
                   if r.get("phase") == "autoscale-summary")
    print(json.dumps({
        "metric": "elastic autoscaling: SLO attainment at lower cost "
                  "than the equal-peak static fleet",
        "value": summary["slo_attainment"],
        "unit": "attainment fraction",
        "vs_baseline": 1.0 if summary["invariants_ok"] and
        summary["deterministic"] else 0.0,
        "extra": {k: summary[k] for k in
                  ("deterministic", "slo_vs_static_ok",
                   "cost_vs_static_ok", "cost_savings_fraction",
                   "cost_replica_steps", "static_peak_cost",
                   "scale_ups", "retires_completed", "flaps",
                   "scale_events_span_verified",
                   "chaos_deterministic", "chaos_invariants_ok",
                   "process_ok", "trace_connected")},
    }), flush=True)
    ok = (summary["invariants_ok"] and summary["deterministic"] and
          summary["slo_vs_static_ok"] and
          summary["cost_vs_static_ok"] and
          summary["chaos_invariants_ok"] and summary["process_ok"])
    return 0 if ok else 4


def main():
    if "--zero-overlap" in sys.argv[1:]:
        return run_zero_overlap()
    if "--fleet" in sys.argv[1:]:
        return run_fleet()
    if "--disagg" in sys.argv[1:]:
        return run_disagg()
    if "--spec-serve" in sys.argv[1:]:
        return run_spec_serve()
    if "--fabric-obs" in sys.argv[1:]:
        return run_fabric_obs()
    if "--fabric" in sys.argv[1:]:
        return run_fabric()
    if "--request-trace" in sys.argv[1:]:
        return run_request_trace()
    if "--autoscale" in sys.argv[1:]:
        return run_autoscale()
    return run_training()


if __name__ == "__main__":
    sys.exit(main())
