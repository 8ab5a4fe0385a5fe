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

The flags (``--fleet``, ``--disagg``, ``--spec-serve``, ``--fabric``,
``--fabric-obs``, ``--request-trace``, ``--autoscale``) are CPU
emitters of simulation artifacts; they measure no device.
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


def run_fleet(out_path="FLEET_SERVE.jsonl"):
    """``--fleet``: CPU-deterministic fleet-serving audit — the
    N-replica router + latent-based KV migration stack under seeded
    replica crash/hang/partition chaos on the shared virtual clock
    (docs/serving.md / docs/resilience.md). Emits per-replica
    occupancy, per-migration rows and a summary with the span-derived
    migration/decode overlap ratio; self-compares against the
    committed perf trajectory before writing, like the serve_loop
    phase. Runs on the CPU."""
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
