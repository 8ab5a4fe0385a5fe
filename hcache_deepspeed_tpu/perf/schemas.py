"""Artifact-family schemas for the committed perf evidence.

Every perf artifact this repo commits at its root (bench JSON
results, ``*_JSONL`` phase streams, the one cited chip log) belongs to
exactly one **family** declared here: a filename
pattern plus a parser that turns the file into typed
:class:`MetricPoint` rows. The registry (``perf.registry``) walks the
root through :func:`classify`; the golden-schema tier-1 test walks the
same way and fails when a committed artifact matches no family and is
not allowlisted in ``perf/KNOWN_UNINDEXED`` — so future PRs cannot
silently add unindexed evidence files, and ``perf lint`` applies the
same rule to artifact names written by source code.

Parsers are deliberately tolerant of the artifacts' real-world warts
(log lines interleaved into JSONL streams, rows embedded in a captured
``tail`` field, zero-byte files from interrupted runs) but
STRICT about classification: an unknown name is an error, a known name
that fails to parse is an error, an empty file is recorded as
``status="empty"`` — visible, never silently skipped.
"""

import json
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

@dataclass
class MetricPoint:
    """One indexed measurement."""
    metric: str                  # e.g. "train.tokens_per_sec_per_chip"
    value: float
    file: str
    unit: str = ""
    phase: str = ""
    #: measurement timestamp when the artifact carries one
    utc: Optional[str] = None
    tags: Dict[str, str] = field(default_factory=dict)

    def to_json(self) -> Dict:
        out = {"metric": self.metric, "value": self.value,
               "file": self.file}
        if self.unit:
            out["unit"] = self.unit
        if self.phase:
            out["phase"] = self.phase
        if self.utc:
            out["utc"] = self.utc
        if self.tags:
            out["tags"] = dict(self.tags)
        return out


@dataclass
class ParsedArtifact:
    file: str
    family: str
    status: str                      # "ok" | "empty" | "meta"
    points: List[MetricPoint] = field(default_factory=list)
    #: artifact-level note (e.g. why it yields no points)
    note: str = ""


# ----------------------------------------------------------------- #
# raw readers
# ----------------------------------------------------------------- #
def read_json(text: str):
    return json.loads(text)


def read_jsonl_rows(text: str) -> List[Dict]:
    """Every parseable JSON object line; the committed streams carry
    interleaved engine log lines (``[2026-08-01 ...] [INFO] ...``) that
    a strict reader would choke on."""
    rows = []
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(row, dict):
            rows.append(row)
    return rows


# ----------------------------------------------------------------- #
# family parsers — each returns a list of MetricPoint
# ----------------------------------------------------------------- #
def _bench_payload_points(payload: Dict, file: str) -> List[MetricPoint]:
    """Points from one bench.py training result line."""
    pts: List[MetricPoint] = []
    if not isinstance(payload, dict):
        return pts
    extra = payload.get("extra") or {}
    utc = extra.get("utc")
    value = payload.get("value")
    if "metric" in payload and isinstance(value, (int, float)):
        cfg = str(extra.get("config", ""))
        tags = {"config": cfg} if cfg else {}
        if value:
            pts.append(MetricPoint(
                "train.tokens_per_sec_per_chip", float(value), file,
                unit=payload.get("unit", "tokens/sec"),
                phase="train-bench", utc=utc, tags=tags))
        if isinstance(extra.get("mfu"), (int, float)) and extra["mfu"]:
            pts.append(MetricPoint(
                "train.mfu", float(extra["mfu"]), file,
                phase="train-bench", utc=utc, tags=tags))
        if isinstance(payload.get("vs_baseline"), (int, float)) and \
                payload["vs_baseline"]:
            pts.append(MetricPoint(
                "train.vs_baseline", float(payload["vs_baseline"]),
                file, phase="train-bench", utc=utc, tags=tags))
    return pts


def parse_bench_result(text: str, file: str) -> List[MetricPoint]:
    """Single bench payload (BENCH_FRESH/BENCH_LOCAL/VET_*): either a
    result line or a vet-error record ({config, error, mfu: null})."""
    doc = read_json(text)
    if "metric" not in doc and "error" in doc:
        # vet error: indexed as a zero-valued outcome gauge so the
        # failed-config evidence is queryable, not just archived
        return [MetricPoint("vet.ok", 0.0, file, phase="config-vet",
                            tags={"config": str(doc.get("config", ""))})]
    pts = _bench_payload_points(doc, file)
    if doc.get("metric") and not doc.get("error"):
        cfg = str((doc.get("extra") or {}).get("config", ""))
        pts.append(MetricPoint("vet.ok", 1.0, file, phase="config-vet",
                               tags={"config": cfg} if cfg else {}))
    return pts


def parse_baseline_meta(text: str, file: str) -> List[MetricPoint]:
    read_json(text)          # must parse; carries no metric points
    return []


def parse_zero_overlap(text: str, file: str) -> List[MetricPoint]:
    rows = read_jsonl_rows(text)
    pts: List[MetricPoint] = []
    for row in rows:
        phase = row.get("phase", "")
        if phase == "summary":
            utc = row.get("utc")
            for key, metric in (
                    ("gather_overlap_ratio_on",
                     "zero_overlap.gather_overlap_ratio"),
                    ("reduce_overlap_ratio_on",
                     "zero_overlap.reduce_overlap_ratio"),
                    ("prefetch_on_gather_pairs",
                     "zero_overlap.gather_pairs"),
                    ("native_async_pairs",
                     "zero_overlap.native_async_pairs"),
                    ("qrs_wire_fraction_of_fp32",
                     "zero_overlap.qrs_wire_fraction_of_fp32"),
                    ("structural_overlap_ratio_decomposed",
                     "zero_overlap.structural_overlap_ratio"),
                    ("domino_decomposed_overlapped_pairs",
                     "domino.decomposed_overlapped_pairs"),
                    ("hier_structural_overlap_ratio",
                     "zero_overlap.hier_structural_overlap_ratio"),
                    ("hier_interaxis_wire_fraction",
                     "zero_overlap.hier_interaxis_wire_fraction"),
                    ("hier_longhaul_gather_fraction",
                     "zero_overlap.hier_longhaul_gather_fraction"),
                    ("hier_pod_wire_seconds_inter",
                     "zero_overlap.hier_pod_wire_seconds_inter"),
                    ("hier_pod_wire_seconds_intra",
                     "zero_overlap.hier_pod_wire_seconds_intra"),
                    ("domino_hier_overlapped_pairs",
                     "domino.hier_overlapped_pairs"),
                    ("hier_pipelined_structural_ratio",
                     "zero_overlap.hier_pipelined_structural_ratio"),
                    ("hier_pipelined_cross_axis_pairs",
                     "zero_overlap.hier_pipelined_cross_axis_pairs"),
                    ("wire_cal_gbps_inter",
                     "zero_overlap.wire_cal_gbps_inter"),
                    ("wire_cal_gbps_intra",
                     "zero_overlap.wire_cal_gbps_intra"),
                    ("wire_cal_divergence_inter",
                     "zero_overlap.wire_cal_divergence_inter"),
                    ("wire_cal_divergence_intra",
                     "zero_overlap.wire_cal_divergence_intra"),
                    ("fused_subsumed_pairs",
                     "zero_overlap.fused_subsumed_pairs"),
                    ("fused_mid_gather_leaves",
                     "zero_overlap.fused_mid_gather_leaves"),
                    ("fused_wallclock_speedup",
                     "zero_overlap.fused_wallclock_speedup")):
                if isinstance(row.get(key), (int, float)):
                    pts.append(MetricPoint(metric, float(row[key]),
                                           file, phase=phase, utc=utc))
            for key, metric in (
                    ("bitwise_parity", "zero_overlap.bitwise_parity"),
                    ("qrs_bitwise_depth_parity",
                     "zero_overlap.qrs_bitwise_depth_parity"),
                    ("qrs_trajectory_within_tol",
                     "zero_overlap.qrs_trajectory_within_tol"),
                    ("decomposed_bitwise_vs_native",
                     "zero_overlap.decomposed_bitwise_vs_native"),
                    ("decomposed_qwire_bitwise",
                     "zero_overlap.decomposed_qwire_bitwise"),
                    ("domino_decomposed_value_parity",
                     "domino.decomposed_value_parity"),
                    ("hier_bitwise_vs_native",
                     "zero_overlap.hier_bitwise_vs_native"),
                    ("hier_bitwise_vs_flat",
                     "zero_overlap.hier_bitwise_vs_flat"),
                    ("hier_qwire_bitwise",
                     "zero_overlap.hier_qwire_bitwise"),
                    ("hier_longhaul_trajectory_within_tol",
                     "zero_overlap.hier_longhaul_trajectory_within_tol"),
                    ("domino_hier_value_parity",
                     "domino.hier_value_parity"),
                    ("hier_hpz_unified_bitwise",
                     "zero_overlap.hier_hpz_unified_bitwise"),
                    ("hier_hpz_secondary_on_mesh",
                     "zero_overlap.hier_hpz_secondary_on_mesh"),
                    ("hier_pipelined_bitwise",
                     "zero_overlap.hier_pipelined_bitwise"),
                    ("hier_16dev_parity",
                     "zero_overlap.hier_16dev_parity"),
                    ("wire_cal_shape_ok",
                     "zero_overlap.wire_cal_shape_ok"),
                    ("fused_parity_plain",
                     "zero_overlap.fused_parity_plain"),
                    ("fused_parity_qwire",
                     "zero_overlap.fused_parity_qwire"),
                    ("fused_audit_gate",
                     "zero_overlap.fused_audit_gate"),
                    ("fused_le_unfused_largest",
                     "zero_overlap.fused_le_unfused_largest"),
                    ("mesh3d_bookkeeping_ok",
                     "zero_overlap.mesh3d_bookkeeping_ok"),
                    ("fused_16dev_parity",
                     "zero_overlap.fused_16dev_parity")):
                if key in row:
                    pts.append(MetricPoint(metric,
                                           1.0 if row[key] else 0.0,
                                           file, phase=phase, utc=utc))
        elif phase in ("domino-audit", "domino-audit-int8") and \
                row.get("overlap"):
            suffix = "int8" if phase.endswith("int8") else "fp"
            if "derived_async_pairs" in row:
                pts.append(MetricPoint(
                    f"domino.derived_async_pairs_{suffix}",
                    float(row["derived_async_pairs"]), file,
                    phase=phase))
    return pts


def parse_serve_loop(text: str, file: str) -> List[MetricPoint]:
    rows = read_jsonl_rows(text)
    pts: List[MetricPoint] = []
    for row in rows:
        if row.get("phase") == "serve-loop-summary":
            # the workload identity rides as the config tag so a
            # differently-shaped trace (smoke run, other rps) is never
            # gated against the committed acceptance trace
            tags = {"model": str(row.get("model", "")),
                    "config": (
                        f"{row.get('model', '')}"
                        f"-n{row.get('n_requests', '')}"
                        f"-rps{row.get('rps', '')}"
                        f"-p{row.get('prompt_len', '')}"
                        f"-new{row.get('max_new', '')}"
                        f"-kv{row.get('kv_blocks', '')}"
                        f"x{row.get('block_size', '')}"
                        f"-vc{int(bool(row.get('virtual_clock')))}")}
            for fam, lower in (("ttft_s", True), ("tpot_s", True),
                               ("queue_wait_s", True)):
                block = row.get(fam) or {}
                for q in ("p50", "p99"):
                    if isinstance(block.get(q), (int, float)):
                        pts.append(MetricPoint(
                            f"serve_loop.{fam}_{q}", float(block[q]),
                            file, unit="s", phase="serve-loop",
                            tags=tags))
            for key, metric in (
                    ("gen_tokens_per_sec",
                     "serve_loop.gen_tokens_per_sec"),
                    ("restore_overlap_ratio",
                     "serve_loop.restore_overlap_ratio"),
                    ("preemptions", "serve_loop.preemptions"),
                    ("restores", "serve_loop.restores"),
                    ("dropped", "serve_loop.dropped")):
                if isinstance(row.get(key), (int, float)):
                    pts.append(MetricPoint(metric, float(row[key]),
                                           file, phase="serve-loop",
                                           tags=tags))
            parity = row.get("parity") or {}
            if parity.get("checked"):
                pts.append(MetricPoint(
                    "serve_loop.restore_parity_ok",
                    1.0 if parity["ok"] == parity["checked"] else 0.0,
                    file, phase="serve-loop", tags=tags))
        elif row.get("phase") == "serve-loop-slo":
            for name, v in (row.get("burn_rates") or {}).items():
                pts.append(MetricPoint(
                    f"serve_loop.slo_{name}_burn_rate", float(v),
                    file, phase="serve-loop-slo"))
            if "prometheus_valid" in row:
                pts.append(MetricPoint(
                    "serve_loop.prometheus_snapshot_valid",
                    1.0 if row["prometheus_valid"] else 0.0, file,
                    phase="serve-loop-slo"))
    return pts


def parse_chaos_serve(text: str, file: str) -> List[MetricPoint]:
    rows = read_jsonl_rows(text)
    pts: List[MetricPoint] = []
    for row in rows:
        if row.get("phase") == "chaos-summary":
            pts.append(MetricPoint(
                "chaos.deterministic",
                1.0 if row.get("deterministic") else 0.0, file,
                phase="chaos-summary"))
            pts.append(MetricPoint(
                "chaos.invariants_ok",
                1.0 if row.get("invariants_ok") else 0.0, file,
                phase="chaos-summary"))
            pts.append(MetricPoint(
                "chaos.violations", float(len(row.get("violations",
                                                      []))),
                file, phase="chaos-summary"))
        elif row.get("phase") == "chaos-ckpt":
            pts.append(MetricPoint(
                "chaos.ckpt_fallback_ok",
                1.0 if row.get("fallback_ok") else 0.0, file,
                phase="chaos-ckpt"))
    return pts


def parse_fleet_serve(text: str, file: str) -> List[MetricPoint]:
    rows = read_jsonl_rows(text)
    pts: List[MetricPoint] = []
    for row in rows:
        phase = row.get("phase", "")
        if phase == "fleet-summary":
            for key, metric in (
                    ("deterministic", "fleet.deterministic"),
                    ("invariants_ok", "fleet.invariants_ok"),
                    ("migration_balance_ok",
                     "fleet.migration_balance_ok"),
                    ("span_counter_agreement",
                     "fleet.span_counter_agreement")):
                if key in row:
                    pts.append(MetricPoint(metric,
                                           1.0 if row[key] else 0.0,
                                           file, phase=phase))
            for key, metric in (
                    ("migration_overlap_ratio",
                     "fleet.migration_overlap_ratio"),
                    ("span_overlap_ratio",
                     "fleet.span_overlap_ratio"),
                    ("evictions", "fleet.evictions"),
                    ("landings", "fleet.landings"),
                    ("recompute_landings", "fleet.recompute_landings"),
                    ("expired_in_transit",
                     "fleet.expired_in_transit"),
                    ("replica_crashes", "fleet.replica_crashes")):
                if isinstance(row.get(key), (int, float)):
                    pts.append(MetricPoint(metric, float(row[key]),
                                           file, phase=phase))
            pts.append(MetricPoint(
                "fleet.violations",
                float(len(row.get("violations", []))), file,
                phase=phase))
        elif phase == "fleet-replica":
            tags = {"replica": str(row.get("replica", "")),
                    "state": str(row.get("state", ""))}
            for key, metric in (
                    ("mean_occupancy", "fleet.replica_mean_occupancy"),
                    ("kv_util_peak", "fleet.replica_kv_util_peak"),
                    ("restores", "fleet.replica_restores"),
                    ("preemptions", "fleet.replica_preemptions")):
                if isinstance(row.get(key), (int, float)):
                    pts.append(MetricPoint(metric, float(row[key]),
                                           file, phase=phase,
                                           tags=tags))
    return pts


def parse_disagg_serve(text: str, file: str) -> List[MetricPoint]:
    rows = read_jsonl_rows(text)
    pts: List[MetricPoint] = []
    for row in rows:
        phase = row.get("phase", "")
        if phase == "disagg-summary":
            for key, metric in (
                    ("deterministic", "disagg.deterministic"),
                    ("stream_parity", "disagg.stream_parity"),
                    ("invariants_ok", "disagg.invariants_ok"),
                    ("span_counter_agreement",
                     "disagg.span_counter_agreement")):
                if key in row:
                    pts.append(MetricPoint(metric,
                                           1.0 if row[key] else 0.0,
                                           file, phase=phase))
            for key, metric in (
                    ("handoff_overlap_ratio",
                     "disagg.handoff_overlap_ratio"),
                    ("handoffs", "disagg.handoffs"),
                    ("colocated_decodes", "disagg.colocated_decodes"),
                    ("decode_tier_tpot_p95",
                     "disagg.decode_tier_tpot_p95"),
                    ("decode_tier_tpot_p99",
                     "disagg.decode_tier_tpot_p99"),
                    ("colocated_tpot_p99",
                     "disagg.colocated_tpot_p99")):
                if isinstance(row.get(key), (int, float)):
                    pts.append(MetricPoint(metric, float(row[key]),
                                           file, phase=phase))
            d99 = row.get("decode_tier_tpot_p99")
            c99 = row.get("colocated_tpot_p99")
            if isinstance(d99, (int, float)) and \
                    isinstance(c99, (int, float)) and d99 > 0:
                # the headline: how much better the decode tier's
                # tail is than the equal-replica colocated baseline
                # (> 1.0 = disagg wins; the bench hard-gates it)
                pts.append(MetricPoint(
                    "disagg.decode_tpot_p99_speedup",
                    round(c99 / d99, 6), file, unit="x",
                    phase=phase))
            pts.append(MetricPoint(
                "disagg.violations",
                float(len(row.get("violations", []))), file,
                phase=phase))
        elif phase == "disagg-int8-wire":
            if "stream_parity_vs_fullwidth" in row:
                pts.append(MetricPoint(
                    "disagg.int8_wire_stream_parity",
                    1.0 if row["stream_parity_vs_fullwidth"]
                    else 0.0, file, phase=phase))
            if isinstance(row.get("wire_fraction"), (int, float)):
                pts.append(MetricPoint(
                    "disagg.int8_wire_fraction",
                    float(row["wire_fraction"]), file, phase=phase))
        elif phase == "disagg-chunked-prefill":
            if isinstance(row.get("prefill_chunks"), (int, float)):
                pts.append(MetricPoint(
                    "disagg.prefill_chunks",
                    float(row["prefill_chunks"]), file, phase=phase))
            if "invariants_ok" in row:
                pts.append(MetricPoint(
                    "disagg.chunked_invariants_ok",
                    1.0 if row["invariants_ok"] else 0.0, file,
                    phase=phase))
        elif phase == "disagg-chaos":
            for key, metric in (
                    ("deterministic", "disagg.chaos_deterministic"),
                    ("invariants_ok", "disagg.chaos_invariants_ok")):
                if key in row:
                    pts.append(MetricPoint(metric,
                                           1.0 if row[key] else 0.0,
                                           file, phase=phase))
        elif phase == "disagg-tier":
            tags = {"tier": str(row.get("tier", ""))}
            for key, metric in (
                    ("preemptions", "disagg.tier_preemptions"),
                    ("restores", "disagg.tier_restores"),
                    ("mean_occupancy",
                     "disagg.tier_mean_occupancy")):
                if isinstance(row.get(key), (int, float)):
                    pts.append(MetricPoint(metric, float(row[key]),
                                           file, phase=phase,
                                           tags=tags))
    return pts


def parse_request_trace(text: str, file: str) -> List[MetricPoint]:
    """REQUEST_TRACE.jsonl: fleet-wide causal-tracing gates — DAG
    connectivity, attribution closure, run/flight determinism, and
    the p99 TTFT attribution profile."""
    rows = read_jsonl_rows(text)
    pts: List[MetricPoint] = []
    for row in rows:
        phase = row.get("phase", "")
        if phase == "request-trace-summary":
            utc = row.get("utc")
            for key, metric in (
                    ("dag_connected", "request_trace.dag_connected"),
                    ("closure_ok", "request_trace.closure_ok"),
                    ("deterministic", "request_trace.deterministic"),
                    ("flight_deterministic",
                     "request_trace.flight_deterministic")):
                if key in row:
                    pts.append(MetricPoint(metric,
                                           1.0 if row[key] else 0.0,
                                           file, phase=phase, utc=utc))
            for key, metric in (
                    ("closure_max_residual",
                     "request_trace.closure_max_residual"),
                    ("flight_bundles", "request_trace.flight_bundles"),
                    ("handoffs", "request_trace.handoffs"),
                    ("crash_evacuations",
                     "request_trace.crash_evacuations"),
                    ("traced_requests",
                     "request_trace.traced_requests"),
                    ("ttft_p99_s", "request_trace.ttft_p99_s")):
                if isinstance(row.get(key), (int, float)):
                    pts.append(MetricPoint(metric, float(row[key]),
                                           file, phase=phase, utc=utc))
            # the headline p99-TTFT attribution profile: seconds per
            # phase at the 99th percentile across the traced requests
            for attr_phase, v in sorted(
                    (row.get("ttft_attr_p99_s") or {}).items()):
                if isinstance(v, (int, float)):
                    pts.append(MetricPoint(
                        f"request_trace.ttft_attr_{attr_phase}_p99_s",
                        float(v), file, unit="s", phase=phase,
                        utc=utc))
            pts.append(MetricPoint(
                "request_trace.violations",
                float(len(row.get("violations", []))), file,
                phase=phase, utc=utc))
        elif phase == "request-trace-leg":
            tags = {"leg": str(row.get("leg", ""))}
            for key, metric in (
                    ("deterministic", "request_trace.leg_deterministic"),
                    ("connected", "request_trace.leg_connected"),
                    ("flight_deterministic",
                     "request_trace.leg_flight_deterministic")):
                if key in row:
                    pts.append(MetricPoint(metric,
                                           1.0 if row[key] else 0.0,
                                           file, phase=phase,
                                           tags=tags))
            for key, metric in (
                    ("max_closure_residual",
                     "request_trace.leg_max_closure_residual"),
                    ("flight_bundles",
                     "request_trace.leg_flight_bundles")):
                if isinstance(row.get(key), (int, float)):
                    pts.append(MetricPoint(metric, float(row[key]),
                                           file, phase=phase,
                                           tags=tags))
    return pts


def _workload_tag(file: str) -> Dict[str, str]:
    """The workload identity is the filename stem — SERVE_7B_INT8 and
    SERVE_7B measure different programs and must never be compared as
    one series."""
    return {"workload": file.rsplit(".", 1)[0]}


def parse_serve_bench(text: str, file: str) -> List[MetricPoint]:
    """SERVE_* / DECODE_DIAG_* / LOOKUP_* / SWEEP_* phase rows from
    ``inference/benchmark.py``."""
    rows = read_jsonl_rows(text)
    tags = _workload_tag(file)
    pts: List[MetricPoint] = []
    for row in rows:
        phase = row.get("phase", "")
        if "error" in row:
            continue                     # OOM/fallback notes, not data
        rtags = dict(tags)
        if "batch" in row:
            rtags["batch"] = str(row["batch"])
        if "offered_rps" in row:
            rtags["offered_rps"] = str(row["offered_rps"])
        if "lanes" in row:
            rtags["lanes"] = str(row["lanes"])
        if "variant" in row:
            rtags["variant"] = str(row["variant"])
        if isinstance(row.get("tokens_per_sec"), (int, float)):
            pts.append(MetricPoint(
                f"serve.{phase}.tokens_per_sec",
                float(row["tokens_per_sec"]), file,
                unit="tokens/sec", phase=phase, tags=rtags))
        if isinstance(row.get("ms_per_step"), (int, float)):
            pts.append(MetricPoint(
                f"serve.{phase}.ms_per_step",
                float(row["ms_per_step"]), file, unit="ms",
                phase=phase, tags=rtags))
        if isinstance(row.get("ms_per_token"), (int, float)):
            pts.append(MetricPoint(
                f"serve.{phase}.ms_per_token",
                float(row["ms_per_token"]), file, unit="ms",
                phase=phase, tags=rtags))
        if isinstance(row.get("gen_tokens_per_sec"), (int, float)):
            pts.append(MetricPoint(
                f"serve.{phase}.gen_tokens_per_sec",
                float(row["gen_tokens_per_sec"]), file,
                unit="tokens/sec", phase=phase, tags=rtags))
        if isinstance(row.get("effective_rps"), (int, float)):
            pts.append(MetricPoint(
                f"serve.{phase}.effective_rps",
                float(row["effective_rps"]), file, unit="req/s",
                phase=phase, tags=rtags))
        # decode-diag stretch decomposition (hds_decode_diag rows)
        for key in ("marginal_ms_per_token", "fixed_ms_per_stretch",
                    "implied_gbps"):
            if isinstance(row.get(key), (int, float)):
                pts.append(MetricPoint(
                    f"serve.{phase}.{key}", float(row[key]), file,
                    phase=phase, tags=rtags))
    return pts


def parse_restore_bench(text: str, file: str) -> List[MetricPoint]:
    rows = read_jsonl_rows(text)
    tags = _workload_tag(file)
    pts: List[MetricPoint] = []
    for row in rows:
        phase = row.get("phase", "")
        rtags = dict(tags)
        if "batch" in row:
            rtags["batch"] = str(row["batch"])
        if "prompt_len" in row:
            rtags["prompt_len"] = str(row["prompt_len"])
        if phase == "hcache-restore" and \
                isinstance(row.get("speedup"), (int, float)):
            pts.append(MetricPoint("restore.speedup_e2e",
                                   float(row["speedup"]), file,
                                   phase=phase, tags=rtags))
        elif phase == "hcache-restore-marginal":
            for key, metric in (
                    ("speedup_replay", "restore.speedup_replay"),
                    ("speedup_e2e", "restore.speedup_e2e_marginal"),
                    ("link_gbps", "restore.link_gbps")):
                if isinstance(row.get(key), (int, float)):
                    pts.append(MetricPoint(metric, float(row[key]),
                                           file, phase=phase,
                                           tags=rtags))
        elif phase == "restore-crossover-summary":
            cl = row.get("crossover_prompt_len")
            if isinstance(cl, (int, float)):
                pts.append(MetricPoint(
                    "restore.crossover_prompt_len", float(cl), file,
                    phase=phase,
                    tags={"model": str(row.get("model", ""))}))
    return pts


def parse_spec_serve(text: str, file: str) -> List[MetricPoint]:
    """SPEC_SERVE.jsonl: scheduler-dispatched speculative decode +
    fleet-wide radix prefix reuse with latent prefix broadcast
    (``bench.py --spec-serve``). The summary row carries the headline
    gates; the phase rows carry their own verdicts as trajectory."""
    rows = read_jsonl_rows(text)
    pts: List[MetricPoint] = []

    def flag(metric, row, key, phase):
        if key in row:
            pts.append(MetricPoint(metric,
                                   1.0 if row[key] else 0.0, file,
                                   phase=phase))

    for row in rows:
        phase = row.get("phase", "")
        if phase == "spec-serve-summary":
            for key, metric in (
                    ("accepted_tokens_per_step",
                     "spec.accepted_tokens_per_step"),
                    ("reprefill_savings",
                     "spec.prefix_reprefill_savings"),
                    ("lookup_virtual_speedup",
                     "spec.lookup_virtual_speedup"),
                    ("mixed_virtual_speedup",
                     "spec.mixed_virtual_speedup"),
                    ("prefix_broadcasts", "spec.prefix_broadcasts"),
                    ("prefix_tokens_reused",
                     "spec.prefix_tokens_reused")):
                if isinstance(row.get(key), (int, float)):
                    pts.append(MetricPoint(metric, float(row[key]),
                                           file, phase=phase))
            flag("spec.stream_parity", row, "stream_parity", phase)
            flag("spec.deterministic", row, "deterministic", phase)
            flag("spec.invariants_ok", row, "invariants_ok", phase)
            pts.append(MetricPoint(
                "spec.violations",
                float(len(row.get("violations", []))), file,
                phase=phase))
        elif phase == "spec-lookup":
            flag("spec.lookup_stream_parity", row, "stream_parity",
                 phase)
        elif phase == "spec-prefix":
            flag("spec.prefix_stream_parity", row, "stream_parity",
                 phase)
        elif phase == "spec-slo":
            if isinstance(row.get("final_level"), (int, float)):
                pts.append(MetricPoint(
                    "spec.slo_final_level", float(row["final_level"]),
                    file, phase=phase))
    return pts


def parse_fabric_serve(text: str, file: str) -> List[MetricPoint]:
    """FABRIC_SERVE.jsonl: the deployment fabric audit (``bench.py
    --fabric``) — process-vs-in-memory transport parity plus the
    literal kill-a-process chaos leg. The boolean gates are hard
    (rel=0.0 in TOLERANCES); the measured wire throughput is
    wall-clock on whatever host ran the bench and is recorded as
    informational trajectory only."""
    rows = read_jsonl_rows(text)
    pts: List[MetricPoint] = []
    for row in rows:
        if row.get("phase") != "fabric-summary":
            continue
        phase = "fabric-summary"
        for key, metric in (
                ("deterministic", "fabric.deterministic"),
                ("stream_parity", "fabric.stream_parity"),
                ("digest_transport_invariant",
                 "fabric.digest_transport_invariant"),
                ("trace_connected", "fabric.trace_connected"),
                ("chaos_ok", "fabric.chaos_ok"),
                ("invariants_ok", "fabric.invariants_ok")):
            if key in row:
                pts.append(MetricPoint(metric,
                                       1.0 if row[key] else 0.0,
                                       file, phase=phase))
        for key, metric in (
                ("two_hop_deliveries", "fabric.two_hop_deliveries"),
                ("max_trace_hops", "fabric.max_trace_hops"),
                ("chaos_kills", "fabric.chaos_kills"),
                ("replica_crashes", "fabric.replica_crashes"),
                ("done_after_kill", "fabric.done_after_kill"),
                ("bootstrap_mismatches",
                 "fabric.bootstrap_mismatches"),
                ("measured_wire_bytes_per_s",
                 "fabric.measured_wire_bytes_per_s")):
            if isinstance(row.get(key), (int, float)):
                pts.append(MetricPoint(metric, float(row[key]),
                                       file, phase=phase))
        pts.append(MetricPoint(
            "fabric.violations",
            float(len(row.get("violations", []))), file,
            phase=phase))
    return pts


def parse_fabric_obs(text: str, file: str) -> List[MetricPoint]:
    """FABRIC_OBS.jsonl: the cross-process telemetry-plane audit
    (``bench.py --fabric-obs``) — harvest digest invariance, assembled
    cross-process timeline validity, SIGKILL postmortem telemetry, and
    the harvest-overhead budget. The boolean gates are hard (rel=0.0
    in TOLERANCES) and the overhead fraction is upper-bounded; the
    per-link wire percentiles are wall-clock on whatever host ran the
    bench and index as informational trajectory only."""
    rows = read_jsonl_rows(text)
    pts: List[MetricPoint] = []
    for row in rows:
        if row.get("phase") != "fabric-obs-summary":
            continue
        phase = "fabric-obs-summary"
        for key, metric in (
                ("deterministic", "fabric_obs.deterministic"),
                ("harvest_digest_invariant",
                 "fabric_obs.harvest_digest_invariant"),
                ("timeline_valid", "fabric_obs.timeline_valid"),
                ("postmortem_has_telemetry",
                 "fabric_obs.postmortem_has_telemetry"),
                ("chaos_ok", "fabric_obs.chaos_ok"),
                ("invariants_ok", "fabric_obs.invariants_ok")):
            if key in row:
                pts.append(MetricPoint(metric,
                                       1.0 if row[key] else 0.0,
                                       file, phase=phase))
        for key, metric in (
                ("harvests", "fabric_obs.harvests"),
                ("harvest_failures", "fabric_obs.harvest_failures"),
                ("harvest_overhead_fraction",
                 "fabric_obs.harvest_overhead_fraction"),
                ("worker_rows", "fabric_obs.worker_rows"),
                ("worker_spans", "fabric_obs.worker_spans"),
                ("cross_worker_arrows",
                 "fabric_obs.cross_worker_arrows"),
                ("wire_latency_p50_s",
                 "fabric_obs.wire_latency_p50_s"),
                ("wire_latency_p99_s",
                 "fabric_obs.wire_latency_p99_s"),
                ("wire_bytes_per_s_p50",
                 "fabric_obs.wire_bytes_per_s_p50"),
                ("wire_bytes_per_s_p99",
                 "fabric_obs.wire_bytes_per_s_p99")):
            if isinstance(row.get(key), (int, float)):
                pts.append(MetricPoint(metric, float(row[key]),
                                       file, phase=phase))
        pts.append(MetricPoint(
            "fabric_obs.violations",
            float(len(row.get("violations", []))), file,
            phase=phase))
    return pts


def parse_autoscale_serve(text: str, file: str) -> List[MetricPoint]:
    """AUTOSCALE_SERVE.jsonl: the elastic-autoscaling audit
    (``bench.py --autoscale``) — the hysteresis control loop vs static
    fleets on the bursty multi-tenant trace, scale-event chaos, and
    the process-mode spawn/reap leg. The boolean gates are hard
    (rel=0.0 in TOLERANCES); SLO attainment and the cost-savings
    fraction are the headline trajectory."""
    rows = read_jsonl_rows(text)
    pts: List[MetricPoint] = []
    for row in rows:
        if row.get("phase") != "autoscale-summary":
            continue
        phase = "autoscale-summary"
        for key, metric in (
                ("deterministic", "autoscale.deterministic"),
                ("slo_vs_static_ok", "autoscale.slo_vs_static_ok"),
                ("cost_vs_static_ok", "autoscale.cost_vs_static_ok"),
                ("scale_events_span_verified",
                 "autoscale.scale_events_span_verified"),
                ("chaos_deterministic",
                 "autoscale.chaos_deterministic"),
                ("chaos_invariants_ok",
                 "autoscale.chaos_invariants_ok"),
                ("process_ok", "autoscale.process_ok"),
                ("trace_connected", "autoscale.trace_connected"),
                ("invariants_ok", "autoscale.invariants_ok")):
            if key in row:
                pts.append(MetricPoint(metric,
                                       1.0 if row[key] else 0.0,
                                       file, phase=phase))
        for key, metric in (
                ("slo_attainment", "autoscale.slo_attainment"),
                ("cost_savings_fraction",
                 "autoscale.cost_savings_fraction"),
                ("cost_replica_steps",
                 "autoscale.cost_replica_steps"),
                ("static_peak_cost", "autoscale.static_peak_cost"),
                ("scale_ups", "autoscale.scale_ups"),
                ("retires_completed",
                 "autoscale.retires_completed"),
                ("flaps", "autoscale.flaps")):
            if isinstance(row.get(key), (int, float)):
                pts.append(MetricPoint(metric, float(row[key]),
                                       file, phase=phase))
        pts.append(MetricPoint(
            "autoscale.violations",
            float(len(row.get("violations", []))), file,
            phase=phase))
    return pts


def parse_paged_vet(text: str, file: str) -> List[MetricPoint]:
    rows = read_jsonl_rows(text)
    pts = []
    for row in rows:
        if row.get("phase") != "paged-vet":
            continue
        tags = {"head_tile": str(row.get("head_tile", ""))}
        pts.append(MetricPoint("paged_vet.ok",
                               1.0 if row.get("ok") else 0.0, file,
                               phase="paged-vet", tags=tags))
        if isinstance(row.get("max_abs_err"), (int, float)):
            pts.append(MetricPoint("paged_vet.max_abs_err",
                                   float(row["max_abs_err"]), file,
                                   phase="paged-vet", tags=tags))
    return pts


_DOMINO_PAIRS_RE = re.compile(
    r"(\d+)\s+native async pair|native[_ ]async[_ ]pairs\D*(\d+)",
    re.IGNORECASE)


def parse_chip_log(text: str, file: str) -> List[MetricPoint]:
    """Best-effort mining of the one committed chip log the code cites
    (``DOMINO_TPU_r4.log``): the Domino native-pair verdict. A log
    without one still indexes (the file is classified, not ignored)."""
    m = _DOMINO_PAIRS_RE.search(text)
    if not m:
        return []
    n = next(g for g in m.groups() if g is not None)
    return [MetricPoint("domino.native_async_pairs_on_chip", float(n),
                        file, phase="chip-log")]


def parse_index_meta(text: str, file: str) -> List[MetricPoint]:
    """PERF_TRAJECTORY.json itself — parses, carries no points (it IS
    the index)."""
    read_json(text)
    return []


# ----------------------------------------------------------------- #
# the family table
# ----------------------------------------------------------------- #
@dataclass(frozen=True)
class ArtifactFamily:
    name: str
    pattern: str                           # regex over the basename
    parser: Callable[[str, str], List[MetricPoint]]
    description: str

    def matches(self, filename: str) -> bool:
        return re.match(self.pattern, filename) is not None


FAMILIES: List[ArtifactFamily] = [
    ArtifactFamily(
        "perf-index", r"^PERF_TRAJECTORY\.json$", parse_index_meta,
        "the committed perf index itself (meta, not an artifact)"),
    ArtifactFamily(
        "bench-result", r"^(BENCH_FRESH|BENCH_LOCAL)\.json$",
        parse_bench_result,
        "single bench.py result line"),
    ArtifactFamily(
        "config-vet", r"^VET_[A-Z0-9_]+\.json$", parse_bench_result,
        "per-config chip vetting record (result line or typed error)"),
    ArtifactFamily(
        "baseline-meta", r"^BASELINE\.json$", parse_baseline_meta,
        "reference-target metadata (no metric points)"),
    ArtifactFamily(
        "zero-overlap", r"^ZERO_OVERLAP(_TPU)?\.jsonl$",
        parse_zero_overlap,
        "ZeRO-3 overlap + quantized-wire + decomposed-ring audit "
        "stream (bench.py --zero-overlap; hlo_audit rows; _TPU = the "
        "same phases captured on chip)"),
    ArtifactFamily(
        "serve-loop", r"^SERVE_LOOP\.jsonl$", parse_serve_loop,
        "continuous-batching serve-loop trace: per-request rows + "
        "summary percentiles + SLO row"),
    ArtifactFamily(
        "chaos-serve", r"^CHAOS_SERVE\.jsonl$", parse_chaos_serve,
        "chaos harness: fault plan, invariants, determinism gate"),
    ArtifactFamily(
        "fleet-serve", r"^FLEET_SERVE\.jsonl$", parse_fleet_serve,
        "fleet serving: N-replica router + latent migration under "
        "replica chaos (per-replica occupancy, migration accounting, "
        "span-derived overlap, determinism gate)"),
    ArtifactFamily(
        "disagg-serve", r"^DISAGG_SERVE\.jsonl$", parse_disagg_serve,
        "disaggregated prefill/decode serving: tier coordinator vs "
        "equal-replica colocated baseline (decode-tail win, stream "
        "parity, span-derived handoff overlap, int8 latent wire, "
        "chunked prefill, tier chaos, determinism gates)"),
    ArtifactFamily(
        "spec-serve", r"^SPEC_SERVE\.jsonl$", parse_spec_serve,
        "scheduler-dispatched speculative decode + fleet-wide radix "
        "prefix reuse with latent prefix broadcast (accepted-tokens/"
        "step, re-prefill savings, stream parity, SLO-aware ladder, "
        "determinism gates)"),
    ArtifactFamily(
        "fabric-serve", r"^FABRIC_SERVE\.jsonl$", parse_fabric_serve,
        "deployment fabric: process-vs-in-memory replica transport "
        "parity (digest invariance, bitwise streams, two-hop socket "
        "crossings, cross-process trace hops, measured-vs-priced "
        "wire) + the literal kill-a-process chaos leg"),
    ArtifactFamily(
        "fabric-obs", r"^FABRIC_OBS\.jsonl$", parse_fabric_obs,
        "cross-process telemetry plane: worker span/metric harvest "
        "over the fabric control channel (digest-invisibility gate, "
        "assembled cross-process timeline with real worker rows + "
        "cross-worker arrows, SIGKILL postmortem telemetry, harvest "
        "overhead budget, per-link wire percentiles)"),
    ArtifactFamily(
        "autoscale-serve", r"^AUTOSCALE_SERVE\.jsonl$",
        parse_autoscale_serve,
        "SLO-driven elastic autoscaling: hysteresis control loop vs "
        "equal-peak static fleets (attainment at strictly lower "
        "replica-step cost), span-verified scale events, scale-event "
        "chaos (aborted bootstrap / mid-drain crash / faulted "
        "pre-warm), process-mode worker spawn/kill-recovery/reap"),
    ArtifactFamily(
        "request-trace", r"^REQUEST_TRACE\.jsonl$",
        parse_request_trace,
        "fleet-wide causal request tracing: cross-replica span-DAG "
        "connectivity, additive critical-path attribution with the "
        "closure gate, p99-TTFT attribution profile, and the "
        "anomaly-triggered flight-recorder determinism gate"),
    ArtifactFamily(
        "restore-bench",
        r"^RESTORE_[A-Z0-9_]+\.jsonl$", parse_restore_bench,
        "HCache restore benchmarks: e2e/marginal speedups + "
        "crossover curve"),
    ArtifactFamily(
        "serve-bench",
        r"^(SERVE|DECODE_DIAG|LOOKUP|SWEEP)_[A-Z0-9_]+\.jsonl$",
        parse_serve_bench,
        "serving benchmark phase streams (prefill/decode/sweep/"
        "lookup/floors)"),
    ArtifactFamily(
        "paged-vet", r"^PAGED_VET\.jsonl$", parse_paged_vet,
        "paged-attention kernel numeric vetting rows"),
    ArtifactFamily(
        "chip-log", r"^DOMINO_TPU_r\d+\.log$", parse_chip_log,
        "free-form chip session log (best-effort mining)"),
]


def classify(filename: str) -> Optional[ArtifactFamily]:
    for fam in FAMILIES:
        if fam.matches(filename):
            return fam
    return None


def parse_artifact(path, filename: str) -> ParsedArtifact:
    """Classify + parse one committed artifact. Raises ``KeyError`` on
    an unknown name and re-raises parser errors (a known family that
    stopped parsing is a broken artifact, not a skippable one)."""
    fam = classify(filename)
    if fam is None:
        raise KeyError(f"no artifact family matches {filename!r} "
                       "(declare one in perf/schemas.py or allowlist "
                       "it in perf/KNOWN_UNINDEXED)")
    with open(path, encoding="utf-8", errors="replace") as fh:
        text = fh.read()
    if not text.strip():
        return ParsedArtifact(filename, fam.name, "empty",
                              note="zero-byte artifact (interrupted "
                                   "run)")
    if filename.endswith(".jsonl") and not read_jsonl_rows(text):
        # log-prefix lines only: the run died before its first row —
        # visible as empty, same as a zero-byte session
        return ParsedArtifact(filename, fam.name, "empty",
                              note="no data rows (interrupted before "
                                   "first JSON row)")
    points = fam.parser(text, filename)
    status = "meta" if fam.name in ("baseline-meta", "perf-index") \
        else "ok"
    return ParsedArtifact(filename, fam.name, status, points)
