"""Regression sentinel: compare fresh perf points against the
committed trajectory.

The repo's perf story is "claims computed from committed evidence"
(the hlo_audit / wire-bytes precedent): every number a PR committed as
evidence is a number a later PR can regress without noticing — unless
something diffs. This module is that diff:

* :data:`TOLERANCES` declares the **headline metrics** (the ones whose
  regression fails a check) with per-metric direction + tolerance;
* :func:`check_points` compares a list of fresh points against a
  baseline index's ``headline`` block;
* :func:`check_artifact` parses any file the registry understands and
  checks it — ``perf check --against PERF_TRAJECTORY.json FILE...``;
* :func:`self_check_rows` is the in-process hook ``bench.py
  --zero-overlap`` and ``serve_loop`` call before writing their
  artifact: the run self-compares and records the verdicts in the
  artifact itself (non-fatal there — the CLI gate is where failure
  has an exit code);
* :func:`self_test` synthesizes a baseline + a regressed point and
  proves the gate trips — ``perf check --self-test`` runs inside
  tier-1 (pure CPU, no chip).

A regression verdict compares against the baseline's **best** value
(per direction).
"""

import json
import os
import tempfile
from dataclasses import dataclass
from typing import Dict, List, Optional

from .schemas import MetricPoint


@dataclass(frozen=True)
class Tolerance:
    #: "higher" = bigger is better (throughput), "lower" = smaller is
    #: better (latency, wire fraction)
    direction: str = "higher"
    #: allowed relative slack vs the baseline headline
    rel: float = 0.05
    #: absolute slack floor (rescues near-zero baselines)
    abs: float = 0.0


#: the headline metrics the sentinel gates on. Everything else in the
#: index is informational trajectory.
TOLERANCES: Dict[str, Tolerance] = {
    # chip training throughput
    "train.tokens_per_sec_per_chip": Tolerance("higher", rel=0.10),
    "train.mfu": Tolerance("higher", rel=0.10),
    # ZeRO-3 overlap structure (CPU-deterministic: tight tolerances)
    "zero_overlap.gather_overlap_ratio": Tolerance("higher", rel=0.02),
    "zero_overlap.reduce_overlap_ratio": Tolerance("higher", rel=0.02),
    "zero_overlap.gather_pairs": Tolerance("higher", rel=0.0),
    "zero_overlap.qrs_wire_fraction_of_fp32":
        Tolerance("lower", rel=0.05),
    "zero_overlap.bitwise_parity": Tolerance("higher", rel=0.0),
    "zero_overlap.qrs_bitwise_depth_parity":
        Tolerance("higher", rel=0.0),
    "zero_overlap.qrs_trajectory_within_tol":
        Tolerance("higher", rel=0.0),
    # decomposed ring transport (CPU-deterministic structural audit)
    "zero_overlap.structural_overlap_ratio":
        Tolerance("higher", rel=0.02),
    "zero_overlap.decomposed_bitwise_vs_native":
        Tolerance("higher", rel=0.0),
    "zero_overlap.decomposed_qwire_bitwise":
        Tolerance("higher", rel=0.0),
    "domino.decomposed_overlapped_pairs": Tolerance("higher", rel=0.0),
    "domino.decomposed_value_parity": Tolerance("higher", rel=0.0),
    # hierarchical (2-D mesh) transport: bitwise bools are hard gates,
    # the wire fractions/seconds are byte-deterministic on CPU (tight),
    # structural ratio tolerates program-shape evolution like the flat
    # rings'
    "zero_overlap.hier_structural_overlap_ratio":
        Tolerance("higher", rel=0.02),
    "zero_overlap.hier_bitwise_vs_native": Tolerance("higher", rel=0.0),
    "zero_overlap.hier_bitwise_vs_flat": Tolerance("higher", rel=0.0),
    "zero_overlap.hier_qwire_bitwise": Tolerance("higher", rel=0.0),
    "zero_overlap.hier_longhaul_trajectory_within_tol":
        Tolerance("higher", rel=0.0),
    "zero_overlap.hier_interaxis_wire_fraction":
        Tolerance("lower", rel=0.05),
    "zero_overlap.hier_longhaul_gather_fraction":
        Tolerance("lower", rel=0.05),
    "zero_overlap.hier_pod_wire_seconds_inter":
        Tolerance("lower", rel=0.05),
    "zero_overlap.hier_pod_wire_seconds_intra":
        Tolerance("lower", rel=0.05),
    "domino.hier_overlapped_pairs": Tolerance("higher", rel=0.0),
    "domino.hier_value_parity": Tolerance("higher", rel=0.0),
    # ISSUE 15: unified hpZ tiering + phase pipelining + 16-device
    # factorings + measured wire calibration. Bitwise/parity bools and
    # shape validity are hard gates; the pipelined structural ratio
    # tolerates program-shape evolution like the other ratios; the
    # cross-axis pair count must never drop to zero. The measured
    # GB/s themselves are NOT gated (wall clock on whatever host ran
    # the bench — trajectory-informational only).
    "zero_overlap.hier_hpz_unified_bitwise":
        Tolerance("higher", rel=0.0),
    "zero_overlap.hier_hpz_secondary_on_mesh":
        Tolerance("higher", rel=0.0),
    "zero_overlap.hier_pipelined_bitwise":
        Tolerance("higher", rel=0.0),
    "zero_overlap.hier_pipelined_structural_ratio":
        Tolerance("higher", rel=0.02),
    "zero_overlap.hier_pipelined_cross_axis_pairs":
        Tolerance("higher", rel=0.0),
    "zero_overlap.hier_16dev_parity": Tolerance("higher", rel=0.0),
    "zero_overlap.wire_cal_shape_ok": Tolerance("higher", rel=0.0),
    # ISSUE 18: fused computation-collective kernels. The bitwise
    # parity bools, the in-kernel audit differential, the
    # fused<=unfused wall-clock verdict, the 3-D mesh bookkeeping
    # gates, and the 16-dev fused parity are HARD gates; the subsumed
    # pair count must never drop below the committed count; the
    # wall-clock speedup is trajectory-gated loosely (shared CI
    # hosts), never a hard floor above 1.0 — the boolean verdict at
    # the largest payload is the hard form of that claim.
    "zero_overlap.fused_parity_plain": Tolerance("higher", rel=0.0),
    "zero_overlap.fused_parity_qwire": Tolerance("higher", rel=0.0),
    "zero_overlap.fused_audit_gate": Tolerance("higher", rel=0.0),
    "zero_overlap.fused_subsumed_pairs": Tolerance("higher", rel=0.0),
    "zero_overlap.fused_mid_gather_leaves":
        Tolerance("higher", rel=0.0),
    "zero_overlap.fused_le_unfused_largest":
        Tolerance("higher", rel=0.0),
    "zero_overlap.fused_wallclock_speedup":
        Tolerance("higher", rel=0.50),
    "zero_overlap.mesh3d_bookkeeping_ok": Tolerance("higher", rel=0.0),
    "zero_overlap.fused_16dev_parity": Tolerance("higher", rel=0.0),
    # serve-loop percentiles (wall-clock on shared CI hosts: loose)
    "serve_loop.ttft_s_p50": Tolerance("lower", rel=0.50, abs=0.5),
    "serve_loop.ttft_s_p99": Tolerance("lower", rel=0.50, abs=0.5),
    "serve_loop.tpot_s_p50": Tolerance("lower", rel=0.50, abs=0.05),
    "serve_loop.tpot_s_p99": Tolerance("lower", rel=0.50, abs=0.05),
    "serve_loop.gen_tokens_per_sec": Tolerance("higher", rel=0.50),
    "serve_loop.restore_overlap_ratio": Tolerance("higher", rel=0.05),
    "serve_loop.restore_parity_ok": Tolerance("higher", rel=0.0),
    "serve_loop.dropped": Tolerance("lower", rel=0.0),
    # chaos invariants are booleans: any drop from 1.0 fails
    "chaos.deterministic": Tolerance("higher", rel=0.0),
    "chaos.invariants_ok": Tolerance("higher", rel=0.0),
    "chaos.ckpt_fallback_ok": Tolerance("higher", rel=0.0),
    # fleet chaos gates (CPU-deterministic; booleans are hard gates,
    # the overlap ratio tolerates router-policy evolution)
    "fleet.deterministic": Tolerance("higher", rel=0.0),
    "fleet.invariants_ok": Tolerance("higher", rel=0.0),
    "fleet.migration_balance_ok": Tolerance("higher", rel=0.0),
    "fleet.span_counter_agreement": Tolerance("higher", rel=0.0),
    "fleet.migration_overlap_ratio": Tolerance("higher", rel=0.25),
    "fleet.violations": Tolerance("lower", rel=0.0),
    # speculative serving + prefix reuse gates (CPU-deterministic:
    # booleans are hard gates; the two headline ratios tolerate trace
    # evolution like the other serving families)
    "spec.accepted_tokens_per_step": Tolerance("higher", rel=0.25),
    "spec.prefix_reprefill_savings": Tolerance("higher", rel=0.25),
    "spec.lookup_virtual_speedup": Tolerance("higher", rel=0.25),
    "spec.mixed_virtual_speedup": Tolerance("higher", rel=0.25),
    "spec.stream_parity": Tolerance("higher", rel=0.0),
    "spec.deterministic": Tolerance("higher", rel=0.0),
    "spec.invariants_ok": Tolerance("higher", rel=0.0),
    "spec.violations": Tolerance("lower", rel=0.0),
    # disaggregated serving gates (CPU-deterministic; booleans are
    # hard gates, the ratios tolerate scheduler-policy evolution)
    "disagg.deterministic": Tolerance("higher", rel=0.0),
    "disagg.stream_parity": Tolerance("higher", rel=0.0),
    "disagg.invariants_ok": Tolerance("higher", rel=0.0),
    "disagg.span_counter_agreement": Tolerance("higher", rel=0.0),
    "disagg.chaos_deterministic": Tolerance("higher", rel=0.0),
    "disagg.chaos_invariants_ok": Tolerance("higher", rel=0.0),
    "disagg.int8_wire_stream_parity": Tolerance("higher", rel=0.0),
    "disagg.chunked_invariants_ok": Tolerance("higher", rel=0.0),
    "disagg.violations": Tolerance("lower", rel=0.0),
    #: the headline ratio must stay above 1.0 (decode tier beats the
    #: colocated baseline); 25% slack absorbs policy evolution but a
    #: drop under ~1.0 regresses the architecture's reason to exist
    "disagg.decode_tpot_p99_speedup": Tolerance("higher", rel=0.25),
    "disagg.handoff_overlap_ratio": Tolerance("higher", rel=0.25),
    "disagg.int8_wire_fraction": Tolerance("lower", rel=0.10),
    # deployment fabric (ISSUE 16): the transport must move bytes, not
    # outcomes — parity/determinism/connectivity booleans are hard
    # gates, as are zero bootstrap mismatches and exactly-zero
    # violations. Hop/delivery counts may evolve with routing policy
    # (loose); the measured wire bytes/s is wall clock on whatever
    # host ran the bench and is deliberately NOT gated.
    "fabric.deterministic": Tolerance("higher", rel=0.0),
    "fabric.stream_parity": Tolerance("higher", rel=0.0),
    "fabric.digest_transport_invariant": Tolerance("higher", rel=0.0),
    "fabric.trace_connected": Tolerance("higher", rel=0.0),
    "fabric.chaos_ok": Tolerance("higher", rel=0.0),
    "fabric.invariants_ok": Tolerance("higher", rel=0.0),
    "fabric.bootstrap_mismatches": Tolerance("lower", rel=0.0),
    "fabric.violations": Tolerance("lower", rel=0.0),
    "fabric.two_hop_deliveries": Tolerance("higher", rel=0.50),
    "fabric.max_trace_hops": Tolerance("higher", rel=0.50),
    # cross-process telemetry plane (ISSUE 17): observation must be
    # digest-invisible and cheap — the invisibility/validity booleans
    # are hard gates, violations must be exactly zero, and the
    # measured harvest overhead is upper-bounded with absolute
    # headroom (it is a wall-clock ratio on whatever host ran the
    # bench, but the 5% budget is part of the contract). Span/arrow
    # counts may evolve with routing policy (loose); the per-link
    # wire percentiles are wall clock and deliberately NOT gated.
    "fabric_obs.deterministic": Tolerance("higher", rel=0.0),
    "fabric_obs.harvest_digest_invariant": Tolerance("higher",
                                                     rel=0.0),
    "fabric_obs.timeline_valid": Tolerance("higher", rel=0.0),
    "fabric_obs.postmortem_has_telemetry": Tolerance("higher",
                                                     rel=0.0),
    "fabric_obs.chaos_ok": Tolerance("higher", rel=0.0),
    "fabric_obs.invariants_ok": Tolerance("higher", rel=0.0),
    "fabric_obs.violations": Tolerance("lower", rel=0.0),
    "fabric_obs.harvest_failures": Tolerance("lower", rel=0.0),
    "fabric_obs.harvest_overhead_fraction":
        Tolerance("lower", rel=0.0, abs=0.05),
    "fabric_obs.worker_rows": Tolerance("higher", rel=0.0),
    "fabric_obs.worker_spans": Tolerance("higher", rel=0.50),
    "fabric_obs.cross_worker_arrows": Tolerance("higher", rel=0.50),
    # elastic autoscaling (ISSUE 19): the control loop must beat the
    # equal-peak static fleet on cost WITHOUT giving up SLO
    # attainment, deterministically, with every scale event
    # span-verified and every scale-fault recovered — all of that is
    # a hard boolean gate plus exactly-zero violations. Attainment
    # itself gets a little slack (trace/policy evolution), the
    # cost-savings fraction more (it moves with the control policy),
    # and the raw step costs / event counts are informational
    # trajectory (loose).
    "autoscale.deterministic": Tolerance("higher", rel=0.0),
    "autoscale.slo_vs_static_ok": Tolerance("higher", rel=0.0),
    "autoscale.cost_vs_static_ok": Tolerance("higher", rel=0.0),
    "autoscale.scale_events_span_verified": Tolerance("higher",
                                                      rel=0.0),
    "autoscale.chaos_deterministic": Tolerance("higher", rel=0.0),
    "autoscale.chaos_invariants_ok": Tolerance("higher", rel=0.0),
    "autoscale.process_ok": Tolerance("higher", rel=0.0),
    "autoscale.trace_connected": Tolerance("higher", rel=0.0),
    "autoscale.invariants_ok": Tolerance("higher", rel=0.0),
    "autoscale.violations": Tolerance("lower", rel=0.0),
    "autoscale.slo_attainment": Tolerance("higher", rel=0.05),
    "autoscale.cost_savings_fraction": Tolerance("higher", rel=0.25),
    "autoscale.cost_replica_steps": Tolerance("lower", rel=0.50),
    "autoscale.scale_ups": Tolerance("higher", rel=0.50),
    "autoscale.retires_completed": Tolerance("higher", rel=0.50),
    "autoscale.flaps": Tolerance("lower", rel=0.0, abs=2.0),
    # causal request tracing (CPU-deterministic; the booleans are hard
    # gates, the closure residual has an absolute bar — attribution
    # must sum to measured E2E within 1% regardless of baseline)
    "request_trace.dag_connected": Tolerance("higher", rel=0.0),
    "request_trace.closure_ok": Tolerance("higher", rel=0.0),
    "request_trace.deterministic": Tolerance("higher", rel=0.0),
    "request_trace.flight_deterministic": Tolerance("higher", rel=0.0),
    "request_trace.closure_max_residual":
        Tolerance("lower", rel=0.0, abs=0.01),
    "request_trace.violations": Tolerance("lower", rel=0.0),
    # the headline p99-TTFT attribution keys: which stage owns the
    # tail. Scheduler-policy evolution legitimately moves these, so
    # wide slack — what must not happen silently is the queue/prefill
    # share of the p99 TTFT exploding
    "request_trace.ttft_attr_queue_p99_s":
        Tolerance("lower", rel=0.50, abs=0.05),
    "request_trace.ttft_attr_prefill_p99_s":
        Tolerance("lower", rel=0.50, abs=0.05),
}


@dataclass
class Verdict:
    metric: str
    status: str                  # "ok" | "regression" | "improved" | \
    #                              "no-baseline"
    new_value: float
    baseline: Optional[float] = None
    baseline_file: str = ""
    limit: Optional[float] = None
    detail: str = ""

    def to_json(self) -> Dict:
        out = {"metric": self.metric, "status": self.status,
               "new_value": self.new_value}
        if self.baseline is not None:
            out["baseline"] = self.baseline
            out["baseline_file"] = self.baseline_file
        if self.limit is not None:
            out["limit"] = round(self.limit, 6)
        if self.detail:
            out["detail"] = self.detail
        return out


def _limit(baseline: float, tol: Tolerance) -> float:
    slack = abs(baseline) * tol.rel + tol.abs
    return baseline - slack if tol.direction == "higher" \
        else baseline + slack


def check_points(points: List[MetricPoint],
                 baseline_index: Dict) -> List[Verdict]:
    """Compare fresh points against the baseline index headline. Only
    headline metrics produce verdicts; multiple fresh points for one
    metric are each checked (worst wins the summary)."""
    headline = baseline_index.get("headline", {})
    verdicts: List[Verdict] = []
    for p in points:
        tol = TOLERANCES.get(p.metric)
        if tol is None:
            continue
        base = headline.get(p.metric)
        if base is None:
            verdicts.append(Verdict(p.metric, "no-baseline", p.value))
            continue
        # like-for-like only: a point measured on a different config /
        # workload than the headline is a different program, not a
        # regression candidate (vet runs of 7B-layer shapes must not
        # "regress" the 350m headline)
        bcfg = (base.get("tags") or {}).get("config")
        pcfg = p.tags.get("config")
        if bcfg and pcfg and bcfg != pcfg:
            continue
        limit = _limit(base["value"], tol)
        if tol.direction == "higher":
            bad = p.value < limit
            better = p.value > base["value"]
        else:
            bad = p.value > limit
            better = p.value < base["value"]
        status = "regression" if bad else (
            "improved" if better else "ok")
        detail = ""
        if bad:
            detail = (f"{p.value} vs baseline {base['value']} "
                      f"({base['file']}), limit {round(limit, 6)} "
                      f"[{tol.direction} is better]")
        verdicts.append(Verdict(p.metric, status, p.value,
                                baseline=base["value"],
                                baseline_file=base["file"],
                                limit=limit, detail=detail))
    return verdicts


def regressions(verdicts: List[Verdict]) -> List[Verdict]:
    return [v for v in verdicts if v.status == "regression"]


def check_headline(fresh_index: Dict,
                   baseline_index: Dict) -> List[Verdict]:
    """The repo-level gate: rebuild the index from the working tree
    and require every gated headline metric to still reach the
    committed baseline's headline (within tolerance). History is not
    re-judged — old rounds stay old rounds; what must not happen is
    the *best committed evidence* for a metric getting worse (an
    artifact regenerated with a worse number, or deleted so a worse
    one becomes the best)."""
    base_head = baseline_index.get("headline", {})
    fresh_head = fresh_index.get("headline", {})
    verdicts: List[Verdict] = []
    for metric, base in base_head.items():
        tol = TOLERANCES.get(metric)
        if tol is None:
            continue
        fresh = fresh_head.get(metric)
        if fresh is None:
            verdicts.append(Verdict(
                metric, "regression", float("nan"),
                baseline=base["value"], baseline_file=base["file"],
                detail=f"headline metric vanished from the tree "
                       f"(was {base['value']} in {base['file']})"))
            continue
        limit = _limit(base["value"], tol)
        if tol.direction == "higher":
            bad = fresh["value"] < limit
            better = fresh["value"] > base["value"]
        else:
            bad = fresh["value"] > limit
            better = fresh["value"] < base["value"]
        status = "regression" if bad else (
            "improved" if better else "ok")
        detail = ""
        if bad:
            detail = (f"tree headline {fresh['value']} "
                      f"({fresh['file']}) vs committed "
                      f"{base['value']} ({base['file']}), limit "
                      f"{round(limit, 6)} [{tol.direction} is better]")
        verdicts.append(Verdict(metric, status, fresh["value"],
                                baseline=base["value"],
                                baseline_file=base["file"],
                                limit=limit, detail=detail))
    return verdicts


def check_artifact(path: str,
                   baseline_index: Dict) -> List[Verdict]:
    """Parse ``path`` with its registry schema and gate it."""
    from .schemas import parse_artifact
    parsed = parse_artifact(path, os.path.basename(path))
    return check_points(parsed.points, baseline_index)


def self_check_rows(filename: str, rows: List[Dict],
                    root: Optional[str] = None) -> Dict:
    """The bench hook: parse ``rows`` (the artifact about to be
    written) through ``filename``'s family schema and compare against
    the committed index. Returns a JSON-safe summary row the bench
    appends to its artifact; never raises and never blocks the write —
    a bench run's job is to record evidence, the CLI gate's job is to
    fail on it."""
    from .registry import INDEX_NAME, load_index, repo_root
    from .schemas import classify
    try:
        root = root or repo_root()
    except FileNotFoundError:
        return {"phase": "perf-check", "skipped": "no repo root"}
    fam = classify(os.path.basename(filename))
    if fam is None:
        return {"phase": "perf-check",
                "skipped": f"no schema for {filename}"}
    try:
        baseline = load_index(root=root)
    except (OSError, json.JSONDecodeError) as exc:
        return {"phase": "perf-check",
                "skipped": f"no committed {INDEX_NAME}: {exc}"}
    text = "\n".join(json.dumps(r) for r in rows)
    try:
        points = fam.parser(text, os.path.basename(filename))
        verdicts = check_points(points, baseline)
    except Exception as exc:   # noqa: BLE001 — evidence first
        return {"phase": "perf-check", "skipped": f"parse: {exc!r}"}
    regs = regressions(verdicts)
    return {
        "phase": "perf-check",
        "against": INDEX_NAME,
        "baseline_generated_utc": baseline.get("generated_utc"),
        "checked": len(verdicts),
        "regressions": [v.to_json() for v in regs],
        "ok": not regs,
    }


# ----------------------------------------------------------------- #
def self_test(verbose: bool = False) -> bool:
    """Prove the gate trips: build a synthetic baseline index, a
    matching fresh artifact, then regress one headline metric per
    direction and assert the verdicts flip. Pure CPU, no chip, no
    repo state — runs inside tier-1."""
    baseline = {
        "headline": {
            "train.tokens_per_sec_per_chip": {
                "value": 50000.0, "file": "BENCH_FRESH.json",
                "direction": "higher", "rel_tolerance": 0.10,
                "abs_tolerance": 0.0},
            "zero_overlap.qrs_wire_fraction_of_fp32": {
                "value": 0.33, "file": "ZERO_OVERLAP.jsonl",
                "direction": "lower", "rel_tolerance": 0.05,
                "abs_tolerance": 0.0},
            "chaos.deterministic": {
                "value": 1.0, "file": "CHAOS_SERVE.jsonl",
                "direction": "higher", "rel_tolerance": 0.0,
                "abs_tolerance": 0.0},
        }
    }
    ok_points = [
        MetricPoint("train.tokens_per_sec_per_chip", 49000.0, "new"),
        MetricPoint("zero_overlap.qrs_wire_fraction_of_fp32", 0.32,
                    "new"),
        MetricPoint("chaos.deterministic", 1.0, "new"),
    ]
    bad_points = [
        MetricPoint("train.tokens_per_sec_per_chip", 40000.0, "new"),
        MetricPoint("zero_overlap.qrs_wire_fraction_of_fp32", 0.50,
                    "new"),
        MetricPoint("chaos.deterministic", 0.0, "new"),
    ]
    ok_verdicts = check_points(ok_points, baseline)
    bad_verdicts = check_points(bad_points, baseline)
    checks = [
        (not regressions(ok_verdicts),
         "within-tolerance points must pass"),
        (len(regressions(bad_verdicts)) == 3,
         "all three synthetic regressions must trip"),
        (all(v.status == "regression" for v in bad_verdicts),
         "every regressed point gets a regression verdict"),
    ]
    # round-trip through a real file + the artifact path
    with tempfile.TemporaryDirectory() as tmp:
        art = os.path.join(tmp, "CHAOS_SERVE.jsonl")
        with open(art, "w") as fh:
            fh.write(json.dumps(
                {"phase": "chaos-summary", "deterministic": False,
                 "invariants_ok": True, "violations": []}) + "\n")
        file_verdicts = check_artifact(art, baseline)
        checks.append(
            (any(v.status == "regression" and
                 v.metric == "chaos.deterministic"
                 for v in file_verdicts),
             "file-based check must catch the regressed boolean"))
    passed = all(ok for ok, _ in checks)
    if verbose or not passed:
        for ok, what in checks:
            print(f"[perf self-test] {'PASS' if ok else 'FAIL'}: "
                  f"{what}")
    return passed
