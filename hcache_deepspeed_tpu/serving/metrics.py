"""Serving metrics: per-request histograms + scheduler gauges.

Emission rides the existing monitor event path: :meth:`ServingMetrics.
emit` produces the same ``(label, value, step)`` tuples
``monitor.MonitorMaster.write_events`` fans out to
TensorBoard/W&B/Comet/CSV, so serving telemetry lands wherever training
telemetry already does — no new sink plumbing. On top of that, the
whole metric set renders into a ``telemetry.prometheus.MetricRegistry``
(:meth:`ServingMetrics.to_registry` / :meth:`prometheus_text`) for
scrape-style exposition, and an attached
:class:`~..telemetry.slo.SLOTracker` turns the terminal-request stream
into TTFT/TPOT/availability burn-rate gauges the scheduler re-emits on
its ``sched.step`` spans.
"""

from bisect import bisect_left
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..telemetry.critical_path import (CriticalPathProfile, attribute,
                                       closure, connected)
from ..telemetry.sketch import QuantileSketch
from ..telemetry.slo import SLOTracker


class Histogram:
    """Streaming histogram over fixed buckets + bounded percentiles.

    Percentiles are **exact** (bit-identical to ``np.percentile`` over
    the raw stream) while the trace holds at most ``max_exact``
    observations; past that the raw values collapse into a
    :class:`~..telemetry.sketch.QuantileSketch` and memory stays O(1)
    in trace length (the north-star serving process runs for weeks —
    keep-everything percentiles don't). ``exact=True`` retains the old
    keep-everything behavior for parity tests and offline analysis.

    Bucket counts are exact in both modes; bucket search is a
    ``bisect`` over the sorted edges instead of the old linear scan.
    """

    def __init__(self, buckets: Tuple[float, ...] = (),
                 max_exact: int = 65536, exact: bool = False):
        self.buckets = tuple(sorted(buckets))
        self.bucket_counts = [0] * (len(self.buckets) + 1)
        self.max_exact = int(max_exact)
        self.exact = bool(exact)
        self._values: Optional[List[float]] = []
        self._sketch: Optional[QuantileSketch] = None

    def observe(self, value: float) -> None:
        value = float(value)
        if self._sketch is not None:
            self._sketch.add(value)
        else:
            self._values.append(value)
            if not self.exact and len(self._values) > self.max_exact:
                # exact -> sketch handoff: bulk-load every value seen
                # so far, then stop retaining raw observations
                self._sketch = QuantileSketch()
                self._sketch.extend(self._values)
                self._values = None
        if self.buckets:
            self.bucket_counts[
                bisect_left(self.buckets, value)] += 1

    @property
    def count(self) -> int:
        if self._sketch is not None:
            return self._sketch.n
        return len(self._values)

    @property
    def sum(self) -> float:
        if self._sketch is not None:
            return self._sketch.sum
        return float(np.sum(self._values)) if self._values else 0.0

    def mean(self) -> Optional[float]:
        return self.sum / self.count if self.count else None

    def percentile(self, q: float) -> Optional[float]:
        if self._sketch is not None:
            return self._sketch.quantile(q)
        if not self._values:
            return None
        return float(np.percentile(np.asarray(self._values), q))

    def summary(self) -> Dict:
        if not self.count:
            return {"count": 0}
        return {"count": self.count,
                "mean": round(self.mean(), 6),
                "p50": round(self.percentile(50), 6),
                "p90": round(self.percentile(90), 6),
                "p99": round(self.percentile(99), 6)}


#: default latency bucket edges (seconds) for Prometheus exposition —
#: 1 ms to ~2 min in roughly-doubling steps; bucket *counts* are what
#: scrapers aggregate, quantile queries stay sketch-side
LATENCY_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)


class ServingMetrics:
    """Aggregates the scheduler's StepReports + finished requests."""

    def __init__(self, slo: Optional[SLOTracker] = None,
                 exact_histograms: bool = False):
        kw = dict(exact=exact_histograms)
        self.ttft = Histogram(LATENCY_BUCKETS_S, **kw)
        self.tpot = Histogram(LATENCY_BUCKETS_S, **kw)
        self.queue_wait = Histogram(LATENCY_BUCKETS_S, **kw)
        #: the caller's wait for the server lock in ``submit``, before
        #: ``arrival_time``: TTFT and queue wait above start after it
        self.lock_wait = Histogram(LATENCY_BUCKETS_S, **kw)
        # the TTFT decomposition (queue-wait / prefill-compute /
        # handoff-transit): TTFT = queue_wait + prefill_compute; the
        # handoff-transit component is the cross-tier latent ship a
        # disaggregated fleet charges between the first and second
        # token (0-count under colocated serving) — split out so a
        # disagg win/loss is attributable, not an aggregate mystery
        self.prefill_compute = Histogram(LATENCY_BUCKETS_S, **kw)
        self.handoff_transit = Histogram(LATENCY_BUCKETS_S, **kw)
        self.preemptions_per_request = Histogram(**kw)
        #: burn-rate tracker; pass ``slo=False`` to disable entirely
        self.slo = SLOTracker() if slo is None else (slo or None)
        #: last-computed burn-rate gauge dict (refreshed per step; the
        #: scheduler copies these onto its ``sched.step`` span)
        self.slo_gauges: Dict[str, float] = {}
        self.counters = {"admitted": 0, "finished": 0, "cancelled": 0,
                         "preemptions": 0, "restores": 0,
                         "recompute_reentries": 0, "restore_chunks": 0,
                         "overlapped_restores": 0, "tokens_out": 0,
                         # chunked-prefill accounting: prompt slices
                         # dispatched, and the steps in which a slice
                         # shared the ragged put with live decode lanes
                         # (the head-of-line blocking it removes)
                         "prefill_chunk_steps": 0,
                         "prefill_chunks": 0,
                         # prompt tokens dispatched through prefill
                         # (the re-prefill savings baseline prefix
                         # reuse is measured against)
                         "prefill_tokens": 0,
                         # speculative-decode accounting (fused
                         # multi-token steps): lane-steps dispatched
                         # through put_spec, draft/accept/emit token
                         # totals, rejected-KV rollbacks
                         "spec_steps": 0, "spec_lane_steps": 0,
                         "spec_drafted": 0, "spec_accepted": 0,
                         "spec_emitted": 0, "spec_rollback_tokens": 0,
                         # fleet-wide prefix reuse: admissions that
                         # adopted a warm prefix via the restore path
                         # and the prompt tokens never re-prefilled
                         "prefix_adoptions": 0,
                         "prefix_tokens_reused": 0,
                         # SLO-aware degradation mode
                         "slo_degraded_steps": 0,
                         "steps": 0, "idle_steps": 0,
                         # resilience counters (chaos harness asserts
                         # these against the scheduler's own totals)
                         "failed": 0, "quarantined": 0,
                         "faults_injected": 0, "retries": 0,
                         "breaker_trips": 0, "restore_aborts": 0,
                         "watchdog_aborts": 0, "shed": 0,
                         "degraded_steps": 0, "deadline_failures": 0}
        self.rejected: Dict[str, int] = {}
        #: typed failure causes -> counts (the FAILED-state analog of
        #: ``rejected``)
        self.failures: Dict[str, int] = {}
        # -- per-request critical-path attribution profiles ---------- #
        #: E2E attribution (every terminal traced request) and the
        #: TTFT decomposition (requests that produced a first token),
        #: per phase, on the bounded quantile sketches — "which stage
        #: owns my p99" as a live metric, not an offline query
        self.critical_path_e2e = CriticalPathProfile()
        self.critical_path_ttft = CriticalPathProfile()
        #: attribution-closure / DAG-connectivity gate failures seen
        #: on finished requests (0 is the contract; non-zero means an
        #: instrumentation hole, surfaced rather than averaged away)
        self.trace_closure_failures = 0
        self.trace_disconnected = 0
        self.trace_max_closure_residual = 0.0
        # last-step gauges
        self.gauges = {"batch_occupancy": 0.0, "kv_utilization": 0.0,
                       "queue_depth": 0.0, "suspended": 0.0,
                       "restore_overlap_ratio": 0.0,
                       "degradation_level": 0.0,
                       # tokens emitted per speculative lane-step
                       # (1.0 is the non-speculative floor; the
                       # SPEC_SERVE artifact gates > 1.3 on the
                       # lookup-friendly trace)
                       "spec_accepted_tokens_per_step": 0.0,
                       # of the latent bytes landed on the host, the
                       # share copied while a program ran (the rest
                       # was landed while a reader or the engine
                       # waited); engine.latent_stats()
                       "latent_land_hidden_share": 0.0,
                       "slo_level": 0.0}

    # ------------------------------------------------------------- #
    # scheduler hooks
    # ------------------------------------------------------------- #
    def on_step(self, report, scheduler) -> None:
        c = self.counters
        c["steps"] += 1
        if not report.work_done:
            c["idle_steps"] += 1
        c["admitted"] += len(report.admitted)
        c["preemptions"] += len(report.preempted)
        c["restores"] += len(report.restored)
        c["recompute_reentries"] += len(report.recomputed)
        c["restore_chunks"] += report.restore_chunks
        c["overlapped_restores"] += report.overlapped_restores
        c["prefill_chunks"] += report.prefill_chunks
        if report.prefill_chunks:
            c["prefill_chunk_steps"] += 1
        c["prefill_tokens"] += report.prefill_tokens
        if report.spec_lanes:
            c["spec_steps"] += 1
        c["spec_lane_steps"] += report.spec_lanes
        c["spec_drafted"] += report.spec_drafted
        c["spec_accepted"] += report.spec_accepted
        c["spec_emitted"] += report.spec_emitted
        c["spec_rollback_tokens"] += report.spec_rollback_tokens
        c["prefix_adoptions"] += len(report.prefix_adoptions)
        c["prefix_tokens_reused"] += report.prefix_tokens_reused
        if report.slo_level > 0:
            c["slo_degraded_steps"] += 1
        c["failed"] += len(report.failed)
        c["quarantined"] += len(report.quarantined)
        c["faults_injected"] += report.faults
        c["retries"] += report.retries
        c["breaker_trips"] += report.breaker_trips
        c["restore_aborts"] += report.restore_aborts
        c["watchdog_aborts"] += report.watchdog_aborts
        c["shed"] += report.shed
        if report.degradation_level > 0:
            c["degraded_steps"] += 1
        for _, error in report.failed:
            self.failures[error] = self.failures.get(error, 0) + 1
            if error == "deadline_exceeded":
                c["deadline_failures"] += 1
        for _, reason in report.rejected:
            self.rejected[reason] = self.rejected.get(reason, 0) + 1
        engine = scheduler.engine
        sm = engine.config.state_manager
        lanes = report.decode_lanes + report.spec_lanes + \
            len(report.admitted)
        self.gauges["batch_occupancy"] = \
            lanes / max(sm.max_ragged_sequence_count, 1)
        alloc = engine.state.allocator
        self.gauges["kv_utilization"] = \
            1.0 - alloc.free_blocks / max(alloc.num_blocks, 1)
        self.gauges["queue_depth"] = float(len(scheduler.queue))
        self.gauges["suspended"] = float(len(scheduler.suspended))
        if getattr(engine, "recurrent", False):
            # a hybrid trunk: slots of the recurrent-state pools in use,
            # and the evicted sequences' state rows held on the host
            self.gauges["state_slots_in_use"] = float(report.state_slots)
            self.gauges["state_host_bytes"] = float(sum(
                sum(a.nbytes for a in r.state_rows)
                for r in scheduler.suspended.values()
                if r.state_rows is not None))
        if getattr(engine, "diffusion", False):
            # generation by diffusion over blocks: tokens made final
            # over the forwards of a lane's block that made them
            stats = engine.diffusion_stats()
            if stats["lane_passes"]:
                self.gauges["tokens_per_forward"] = \
                    stats["tokens_committed"] / stats["lane_passes"]
        self.gauges["degradation_level"] = \
            float(report.degradation_level)
        if scheduler.total_restores:
            self.gauges["restore_overlap_ratio"] = \
                scheduler.overlapped_restores / scheduler.total_restores
        if scheduler.total_spec_lane_steps:
            self.gauges["spec_accepted_tokens_per_step"] = \
                scheduler.total_spec_emitted / \
                scheduler.total_spec_lane_steps
        latent_stats = getattr(engine, "latent_stats", None)
        if latent_stats is not None:
            stats = latent_stats()
            landed = stats["landed_hidden_bytes"] + \
                stats["landed_forced_bytes"]
            if landed:
                self.gauges["latent_land_hidden_share"] = \
                    stats["landed_hidden_bytes"] / landed
        self.gauges["slo_level"] = float(report.slo_level)
        if self.slo is not None:
            # degradation level is SLO *context* (read-only), and the
            # burn-rate gauges are refreshed on this step's clock so
            # the sched.step span carries current values
            self.slo.note_degradation(report.t,
                                      report.degradation_level)
            self.slo_gauges = self.slo.gauges(report.t)

    def on_finish(self, req) -> None:
        self._observe_trace(req)
        if self.slo is not None and req.finished_at is not None:
            # every terminal request feeds availability; latency SLIs
            # only see requests that measured them (a FAILED request
            # with no first token is an availability miss, not a TTFT
            # miss). Cancellations are the caller's choice — neutral.
            if not req.cancelled:
                self.slo.observe_request(
                    req.finished_at, ok=req.state.name == "DONE",
                    ttft_s=req.ttft(), tpot_s=req.tpot())
        if req.state.name == "FAILED":
            return           # typed failures counted via report.failed
        if req.reject_reason and req.reject_reason != "cancelled":
            return                      # rejections counted via reports
        key = "cancelled" if req.cancelled else "finished"
        self.counters[key] += 1
        self.counters["tokens_out"] += len(req.tokens_out)
        if req.ttft() is not None:
            self.ttft.observe(req.ttft())
        if req.tpot() is not None:
            self.tpot.observe(req.tpot())
        if req.queue_wait() is not None:
            self.queue_wait.observe(req.queue_wait())
        if req.lock_wait() is not None:
            self.lock_wait.observe(req.lock_wait())
        if req.prefill_compute() is not None:
            self.prefill_compute.observe(req.prefill_compute())
        if getattr(req, "n_handoffs", 0):
            self.handoff_transit.observe(req.handoff_transit_s)
        self.preemptions_per_request.observe(req.n_preemptions)

    def _observe_trace(self, req) -> None:
        """Fold a terminal request's causal trace into the critical-
        path profiles, gating closure + connectivity as it lands."""
        ctx = getattr(req, "trace", None)
        if ctx is None or not ctx.spans:
            return
        ok, _reason = connected(ctx)
        if not ok:
            self.trace_disconnected += 1
        e2e = None
        if req.finished_at is not None:
            e2e = req.finished_at - req.arrival_time
        closed, residual = closure(ctx, e2e)
        if residual != float("inf"):
            self.trace_max_closure_residual = max(
                self.trace_max_closure_residual, residual)
        if not closed:
            self.trace_closure_failures += 1
        self.critical_path_e2e.observe(attribute(ctx))
        if req.first_token_at is not None:
            self.critical_path_ttft.observe(
                attribute(ctx, until=req.first_token_at))

    def critical_path_summary(self) -> Dict:
        return {
            "e2e": self.critical_path_e2e.summary(),
            "ttft": self.critical_path_ttft.summary(),
            "closure_failures": self.trace_closure_failures,
            "disconnected": self.trace_disconnected,
            "max_closure_residual":
                round(self.trace_max_closure_residual, 9),
        }

    # ------------------------------------------------------------- #
    # sinks
    # ------------------------------------------------------------- #
    def events(self, step: int) -> List[Tuple[str, float, int]]:
        """The monitor event-tuple list for one emission step."""
        out = []
        for name, hist in (("ttft_s", self.ttft), ("tpot_s", self.tpot),
                           ("queue_wait_s", self.queue_wait),
                           ("lock_wait_s", self.lock_wait),
                           ("prefill_compute_s", self.prefill_compute),
                           ("handoff_transit_s", self.handoff_transit)):
            for q in (50, 90, 99):
                v = hist.percentile(q)
                if v is not None:
                    out.append((f"serving/{name}/p{q}", v, step))
        for name, value in self.gauges.items():
            out.append((f"serving/{name}", float(value), step))
        for name, value in sorted(self.slo_gauges.items()):
            out.append((f"serving/{name}", float(value), step))
        for name, value in self.counters.items():
            out.append((f"serving/{name}", float(value), step))
        for reason, n in sorted(self.rejected.items()):
            out.append((f"serving/rejected/{reason}", float(n), step))
        for error, n in sorted(self.failures.items()):
            out.append((f"serving/failed/{error}", float(n), step))
        return out

    def emit(self, monitor, step: int, flush: bool = False) -> None:
        """Write through the MonitorMaster fan-out (rank-0 gated there).
        ``flush=True`` additionally flushes buffered sinks — the
        deterministic end-of-trace hook (see ``monitor.Monitor.flush``
        for the contract)."""
        if monitor is None or not getattr(monitor, "enabled", True):
            return
        monitor.write_events(self.events(step))
        if flush:
            monitor.flush()

    # ------------------------------------------------------------- #
    # Prometheus exposition
    # ------------------------------------------------------------- #
    def to_registry(self, registry=None, labels=None):
        """Render the full metric set into a ``MetricRegistry``
        (created on demand) — counters as counters, gauges as gauges,
        latency histograms with their bucket counts + sketch-derived
        quantile gauges. ``labels`` are merged into every sample: the
        fleet renders N replicas' metric sets into ONE registry with
        ``labels={"replica": "<id>"}`` so scrapers see one labeled
        family per metric instead of N name-mangled ones."""
        from ..telemetry.prometheus import MetricRegistry
        reg = registry if registry is not None else \
            MetricRegistry(namespace="hds_serving")
        base = dict(labels or {})

        def lbl(extra=None):
            if not extra:
                return dict(base) or None
            merged = dict(base)
            merged.update(extra)
            return merged

        for name, value in self.counters.items():
            reg.set_counter(name, value, labels=lbl(),
                            help=f"serving counter {name}")
        for reason, n in self.rejected.items():
            reg.set_counter("rejected", n,
                            labels=lbl({"reason": reason}),
                            help="rejected requests by reason")
        for error, n in self.failures.items():
            reg.set_counter("failed_typed", n,
                            labels=lbl({"error": error}),
                            help="typed request failures by cause")
        for name, value in self.gauges.items():
            reg.set_gauge(name, value, labels=lbl(),
                          help=f"serving gauge {name}")
        for name, value in self.slo_gauges.items():
            reg.set_gauge(name, value, labels=lbl(),
                          help="SLO burn-rate gauge (see telemetry.slo)")
        for name, hist in (("ttft_seconds", self.ttft),
                           ("tpot_seconds", self.tpot),
                           ("queue_wait_seconds", self.queue_wait),
                           ("lock_wait_seconds", self.lock_wait),
                           ("prefill_compute_seconds",
                            self.prefill_compute),
                           ("handoff_transit_seconds",
                            self.handoff_transit)):
            if hist.buckets:
                reg.set_histogram(name, hist.bucket_counts,
                                  hist.buckets, hist.count, hist.sum,
                                  labels=lbl(),
                                  help=f"serving latency {name}")
            for q in (50, 90, 99):
                v = hist.percentile(q)
                if v is not None:
                    reg.set_gauge(f"{name}_p{q}", v, labels=lbl(),
                                  help=f"{name} p{q} (sketch)")
        self.critical_path_e2e.to_registry(
            reg, prefix="critical_path_e2e", labels=lbl())
        self.critical_path_ttft.to_registry(
            reg, prefix="critical_path_ttft", labels=lbl())
        reg.set_counter("trace_closure_failures",
                        self.trace_closure_failures, labels=lbl(),
                        help="terminal requests whose attribution "
                             "failed the closure gate")
        reg.set_counter("trace_disconnected",
                        self.trace_disconnected, labels=lbl(),
                        help="terminal requests whose span DAG was "
                             "not connected")
        return reg

    def prometheus_text(self) -> str:
        return self.to_registry().render()

    def summary(self) -> Dict:
        out = {
            "ttft_s": self.ttft.summary(),
            "tpot_s": self.tpot.summary(),
            "queue_wait_s": self.queue_wait.summary(),
            "lock_wait_s": self.lock_wait.summary(),
            "prefill_compute_s": self.prefill_compute.summary(),
            "handoff_transit_s": self.handoff_transit.summary(),
            "preemptions_per_request":
                self.preemptions_per_request.summary(),
            "counters": dict(self.counters),
            "rejected": dict(self.rejected),
            "failures": dict(self.failures),
            "gauges": {k: round(v, 6) for k, v in self.gauges.items()},
            "critical_path": self.critical_path_summary(),
        }
        if self.slo is not None:
            out["slo"] = self.slo.summary()
        return out
