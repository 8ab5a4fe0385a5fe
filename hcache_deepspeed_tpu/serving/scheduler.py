"""Continuous-batching scheduler with HCache-aware preemption.

Each :meth:`ContinuousBatchingScheduler.step` builds ONE ragged
``put()`` mixing the resident sequences' decode tokens with newly
admitted prompts (the FastGen continuous-batching discipline the
engine's ``generate()`` loop uses), but adds what a production frontend
needs on top:

* **admission by verdict** — every ``can_schedule`` rejection routes
  through :data:`..inference.scheduling.BACKPRESSURE_ACTION`, so each
  failure mode gets its own corrective action (wait / skip / preempt /
  reject) instead of a blanket retry;
* **preemption under KV pressure** — victims are chosen lowest
  priority first (then latest deadline, then youngest) and suspended to
  HOST: in latent mode the sequence is flushed outright and its HCache
  latents (already accumulated on host by ``put``'s capture path) become
  the restore payload; in exact-KV mode ``suspend_sequence`` copies the
  cache blocks out;
* **restore overlapped with decode** — a suspended request re-enters
  through ``restore_kv``, issued in the same host step as (and with no
  host sync before) the residents' decode dispatch: the latent host→HBM
  ships run on the transfer stream while the previous dispatches
  compute, the same independent-resources overlap (host link vs MXU) as
  T3's NIC-vs-SM fine-grained overlap (arXiv:2401.16677).

The scheduler is clock- and engine-agnostic: with a ``VirtualClock``
and a :class:`.sim.SimulatedEngine` the whole policy is a deterministic
pure function of (trace, seed) — ``events`` is the replayable log the
determinism tests assert on.
"""

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..inference.scheduling import (BACKPRESSURE_ACTION, BackpressureAction,
                                    BlockPass, SchedulingError,
                                    SchedulingResult)
from ..resilience.degradation import DegradationLadder, DegradationLevel
from ..resilience.policy import ResiliencePolicy
from ..resilience.retry import CircuitBreaker, Watchdog
from ..runtime.config import HDSConfigError
from ..telemetry.flight import get_flight_recorder
from ..telemetry.tracer import get_tracer
from .clock import MonotonicClock
from .crossover import RestoreCrossoverModel
from .request import OpenBlock, Request, RequestState
from .spec import (SLODegradation, SLOModeConfig, SpeculationConfig,
                   lookup_draft, validate_slo_mode_config,
                   validate_speculation_config)


def greedy_sample(req: Request, logits_row) -> int:
    return int(np.argmax(logits_row))


@dataclass
class StepReport:
    """What one scheduler step did (the server's cost model and the
    metrics layer both consume this)."""
    step: int
    t: float
    admitted: List[int] = field(default_factory=list)
    rejected: List[Tuple[int, str]] = field(default_factory=list)
    preempted: List[int] = field(default_factory=list)
    restored: List[int] = field(default_factory=list)
    #: crossover-policy re-entries that re-prefilled instead of
    #: restoring (cheaper side of the analytic model)
    recomputed: List[int] = field(default_factory=list)
    finished: List[int] = field(default_factory=list)
    cancelled: List[int] = field(default_factory=list)
    #: typed hard failures closed this step: (uid, error)
    failed: List[Tuple[int, str]] = field(default_factory=list)
    #: subset of ``failed`` closed by the dispatch quarantine
    quarantined: List[int] = field(default_factory=list)
    decode_lanes: int = 0
    prefill_tokens: int = 0
    #: chunked-prefill slices dispatched this step (Dynamic SplitFuse
    #: at the scheduler grain: each slice rides the same ragged put as
    #: the residents' decode tokens, so a long prompt never head-of-
    #: line blocks decode for more than one chunk's worth of compute)
    prefill_chunks: int = 0
    restored_tokens: int = 0
    #: restore replay chunks issued this step (lane progress)
    restore_chunks: int = 0
    #: restores whose lane overlapped resident decode (each restore
    #: counted once, in the step its overlap is first observed — the
    #: overlap the HCache story is about)
    overlapped_restores: int = 0
    # -- resilience accounting --------------------------------------- #
    #: faults observed this step (injected or real engine exceptions)
    faults: int = 0
    #: restore-lane chunk retries issued this step (backoff slept)
    retries: int = 0
    #: circuit-breaker trips this step
    breaker_trips: int = 0
    #: restore lanes aborted (retry exhaustion or watchdog)
    restore_aborts: int = 0
    #: lanes aborted specifically by the stuck-lane watchdog
    watchdog_aborts: int = 0
    #: queued requests shed by the degradation ladder
    shed: int = 0
    #: degradation ladder level applied to this step's decisions
    degradation_level: int = 0
    # -- speculative-decode accounting -------------------------------- #
    #: decode lanes dispatched through the fused speculative step this
    #: step (subset of ``decode_lanes``)
    spec_lanes: int = 0
    #: draft tokens fed for verification this step
    spec_drafted: int = 0
    #: draft tokens accepted (bonus tokens not counted)
    spec_accepted: int = 0
    #: tokens emitted by speculative lanes (accepted + bonus; 1 per
    #: lane is the non-speculative floor)
    spec_emitted: int = 0
    #: rejected draft KV rolled back (tokens)
    spec_rollback_tokens: int = 0
    # -- fleet-wide prefix reuse -------------------------------------- #
    #: admissions that adopted a warm prefix via the restore path
    prefix_adoptions: List[int] = field(default_factory=list)
    #: prompt tokens NOT re-prefilled thanks to adoption this step
    prefix_tokens_reused: int = 0
    #: SLO-aware degradation level applied this step (0 = normal,
    #: 1 = speculation off, 2 = + forced chunked prefill, 3 = + shed)
    slo_level: int = 0
    #: sequences holding a slot of the recurrent-state pools after this
    #: step (a hybrid trunk; 0 for a trunk with no recurrent layer)
    state_slots: int = 0
    # -- generation by diffusion over blocks -------------------------- #
    #: lanes that fed an open block this step (they are the step's
    #: ``decode_lanes``), and those of them whose pass committed
    block_lanes: int = 0
    commit_lanes: int = 0
    #: positions that denoise passes filled, and positions made final
    tokens_unmasked: int = 0
    tokens_committed: int = 0

    @property
    def work_done(self) -> bool:
        return bool(self.admitted or self.restored or self.finished or
                    self.decode_lanes or self.spec_lanes or
                    self.prefill_tokens or
                    self.rejected or self.preempted or self.cancelled or
                    self.recomputed or self.restore_chunks or
                    self.failed or self.faults or self.restore_aborts)


class ContinuousBatchingScheduler:
    """Single-threaded scheduling core (the server serializes access).

    ``engine`` needs the ``InferenceEngineV2`` serving surface:
    ``can_schedule``/``put``/``flush``/``restore_kv``/
    ``suspend_sequence``/``resume_sequence``, ``state``, ``block_size``,
    ``max_context`` and ``config`` — :class:`.sim.SimulatedEngine`
    provides the same surface without a model.
    """

    def __init__(self, engine, clock=None,
                 sample_fn: Callable[[Request, np.ndarray], int] = None,
                 metrics=None, crossover: RestoreCrossoverModel = None,
                 restore_chunks_per_step: int = 1,
                 resilience: ResiliencePolicy = None,
                 replica_id: int = 0,
                 prefill_chunk: int = 0,
                 preempt_restore_grace: int = 0,
                 restore_priority_barrier: bool = False,
                 speculation: SpeculationConfig = None,
                 slo_mode: SLOModeConfig = None,
                 prefix_cache=None, denoising_steps: int = 2,
                 block_token_fn: Callable[[Request, int], None] = None):
        self.engine = engine
        #: fleet position of this scheduler (0 = standalone/replica 0);
        #: folded into the retry-jitter RNG key so N replicas retrying
        #: concurrently draw from independent per-site streams
        self.replica_id = int(replica_id)
        self.clock = clock or MonotonicClock()
        self.sample_fn = sample_fn or greedy_sample
        self.metrics = metrics
        #: latent-preempt mode: evict = flush + keep host latents,
        #: restore = restore_kv (frees the tracked slot too). Without
        #: latent capture the exact-KV suspend/resume path is used.
        self.latent_preemption = bool(engine.config.hcache.enable_latents)
        #: a trunk with recurrent layers: eviction also takes the
        #: sequence's state rows to the host (``Request.state_rows``)
        self._recurrent = bool(getattr(engine, "recurrent", False))
        #: restore-vs-recompute crossover model consulted per preempted
        #: sequence at re-entry (latent mode only; None = always
        #: restore, the pre-policy behavior). Built lazily from the
        #: engine's profile so an uncalibrated model still exists to
        #: absorb telemetry samples.
        self.crossover = crossover
        if self.crossover is None and self.latent_preemption and \
                hasattr(engine, "restore_profile"):
            self.crossover = RestoreCrossoverModel(
                engine.restore_profile())
        #: replay chunks issued per step while a restore lane is open
        #: (the decode-interleave grain: smaller = more decode steps
        #: hide under one restore; 0 = drain a lane in one step)
        self.restore_chunks_per_step = restore_chunks_per_step
        #: scheduler-grain chunked prefill (Dynamic SplitFuse): a
        #: prompt longer than this dispatches in per-step slices that
        #: share each ragged put with the residents' decode tokens —
        #: the request stays PREFILL (a resident, never a preemption
        #: victim) until its last slice samples the first token.
        #: 0 = monolithic prefill (the historical behavior; committed
        #: chaos digests replay unchanged)
        self.prefill_chunk = max(0, int(prefill_chunk))
        #: restore→preempt livelock guard: a resident restored within
        #: the last N steps is not a preemption victim until it has
        #: had a decode dispatch — without it, a persistent higher-
        #: priority admission can evict each freshly-restored resident
        #: every step while the restore pass restores another, and the
        #: step makes no token progress forever. 0 = no protection
        #: (the historical victim policy; committed digests replay)
        self.preempt_restore_grace = max(0, int(preempt_restore_grace))
        #: head-of-line restore: when the best suspended candidate
        #: does not fit, do NOT let smaller lower-ranked payloads
        #: leapfrog it — freed blocks accrue to the head instead, so
        #: a large (long-context) restore cannot be starved by a
        #: stream of small landings. False = the historical
        #: smaller-may-still-fit policy (better pool utilization,
        #: unbounded big-payload wait; committed digests replay)
        self.restore_priority_barrier = bool(restore_priority_barrier)
        #: scheduler-dispatched speculative decode (None/disabled =
        #: the historical one-token-per-lane step; committed chaos
        #: digests replay). Validated typed at build — no silent
        #: clamps (the validate_overlap_config pattern).
        self.speculation = speculation
        if speculation is not None and speculation.enabled:
            validate_speculation_config(speculation, engine.config)
            if not hasattr(engine, "put_spec"):
                raise HDSConfigError(
                    "speculation requires an engine exposing the "
                    "fused put_spec verify step "
                    f"({type(engine).__name__} does not)")
            if self.latent_preemption and \
                    not getattr(engine, "spec_latent_capture", False):
                raise HDSConfigError(
                    "speculation under latent preemption requires an "
                    "engine whose put_spec captures accepted-span "
                    "latents; this engine only speculates with "
                    "hcache.enable_latents=false (exact-KV "
                    "suspension)")
            if sample_fn is not None and sample_fn is not greedy_sample:
                raise HDSConfigError(
                    "speculation is greedy-exact only: acceptance "
                    "verifies drafts against greedy targets, so a "
                    "custom sample_fn would silently change the "
                    "stream — disable speculation or drop sample_fn")
        #: a model that generates by diffusion over blocks: a DECODE
        #: resident holds an open block (``Request.block``), a step is
        #: a pass over it, and tokens come a block at a time, at commit:
        #: chosen on the device, so ``sample_fn`` has no row to read;
        #: ``block_token_fn(req, token)`` is told each token as it is
        #: emitted. A denoise pass fills the ``ceil(block /
        #: denoising_steps)`` masked positions of highest confidence
        self._block_len = int(getattr(engine, "block_len", 1))
        self._diffusion = self._block_len > 1
        self.denoising_steps = int(denoising_steps)
        self.block_token_fn = block_token_fn
        if self._diffusion:
            self._mask_id = int(engine.mask_token_id)
            if self.denoising_steps < 1:
                raise HDSConfigError(
                    f"denoising_steps must be >= 1, got {denoising_steps}")
            if sample_fn is not None and sample_fn is not greedy_sample:
                raise HDSConfigError(
                    "a model that generates by diffusion over blocks "
                    "chooses its tokens on the device: sample_fn is "
                    "never called; block_token_fn is told the tokens")
            if speculation is not None and speculation.enabled:
                raise HDSConfigError(
                    "speculation drafts one-token decode steps; a model "
                    "that generates by diffusion over blocks has none "
                    "(engine.put_spec refuses it by name)")
            if prefix_cache is not None:
                raise HDSConfigError(
                    "warm-prefix adoption is not supported for a model "
                    "that generates by diffusion over blocks")
        #: current step's drafts: uid -> proposed tokens (rebuilt per
        #: step by _draft_pass; consulted by _next_feed so admission /
        #: pressure verdicts budget the full speculative feed)
        self._drafts: Dict[int, List[int]] = {}
        #: SLO-aware degradation (TTFT/TPOT burn -> speculation off =>
        #: chunked prefill => shed); disabled = ladder untouched
        if slo_mode is not None:
            validate_slo_mode_config(slo_mode)
        self.slo = SLODegradation(slo_mode)
        self.slo_level = 0
        #: fleet-wide prefix reuse: the replica's warm-prefix cache
        #: (None = no reuse, the historical admission path)
        self.prefix_cache = prefix_cache

        self.queue: List[Request] = []           # QUEUED, submit order
        self.running: Dict[int, Request] = {}    # DECODE residents
        self.suspended: Dict[int, Request] = {}  # SUSPENDED (KV on host)
        self.restoring: Dict[int, Request] = {}  # RESTORING (lane open)
        self.done: Dict[int, Request] = {}       # DONE / REJECTED
        #: replayable (step, event, uid, detail) log; identical across
        #: runs of the same trace under a virtual clock
        self.events: List[Tuple[int, str, int, str]] = []
        self.step_idx = 0
        self.total_restores = 0
        self.total_recomputes = 0
        self.overlapped_restores = 0
        # -- speculative-decode + prefix-reuse totals ----------------- #
        self.total_spec_lane_steps = 0
        self.total_spec_drafted = 0
        self.total_spec_accepted = 0
        self.total_spec_emitted = 0
        self.total_spec_rolled_back = 0
        self.total_prefix_adoptions = 0
        self.total_prefix_tokens_reused = 0
        #: uids whose open lane already earned its (single) overlap
        #: credit — a multi-step lane must not count once per step
        self._overlap_credited = set()
        # -- resilience machinery ------------------------------------ #
        #: recovery knobs; defaults are inert on a fault-free trace
        self.resilience = resilience or ResiliencePolicy()
        r = self.resilience
        #: restore-path circuit breaker: repeated restore faults trip
        #: re-entry over to the crossover recompute path until cooldown
        self.breaker = CircuitBreaker(threshold=r.breaker_threshold,
                                      window=r.breaker_window,
                                      cooldown=r.breaker_cooldown)
        #: stuck-lane watchdog (no chunk progress in N steps -> abort)
        self.watchdog = Watchdog(limit=r.watchdog_steps)
        #: graceful-degradation ladder (shed -> cap -> pause)
        self.ladder = DegradationLadder(r.ladder)
        self.degradation = DegradationLevel.NORMAL
        #: seeded jitter stream for restore-retry backoff. Replica 0
        #: keeps the historical 2-word key so committed single-engine
        #: chaos digests replay unchanged; other replicas append their
        #: id, giving every fleet member an independent stream (the
        #: fleet determinism gate depends on streams never aliasing)
        rng_key = [r.seed & 0x7FFFFFFF, 0x5E71]
        if self.replica_id:
            rng_key.append(self.replica_id)
        self._retry_rng = np.random.default_rng(rng_key)
        self.total_faults = 0
        self.total_retries = 0
        self._fault_sites: Dict[str, int] = {}
        #: faults since the ladder last observed (consumed per step)
        self._fault_events = 0

    # ------------------------------------------------------------- #
    # intake
    # ------------------------------------------------------------- #
    def submit(self, req: Request) -> None:
        # request-lifetime async interval: QUEUED here, closed at
        # DONE/REJECTED in _close/_reject — the per-request lane in the
        # exported trace; state edges ride the sched.* instants _event
        # emits
        if not req.async_span_begun:
            # once per request LIFETIME: a crash-evacuated request
            # re-submitted through a surviving replica's scheduler
            # keeps its original interval (ended exactly once at its
            # terminal state, wherever that lands)
            req.async_span_begun = True
            get_tracer().async_begin("request", req.uid,
                                     prio=req.priority,
                                     prompt=len(req.prompt),
                                     replica=self.replica_id,
                                     trace="" if req.trace is None
                                     else req.trace.trace_id)
        if self._diffusion and req.block is None:
            req.block = OpenBlock([self._mask_id] * self._block_len)
        self._event("queued", req.uid, f"prio={req.priority}")
        self.queue.append(req)

    def cancel(self, uid: int) -> None:
        """Mark a request for cancellation; honored at the next step.
        A request mid-restore has its open lane aborted at that point
        (``engine.abort_restore`` — the abort owns the in-flight replay
        chunks, so the lane's blocks free without corrupting the pool)
        and its host latents dropped."""
        for pool in (self.queue, self.running.values(),
                     self.suspended.values(), self.restoring.values()):
            for req in pool:
                if req.uid == uid:
                    req.cancelled = True
                    return

    @property
    def has_work(self) -> bool:
        return bool(self.queue or self.running or self.suspended or
                    self.restoring)

    def request(self, uid: int) -> Optional[Request]:
        if uid in self.done:
            return self.done[uid]
        if uid in self.running:
            return self.running[uid]
        if uid in self.suspended:
            return self.suspended[uid]
        if uid in self.restoring:
            return self.restoring[uid]
        for req in self.queue:
            if req.uid == uid:
                return req
        return None

    # ------------------------------------------------------------- #
    # one continuous-batching step
    # ------------------------------------------------------------- #
    def step(self) -> StepReport:
        self.step_idx += 1
        now = self.clock.now()
        report = StepReport(step=self.step_idx, t=now)
        tracer = get_tracer()
        with tracer.span("sched.step", sched_step=self.step_idx,
                         replica=self.replica_id) as sp:
            with tracer.span("sched.passes"):
                self._cancellation_pass(report)
                self._deadline_pass(report, now)
                self._degradation_pass(report)
                self._slo_pass(report)
                self._restore_pass(report)
                self._draft_pass()
            with tracer.span("sched.admission", queued=len(self.queue)):
                admits = self._admission_pass(report, now)
                admits = self._pressure_pass(admits, report)
            self._dispatch(admits, report, now)
            with tracer.span("sched.passes"):
                self._watchdog_pass(report)
            if self._recurrent:
                report.state_slots = self.engine.state.state_slots_in_use
            if self.metrics is not None:
                with tracer.span("sched.metrics"):
                    self.metrics.on_step(report, self)
                    if self.metrics.slo_gauges:
                        # SLO burn rates ride the sched.step span,
                        # read-only context for whoever drives the
                        # degradation ladder from them later (ROADMAP
                        # item 4) — the span is the contract, the
                        # tracker never steers the scheduler
                        sp.set(**{k: round(float(v), 6) for k, v in
                                  self.metrics.slo_gauges.items()})
                        self._flight_slo_check(now)
        return report

    # ------------------------------------------------------------- #
    def _event(self, event: str, uid: int, detail: str = "") -> None:
        self.events.append((self.step_idx, event, uid, detail))
        # every lifecycle edge doubles as a trace instant (preempt /
        # restore / admit / finish ... on the request's timeline);
        # the replica stamp is what lets the assembler fan a fleet
        # run out into per-replica Perfetto process rows
        get_tracer().instant(f"sched.{event}", uid=uid,
                             sched_step=self.step_idx,
                             replica=self.replica_id, detail=detail)

    # ------------------------------------------------------------- #
    # flight-recorder triggers (read-only: never touches the event
    # log, the RNG or the clock — chaos digests replay unchanged)
    # ------------------------------------------------------------- #
    def flight_snapshot(self, last_events: int = 32) -> Dict:
        """Deterministic postmortem core: pool depths, breaker/ladder
        state, fault accounting, the event-log tail — everything is a
        pure function of (trace, seed) under the virtual clock."""
        snap = {
            "replica": self.replica_id,
            "step": self.step_idx,
            "t": round(self.clock.now(), 9),
            "pools": {"queue": len(self.queue),
                      "running": len(self.running),
                      "suspended": len(self.suspended),
                      "restoring": len(self.restoring),
                      "done": len(self.done)},
            "breaker": self.breaker.state.name,
            "degradation": int(self.degradation),
            "slo_level": self.slo_level,
            "fault_summary": self.fault_summary(),
            "free_blocks": self.engine.state.free_blocks,
            "events_tail": [list(e)
                            for e in self.events[-last_events:]],
        }
        if self.metrics is not None:
            snap["counters"] = dict(self.metrics.counters)
            snap["failures"] = dict(self.metrics.failures)
            snap["slo_gauges"] = {k: round(float(v), 6) for k, v in
                                  self.metrics.slo_gauges.items()}
        return snap

    def _flight(self, trigger: str, reason: str) -> None:
        rec = get_flight_recorder()
        src = f"replica{self.replica_id}"
        if not rec.should_fire(trigger, src, self.step_idx):
            return
        tracer = get_tracer()
        rec.dump(trigger, reason, source=src, step=self.step_idx,
                 t=self.clock.now(), snapshot=self.flight_snapshot(),
                 spans=tracer.events()[-rec.span_tail:]
                 if tracer.enabled else None)

    def _flight_slo_check(self, now: float) -> None:
        """Arm the ``slo_burn`` trigger when any burn-rate gauge
        crosses the recorder's threshold (default 10x — the error
        budget gone in a tenth of its window)."""
        rec = get_flight_recorder()
        worst_name, worst = "", 0.0
        for name, v in self.metrics.slo_gauges.items():
            if name.endswith("_burn_rate") and float(v) > worst:
                worst_name, worst = name, float(v)
        if worst >= rec.slo_burn_threshold:
            self._flight("slo_burn",
                         f"{worst_name}={worst:.3f} >= "
                         f"{rec.slo_burn_threshold:g}")

    def _close(self, req: Request, report: StepReport, now: float,
               cancelled: bool = False) -> None:
        req.finished_at = now
        req.transition(RequestState.DONE)
        self.done[req.uid] = req
        (report.cancelled if cancelled else report.finished).append(req.uid)
        self._event("cancel" if cancelled else "finish", req.uid,
                    f"tokens={len(req.tokens_out)}")
        get_tracer().async_end("request", req.uid,
                               tokens=len(req.tokens_out),
                               preemptions=req.n_preemptions,
                               restores=req.n_restores,
                               replica=self.replica_id)
        if self.metrics is not None:
            self.metrics.on_finish(req)

    def _reject(self, req: Request, reason: str,
                report: StepReport) -> None:
        req.reject_reason = reason
        req.finished_at = self.clock.now()
        req.transition(RequestState.REJECTED)
        self.done[req.uid] = req
        report.rejected.append((req.uid, reason))
        self._event("reject", req.uid, reason)
        get_tracer().async_end("request", req.uid, reject=reason,
                               replica=self.replica_id)
        if self.metrics is not None:
            self.metrics.on_finish(req)

    # ------------------------------------------------------------- #
    # resilience: typed failures, fault accounting, degradation
    # ------------------------------------------------------------- #
    def _fail(self, req: Request, error: str, report: StepReport,
              now: float = None, quarantined: bool = False) -> None:
        """Close ``req`` in the typed FAILED terminal state."""
        now = self.clock.now() if now is None else now
        req.error = error
        req.finished_at = now
        req.transition(RequestState.FAILED)
        self.done[req.uid] = req
        report.failed.append((req.uid, error))
        if quarantined:
            report.quarantined.append(req.uid)
        self._event("fail", req.uid, error)
        get_tracer().async_end("request", req.uid, error=error,
                               replica=self.replica_id)
        if self.metrics is not None:
            self.metrics.on_finish(req)

    def _note_fault(self, exc: BaseException,
                    report: StepReport) -> None:
        """Account one fault (injected or a real engine exception)."""
        self.total_faults += 1
        self._fault_events += 1
        report.faults += 1
        site = getattr(exc, "site", None) or type(exc).__name__
        self._fault_sites[site] = self._fault_sites.get(site, 0) + 1
        uid = getattr(exc, "uid", None)
        self._event("fault", -1 if uid is None else uid, f"site={site}")

    def _safe_flush(self, uid: int) -> None:
        """Free ``uid``'s engine state if it exists and has no open
        restore lane — the idempotent cleanup every failure path uses
        so quarantined/expired requests can never leak KV blocks."""
        try:
            if self.engine.state.get_sequence(uid) is None:
                return
            if uid in getattr(self.engine, "restoring_uids", ()):
                return        # lane abort owns that path
            self.engine.flush(uid)
        except Exception:
            pass              # the engine may be the thing that broke

    def fault_summary(self) -> Dict:
        return {"total_faults": self.total_faults,
                "by_site": dict(self._fault_sites),
                "retries": self.total_retries,
                "breaker_trips": self.breaker.trips,
                "breaker_state": self.breaker.state.name,
                "watchdog_aborts": self.watchdog.aborts,
                "degraded_steps": self.ladder.degraded_steps,
                "degradation_level": int(self.degradation)}

    def fail_all_live(self, error: str) -> List[int]:
        """Hard-fail every non-terminal request (server death path).
        Engine state is NOT touched — the engine is presumed broken;
        the caller owns whatever cleanup is still possible."""
        now = self.clock.now()
        failed = []
        for req in list(self.queue):
            self.queue.remove(req)
            self._fail(req, error, StepReport(self.step_idx, now), now)
            failed.append(req.uid)
        for pool in (self.running, self.suspended, self.restoring):
            for uid in list(pool):
                req = pool.pop(uid)
                self._fail(req, error, StepReport(self.step_idx, now),
                           now)
                failed.append(uid)
        return failed

    # ------------------------------------------------------------- #
    # fleet hooks: cross-replica migration + drain + crash evacuation
    # ------------------------------------------------------------- #
    def detach_for_migration(self, uid: int) -> Optional[Request]:
        """Detach ``uid`` for cross-replica migration (fleet rebalance
        or graceful drain). The request leaves in ``SUSPENDED`` state
        with its host latent payload as the transfer body: running
        requests are preempted to latents first (their engine state is
        flushed), restoring requests get their open lane aborted
        (payload untouched — a replay consumes latents, it does not
        move them), queued requests detach as-is in ``QUEUED``. Engine
        state for ``uid`` is fully freed on this replica. Returns None
        for unknown/terminal uids."""
        for req in self.queue:
            if req.uid == uid:
                self.queue.remove(req)
                self._event("migrate_out", uid, "from=queued")
                return req
        if uid in self.suspended:
            req = self.suspended.pop(uid)
            if not self.latent_preemption:
                # exact-KV host copy lives in THIS engine and cannot
                # travel; drop it — the destination recomputes
                self._safe_flush(uid)
                req.latents = None
            self._event("migrate_out", uid, "from=suspended")
            return req
        if uid in self.restoring:
            self.engine.abort_restore(uid)
            req = self.restoring.pop(uid)
            self._overlap_credited.discard(uid)
            self.watchdog.drop(uid)
            req.transition(RequestState.SUSPENDED)
            req.suspended_in_step = self.step_idx
            self._event("migrate_out", uid, "from=restoring")
            return req
        if uid in self.running:
            req = self.running[uid]
            if req.state == RequestState.PREFILL:
                # mid-chunk prefill: nothing restorable exists yet —
                # rewind to QUEUED (partial latents dropped, engine
                # state freed); the caller re-routes the queue slot
                del self.running[uid]
                self._safe_flush(uid)
                req.latents = None
                req.prefill_pos = 0
                req.admitted_at = None
                req.transition(RequestState.QUEUED)
                self._event("migrate_out", uid, "from=prefill")
                return req
            req = self.running.pop(uid)
            if self.latent_preemption and req.latents is not None and \
                    req.latents.shape[1] == req.cached_tokens:
                self.engine.flush(uid)
            else:
                # incomplete/no payload: free the device state anyway;
                # the destination re-enters via recompute
                self._safe_flush(uid)
                req.latents = None
            req.transition(RequestState.SUSPENDED)
            req.n_preemptions += 1
            req.suspended_in_step = self.step_idx
            self._event("migrate_out", uid, "from=running")
            return req
        return None

    def adopt_suspended(self, req: Request) -> None:
        """Adopt a migrated-in request. It arrives ``SUSPENDED`` with
        (when intact) its latent payload; the normal restore pass —
        crossover policy, breaker, recompute fallback — re-enters it.
        The anti-thrash step stamp is re-armed on THIS scheduler's
        step counter (the source's counter is meaningless here)."""
        if req.state != RequestState.SUSPENDED:
            raise ValueError(
                f"adopt_suspended: request {req.uid} is "
                f"{req.state.name}, not SUSPENDED")
        if self.request(req.uid) is not None:
            raise ValueError(f"uid {req.uid} already known here")
        req.suspended_in_step = self.step_idx
        self.suspended[req.uid] = req
        self._event("migrate_in",
                    req.uid, f"tokens={req.cached_tokens} "
                    f"payload={'latents' if req.latents is not None else 'none'}")

    def adopt_queued(self, req: Request) -> None:
        """Adopt a re-routed queued request (crash recovery / drain of
        not-yet-admitted work)."""
        if req.state != RequestState.QUEUED:
            raise ValueError(
                f"adopt_queued: request {req.uid} is {req.state.name}")
        if self.request(req.uid) is not None:
            raise ValueError(f"uid {req.uid} already known here")
        self.queue.append(req)
        self._event("migrate_in", req.uid, "from=queued")

    def evacuate_live(self) -> Tuple[List[Request], List[Request]]:
        """Crash-recovery hook: detach every non-terminal request
        WITHOUT touching the engine (it is presumed dead — its blocks
        died with it and are excluded from the fleet leak invariant).
        Returns ``(queued, live)``: queued requests re-route as-is;
        live ones leave ``SUSPENDED``, replayable from whatever latent
        payload they carried when the replica died (requests without a
        full payload re-enter via recompute on their new replica)."""
        queued = list(self.queue)
        self.queue.clear()
        live: List[Request] = []
        for pool in (self.running, self.restoring, self.suspended):
            for uid in list(pool):
                req = pool.pop(uid)
                self._overlap_credited.discard(uid)
                self.watchdog.drop(uid)
                origin = req.state.name
                if req.state == RequestState.PREFILL and \
                        not req.tokens_out:
                    # crashed mid-prompt (chunked prefill): nothing
                    # decodable exists — rewind to QUEUED so the fleet
                    # requeues it onto a surviving (prefill) replica
                    req.latents = None
                    req.prefill_pos = 0
                    req.admitted_at = None
                    req.transition(RequestState.QUEUED)
                    self._event("evacuate", uid, f"from={origin}")
                    queued.append(req)
                    continue
                if req.latents is None or \
                        req.latents.shape[1] != req.cached_tokens:
                    req.latents = None      # partial payload: recompute
                if req.state != RequestState.SUSPENDED:
                    req.transition(RequestState.SUSPENDED)
                req.suspended_in_step = self.step_idx
                self._event("evacuate", uid, f"from={origin}")
                live.append(req)
        return queued, live

    def _deadline_pass(self, report: StepReport, now: float) -> None:
        """Enforce per-request absolute deadlines: an expired request
        hard-fails typed instead of burning capacity. Requests with an
        open restore lane are skipped (freeing blocks under in-flight
        replay writes would corrupt the pool) and caught on a later
        pass once the lane has drained or aborted."""
        if not self.resilience.enforce_deadlines:
            return

        def expired(r):
            return r.deadline is not None and now > r.deadline

        for req in [r for r in self.queue if expired(r)]:
            self.queue.remove(req)
            self._fail(req, "deadline_exceeded", report, now)
        for uid in [u for u, r in self.running.items() if expired(r)]:
            req = self.running.pop(uid)
            self._safe_flush(uid)
            self._fail(req, "deadline_exceeded", report, now)
        for uid in [u for u, r in self.suspended.items() if expired(r)]:
            req = self.suspended.pop(uid)
            if not self.latent_preemption:
                self._safe_flush(uid)
            self._fail(req, "deadline_exceeded", report, now)

    def _degradation_pass(self, report: StepReport) -> None:
        """Feed the ladder last step's fault count + current pressure;
        apply the SHED action here (CAP/PAUSE apply at admission)."""
        faults_since = self._fault_events
        self._fault_events = 0
        alloc = self.engine.state.allocator
        kv_util = 1.0 - alloc.free_blocks / max(alloc.num_blocks, 1)
        self.degradation = self.ladder.observe(
            self.step_idx, faults_since, kv_util, len(self.queue))
        report.degradation_level = int(self.degradation)
        # shed only a real backlog: a queue the batch could absorb next
        # step is not load worth refusing, even mid-storm
        backlog = len(self.queue) > \
            self.engine.config.state_manager.max_ragged_sequence_count
        if self.degradation >= DegradationLevel.SHED and backlog:
            victim = min(self.queue,
                         key=lambda r: (r.priority, -r.arrival_time,
                                        -r.uid))
            self.queue.remove(victim)
            self._reject(victim, "shed_degraded", report)
            report.shed += 1

    def _slo_pass(self, report: StepReport) -> None:
        """SLO-aware degradation: walk the escalation ladder
        (speculation off => forced chunked prefill => shed) from the
        TTFT/TPOT burn-rate gauges the metrics layer computed at the
        end of the previous step. Deterministic under the virtual
        clock — the gauges are pure functions of virtual timestamps."""
        if not self.slo.enabled:
            return
        gauges = self.metrics.slo_gauges if self.metrics is not None \
            else {}
        prev = self.slo_level
        self.slo_level = self.slo.observe(gauges)
        report.slo_level = self.slo_level
        if self.slo_level != prev:
            self._event(
                "slo_degrade" if self.slo_level > prev
                else "slo_recover", -1,
                f"level={self.slo_level} "
                f"({SLODegradation.LEVELS[self.slo_level]})")
        backlog = len(self.queue) > \
            self.engine.config.state_manager.max_ragged_sequence_count
        if self.slo_level >= 3 and backlog:
            victim = min(self.queue,
                         key=lambda r: (r.priority, -r.arrival_time,
                                        -r.uid))
            self.queue.remove(victim)
            self._reject(victim, "shed_slo", report)
            report.shed += 1

    @property
    def _prefill_chunk_now(self) -> int:
        """Effective scheduler-grain prefill chunk: the configured one,
        tightened by the SLO ladder at level >= 2 (forced Dynamic
        SplitFuse — long prompts stop head-of-line blocking decode
        while the TTFT budget burns)."""
        chunk = self.prefill_chunk
        if self.slo.enabled and self.slo_level >= 2:
            forced = self.slo.config.chunked_prefill_tokens
            chunk = min(chunk, forced) if chunk else forced
        return chunk

    def _spec_active(self) -> bool:
        """Speculation dispatches this step: configured, and not
        suppressed by the SLO ladder (level >= 1 turns it off — the
        drafted tokens stop inflating the per-step token budget)."""
        return (self.speculation is not None and
                self.speculation.enabled and self.slo_level < 1)

    def _draft_pass(self) -> None:
        """Build this step's prompt-lookup drafts for DECODE residents
        (host-side PLD over ``prompt + tokens_out``). Draft length is
        capped by the remaining generation budget (minus the bonus
        token) and the context window, so a speculative stretch can
        never overshoot ``max_new_tokens`` or ``max_context``."""
        self._drafts = {}
        if not self._spec_active():
            return
        cfg = self.speculation
        min_hist = cfg.min_history or (cfg.ngram + 1)
        for uid, req in self.running.items():
            if req.state != RequestState.DECODE:
                continue
            if req.restored_in_step == self.step_idx:
                continue          # re-entered this step; decodes next
            cap = req.max_new_tokens - len(req.tokens_out) - 1
            cap = min(cap,
                      self.engine.max_context - req.cached_tokens - 1,
                      cfg.max_draft)
            if cap <= 0:
                continue
            hist = list(req.prompt) + req.tokens_out
            if len(hist) < min_hist:
                continue
            draft = lookup_draft(hist, cfg.ngram, cap, cfg.window)
            if draft:
                self._drafts[uid] = draft

    def _cancellation_pass(self, report: StepReport) -> None:
        now = self.clock.now()
        for req in [r for r in self.queue if r.cancelled]:
            self.queue.remove(req)
            self._reject(req, "cancelled", report)
        for uid in [u for u, r in self.running.items() if r.cancelled]:
            req = self.running.pop(uid)
            self.engine.flush(uid)
            self._close(req, report, now, cancelled=True)
        for uid in [u for u, r in self.suspended.items() if r.cancelled]:
            req = self.suspended.pop(uid)
            if not self.latent_preemption:
                # exact-KV mode keeps the sequence tracked (host copy
                # attached) while suspended; release the slot
                self.engine.flush(uid)
            self._close(req, report, now, cancelled=True)
        for uid in [u for u, r in self.restoring.items() if r.cancelled]:
            # cancel racing an open restore lane: abort the lane (the
            # engine frees its blocks + tracked slots; in-flight replay
            # chunks are owned by the abort), drop the host latents —
            # nothing will ever replay them — and close cancelled. Lane
            # mates (multi-uid lanes; the scheduler itself only opens
            # single-uid ones) go back to SUSPENDED uncharged: they lost
            # their lane through no fault of their own.
            req = self.restoring.pop(uid)
            aborted = self.engine.abort_restore(uid)
            self._overlap_credited.discard(uid)
            self.watchdog.drop(uid)
            for mate_uid in aborted:
                if mate_uid == uid:
                    continue
                mate = self.restoring.pop(mate_uid, None)
                if mate is None:
                    continue
                self._overlap_credited.discard(mate_uid)
                self.watchdog.drop(mate_uid)
                mate.transition(RequestState.SUSPENDED)
                mate.suspended_in_step = self.step_idx
                self.suspended[mate_uid] = mate
                self._event("restore_abort", mate_uid,
                            "lane_mate_cancelled")
            req.latents = None
            self._event("restore_abort", uid, "cancelled")
            self._close(req, report, now, cancelled=True)

    # ------------------------------------------------------------- #
    # restore (suspended -> RESTORING, dispatch overlapped with decode)
    # ------------------------------------------------------------- #
    def _restore_candidates(self) -> List[Request]:
        """Suspended requests that fit back right now, best-first.

        Budget checks mirror the engine's so ``restore_kv`` cannot
        raise mid-step: a tracked slot (latent mode re-creates the
        sequence), KV blocks for the full cached span plus a decode
        headroom of one block per resident (residents crossing a block
        boundary next step must not be starved by the restore — the
        anti-thrash guard), and a free decode lane next step. A trunk
        with window layers is asked of both its pools: the window
        layers' for the rows still inside the window.
        """
        sm = self.engine.config.state_manager
        state = self.engine.state
        free = state.free_blocks
        free_window = state.free_window_blocks
        headroom = len(self.running)
        # open lanes become decode lanes when they complete — budget
        # them now so completions can't overflow the ragged batch
        lanes = len(self.running) + len(self.restoring)
        tracked = self.engine.state.n_tracked_sequences
        out = []
        order = sorted(self.suspended.values(),
                       key=lambda r: (-r.priority, r.arrival_time, r.uid))
        for req in order:
            if req.suspended_in_step >= self.step_idx:
                continue      # never restore in the eviction step
            if lanes + 1 > sm.max_ragged_sequence_count:
                break
            if self.latent_preemption:
                need = -(-req.cached_tokens // self.engine.block_size)
                need_window = state.window_blocks_needed(
                    None, req.cached_tokens, behind=False)
                if tracked + 1 > sm.max_tracked_sequences:
                    break
            else:
                seq = self.engine.state.get_sequence(req.uid)
                need = self.engine.state.blocks_needed(seq, 0)
                need_window = 0
            if need > free - headroom or (
                    need_window and
                    need_window > free_window - headroom):
                if self.restore_priority_barrier:
                    break     # head-of-line: nobody leapfrogs
                continue      # smaller suspendees may still fit
            free -= need
            free_window -= need_window
            lanes += 1
            tracked += 1
            out.append(req)
        return out

    def _occupancy(self) -> float:
        sm = self.engine.config.state_manager
        return (len(self.running) + len(self.restoring)) / \
            max(sm.max_ragged_sequence_count, 1)

    def _recompute_feasible(self, req: Request) -> bool:
        """A recompute re-entry re-prefills the full cached prefix plus
        the pending fed token in ONE standalone forward — it must fit
        the per-forward token budget and the engine's verdict."""
        tokens = self._reentry_tokens(req)
        sm = self.engine.config.state_manager
        per_fwd = min(tokens, sm.prefill_chunk) if sm.prefill_chunk \
            else tokens
        if per_fwd > sm.max_ragged_batch_size:
            return False
        return self.engine.can_schedule([req.uid], [tokens]) == \
            SchedulingResult.Success

    def _reentry_tokens(self, req: Request) -> int:
        """Tokens a recompute re-entry forwards: the cached prefix and
        the pending fed token; where generation goes by blocks the
        committed blocks alone (the open block starts again)."""
        return req.cached_tokens + (0 if self._diffusion else 1)

    def _recompute_reentry(self, req: Request, report: StepReport,
                           now: float) -> None:
        """Crossover said recompute: rebuild the KV by re-prefilling
        prompt + every generated token in one forward (full stack, no
        link bytes), sampling the next token from its logits — the
        request rejoins the decode set one token ahead, with its latent
        payload re-captured by the prefill itself."""
        del self.suspended[req.uid]
        req.transition(RequestState.RESTORING)
        if req.trace is not None:
            # the crossover chose the re-prefill side: relabel the
            # re-entry span so attribution separates recompute compute
            # from restore-lane ship/replay time
            req.trace.relabel("recompute")
        tokens = req.cached_ids() if self._diffusion \
            else list(req.prompt) + req.tokens_out
        with get_tracer().span("sched.recompute_issue", uid=req.uid,
                               sched_step=self.step_idx,
                               replica=self.replica_id,
                               tokens=len(tokens)):
            # the prefill re-captures the latents — but hold the old
            # payload until the put succeeds: a faulted re-prefill must
            # not cost the request its only restore payload
            saved = req.latents
            req.latents = None
            try:
                # nothing committed yet: nothing to forward again
                logits, latents = self.engine.put([req.uid], [tokens]) \
                    if tokens else ([None], [None])
            except BaseException:
                req.latents = saved
                raise
        req.absorb_latents(latents[0])
        req.n_recomputes += 1
        req.restored_in_step = self.step_idx
        self.total_recomputes += 1
        report.recomputed.append(req.uid)
        self._event("restore", req.uid,
                    f"mode=recompute tokens={len(tokens)}")
        if self._diffusion:
            # the committed blocks are back; the open block (reopened
            # at eviction) takes its passes with the residents
            req.transition(RequestState.DECODE)
            self.running[req.uid] = req
            return
        tok = self.sample_fn(req, logits[0])
        req.tokens_out.append(tok)
        if len(req.tokens_out) >= req.max_new_tokens or (
                req.eos_token_id is not None and
                tok == req.eos_token_id):
            self.engine.flush(req.uid)
            self._close(req, report, now)
            return
        req.transition(RequestState.DECODE)
        self.running[req.uid] = req

    def _try_recompute(self, req: Request, report: StepReport,
                       now: float) -> None:
        """Recompute re-entry with fault containment: a faulted
        re-prefill sends the request back to SUSPENDED (payload intact)
        and charges a restore failure, instead of wedging the step."""
        try:
            self._recompute_reentry(req, report, now)
        except SchedulingError:
            raise
        except Exception as exc:
            self._note_fault(exc, report)
            self._safe_flush(req.uid)
            self._restore_failure(req, report, now,
                                  f"recompute_fault:"
                                  f"{getattr(exc, 'site', 'engine')}")
        else:
            self.breaker.record_success(self.step_idx)

    def _restore_failure(self, req: Request, report: StepReport,
                         now: float, reason: str,
                         count_breaker: bool = True) -> None:
        """Common tail of every failed re-entry attempt: breaker
        accounting, bounded per-request failure budget, then back to
        SUSPENDED (payload intact) or typed FAILED at the cap. The
        request is in RESTORING state and in no pool when called."""
        if count_breaker:
            if self.breaker.record_failure(self.step_idx):
                report.breaker_trips += 1
                self._event("breaker_trip", req.uid, reason)
                self._flight("breaker_open",
                             f"uid={req.uid} {reason}")
        req.n_restore_failures += 1
        req.suspended_in_step = self.step_idx
        report.restore_aborts += 1
        if req.n_restore_failures >= \
                self.resilience.max_restore_failures:
            self._fail(req, "restore_failed", report, now)
            return
        req.transition(RequestState.SUSPENDED)
        self.suspended[req.uid] = req
        self._event("restore_fail", req.uid, reason)

    def _restore_pass(self, report: StepReport) -> None:
        now = self.clock.now()
        for req in self._restore_candidates():
            if self._recurrent and req.state_rows is None:
                req.latents = None      # K/V alone cannot bring it back
            if self.latent_preemption and req.latents is None:
                # no restorable payload (crash-recovered from a dead
                # replica, or migrated out of exact-KV suspension):
                # recompute re-entry is the only road back — re-prefill
                # prompt + generated tokens when it fits, else wait
                sm = self.engine.config.state_manager
                tokens = self._reentry_tokens(req)
                per_fwd = min(tokens, sm.prefill_chunk) \
                    if sm.prefill_chunk else tokens
                if per_fwd > sm.max_ragged_batch_size:
                    # no forward will EVER fit this re-prefill and no
                    # payload exists to restore from: fail typed
                    # instead of parking it suspended forever
                    del self.suspended[req.uid]
                    self._fail(req, "recompute_infeasible", report,
                               now)
                    continue
                if self._recompute_feasible(req):
                    self._event("recompute_forced", req.uid,
                                "no_latents")
                    self._try_recompute(req, report, now)
                continue
            if not self.breaker.allow(self.step_idx):
                # breaker OPEN: the restore path is considered broken —
                # cross over to the recompute re-entry (full re-prefill,
                # no link bytes) when it fits; otherwise the request
                # waits out the cooldown suspended
                if self.latent_preemption and \
                        self._recompute_feasible(req):
                    self._event("breaker_recompute", req.uid,
                                self.breaker.state.name)
                    self._try_recompute(req, report, now)
                continue
            if self.latent_preemption and self.crossover is not None \
                    and self.crossover.decide(
                        req.cached_tokens, self._occupancy()) == \
                    "recompute" and self._recompute_feasible(req):
                self._try_recompute(req, report, now)
                continue
            del self.suspended[req.uid]
            req.transition(RequestState.RESTORING)
            # half of the explicit restore/decode overlap span pair:
            # this span covers the restore lane OPEN (staging + the
            # first chunk ships); the decode dispatches issued while
            # the lane drains (sched.decode_dispatch, which carries
            # overlapped_restores) are the other half — the overlap
            # ratio is computed from the pair, never inferred from
            # wall-clock adjacency
            with get_tracer().span("sched.restore_issue", uid=req.uid,
                                   sched_step=self.step_idx,
                                   replica=self.replica_id,
                                   tokens=req.cached_tokens):
                if self.latent_preemption:
                    tokens = req.cached_ids()
                    # only a hybrid trunk's engine takes ``states``: the
                    # simulator and the fabric's engines keep the
                    # three-argument form
                    states = {"states": [req.state_rows]} \
                        if self._recurrent else {}
                    try:
                        self.engine.begin_restore([req.uid], [tokens],
                                                  [req.latents], **states)
                    except SchedulingError:
                        raise
                    except Exception as exc:
                        self._note_fault(exc, report)
                        self._safe_flush(req.uid)
                        self._restore_failure(
                            req, report, now,
                            f"begin_fault:"
                            f"{getattr(exc, 'site', 'engine')}")
                        continue
                    self.total_restores += 1
                    self.restoring[req.uid] = req
                    req.state_rows = None   # back on the device
                    self._event("restore_begin", req.uid,
                                f"tokens={req.cached_tokens}")
                    # the lane drains chunk by chunk between this
                    # step's (and the next steps') decode dispatches;
                    # the request re-enters the decode set when its
                    # last replay chunk has issued
                    continue
                self.engine.resume_sequence(req.uid)
            # exact-KV resume is synchronous: back into the decode set
            # now, decoding again from the NEXT step's batch (its next
            # fed token is tokens_out[-1])
            req.n_restores += 1
            req.restored_in_step = self.step_idx
            self.total_restores += 1
            report.restored.append(req.uid)
            report.restored_tokens += req.cached_tokens
            self._event("restore", req.uid,
                        f"mode=kv tokens={req.cached_tokens}")
            req.transition(RequestState.DECODE)
            self.running[req.uid] = req

    # ------------------------------------------------------------- #
    # restore lanes (decode-interleaved chunk progress)
    # ------------------------------------------------------------- #
    def _advance_with_retry(self, max_chunks: int,
                            report: StepReport):
        """``engine.advance_restores`` under the bounded-retry policy:
        a faulted chunk ship backs off (exponential + seeded jitter,
        the clock sleeps so virtual time advances deterministically)
        and re-issues; exhaustion re-raises to the lane-abort path."""
        policy = self.resilience.retry
        attempt = 0
        while True:
            try:
                return self.engine.advance_restores(max_chunks)
            except SchedulingError:
                raise
            except Exception as exc:
                self._note_fault(exc, report)
                attempt += 1
                if attempt >= policy.max_attempts:
                    raise
                delay = policy.delay(attempt, self._retry_rng)
                self.total_retries += 1
                report.retries += 1
                uid = getattr(exc, "uid", None)
                self._event(
                    "retry", -1 if uid is None else uid,
                    f"site={getattr(exc, 'site', 'engine')} "
                    f"attempt={attempt} delay={delay:.5f}")
                self.clock.sleep(delay)
                # attribution honesty: the backoff sleep is wall the
                # open lanes waited through — carve it out of their
                # restore spans as its own category
                for r in self.restoring.values():
                    if r.trace is not None:
                        r.trace.charge("retry_backoff", delay)

    def _abort_lane(self, uid: Optional[int], report: StepReport,
                    reason: str) -> None:
        """Abort the open restore lane holding ``uid`` (or the oldest
        lane when blame is unattributable): the engine frees the lane's
        blocks, its requests go back to SUSPENDED with their host
        payload intact — or typed FAILED at the failure cap."""
        now = self.clock.now()
        if uid is None or uid not in self.restoring:
            open_uids = [u for u in
                         getattr(self.engine, "restoring_uids", ())
                         if u in self.restoring]
            if not open_uids:
                return
            uid = open_uids[0]
        aborted = self.engine.abort_restore(uid)
        for u in aborted:
            req = self.restoring.pop(u, None)
            self._overlap_credited.discard(u)
            self.watchdog.drop(u)
            if req is None:
                continue
            self._event("restore_abort", u, reason)
            self._restore_failure(req, report, now, reason)

    def _watchdog_pass(self, report: StepReport) -> None:
        """Abort lanes that made no chunk progress in N steps — a
        stuck ship/replay must not pin KV blocks forever."""
        if not self.restoring:
            return
        for u in list(self.restoring):
            if u in self.restoring and \
                    self.watchdog.stuck(u, self.step_idx):
                self.watchdog.aborts += 1
                report.watchdog_aborts += 1
                self._event("watchdog_abort", u,
                            f"no_progress>{self.watchdog.limit}")
                self._flight("watchdog",
                             f"uid={u} no_progress>"
                             f"{self.watchdog.limit}")
                self._abort_lane(u, report, "watchdog")

    def _advance_restore_lanes(self, report: StepReport,
                               had_decode: bool) -> int:
        """Issue up to ``restore_chunks_per_step`` replay chunks across
        the open lanes; lanes advancing while resident decode was
        dispatched this step earn their (one-time) overlap credit.
        Completed lanes re-enter the decode set. Chunk faults retry
        with backoff; retry exhaustion aborts the lane (breaker
        accounting included) instead of wedging the step."""
        if not self.restoring:
            return 0
        try:
            chunks, completed, touched = self._advance_with_retry(
                self.restore_chunks_per_step, report)
        except SchedulingError:
            raise
        except Exception as exc:
            self._abort_lane(getattr(exc, "uid", None), report,
                             f"retry_exhausted:"
                             f"{getattr(exc, 'site', 'engine')}")
            return 0
        report.restore_chunks += chunks
        for uid in touched:
            self.watchdog.note(uid, self.step_idx)
        if had_decode:
            for uid in touched:
                if uid in self._overlap_credited:
                    continue
                self._overlap_credited.add(uid)
                self.overlapped_restores += 1
                report.overlapped_restores += 1
        for uid in completed:
            req = self.restoring.pop(uid)
            self._overlap_credited.discard(uid)
            self.watchdog.drop(uid)
            self.breaker.record_success(self.step_idx)
            req.n_restores += 1
            req.restored_in_step = self.step_idx
            report.restored.append(uid)
            report.restored_tokens += req.cached_tokens
            self._event("restore", uid,
                        f"mode=latents tokens={req.cached_tokens}")
            req.transition(RequestState.DECODE)
            self.running[uid] = req
        return chunks

    # ------------------------------------------------------------- #
    # admission (queue -> this step's prefill set)
    # ------------------------------------------------------------- #
    def _admission_order(self) -> List[Request]:
        return sorted(self.queue,
                      key=lambda r: (-r.priority, r.arrival_time, r.uid))

    def _victims(self, exclude=(),
                 grace: bool = False) -> List[Request]:
        """Preemption victims, best-victim-first: lowest priority, then
        latest deadline (no deadline = least urgent), youngest last-in
        first-evicted, uid as the deterministic tiebreak.

        ``grace=True`` additionally protects freshly-restored residents
        (``preempt_restore_grace``) — used by ADMISSION preemption
        only: a persistent high-priority admission otherwise evicts
        each just-restored resident every step while the restore pass
        restores another, and the loop makes no token progress. The
        pressure pass never applies the grace — when the residents
        alone exceed the pool, someone must go."""
        cand = [r for r in self.running.values()
                if r.uid not in exclude and
                r.state == RequestState.DECODE]
        if grace and self.preempt_restore_grace:
            cand = [r for r in cand
                    if r.restored_in_step < 0 or
                    self.step_idx - r.restored_in_step >
                    self.preempt_restore_grace]
        return sorted(
            cand,
            key=lambda r: (r.priority,
                           -(r.deadline if r.deadline is not None
                             else float("inf")),
                           -r.arrival_time, -r.uid))

    def _preempt(self, req: Request, report: StepReport) -> None:
        del self.running[req.uid]
        if self.latent_preemption:
            # HCache eviction: the accumulated latents ARE the host
            # copy; drop the device KV and the tracked slot entirely.
            # Chunks still pending in the store count as covered: the
            # engine lands them under a later program, or the restore
            # that reads the store does
            if req.latents is not None and \
                    req.latents.shape[1] == req.cached_tokens:
                if self._recurrent:
                    req.state_rows = self.engine.snapshot_state(req.uid)
                self.engine.flush(req.uid)
                mode = "latents"
            else:
                # a landing failed and truncated the payload: nothing
                # restorable, the request re-enters via recompute
                self._safe_flush(req.uid)
                req.latents = None
                mode = "latents_lost"
        else:
            self.engine.suspend_sequence(req.uid)
            mode = "kv"
        if self._diffusion:
            # the open block's K and V went with the eviction: the
            # request resumes from its last commit
            req.reopen_block(self._mask_id)
        req.transition(RequestState.SUSPENDED)
        req.n_preemptions += 1
        req.suspended_in_step = self.step_idx
        self.suspended[req.uid] = req
        report.preempted.append(req.uid)
        self._event("preempt", req.uid, f"mode={mode}")

    def _next_feed(self, req: Request) -> int:
        """Tokens this *resident* feeds the next ragged put: one decode
        token (plus this step's speculative draft, which transiently
        occupies batch-token and KV budget until verification rolls
        the rejected tail back), or the next prompt slice for a
        mid-chunk PREFILL resident (scheduler-grain chunked
        prefill)."""
        if req.state == RequestState.PREFILL:
            rest = self._prompt_feed(req) - req.prefill_pos
            chunk = self._prefill_chunk_now
            return min(rest, chunk) if chunk else rest
        if self._diffusion:
            return self._block_len      # a pass over the open block
        return 1 + len(self._drafts.get(req.uid, ()))

    def _prompt_feed(self, req: Request) -> int:
        """Prompt tokens that prefill: all of them; where generation
        goes by blocks the prompt's whole blocks (its partial last block
        stands in the first open block)."""
        return len(req.prompt) // self._block_len * self._block_len

    def _first_feed(self, req: Request) -> int:
        """Tokens an admission candidate would feed this step (its
        first prompt slice under chunked prefill, the whole prompt
        otherwise). Chunked admission budgets per slice — "fits
        eventually" is handled dynamically, like decode growth."""
        chunk = self._prefill_chunk_now
        feed = self._prompt_feed(req) or self._block_len
        return min(feed, chunk) if chunk else feed

    def _trial_verdict(self, admits: List[Request],
                       cand: Optional[Request]) -> SchedulingResult:
        reqs = admits + ([cand] if cand is not None else [])
        uids = list(self.running) + [r.uid for r in reqs]
        lens = [self._next_feed(r) for r in self.running.values()] + \
            [self._first_feed(r) for r in reqs]
        if not uids:
            return SchedulingResult.Success
        return self.engine.can_schedule(uids, lens)

    def _admission_pass(self, report: StepReport,
                        now: float) -> List[Request]:
        admits: List[Request] = []
        if self.degradation >= DegradationLevel.PAUSE_ADMISSIONS:
            if self.queue:
                self._event("admissions_paused", -1,
                            f"level={int(self.degradation)}")
            return admits
        for req in self._admission_order():
            if req.arrival_time > now:
                continue
            if req.total_tokens > self.engine.max_context:
                # permanent: no schedule can ever fit this request
                self.queue.remove(req)
                self._reject(req, "SequenceTokenLimitExceeded", report)
                continue
            sm = self.engine.config.state_manager
            chunk = self._prefill_chunk_now or sm.prefill_chunk
            per_fwd = min(len(req.prompt), chunk) if chunk \
                else len(req.prompt)
            if per_fwd > sm.max_ragged_batch_size:
                # also permanent: the prompt alone overflows every
                # forward's token budget and nothing will chunk it
                self.queue.remove(req)
                self._reject(req, "BatchTokenLimitExceeded", report)
                continue
            while True:
                verdict = self._trial_verdict(admits, req)
                action = BACKPRESSURE_ACTION[verdict]
                if action != BackpressureAction.ADMIT and self._drafts:
                    # drafts yield to admissions: dropping them first
                    # restores the historical verdict arithmetic, so
                    # speculation can never cause a preempt/wait that
                    # the non-speculative scheduler would not have
                    self._event("spec_throttle", -1, verdict.name)
                    self._drafts = {}
                    continue
                if action != BackpressureAction.PREEMPT:
                    break
                victims = [v for v in self._victims(grace=True)
                           if v.priority < req.priority]
                if not victims:
                    if not self.running and not self.suspended and \
                            not self.restoring and not admits:
                        # alone on an empty engine and still over the
                        # pool: permanent (an open restore lane holds
                        # blocks that WILL free — not permanent)
                        action = BackpressureAction.REJECT
                        verdict = SchedulingResult.KVCacheLimitExceeded
                    break
                self._preempt(victims[0], report)
            if action == BackpressureAction.ADMIT:
                if self.degradation >= DegradationLevel.CAP_TOKENS:
                    cap = max(1,
                              self.resilience.ladder.cap_max_new_tokens)
                    if req.max_new_tokens > cap:
                        req.max_new_tokens = cap
                        self._event("degrade_cap", req.uid,
                                    f"max_new={cap}")
                admits.append(req)
            elif action == BackpressureAction.SKIP_CANDIDATE:
                self._event("skip", req.uid, verdict.name)
                continue
            elif action == BackpressureAction.REJECT:
                self.queue.remove(req)
                self._reject(req, verdict.name, report)
            elif action in (BackpressureAction.NEXT_STEP,
                            BackpressureAction.WAIT_TRACKED_SLOT,
                            BackpressureAction.PREEMPT):
                # batch full / waiting on a slot or on blocks nobody
                # preemptible holds: stop scanning this step
                self._event("wait", req.uid, verdict.name)
                if self._recurrent and \
                        action == BackpressureAction.WAIT_TRACKED_SLOT:
                    get_tracer().instant(
                        "sched.state_admit", uid=req.uid,
                        free_slots=self.engine.state.free_state_slots)
                break
        return admits

    # ------------------------------------------------------------- #
    # KV pressure on the composed step (residents' decode growth)
    # ------------------------------------------------------------- #
    def _pressure_pass(self, admits: List[Request],
                       report: StepReport) -> List[Request]:
        while True:
            verdict = self._trial_verdict(admits, None)
            if verdict == SchedulingResult.Success:
                return admits
            if self._drafts:
                # speculative drafts are opportunistic batch growth:
                # under pressure they are the first thing to go —
                # dropping them restores the historical one-token
                # decode budget before anyone is preempted or shed
                self._event("spec_throttle", -1, verdict.name)
                self._drafts = {}
                continue
            if verdict == SchedulingResult.KVCacheLimitExceeded:
                exclude = {r.uid for r in admits}
                victims = self._victims(exclude=exclude)
                if victims:
                    self._preempt(victims[0], report)
                    continue
            if admits:
                # shed the newest admission back to the queue (it was
                # never transitioned, so it simply stays QUEUED)
                self._event("shed", admits[-1].uid, verdict.name)
                admits.pop()
                continue
            # residents alone still over budget and nothing to shed:
            # suspend the worst victim (it is in the batch itself)
            victims = self._victims()
            if not victims:
                # mid-chunk PREFILL residents are not preemptible (no
                # complete latent payload) but CAN rewind: drop the
                # partial prefill back to the queue head and retry the
                # prompt later — the chunked-prefill anti-wedge valve
                mids = sorted(
                    (r for r in self.running.values()
                     if r.state == RequestState.PREFILL),
                    key=lambda r: (-r.arrival_time, -r.uid))
                if mids:
                    self._rewind_prefill(mids[0], "kv_pressure")
                    continue
                raise RuntimeError(
                    f"scheduler wedged: verdict {verdict} with no "
                    "admissions and no preemptible residents")
            self._preempt(victims[0], report)

    def _rewind_prefill(self, req: Request, why: str) -> None:
        """Abandon a mid-chunk prefill: free its engine state, drop the
        partial latents, and put it back at the queue head in QUEUED —
        the chunked analog of rewinding an untouched admit."""
        del self.running[req.uid]
        self._safe_flush(req.uid)
        req.latents = None
        req.prefill_pos = 0
        req.admitted_at = None
        req.transition(RequestState.QUEUED)
        self.queue.insert(0, req)
        self._event("prefill_rewind", req.uid, why)

    # ------------------------------------------------------------- #
    # speculative decode dispatch + warm-prefix adoption
    # ------------------------------------------------------------- #
    def _spec_dispatch(self, lanes: List[Request], report: StepReport,
                       now: float) -> bool:
        """One fused speculative verify step over the drafted decode
        residents: the engine verifies each ``[fed] + draft`` stretch
        against its own greedy targets, accepts the matching prefix
        plus the bonus token, and rolls rejected draft KV back before
        returning — so every lane leaves this call at its last
        ACCEPTED token, which is exactly what preemption-to-latents,
        restore lanes and fault quarantine require of it. Greedy-exact:
        the emitted stream is bitwise identical to one-token-per-step
        decode. Returns True iff the dispatch did decode work (the
        restore-lane overlap credit)."""
        feeds = [[r.tokens_out[-1]] + self._drafts[r.uid]
                 for r in lanes]
        drafted = sum(len(f) - 1 for f in feeds)
        with get_tracer().span("sched.spec_dispatch",
                               sched_step=self.step_idx,
                               replica=self.replica_id,
                               lanes=len(lanes),
                               drafted=drafted) as sp:
            try:
                emitted, latents = self.engine.put_spec(
                    [r.uid for r in lanes], feeds)
            except SchedulingError:
                raise           # budget arithmetic bug — surface it
            except Exception as exc:
                # speculative dispatch fault: same quarantine
                # semantics as the ragged put — the injector fires
                # before any state mutates, so every lane is still at
                # its last accepted token
                self._quarantine_dispatch(exc, lanes, [], report, now)
                return False
            report.spec_lanes += len(lanes)
            report.spec_drafted += drafted
            self.total_spec_lane_steps += len(lanes)
            self.total_spec_drafted += drafted
            for j, req in enumerate(lanes):
                toks = list(emitted[j])
                accepted = len(toks) - 1
                rolled = (len(feeds[j]) - 1) - accepted
                report.spec_accepted += accepted
                report.spec_emitted += len(toks)
                report.spec_rollback_tokens += rolled
                self.total_spec_accepted += accepted
                self.total_spec_emitted += len(toks)
                self.total_spec_rolled_back += rolled
                if self.latent_preemption:
                    try:
                        req.absorb_latents(latents[j])
                    except Exception as exc:
                        self._note_fault(exc, report)
                        self.running.pop(req.uid, None)
                        self._safe_flush(req.uid)
                        self._fail(req,
                                   f"latent_fault:"
                                   f"{getattr(exc, 'site', 'host')}",
                                   report, now, quarantined=True)
                        continue
                if req.trace is not None:
                    # speculation phase stamped into the causal trace:
                    # the open decode span accumulates the per-request
                    # acceptance facts (closure-safe — attrs, not time)
                    req.trace.note(spec_steps=1,
                                   spec_drafted=len(feeds[j]) - 1,
                                   spec_accepted=accepted)
                if req.eos_token_id is not None and \
                        req.eos_token_id in toks:
                    toks = toks[:toks.index(req.eos_token_id) + 1]
                req.tokens_out.extend(toks)
                if len(req.tokens_out) >= req.max_new_tokens or (
                        req.eos_token_id is not None and toks and
                        toks[-1] == req.eos_token_id):
                    del self.running[req.uid]
                    self.engine.flush(req.uid)
                    self._close(req, report, now)
            sp.set(accepted=report.spec_accepted,
                   emitted=report.spec_emitted)
        return True

    def _try_adopt_prefix(self, req: Request,
                          report: StepReport) -> None:
        """Warm-prefix adoption at admission: when this replica's
        prefix cache holds the leading ``m`` tokens of the prompt
        (served locally, or installed by a latent prefix broadcast),
        re-enter them through the engine's restore path — link-bound
        replay instead of a full re-prefill — and prefill only the
        tail. Composes with chunked prefill (``prefill_pos`` starts at
        ``m``); failure of any kind falls back to the plain prefill
        the request was already budgeted for."""
        if req.tokens_out or req.prefill_pos:
            return
        if getattr(self.engine, "restoring_uids", ()):
            # the run-to-completion restore would drain the open
            # scheduler lanes out from under their chunk accounting;
            # adopt on a later admission instead
            return
        m, payload = self.prefix_cache.lookup(req.prompt)
        if m <= 0:
            return
        with get_tracer().span("sched.prefix_adopt", uid=req.uid,
                               sched_step=self.step_idx,
                               replica=self.replica_id, tokens=m):
            try:
                self.engine.restore_kv([req.uid],
                                       [list(req.prompt[:m])],
                                       [payload])
            except SchedulingError:
                return          # budget shortfall: plain prefill
            except Exception as exc:
                self._note_fault(exc, report)
                self.engine.abort_restore(req.uid)
                self._safe_flush(req.uid)
                return
        req.prefill_pos = m
        req.absorb_latents(payload)
        self.total_prefix_adoptions += 1
        self.total_prefix_tokens_reused += m
        report.prefix_adoptions.append(req.uid)
        report.prefix_tokens_reused += m
        # virtual-cost honesty: the adopted span is restore traffic
        # (ship + replay), not prefill compute
        report.restored_tokens += m
        self._event("prefix_adopt", req.uid, f"tokens={m}")
        if req.trace is not None:
            req.trace.note(prefix_adopted=m)

    def _register_prefix(self, req: Request) -> None:
        """Prefill completed with latent capture: the prompt's latent
        slab is a free warm-prefix payload — register it in the
        replica cache (and through it, the fleet-shared radix tree)."""
        if self.prefix_cache is None or not self.latent_preemption:
            return
        if req.latents is None or \
                req.latents.shape[1] < len(req.prompt):
            return
        if self.prefix_cache.register(
                req.prompt, np.asarray(req.latents)[:, :len(req.prompt)],
                stamp=self.step_idx):
            self._event("prefix_register", req.uid,
                        f"tokens={len(req.prompt)}")

    # ------------------------------------------------------------- #
    # dispatch: ONE ragged put for decodes + admitted prefills
    # ------------------------------------------------------------- #
    def _dispatch(self, admits: List[Request], report: StepReport,
                  now: float) -> None:
        # exact-KV overlap accounting: resumes issued this step share
        # the device queue with this decode dispatch — no host sync
        # between them, so the host→HBM swap-in hides under decode
        # compute (latent-mode lanes earn their credit per chunk in
        # _advance_restore_lanes instead)
        if report.restored and not self.latent_preemption:
            residents = [u for u in self.running
                         if u not in set(report.restored)]
            if residents:
                report.overlapped_restores = len(report.restored)
                self.overlapped_restores += len(report.restored)

        restored_set = set(report.restored)
        residents = [r for u, r in self.running.items()
                     if u not in restored_set]
        decodes = [r for r in residents
                   if r.state == RequestState.DECODE]
        # mid-chunk PREFILL residents (scheduler-grain chunked
        # prefill): their next prompt slice rides THIS ragged put
        # beside the decode tokens, so a long prompt costs the batch
        # one chunk per step instead of the whole prompt at once
        chunking = [r for r in residents
                    if r.state == RequestState.PREFILL]
        # lanes holding a prompt-lookup draft dispatch through the
        # fused speculative verify step; everyone else rides the
        # historical ragged put (with speculation off the split is
        # empty and this step is byte-identical to the old path)
        spec_lanes: List[Request] = []
        if self._drafts:
            spec_lanes = [r for r in decodes if r.uid in self._drafts]
            decodes = [r for r in decodes
                       if r.uid not in self._drafts]
        tracer = get_tracer()
        n_slices = len(chunking) + len(admits)
        with tracer.span("sched.batch_build", lanes=len(decodes),
                         slices=n_slices):
            for req in admits:
                self.queue.remove(req)
                req.transition(RequestState.PREFILL)
                req.admitted_at = now
                report.admitted.append(req.uid)
                self._event("admit", req.uid,
                            f"prompt={len(req.prompt)}")
                if self.prefix_cache is not None and \
                        self.latent_preemption:
                    self._try_adopt_prefix(req, report)
                if self._diffusion and not self._prompt_feed(req):
                    # a prompt shorter than a block stands in the first
                    # open block whole: nothing to prefill
                    self._open_first_block(req)
                    decodes.append(req)
            admits = [r for r in admits
                      if r.state == RequestState.PREFILL]
        spec_ok = False
        if spec_lanes:
            spec_ok = self._spec_dispatch(spec_lanes, report, now)
        step_reqs = decodes + chunking + admits
        if not step_reqs:
            # restore-only (or speculation-only) step: the lanes still
            # trickle; a successful speculative dispatch is decode
            # compute the open lanes' ships hide under
            self._advance_restore_lanes(report, had_decode=spec_ok)
            return
        with tracer.span("sched.batch_build", lanes=len(decodes),
                         slices=n_slices):
            slices: Dict[int, List[int]] = {}
            blocks = {}
            if self._diffusion:
                toks: List = [list(r.block.tokens) for r in decodes]
                for r in decodes:
                    blocks[r.uid] = BlockPass(
                        commit=self._mask_id not in r.block.tokens,
                        probe=r.wants_probe())
                report.block_lanes = len(decodes)
            else:
                toks = [[r.tokens_out[-1]] for r in decodes]
            for req in chunking + admits:
                n = self._next_feed(req)
                slices[req.uid] = list(
                    req.prompt[req.prefill_pos:req.prefill_pos + n])
                toks.append(slices[req.uid])
            report.decode_lanes = len(decodes)
            report.prefill_tokens = sum(len(s) for s in slices.values())
            if self._prefill_chunk_now:
                report.prefill_chunks = len(slices)
        # the decode half of the restore-overlap span pair (see
        # _restore_pass): the decode dispatch computes while the open
        # lanes' latent ships ride the link; the replay chunks issued
        # right after it (inside the same span) consume buffers that
        # shipped under THIS dispatch's compute. overlapped_restores
        # lands on the span via set() once the lane advance decides it,
        # so the ratio is read straight off the pair's attributes.
        with tracer.span(
                "sched.decode_dispatch", sched_step=self.step_idx,
                replica=self.replica_id,
                lanes=report.decode_lanes,
                prefill_tokens=report.prefill_tokens,
                overlapped_restores=report.overlapped_restores) as sp:
            try:
                logits, latents = self.engine.put(
                    [r.uid for r in step_reqs], toks,
                    **({"blocks": blocks} if self._diffusion else {}))
            except SchedulingError:
                raise           # admission arithmetic bug — surface it
            except Exception as exc:
                # engine fault mid-step: quarantine the offender (or,
                # unattributable, the whole batch), rewind untouched
                # admits, and keep the loop alive — the step simply did
                # no token work
                self._quarantine_dispatch(exc, decodes + chunking,
                                          admits, report, now)
                report.decode_lanes = 0
                report.prefill_tokens = 0
                if self.latent_preemption and self.restoring:
                    self._advance_restore_lanes(report,
                                                had_decode=spec_ok)
                return
            if self.latent_preemption and self.restoring:
                self._advance_restore_lanes(
                    report, had_decode=bool(decodes) or spec_ok)
                sp.set(overlapped_restores=report.overlapped_restores,
                       restore_chunks=report.restore_chunks)
        # two passes over the step's requests, each its own span: the
        # host latent store first, then sampling and state edges. A
        # request whose absorb faulted is closed in the first and
        # skipped by the second.
        faulted = set()
        if self.latent_preemption:
            with tracer.span("sched.absorb_latents",
                             lanes=len(step_reqs)):
                for j, req in enumerate(step_reqs):
                    try:
                        req.absorb_latents(latents[j])
                    except Exception as exc:
                        # host latent store fault: without an intact
                        # payload the request can no longer be
                        # preempted safely — quarantine it, keep the
                        # rest of the batch's results
                        self._note_fault(exc, report)
                        self.running.pop(req.uid, None)
                        self._safe_flush(req.uid)
                        self._fail(req,
                                   f"latent_fault:"
                                   f"{getattr(exc, 'site', 'host')}",
                                   report, now, quarantined=True)
                        faulted.add(req.uid)
        with tracer.span("sched.sample", lanes=len(step_reqs)):
            for j, req in enumerate(step_reqs):
                if req.uid in faulted:
                    continue
                if self._diffusion:
                    self._advance_block(req, logits[j],
                                        slices.get(req.uid), report, now)
                    continue
                if req.state == RequestState.PREFILL:
                    req.prefill_pos += len(slices[req.uid])
                    if req.prefill_pos < len(req.prompt):
                        # prompt not fully fed yet: stays a PREFILL
                        # resident, no token sampled from a mid-chunk
                        # row
                        self.running[req.uid] = req
                        continue
                tok = self.sample_fn(req, logits[j])
                req.tokens_out.append(tok)
                if req.first_token_at is None:
                    req.first_token_at = now
                if req.state == RequestState.PREFILL:
                    req.transition(RequestState.DECODE)
                    self.running[req.uid] = req
                    self._register_prefix(req)
                if len(req.tokens_out) >= req.max_new_tokens or (
                        req.eos_token_id is not None and
                        tok == req.eos_token_id):
                    del self.running[req.uid]
                    self.engine.flush(req.uid)
                    self._close(req, report, now)

    # ------------------------------------------------------------- #
    # generation by diffusion over blocks
    # ------------------------------------------------------------- #
    def _open_first_block(self, req: Request) -> None:
        """The prompt's whole blocks are in the cache: the request
        becomes a DECODE resident holding its first open block, the
        prompt's partial last block unmasked in it."""
        req.block.committed = self._prompt_feed(req)
        req.reopen_block(self._mask_id)
        req.transition(RequestState.DECODE)
        self.running[req.uid] = req

    def _advance_block(self, req: Request, choice, prompt_slice,
                       report: StepReport, now: float) -> None:
        """What one step's dispatch did for ``req``: a prompt slice moves
        its prefill on; a denoise pass fills the open block's masked
        positions of highest confidence (``OpenBlock.unmask``); a commit
        pass makes the block final and emits its tokens, in order
        (``block_token_fn`` is told each), stopping at EOS or
        ``max_new_tokens`` inside the block."""
        if req.state == RequestState.PREFILL:
            req.prefill_pos += len(prompt_slice)
            req.block.committed = req.prefill_pos
            self.running[req.uid] = req
            if req.prefill_pos >= self._prompt_feed(req):
                self._open_first_block(req)
            return
        block = req.block
        commit = self._mask_id not in block.tokens
        if req.wants_probe():
            req.keep_probe(choice, commit)
        if not commit:
            report.tokens_unmasked += block.unmask(
                choice.tokens, choice.confidence, self._mask_id,
                -(-self._block_len // self.denoising_steps))
            return
        block.committed += self._block_len
        block.ordinal += 1
        block.probe_lost = False
        report.commit_lanes += 1
        report.tokens_committed += self._block_len
        for tok in block.tokens[block.carried:]:
            req.tokens_out.append(int(tok))
            if self.block_token_fn is not None:
                self.block_token_fn(req, int(tok))
            if req.first_token_at is None:
                req.first_token_at = now
            if len(req.tokens_out) >= req.max_new_tokens or (
                    req.eos_token_id is not None and
                    tok == req.eos_token_id):
                del self.running[req.uid]
                self.engine.flush(req.uid)
                self._close(req, report, now)
                return
        req.reopen_block(self._mask_id)

    def _quarantine_dispatch(self, exc: BaseException,
                             decodes: List[Request],
                             admits: List[Request],
                             report: StepReport, now: float) -> None:
        """An engine exception killed this step's ragged put. Blame
        rides ``exc.uid`` when the engine (or injector) attributed it:
        that one request hard-fails with its blocks freed; everyone
        else retries next step. Unattributable exceptions fail the
        whole dispatched batch — the conservative floor that still
        keeps the server loop alive for future requests."""
        self._note_fault(exc, report)
        uid = getattr(exc, "uid", None)
        in_batch = {r.uid for r in decodes} | {r.uid for r in admits}
        offenders = {uid} if uid in in_batch else set(in_batch)
        site = getattr(exc, "site", None) or type(exc).__name__
        # rewind untouched admits to the queue head (original order)
        for req in reversed(admits):
            if req.uid in report.admitted:
                report.admitted.remove(req.uid)
            if req.uid in offenders:
                continue
            req.transition(RequestState.QUEUED)
            req.admitted_at = None
            self._safe_flush(req.uid)   # alloc pre-pass may have run
            self.queue.insert(0, req)
            self._event("rewind", req.uid, f"quarantine site={site}")
        for req in decodes + admits:
            if req.uid not in offenders:
                continue
            self.running.pop(req.uid, None)
            self._safe_flush(req.uid)
            self._fail(req, f"engine_fault:{site}", report, now,
                       quarantined=True)
