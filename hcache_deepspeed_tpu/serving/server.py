"""Thread-based serving frontend with admission control.

Two operating modes over one scheduler:

* **thread mode** (production shape): callers ``submit()`` from any
  thread into a bounded ingress queue; a single scheduler thread drains
  it and runs continuous-batching steps against the engine. One thread
  owns the engine — the ragged engine is not thread-safe, and a single
  dispatch loop is the TPU-native discipline anyway.
* **virtual-clock simulation** (``run_trace`` with a
  :class:`.clock.VirtualClock`): the same scheduler steps over a
  simulated timeline whose step costs come from a deterministic cost
  model, so the entire policy — admissions, preemptions, restores,
  token streams — replays identically for the same trace. This is what
  makes the subsystem CPU-testable without a TPU.

Admission control happens at ingress, before the scheduler sees the
request: a full queue or an estimated-KV-demand overload rejects
immediately with a distinct reason (the caller can shed load upstream),
while schedulable-but-not-yet requests queue normally.
"""

import collections
import contextlib
import threading
from dataclasses import dataclass
from typing import List, Optional

from ..analysis.runtime import make_lock
from ..telemetry.context import TraceContext
from ..telemetry.flight import get_flight_recorder
from ..telemetry.tracer import get_tracer
from .clock import MonotonicClock, VirtualClock
from .metrics import ServingMetrics
from .request import Request, RequestState
from .scheduler import ContinuousBatchingScheduler


#: thread mode: how long the loop stands back between two steps when a
#: caller is blocked on the server lock
_HANDOFF_S = 5e-4


@dataclass
class ServerConfig:
    #: ingress bound: queued-but-not-admitted requests beyond this are
    #: rejected with reason "queue_full"
    max_queue_depth: int = 64
    #: reject when the estimated whole-stretch KV demand of every live
    #: request exceeds this multiple of the usable block pool (demand
    #: beyond 1.0 is served by queueing + preemption; this caps how far
    #: the backlog may run ahead of the hardware)
    kv_demand_fraction: float = 8.0
    #: thread mode: sleep when a step had nothing to do
    idle_sleep_s: float = 0.002
    #: replay chunks issued per scheduler step while a restore lane is
    #: open (the decode-interleave grain; 0 drains a lane in one step)
    restore_chunks_per_step: int = 1
    #: scheduler-grain chunked prefill (Dynamic SplitFuse): long
    #: prompts dispatch in per-step slices of this many tokens so they
    #: never head-of-line block resident decode (0 = monolithic
    #: prefill, the historical behavior). Pair with the engine's
    #: ``state_manager.prefill_chunk`` when its per-forward token
    #: budget also needs the chunk accounting.
    prefill_chunk: int = 0
    #: restore→preempt livelock guard (see the scheduler): a resident
    #: restored within the last N steps is not a preemption victim.
    #: 0 = historical victim policy (committed chaos digests replay)
    preempt_restore_grace: int = 0
    #: head-of-line restore admission (see the scheduler): a large
    #: suspended payload that does not fit blocks smaller ones from
    #: leapfrogging it. False = historical smaller-may-still-fit
    restore_priority_barrier: bool = False
    #: scheduler-dispatched speculative decode (a
    #: :class:`~.spec.SpeculationConfig`; None = the historical
    #: one-token-per-lane step — committed chaos digests replay)
    speculation: object = None
    #: SLO-aware degradation mode (a :class:`~.spec.SLOModeConfig`;
    #: None = the fault-driven ladder alone)
    slo_mode: object = None
    #: generation by diffusion over blocks: the denoise passes a whole
    #: block takes (a pass fills ``ceil(block / denoising_steps)``
    #: masked positions, those of highest confidence)
    denoising_steps: int = 2
    # -- virtual-clock cost model (seconds) -------------------------- #
    step_overhead_s: float = 1e-3
    prefill_token_s: float = 1e-4
    decode_lane_s: float = 5e-4
    restore_token_s: float = 2e-5
    restore_chunk_s: float = 1e-4
    #: per drafted-token verification cost of a fused speculative
    #: step: drafts verify inside one dispatch on lanes the MXU
    #: already occupies, so a verified token is far cheaper than a
    #: dispatched decode step — that gap is the whole speedup
    spec_draft_token_s: float = 5e-5


class RequestTimeout(TimeoutError):
    """``ServingServer.wait`` gave up on a request that is still live;
    ``request`` is that request (``state``, ``tokens_out`` so far)."""

    def __init__(self, request: Request, timeout: float):
        self.request = request
        super().__init__(
            f"request {request.uid} still {request.state.name} after "
            f"{timeout:g}s ({len(request.tokens_out)} tokens out)")


class ServingServer:

    def __init__(self, engine, config: ServerConfig = None, clock=None,
                 metrics: ServingMetrics = None, sample_fn=None,
                 monitor=None, emit_every_steps: int = 50,
                 crossover=None, resilience=None, replica_id: int = 0,
                 prefix_cache=None, block_token_fn=None):
        self.config = config or ServerConfig()
        self.clock = clock or MonotonicClock()
        self.virtual = isinstance(self.clock, VirtualClock)
        self.metrics = metrics or ServingMetrics()
        #: fleet position (0 = standalone); threaded to the scheduler
        #: so per-replica retry jitter streams stay independent
        self.replica_id = int(replica_id)
        self.scheduler = ContinuousBatchingScheduler(
            engine, clock=self.clock, sample_fn=sample_fn,
            metrics=self.metrics, crossover=crossover,
            restore_chunks_per_step=self.config.restore_chunks_per_step,
            resilience=resilience, replica_id=self.replica_id,
            prefill_chunk=self.config.prefill_chunk,
            preempt_restore_grace=self.config.preempt_restore_grace,
            restore_priority_barrier=
            self.config.restore_priority_barrier,
            speculation=self.config.speculation,
            slo_mode=self.config.slo_mode,
            prefix_cache=prefix_cache,
            denoising_steps=self.config.denoising_steps,
            block_token_fn=block_token_fn)
        self.monitor = monitor
        self.emit_every_steps = emit_every_steps
        self._lock = make_lock("ServingServer._lock")
        #: one marker per caller thread blocked on ``_lock`` (deque
        #: append/pop are atomic). ``threading.Lock`` is not fair: the
        #: loop thread releases it after a step and retakes it within
        #: microseconds, so a ``submit()`` would otherwise wait until
        #: the scheduler ran out of work — the loop reads this between
        #: steps and stands back when a caller is waiting.
        self._lock_waiters = collections.deque()
        self._ingress: List[Request] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._next_uid = 0
        #: the exception that killed the scheduler thread, if any;
        #: ``wait()`` re-raises it and ``submit()`` rejects while set
        self.error: Optional[BaseException] = None
        self._metrics_httpd = None
        self._metrics_http_thread = None

    @property
    def healthy(self) -> bool:
        return self.error is None

    # ------------------------------------------------------------- #
    # ingress
    # ------------------------------------------------------------- #
    def _estimated_demand_blocks(self) -> int:
        bs = self.scheduler.engine.block_size
        live = (self._ingress + self.scheduler.queue +
                list(self.scheduler.running.values()) +
                list(self.scheduler.suspended.values()))
        return sum(-(-r.total_tokens // bs) for r in live)

    def _usable_blocks(self) -> int:
        return self.scheduler.engine.state.allocator.num_blocks - 1

    @contextlib.contextmanager
    def _handoff(self):
        """Announce a caller thread about to block on ``_lock`` (see
        ``_lock_waiters``)."""
        self._lock_waiters.append(None)
        try:
            yield
        finally:
            self._lock_waiters.pop()

    def submit(self, prompt=None, request: Request = None,
               **kw) -> Request:
        """Enqueue a request (or build one from ``prompt`` + kwargs).

        Returns the request; a rejected one comes back already in
        ``REJECTED`` state with ``reject_reason`` set ("queue_full" or
        "kv_overload") — the caller is expected to check.
        """
        # the wait for the lock, on the caller's thread. Its name is
        # outside the prefixes the device trace's reduction matches
        # (only the thread that drives the device may use those), so a
        # human sees it beside the loop's spans and no idle gap of the
        # loop is handed to it. Closed by hand: it ends where the
        # ``with`` below has the lock, not where it lets it go.
        entered = self.clock.now()
        waited = get_tracer().span("front.submit.lock_wait")
        waited.__enter__()
        with self._handoff(), self._lock:
            waited.__exit__(None, None, None)
            if request is None:
                request = Request(uid=self._next_uid, prompt=list(prompt),
                                  arrival_time=self.clock.now(),
                                  submitted_at=entered, **kw)
            if request.trace is None:
                # causal tracing starts at the front door: the root
                # queue span opens at arrival so queue-wait attribution
                # matches Request.queue_wait(); ingress rejects below
                # still close the chain with a terminal outcome
                request.trace = TraceContext.mint(
                    request.uid, clock=self.clock,
                    t0=request.arrival_time)
            self._next_uid = max(self._next_uid, request.uid) + 1
            depth = len(self._ingress) + len(self.scheduler.queue)
            reason = ""
            if self.error is not None:
                reason = "server_down"
            elif depth >= self.config.max_queue_depth:
                reason = "queue_full"
            else:
                bs = self.scheduler.engine.block_size
                demand = self._estimated_demand_blocks() + \
                    -(-request.total_tokens // bs)
                if demand > self.config.kv_demand_fraction * \
                        self._usable_blocks():
                    reason = "kv_overload"
            if reason:
                request.reject_reason = reason
                request.finished_at = self.clock.now()
                request.transition(RequestState.REJECTED)
                self.scheduler.done[request.uid] = request
                self.scheduler.events.append(
                    (self.scheduler.step_idx, "reject_ingress",
                     request.uid, reason))
                self.metrics.rejected[reason] = \
                    self.metrics.rejected.get(reason, 0) + 1
                return request
            self._ingress.append(request)
            return request

    def cancel(self, uid: int) -> None:
        with self._handoff(), self._lock:
            for req in self._ingress:
                if req.uid == uid:
                    req.cancelled = True
                    return
            self.scheduler.cancel(uid)

    # ------------------------------------------------------------- #
    # stepping
    # ------------------------------------------------------------- #
    def _virtual_cost(self, report) -> float:
        c = self.config
        return (c.step_overhead_s +
                c.prefill_token_s * report.prefill_tokens +
                c.decode_lane_s * (report.decode_lanes +
                                   report.spec_lanes +
                                   len(report.admitted)) +
                c.restore_token_s * report.restored_tokens +
                c.restore_chunk_s * report.restore_chunks +
                c.spec_draft_token_s * report.spec_drafted)

    def step(self, advance_clock: bool = True):
        """Drain ingress + one scheduler step (thread mode calls this
        in a loop; simulation calls it from ``run_trace``).
        ``advance_clock=False`` leaves the virtual clock to the caller
        — the fleet steps N replicas at one simulated instant and
        advances the shared clock once by the parallel-max cost."""
        tracer = get_tracer()
        # the wait for a submitter to let go of the lock, closed by
        # hand so that the body stays a ``with self._lock`` block (the
        # lock rules of ``analysis/`` read those)
        waited = tracer.span("serve.loop.lock").__enter__()
        with self._lock:
            waited.__exit__(None, None, None)
            with tracer.span("serve.loop.ingress", n=len(self._ingress)):
                for req in self._ingress:
                    self.scheduler.submit(req)
                self._ingress.clear()
            report = self.scheduler.step()
            if self.virtual and advance_clock:
                self.clock.sleep(self._virtual_cost(report))
            if self.monitor is not None and \
                    report.step % self.emit_every_steps == 0:
                self.metrics.emit(self.monitor, report.step)
        return report

    # ------------------------------------------------------------- #
    # deterministic trace replay (simulation AND single-thread bench)
    # ------------------------------------------------------------- #
    def run_trace(self, requests: List[Request],
                  max_steps: int = 1_000_000):
        """Feed ``requests`` at their ``arrival_time``s and step until
        everything finished. Under a VirtualClock this is a pure
        function of the trace; under a real clock it is the
        single-threaded open-loop replay the serve_loop bench uses."""
        pending = sorted(requests,
                         key=lambda r: (r.arrival_time, r.uid))
        steps = 0
        while pending or self.scheduler.has_work or self._ingress:
            now = self.clock.now()
            while pending and pending[0].arrival_time <= now:
                self.submit(request=pending.pop(0))
            if not self.scheduler.has_work and not self._ingress \
                    and pending:
                # idle until the next arrival
                if self.virtual:
                    self.clock.advance_to(pending[0].arrival_time)
                else:
                    self.clock.sleep(pending[0].arrival_time - now)
                continue
            report = self.step()
            if not report.work_done and not self.virtual:
                self.clock.sleep(self.config.idle_sleep_s)
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"run_trace exceeded {max_steps} steps — "
                    "scheduling livelock?\n" + self._snapshot())
        if self.monitor is not None:
            # trace end: flush buffered sinks deterministically (the
            # Monitor.flush contract — CSV buffers, TB flushes per
            # write; both are safe to flush here)
            self.metrics.emit(self.monitor, self.scheduler.step_idx,
                              flush=True)
        return self.metrics

    def _snapshot(self, last_events: int = 20) -> str:
        """Diagnostic scheduler snapshot attached to livelock/crash
        errors — the state one actually needs to debug a wedge.
        Locked: it renders ``_ingress`` and the scheduler pools that
        the loop thread mutates, and its callers (``run_trace``'s
        livelock raise, the post-mortem log in ``_on_loop_error``)
        hold nothing — an unlocked render here was a torn diagnostic
        (HDS-L002)."""
        with self._lock:
            return self._snapshot_locked(last_events)

    def _snapshot_locked(self, last_events: int = 20) -> str:
        s = self.scheduler
        lanes = list(getattr(s.engine, "restoring_uids", ()))
        lines = [
            "scheduler snapshot:",
            f"  step={s.step_idx} degradation={int(s.degradation)} "
            f"breaker={s.breaker.state.name}",
            f"  queue={[r.uid for r in s.queue]}",
            f"  running={sorted(s.running)}",
            f"  suspended={sorted(s.suspended)}",
            f"  restoring={sorted(s.restoring)} open_lanes={lanes}",
            f"  ingress={[r.uid for r in self._ingress]}",
            f"  free_blocks={s.engine.state.free_blocks}",
            f"  last {min(last_events, len(s.events))} events: "
            f"{s.events[-last_events:]}",
        ]
        return "\n".join(lines)

    # ------------------------------------------------------------- #
    # observability surface
    # ------------------------------------------------------------- #
    def metrics_snapshot(self) -> dict:
        """Point-in-time introspection dict: the full metrics summary
        (histograms, counters, gauges, SLO burn rates), scheduler pool
        depths, health, and the Prometheus text rendering — everything
        an operator probe or test needs in one locked read."""
        tracer = get_tracer()
        with self._lock:
            s = self.scheduler
            return {
                "healthy": self.healthy,
                "error": None if self.error is None
                else repr(self.error),
                "step": s.step_idx,
                "pools": {"ingress": len(self._ingress),
                          "queue": len(s.queue),
                          "running": len(s.running),
                          "suspended": len(s.suspended),
                          "restoring": len(s.restoring),
                          "done": len(s.done)},
                "metrics": self.metrics.summary(),
                "slo_gauges": dict(self.metrics.slo_gauges),
                "critical_path": self.metrics.critical_path_summary(),
                "tracer": {"dropped_events": tracer.dropped,
                           "buffered": tracer.buffered},
                "flight": get_flight_recorder().summary(),
                "prometheus": self.metrics.prometheus_text(),
            }

    def start_metrics_http(self, host: str = "127.0.0.1",
                           port: int = 0) -> int:
        """Optional stdlib exposition endpoint: serves the Prometheus
        text at ``/metrics`` (and a JSON-ish health line at
        ``/healthz``) from a daemon thread. Returns the bound port
        (``port=0`` picks a free one). The endpoint only *reads*
        snapshots — it can never steer the scheduler."""
        if self._metrics_httpd is not None:
            return self._metrics_httpd.server_address[1]
        import json as _json
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        server = self

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                if self.path.split("?")[0] == "/metrics":
                    body = server.metrics_snapshot()[
                        "prometheus"].encode()
                    ctype = "text/plain; version=0.0.4; charset=utf-8"
                elif self.path.split("?")[0] == "/healthz":
                    body = _json.dumps(
                        {"healthy": server.healthy}).encode()
                    ctype = "application/json"
                else:
                    self.send_error(404)
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):   # no stderr chatter
                pass

        self._metrics_httpd = ThreadingHTTPServer((host, port),
                                                  _Handler)
        self._metrics_http_thread = threading.Thread(
            target=self._metrics_httpd.serve_forever,
            name="hds-metrics-http", daemon=True)
        self._metrics_http_thread.start()
        return self._metrics_httpd.server_address[1]

    def stop_metrics_http(self) -> None:
        if self._metrics_httpd is None:
            return
        self._metrics_httpd.shutdown()
        self._metrics_httpd.server_close()
        self._metrics_http_thread.join(timeout=5.0)
        self._metrics_httpd = None
        self._metrics_http_thread = None

    # ------------------------------------------------------------- #
    # thread mode
    # ------------------------------------------------------------- #
    def start(self) -> None:
        if self.virtual:
            raise RuntimeError(
                "thread mode needs a real clock; use run_trace for "
                "virtual-clock simulation")
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name="hds-serving", daemon=True)
        self._thread.start()

    def _loop(self) -> None:
        try:
            tracer = get_tracer()
            while not self._stop.is_set():
                report = self.step()
                if report.work_done and not self._lock_waiters:
                    continue
                with tracer.span("serve.loop.yield",
                                 waiters=len(self._lock_waiters)):
                    # idle, or long enough for the woken caller to
                    # take the lock this thread has just released
                    self._stop.wait(_HANDOFF_S if report.work_done
                                    else self.config.idle_sleep_s)
        except BaseException as exc:          # noqa: BLE001
            self._on_loop_error(exc)

    def _on_loop_error(self, exc: BaseException) -> None:
        """The scheduler thread died: capture the error, fail every
        in-flight request typed, and flip the server unhealthy so
        ``submit`` rejects and ``wait`` raises instead of timing out.
        The engine is presumed broken — no engine calls here."""
        with self._lock:
            self.error = exc
            error = f"server_down: {exc!r}"
            for req in self._ingress:
                req.error = error
                req.transition(RequestState.FAILED)
                req.finished_at = self.clock.now()
                self.scheduler.done[req.uid] = req
            self._ingress.clear()
            self.scheduler.fail_all_live(error)
            self.scheduler.events.append(
                (self.scheduler.step_idx, "server_error", -1,
                 repr(exc)))
        get_tracer().instant("server.error", error=repr(exc),
                             replica=self.replica_id)
        try:
            # the crash-path flight dump: the postmortem bundle is the
            # whole point of the recorder — capture it before the log
            # line, while the scheduler state is still coherent
            rec = get_flight_recorder()
            rec.dump("server_crash", repr(exc),
                     source=f"replica{self.replica_id}",
                     step=self.scheduler.step_idx,
                     t=self.clock.now(),
                     snapshot=self.scheduler.flight_snapshot(),
                     spans=get_tracer().events()[-rec.span_tail:]
                     if get_tracer().enabled else None)
        except Exception:       # noqa: BLE001 — the server is already
            pass                # dying; the dump must not mask why
        from ..utils.logging import logger
        logger.error(f"serving loop died: {exc!r}\n{self._snapshot()}")

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        if self._thread is None:
            return
        if drain:
            deadline = self.clock.now() + timeout
            while (self.scheduler.has_work or self._ingress) and \
                    self.clock.now() < deadline:
                if not self._thread.is_alive():
                    break       # nobody is draining; don't spin it out
                self.clock.sleep(self.config.idle_sleep_s)
        self._stop.set()
        self._thread.join(timeout=timeout)
        self._thread = None
        self.stop_metrics_http()

    def wait(self, req: Request, timeout: float = 60.0) -> Request:
        """Block until ``req`` finishes (thread mode helper). Raises
        the captured loop error if the server died while waiting, and
        :class:`RequestTimeout` when ``timeout`` seconds pass first — a
        cold compile of one prefill bucket can outlast the default, so
        size it for the first request of a fresh engine."""
        deadline = self.clock.now() + timeout
        while not req.finished:
            if self.error is not None:
                raise self.error
            if self.clock.now() >= deadline:
                raise RequestTimeout(req, timeout)
            self.clock.sleep(self.config.idle_sleep_s)
        return req
