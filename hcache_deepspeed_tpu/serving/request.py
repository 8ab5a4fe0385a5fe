"""Per-request lifecycle for the serving subsystem.

State machine::

    QUEUED -> PREFILL -> DECODE -> DONE
                 |          ^  \\
                 v          |   -> SUSPENDED -> RESTORING -> DECODE
              (SUSPENDED)   +------------------------------------+
    QUEUED -> REJECTED          (cancel: any live state -> DONE)
    any live state -> FAILED    (typed hard failure, ``error`` set)

``SUSPENDED`` means the request's KV left the device — either as exact
host KV (``suspend_sequence``) or as HCache latents after a flush —
and ``RESTORING`` covers the step in which the restore dispatch is in
flight, overlapped with resident decode. Illegal transitions raise, so
scheduler bugs surface at the exact transition rather than as silently
wrong accounting.

Two resilience-layer edges exist beyond the happy path: ``PREFILL ->
QUEUED`` (an engine fault quarantined another request mid-dispatch;
the untouched admits rewind to the queue) and ``RESTORING ->
SUSPENDED`` (retry exhaustion / watchdog aborted the restore lane; the
host payload is still intact, so the request waits for the next
re-entry). ``FAILED`` is the typed hard-failure terminal: ``error``
names the cause (``deadline_exceeded``, ``engine_fault:<site>``,
``restore_failed``, ``server_down``...).
"""

from dataclasses import dataclass, field
from enum import Enum
from typing import Any, List, Optional

from ..inference.ragged.latents import HostLatentStore
from ..telemetry.context import TraceContext


class RequestState(Enum):
    QUEUED = 0
    PREFILL = 1
    DECODE = 2
    SUSPENDED = 3
    RESTORING = 4
    DONE = 5
    REJECTED = 6
    FAILED = 7


#: legal transitions; DONE/REJECTED/FAILED are terminal. Two
#: cross-cutting edges: cancellation closes any live state to DONE,
#: and any live state may hard-fail to FAILED (deadline, engine fault,
#: restore exhaustion, server death).
_TRANSITIONS = {
    RequestState.QUEUED: {RequestState.PREFILL, RequestState.REJECTED,
                          RequestState.DONE, RequestState.FAILED},
    # PREFILL -> QUEUED: dispatch quarantine rewound an untouched admit
    RequestState.PREFILL: {RequestState.DECODE, RequestState.SUSPENDED,
                           RequestState.QUEUED, RequestState.DONE,
                           RequestState.FAILED},
    RequestState.DECODE: {RequestState.SUSPENDED, RequestState.DONE,
                          RequestState.FAILED},
    RequestState.SUSPENDED: {RequestState.RESTORING, RequestState.DONE,
                             RequestState.FAILED},
    # RESTORING -> SUSPENDED: lane aborted (retry exhaustion/watchdog)
    RequestState.RESTORING: {RequestState.DECODE,
                             RequestState.SUSPENDED, RequestState.DONE,
                             RequestState.FAILED},
    RequestState.DONE: set(),
    RequestState.REJECTED: set(),
    RequestState.FAILED: set(),
}


@dataclass
class OpenBlock:
    """The block a request of a model that generates by diffusion over
    blocks is denoising: ``tokens`` its positions (mask tokens where
    nothing is chosen yet), the first ``carried`` of them the prompt's
    partial last block; ``committed`` the sequence's tokens whose K and
    V are final in the cache (whole blocks), ``passes`` the denoise
    passes this block has had, ``ordinal`` the blocks the request has
    committed before it (0: its first open block)."""
    tokens: List[int]
    carried: int = 0
    committed: int = 0
    passes: int = 0
    ordinal: int = 0
    #: a pass of this block asked for its probe and lost its turn
    probe_lost: bool = False

    def unmask(self, chosen, confidence, mask_id: int, count: int) -> int:
        """One denoise pass's remasking rule (static low-confidence
        remasking): fill the ``count`` masked positions of highest
        ``confidence`` from ``chosen``; ties go to the lower position.
        Returns how many it filled."""
        masked = [i for i, t in enumerate(self.tokens) if t == mask_id]
        masked.sort(key=lambda i: (-float(confidence[i]), i))
        for i in masked[:count]:
            self.tokens[i] = int(chosen[i])
        self.passes += 1
        return len(masked[:count])


@dataclass
class BlockProbe:
    """One probed pass of an open block, as the serving path ran it:
    the block's ``ordinal``, the committed ``context`` ids behind it,
    the ``block`` ids fed (masks and all), the logits ``rows`` ``[B,
    vocab]`` and what each layer's router read, ``router_in`` ``[L, B,
    hidden]``."""
    ordinal: int
    context: List[int]
    block: List[int]
    rows: Any
    router_in: Any


@dataclass
class Request:
    """One serving request plus its lifecycle bookkeeping.

    ``priority``: larger = more important; preemption victims are
    picked lowest-priority-first. ``deadline`` is an absolute clock
    time (same clock as the scheduler's); among equal priorities the
    latest deadline is evicted first.
    """

    uid: int
    prompt: List[int]
    max_new_tokens: int = 32
    arrival_time: float = 0.0
    deadline: Optional[float] = None
    priority: int = 0
    eos_token_id: Optional[int] = None

    # -- generation by diffusion over blocks ------------------------- #
    #: the open block; set at submit by a scheduler over such a model
    block: Optional[OpenBlock] = None
    #: what a check compares with a reference: for each ordinal here,
    #: ascending, every pass of the first block at or after it is asked
    #: for its :class:`BlockProbe` (one lane a dispatch can be probed: a
    #: block that loses a turn, or is reopened, hands its wish to the
    #: next); ``probes`` holds them, whole blocks only, in order
    probe_blocks: List[int] = field(default_factory=list)
    probes: List[BlockProbe] = field(default_factory=list)

    state: RequestState = RequestState.QUEUED
    tokens_out: List[int] = field(default_factory=list)
    #: accumulated HCache latents [L, T, H] covering prompt + all fed
    #: tokens (i.e. every token whose KV is cached) — the restore
    #: payload when this request is preempted in latent mode. Held as a
    #: :class:`~...inference.ragged.latents.HostLatentStore` (coalesced
    #: layer-major buffer, O(1) amortized per-token absorption; quacks
    #: like the ndarray the restore contract expects).
    latents: Optional["HostLatentStore"] = None
    #: a hybrid trunk's recurrent state of this sequence while it is
    #: evicted (``engine.snapshot_state``): latents replay the full
    #: layers' K and V, these rows are copied back whole
    state_rows: Optional[tuple] = None
    #: exact-KV preempt mode: engine keeps host KV under this uid.
    reject_reason: str = ""
    #: typed hard-failure cause; set exactly when state is FAILED
    error: str = ""
    cancelled: bool = False

    # timeline (clock units of the owning scheduler)
    #: entry into ``ServingServer.submit``, before the wait for the
    #: server lock; ``arrival_time`` is stamped after it. None for a
    #: request the caller built (its ``arrival_time`` is the caller's)
    submitted_at: Optional[float] = None
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    #: scheduler step index of the most recent suspend (anti-thrash:
    #: never restored in the same step it was evicted)
    suspended_in_step: int = -1
    #: scheduler step index of the most recent restore/recompute
    #: re-entry (-1 = never restored). With a preemption grace
    #: configured, a just-restored resident is protected until it has
    #: decoded — the guard that breaks restore→preempt livelock under
    #: a persistent high-priority admission backlog
    restored_in_step: int = -1
    n_preemptions: int = 0
    n_restores: int = 0
    #: crossover-policy re-entries that re-prefilled instead of
    #: restoring (the recompute side of the analytic model)
    n_recomputes: int = 0
    #: restore-path failures charged to this request (retry
    #: exhaustion, lane aborts, faulted recompute re-entries); at the
    #: policy cap the request hard-fails with ``restore_failed``
    n_restore_failures: int = 0
    #: chunked-prefill cursor: prompt tokens already fed to the engine
    #: while this request is mid-prefill (0 = not started / monolithic
    #: prefill; == len(prompt) once the last chunk has dispatched)
    prefill_pos: int = 0
    # -- fleet bookkeeping ------------------------------------------ #
    #: replica currently (or last) responsible for this request; None
    #: until the fleet router places it (standalone servers never set
    #: it)
    replica: Optional[int] = None
    #: completed cross-replica migrations (landings, including
    #: recompute landings — transit expiry is not a migration)
    n_migrations: int = 0
    # -- disaggregated-serving bookkeeping -------------------------- #
    #: completed prefill→decode tier handoffs (a handoff is a
    #: migration with the tier link as its wire)
    n_handoffs: int = 0
    #: total simulated seconds this request's latents spent on the
    #: cross-tier handoff link (the handoff-transit TTFT component;
    #: 0.0 for colocated serving)
    handoff_transit_s: float = 0.0
    #: the request decoded on its prefill replica because the decode
    #: tier was saturated (the disagg colocation fallback)
    colocated_fallback: bool = False
    # -- causal tracing --------------------------------------------- #
    #: per-request causal trace context (minted at submit by the
    #: server/fleet frontend; None for bare Requests built in tests —
    #: recording is then a no-op). Serialized into the migration/
    #: handoff payload and rehydrated on the landing replica, so the
    #: span chain crosses replicas (docs/observability.md)
    trace: Optional[TraceContext] = None
    #: the wall-clock tracer's ``request`` async interval has been
    #: opened — exactly once per request lifetime, even when a crash
    #: evacuation re-submits the request through another replica's
    #: scheduler (a re-begin would leave an unclosed interval and
    #: fail the trace validator)
    async_span_begun: bool = False

    def transition(self, new_state: RequestState) -> None:
        if new_state not in _TRANSITIONS[self.state]:
            raise ValueError(
                f"request {self.uid}: illegal transition "
                f"{self.state.name} -> {new_state.name}")
        self.state = new_state
        if self.trace is not None:
            # every legal lifecycle edge is a causal-trace span edge;
            # the context stamps it from the owning serving clock (the
            # virtual clock in simulation), never the wall clock. A
            # terminal edge closes at finished_at — callers set it
            # BEFORE transitioning — so attribution closes against
            # the exact E2E the metrics layer measures
            self.trace.on_state(new_state.name, replica=self.replica,
                                t=self.finished_at
                                if self.finished else None)

    # ------------------------------------------------------------- #
    # derived quantities the scheduler/budgeter reads
    # ------------------------------------------------------------- #
    @property
    def total_tokens(self) -> int:
        """Worst-case context footprint: prompt + whole generation (in
        whole blocks, where generation goes by blocks)."""
        total = len(self.prompt) + self.max_new_tokens
        if self.block is None:
            return total
        return -(-total // len(self.block.tokens)) * len(self.block.tokens)

    @property
    def cached_tokens(self) -> int:
        """Tokens whose KV is (or must be restored to be) on device:
        the prompt plus every generated token already fed back; where
        generation goes by blocks, the committed blocks."""
        if self.block is not None:
            return self.block.committed
        return len(self.prompt) + max(len(self.tokens_out) - 1, 0)

    def cached_ids(self) -> List[int]:
        """The ids of :attr:`cached_tokens`, in order."""
        return (list(self.prompt) + self.tokens_out)[:self.cached_tokens]

    def reopen_block(self, mask_id: int) -> None:
        """Drop what the open block had denoised (its K and V went with
        an eviction): it starts again from masks behind the last commit,
        the prompt's partial last block standing in it as before."""
        size = len(self.block.tokens)
        carried = list(self.prompt[self.block.committed:])
        self.block.tokens = carried + [mask_id] * (size - len(carried))
        self.block.carried = len(carried)
        self.block.passes = 0
        self._drop_probes()

    def wants_probe(self) -> bool:
        """Whether the open block's next pass is asked for its probe."""
        return bool(self.probe_blocks) and \
            self.probe_blocks[0] <= self.block.ordinal and \
            not self.block.probe_lost

    def keep_probe(self, choice, commit: bool) -> None:
        """A pass that :meth:`wants_probe` came back as ``choice``: keep
        its probe; without one (another lane had the dispatch's turn) the
        block's are dropped and the next block is asked. A commit with
        every pass kept fulfils the wish."""
        if choice.logits is None:
            self.block.probe_lost = True
            self._drop_probes()
            return
        self.probes.append(BlockProbe(
            self.block.ordinal, self.cached_ids(), list(self.block.tokens),
            choice.logits, choice.router_in))
        if commit:
            self.probe_blocks.pop(0)

    def _drop_probes(self) -> None:
        """The open block's probes, a part of a block: gone."""
        while self.probes and self.probes[-1].ordinal == self.block.ordinal:
            self.probes.pop()

    @property
    def remaining_tokens(self) -> int:
        return max(self.max_new_tokens - len(self.tokens_out), 0)

    @property
    def finished(self) -> bool:
        return self.state in (RequestState.DONE, RequestState.REJECTED,
                              RequestState.FAILED)

    def absorb_latents(self, new_latents) -> None:
        if new_latents is None:
            return
        if self.latents is None:
            # sized once: everything this request can ever cache
            self.latents = HostLatentStore(capacity=self.total_tokens)
        self.latents.append(new_latents)

    # timing summaries (None until the respective edge happened)
    def ttft(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.arrival_time

    def tpot(self) -> Optional[float]:
        """Mean time per output token after the first."""
        if self.finished_at is None or self.first_token_at is None or \
                len(self.tokens_out) < 2:
            return None
        return (self.finished_at - self.first_token_at) / \
            (len(self.tokens_out) - 1)

    def lock_wait(self) -> Optional[float]:
        """Entry into ``submit`` → the server lock taken: the wait the
        caller paid before ``arrival_time``, which :meth:`ttft` and
        :meth:`queue_wait` therefore leave out."""
        if self.submitted_at is None:
            return None
        return self.arrival_time - self.submitted_at

    def queue_wait(self) -> Optional[float]:
        if self.admitted_at is None:
            return None
        return self.admitted_at - self.arrival_time

    def prefill_compute(self) -> Optional[float]:
        """Admission → first token: the prefill-compute TTFT component
        (TTFT = queue_wait + prefill_compute; the handoff-transit
        component rides ``handoff_transit_s`` and delays the *second*
        token under disaggregation, never the first)."""
        if self.first_token_at is None or self.admitted_at is None:
            return None
        return self.first_token_at - self.admitted_at
