"""Scheduling results for the ragged inference engine.

Reference analog: ``deepspeed/inference/v2/scheduling_utils.py`` —
``SchedulingResult`` / ``SchedulingError`` returned by
``InferenceEngineV2.can_schedule`` (engine_v2.py:217-264).

The backpressure mapping below is consumed by the continuous-batching
scheduler (``serving/scheduler.py``): every non-Success verdict names
the ONE corrective action that can actually clear it, so the serving
loop never retries a permanent failure or rejects a transient one.
"""

from dataclasses import dataclass
from enum import Enum
from typing import Any


class SchedulingResult(Enum):
    Success = 0
    EngineSequenceLimitExceeded = 1
    BatchSequenceLimitExceeded = 2
    BatchTokenLimitExceeded = 3
    KVCacheLimitExceeded = 4
    SequenceTokenLimitExceeded = 5


class BackpressureAction(Enum):
    """What a serving scheduler should do about one can_schedule verdict.

    Each rejection maps to a distinct action because each names a
    different exhausted resource with a different release schedule:
    """
    #: Success — admit the request into this step's ragged batch.
    ADMIT = 0
    #: EngineSequenceLimitExceeded — every tracked-sequence slot is
    #: held; slots free when a sequence finishes (or, in latent-preempt
    #: mode, is evicted wholesale), so the request waits in queue.
    WAIT_TRACKED_SLOT = 1
    #: BatchSequenceLimitExceeded — THIS forward's lane budget is full;
    #: nothing is wrong with the request, stop admitting and retry at
    #: the next step.
    NEXT_STEP = 2
    #: BatchTokenLimitExceeded — this candidate's prompt overflows the
    #: per-forward token budget; a shorter queued prompt may still fit,
    #: so skip the candidate but keep scanning the queue.
    SKIP_CANDIDATE = 3
    #: KVCacheLimitExceeded — block-pool pressure; the scheduler can
    #: manufacture free blocks by suspending victims to host.
    PREEMPT = 4
    #: SequenceTokenLimitExceeded — prompt + generation exceeds
    #: max_context; no amount of waiting or preemption fixes it.
    REJECT = 5


#: SchedulingResult -> the distinct backpressure action that clears it.
BACKPRESSURE_ACTION = {
    SchedulingResult.Success: BackpressureAction.ADMIT,
    SchedulingResult.EngineSequenceLimitExceeded:
        BackpressureAction.WAIT_TRACKED_SLOT,
    SchedulingResult.BatchSequenceLimitExceeded:
        BackpressureAction.NEXT_STEP,
    SchedulingResult.BatchTokenLimitExceeded:
        BackpressureAction.SKIP_CANDIDATE,
    SchedulingResult.KVCacheLimitExceeded: BackpressureAction.PREEMPT,
    SchedulingResult.SequenceTokenLimitExceeded:
        BackpressureAction.REJECT,
}


class SchedulingError(RuntimeError):
    def __init__(self, result: SchedulingResult) -> None:
        self.result = result
        super().__init__(f"Batch scheduling failed with result {result}")


@dataclass
class BlockPass:
    """What :meth:`InferenceEngineV2.put` is told of one lane's block
    (a model that generates by diffusion over blocks): ``commit`` the
    pass is over a clean block, whose K and V become final and whose
    positions count as seen; ``probe`` the caller wants this pass's
    logits rows and routers' inputs on the host (one lane a dispatch:
    the first asked)."""
    commit: bool = False
    probe: bool = False


@dataclass
class BlockChoice:
    """What a block pass gives a lane: per position the token chosen on
    the device and its confidence (the softmax probability of that
    token), and if it was probed the logits rows ``[B, vocab]`` and what
    each layer's router read ``[L, B, hidden]`` (``[L, B, 0]`` of a
    trunk with no router)."""
    tokens: Any
    confidence: Any
    logits: Any = None
    router_in: Any = None
