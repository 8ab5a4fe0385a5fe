"""SLO objectives + sliding-window burn-rate tracking.

The north star serves "heavy traffic from millions of users"; the
resilience ladder (ROADMAP item 4) wants an SLO-aware degradation mode
whose input signal is *how fast the error budget is burning*, not the
raw fault rate. This module declares the objectives and computes those
signals; it deliberately does NOT act on them — the scheduler emits
the burn rates on its ``sched.step`` spans and through the monitor
path, and whoever drives the degradation ladder later consumes them
read-only.

Definitions (the standard SRE arithmetic):

* an **objective** says "fraction ``target`` of requests must be good
  over the budget window", where *good* is SLI-specific (TTFT under
  ``threshold_s``, TPOT under ``threshold_s``, request terminated
  successfully);
* the **burn rate** over a sliding window is
  ``bad_fraction / (1 - target)`` — 1.0 means "burning the budget
  exactly as fast as the objective allows", 10 means the budget is
  gone in a tenth of the budget window. Burn rate over an *empty*
  window is 0.0 (no traffic burns no budget).

Windows are time-sliding (seconds on the serving clock — virtual or
monotonic), memory-bounded by ``max_events`` per objective, so a
long-lived server cannot grow tracker state with traffic.
"""

from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass(frozen=True)
class SLOObjective:
    """One declared objective over one SLI."""
    name: str                    # e.g. "ttft"
    target: float                # good fraction, e.g. 0.95
    #: latency SLIs: good iff observation <= threshold_s;
    #: availability SLIs (threshold_s=None): good iff ok flag
    threshold_s: Optional[float] = None
    #: sliding window the burn rate is computed over
    window_s: float = 60.0

    def __post_init__(self):
        if not 0.0 < self.target < 1.0:
            raise ValueError(f"target must be in (0,1): {self.target}")
        if self.window_s <= 0:
            raise ValueError(f"window_s must be > 0: {self.window_s}")


def default_objectives() -> List[SLOObjective]:
    """TTFT / TPOT / availability defaults for the serve-loop scale
    (sub-second model steps); production deployments declare their
    own."""
    return [
        SLOObjective("ttft", target=0.95, threshold_s=1.0,
                     window_s=60.0),
        SLOObjective("tpot", target=0.95, threshold_s=0.1,
                     window_s=60.0),
        SLOObjective("availability", target=0.999, threshold_s=None,
                     window_s=60.0),
    ]


@dataclass
class _Window:
    objective: SLOObjective
    events: deque = field(default_factory=deque)   # (t, good)
    total: int = 0
    total_bad: int = 0
    #: bad events among ``events``: raised as one enters, lowered as
    #: one leaves, so a read costs the same however many the window
    #: holds (the scheduler reads every step)
    bad: int = 0

    def observe(self, t: float, good: bool, max_events: int) -> None:
        self.events.append((t, bool(good)))
        self.total += 1
        self.total_bad += not good
        self.bad += not good
        while len(self.events) > max_events:
            self._drop()
        self.evict(t)

    def _drop(self) -> None:
        self.bad -= not self.events.popleft()[1]

    def evict(self, now: float) -> None:
        w = self.objective.window_s
        while self.events and now - self.events[0][0] > w:
            self._drop()

    def bad_fraction(self, now: float) -> float:
        self.evict(now)
        if not self.events:
            return 0.0
        return self.bad / len(self.events)

    def burn_rate(self, now: float) -> float:
        return self.bad_fraction(now) / (1.0 - self.objective.target)


class SLOTracker:
    """Evaluates declared objectives over a live request stream.

    ``observe_request`` is fed once per terminal request (the
    ``ServingMetrics.on_finish`` hook); ``note_degradation`` is the
    read-only context channel from the resilience ladder — the
    fraction of recent steps spent degraded is exported beside the
    burn rates so a dashboard can tell "SLO burning because overload"
    from "SLO burning because we are shedding on purpose".
    """

    def __init__(self, objectives: List[SLOObjective] = None,
                 max_events: int = 65536):
        self.objectives = list(objectives) if objectives is not None \
            else default_objectives()
        names = [o.name for o in self.objectives]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate objective names: {names}")
        self.max_events = int(max_events)
        self._windows = {o.name: _Window(o) for o in self.objectives}
        #: degradation-context window: (t, level) — same sliding bound
        self._degradation = deque()
        #: its entries with a level above 0, kept as they come and go
        self._degraded = 0
        self._degradation_window_s = max(
            (o.window_s for o in self.objectives), default=60.0)
        self.last_t = 0.0

    # ------------------------------------------------------------- #
    def observe_request(self, t: float, ok: bool,
                        ttft_s: Optional[float] = None,
                        tpot_s: Optional[float] = None) -> None:
        """One terminal request: ``ok`` feeds availability-style
        objectives; latency objectives only see requests that produced
        the corresponding measurement (a failed request with no first
        token is an availability miss, not a TTFT miss)."""
        self.last_t = t
        for w in self._windows.values():
            o = w.objective
            if o.threshold_s is None:
                w.observe(t, ok, self.max_events)
            elif o.name.startswith("ttft"):
                if ttft_s is not None:
                    w.observe(t, ttft_s <= o.threshold_s,
                              self.max_events)
            elif o.name.startswith("tpot"):
                if tpot_s is not None:
                    w.observe(t, tpot_s <= o.threshold_s,
                              self.max_events)
            elif ok:
                # unknown latency-named objective: treat like
                # availability so a typo'd name can't silently pass
                w.observe(t, True, self.max_events)
            else:
                w.observe(t, False, self.max_events)

    def note_degradation(self, t: float, level: int) -> None:
        self.last_t = max(self.last_t, t)
        level = int(level)
        self._degradation.append((t, level))
        self._degraded += level > 0
        w = self._degradation_window_s
        while self._degradation and t - self._degradation[0][0] > w:
            self._drop_degradation()
        while len(self._degradation) > self.max_events:
            self._drop_degradation()

    def _drop_degradation(self) -> None:
        self._degraded -= self._degradation.popleft()[1] > 0

    # ------------------------------------------------------------- #
    def burn_rates(self, now: Optional[float] = None) -> Dict[str, float]:
        """``{objective: burn_rate}`` over each sliding window."""
        now = self.last_t if now is None else now
        return {name: w.burn_rate(now)
                for name, w in self._windows.items()}

    def degraded_fraction(self, now: Optional[float] = None) -> float:
        """Share of the steps in the window that ends at ``now`` spent
        degraded. On the step's own clock nothing in the deque is older
        than the window (``note_degradation`` has dropped it) and the
        answer is the running count over the length. A reader whose
        clock is past the last step's counts the steps that have left
        its window out, from the old end, and drops none: a later read
        at an earlier ``now`` (``summary(now)``, then ``summary()``)
        still sees them. Steps are noted on a clock that does not run
        backwards, so what has left is the head of the deque."""
        now = self.last_t if now is None else now
        w = self._degradation_window_s
        n, degraded = len(self._degradation), self._degraded
        if n and now - self._degradation[0][0] > w:
            for t, lvl in self._degradation:
                if now - t <= w:
                    break
                n -= 1
                degraded -= lvl > 0
        return degraded / n if n else 0.0

    def gauges(self, now: Optional[float] = None) -> Dict[str, float]:
        """The flat gauge dict the serving metrics/monitor path emits:
        one burn rate per objective plus the degradation context."""
        now = self.last_t if now is None else now
        out = {f"slo_{name}_burn_rate": rate
               for name, rate in self.burn_rates(now).items()}
        out["slo_degraded_fraction"] = self.degraded_fraction(now)
        return out

    def summary(self, now: Optional[float] = None) -> Dict:
        now = self.last_t if now is None else now
        objectives = []
        for o in self.objectives:
            w = self._windows[o.name]
            objectives.append({
                "name": o.name, "target": o.target,
                "threshold_s": o.threshold_s, "window_s": o.window_s,
                "window_events": len(w.events),
                "bad_fraction": round(w.bad_fraction(now), 6),
                "burn_rate": round(w.burn_rate(now), 6),
                "total_observed": w.total,
                "total_bad": w.total_bad,
            })
        return {"objectives": objectives,
                "degraded_fraction":
                    round(self.degraded_fraction(now), 6)}
