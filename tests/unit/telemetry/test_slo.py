"""SLO burn-rate window unit tests (telemetry.slo)."""

import random
from collections import deque

import pytest

from hcache_deepspeed_tpu.telemetry.slo import (SLOObjective,
                                                SLOTracker,
                                                default_objectives)


def tracker(**kw):
    return SLOTracker([
        SLOObjective("ttft", target=0.9, threshold_s=1.0,
                     window_s=10.0),
        SLOObjective("tpot", target=0.9, threshold_s=0.1,
                     window_s=10.0),
        SLOObjective("availability", target=0.99, threshold_s=None,
                     window_s=10.0),
    ], **kw)


def test_burn_rate_zero_on_empty_and_all_good():
    t = tracker()
    assert t.burn_rates(0.0) == {"ttft": 0.0, "tpot": 0.0,
                                 "availability": 0.0}
    for i in range(10):
        t.observe_request(float(i) / 10, ok=True, ttft_s=0.5,
                          tpot_s=0.05)
    assert all(v == 0.0 for v in t.burn_rates().values())


def test_burn_rate_arithmetic():
    """bad_fraction / error_budget: 20% TTFT misses against a 10%
    budget burns at 2x."""
    t = tracker()
    for i in range(10):
        ttft = 2.0 if i < 2 else 0.5          # 2 of 10 miss 1.0s
        t.observe_request(float(i) * 0.1, ok=True, ttft_s=ttft,
                          tpot_s=0.05)
    rates = t.burn_rates(1.0)
    assert rates["ttft"] == pytest.approx(0.2 / 0.1)
    assert rates["tpot"] == 0.0
    assert rates["availability"] == 0.0


def test_burn_rate_100pct_bad_saturates_at_inverse_budget():
    t = tracker()
    for i in range(5):
        t.observe_request(float(i), ok=False)
    # availability budget 1%: all-bad burns at 1/0.01 = 100x
    assert t.burn_rates(4.0)["availability"] == pytest.approx(100.0)


def test_sliding_window_evicts_old_misses():
    t = tracker()
    # 5 misses at t=0..4, then quiet; window is 10s
    for i in range(5):
        t.observe_request(float(i), ok=True, ttft_s=5.0)
    assert t.burn_rates(5.0)["ttft"] > 0
    # at t=30 every miss is >10s old: budget stops burning. The
    # window sees no traffic -> burn 0 (no traffic burns no budget)
    assert t.burn_rates(30.0)["ttft"] == 0.0


def test_window_mixes_eviction_and_fresh_goods():
    t = tracker()
    for i in range(4):
        t.observe_request(float(i), ok=True, ttft_s=5.0)    # misses
    for i in range(4, 12):
        t.observe_request(float(i), ok=True, ttft_s=0.1)    # good
    # at t=12, window [2..12] holds misses at t=2,3 + 8 goods
    assert t.burn_rates(12.0)["ttft"] == \
        pytest.approx((2 / 10) / 0.1)


def test_latency_slis_only_see_measured_requests():
    """A failed request with no first token is an availability miss,
    never a TTFT sample."""
    t = tracker()
    t.observe_request(0.0, ok=False, ttft_s=None, tpot_s=None)
    rates = t.burn_rates(0.0)
    assert rates["availability"] == pytest.approx(100.0)
    assert rates["ttft"] == 0.0 and rates["tpot"] == 0.0


def test_memory_bounded_by_max_events():
    t = tracker(max_events=100)
    for i in range(10_000):
        t.observe_request(0.001 * i, ok=True, ttft_s=0.5, tpot_s=0.05)
    for w in t._windows.values():
        assert len(w.events) <= 100
        assert w.total == 10_000         # totals still exact


def test_degradation_context_gauge():
    t = tracker()
    for i in range(4):
        t.note_degradation(float(i), level=0)
    for i in range(4, 8):
        t.note_degradation(float(i), level=2)
    assert t.degraded_fraction(7.0) == pytest.approx(0.5)
    g = t.gauges(7.0)
    assert g["slo_degraded_fraction"] == pytest.approx(0.5)
    assert set(g) == {"slo_ttft_burn_rate", "slo_tpot_burn_rate",
                      "slo_availability_burn_rate",
                      "slo_degraded_fraction"}


def test_summary_shape():
    t = tracker()
    t.observe_request(0.0, ok=True, ttft_s=0.2, tpot_s=0.01)
    s = t.summary()
    assert {o["name"] for o in s["objectives"]} == \
        {"ttft", "tpot", "availability"}
    for o in s["objectives"]:
        assert 0 <= o["bad_fraction"] <= 1
        assert o["burn_rate"] >= 0


def test_objective_validation():
    with pytest.raises(ValueError):
        SLOObjective("x", target=1.0)
    with pytest.raises(ValueError):
        SLOObjective("x", target=0.9, window_s=0)
    with pytest.raises(ValueError):
        SLOTracker([SLOObjective("a", 0.9), SLOObjective("a", 0.8)])


def test_default_objectives_cover_the_three_slis():
    names = {o.name for o in default_objectives()}
    assert names == {"ttft", "tpot", "availability"}


# ------------------------------------------------------------------ #
# running counts (PR 47): every read equal, float for float, to the
# plain walk the tracker made before it kept counts
# ------------------------------------------------------------------ #
class _PlainWalk:
    """The tracker as it was: every read lists and sums the window.
    Kept here as the reference the running counts are held to."""

    def __init__(self, objectives, max_events):
        self.objectives = objectives
        self.max_events = max_events
        self.events = {o.name: deque() for o in objectives}
        self.totals = {o.name: [0, 0] for o in objectives}
        self.degradation = deque()
        self.window_s = max(o.window_s for o in objectives)
        self.last_t = 0.0

    def _observe(self, o, t, good):
        events = self.events[o.name]
        events.append((t, bool(good)))
        self.totals[o.name][0] += 1
        self.totals[o.name][1] += not good
        while len(events) > self.max_events:
            events.popleft()
        self._evict(o, t)

    def _evict(self, o, now):
        events = self.events[o.name]
        while events and now - events[0][0] > o.window_s:
            events.popleft()

    def observe_request(self, t, ok, ttft_s=None, tpot_s=None):
        self.last_t = t
        for o in self.objectives:
            if o.threshold_s is None:
                self._observe(o, t, ok)
            elif o.name.startswith("ttft"):
                if ttft_s is not None:
                    self._observe(o, t, ttft_s <= o.threshold_s)
            elif o.name.startswith("tpot"):
                if tpot_s is not None:
                    self._observe(o, t, tpot_s <= o.threshold_s)
            else:
                self._observe(o, t, ok)

    def note_degradation(self, t, level):
        self.last_t = max(self.last_t, t)
        self.degradation.append((t, int(level)))
        while self.degradation and \
                t - self.degradation[0][0] > self.window_s:
            self.degradation.popleft()
        while len(self.degradation) > self.max_events:
            self.degradation.popleft()

    def _bad_fraction(self, o, now):
        self._evict(o, now)
        events = self.events[o.name]
        if not events:
            return 0.0
        return sum(1 for _, good in events if not good) / len(events)

    def burn_rates(self, now=None):
        now = self.last_t if now is None else now
        return {o.name: self._bad_fraction(o, now) / (1.0 - o.target)
                for o in self.objectives}

    def degraded_fraction(self, now=None):
        now = self.last_t if now is None else now
        recent = [lvl for t, lvl in self.degradation
                  if now - t <= self.window_s]
        if not recent:
            return 0.0
        return sum(1 for lvl in recent if lvl > 0) / len(recent)

    def gauges(self, now=None):
        now = self.last_t if now is None else now
        out = {f"slo_{name}_burn_rate": rate
               for name, rate in self.burn_rates(now).items()}
        out["slo_degraded_fraction"] = self.degraded_fraction(now)
        return out

    def summary(self, now=None):
        now = self.last_t if now is None else now
        objectives = []
        for o in self.objectives:
            events = self.events[o.name]
            objectives.append({
                "name": o.name, "target": o.target,
                "threshold_s": o.threshold_s, "window_s": o.window_s,
                "window_events": len(events),
                "bad_fraction": round(self._bad_fraction(o, now), 6),
                "burn_rate": round(self._bad_fraction(o, now) /
                                   (1.0 - o.target), 6),
                "total_observed": self.totals[o.name][0],
                "total_bad": self.totals[o.name][1]})
        return {"objectives": objectives,
                "degraded_fraction":
                    round(self.degraded_fraction(now), 6)}


def _objectives(window_s):
    # the degradation window is the longest of them: unequal windows
    # make it differ from two of the three
    return [SLOObjective("ttft", target=0.9, threshold_s=1.0,
                         window_s=window_s),
            SLOObjective("tpot", target=0.95, threshold_s=0.1,
                         window_s=window_s / 2),
            SLOObjective("availability", target=0.99, threshold_s=None,
                         window_s=window_s * 1.5)]


@pytest.mark.parametrize("max_events", [7, 40, 65536])
@pytest.mark.parametrize("window_s", [0.5, 3.0, 60.0])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_running_counts_equal_the_plain_walk(seed, window_s, max_events):
    """One random stream of finished requests, noted steps and reads,
    on a clock that does not run backwards, to the tracker and to the
    plain walk: every gauge the same float after every call. Reads at
    ``now=None``, at the clock, and past the last event by half a
    window and by two (which empties it), each followed by reads at
    the last event's time again; ``max_events`` small enough to
    trim."""
    rng = random.Random(seed * 1000 + max_events)
    objectives = _objectives(window_s)
    got = SLOTracker(objectives, max_events=max_events)
    want = _PlainWalk(objectives, max_events)
    t = 0.0
    for _ in range(600):
        t += rng.choice((0.0, 0.001, 0.01, 0.01, 0.1, window_s / 3))
        op = rng.random()
        if op < 0.3:
            args = dict(
                ok=rng.random() < 0.8,
                ttft_s=rng.choice((None, 0.2, 1.0, 1.5)),
                tpot_s=rng.choice((None, 0.05, 0.1, 0.3)))
            got.observe_request(t, **args)
            want.observe_request(t, **args)
        elif op < 0.8:
            level = rng.choice((0, 0, 0, 1, 2))
            got.note_degradation(t, level)
            want.note_degradation(t, level)
        now = rng.choice((None, t, t + window_s / 2, t + window_s * 2))
        assert got.gauges(now) == want.gauges(now)
        assert got.burn_rates(now) == want.burn_rates(now)
        assert got.degraded_fraction(now) == want.degraded_fraction(now)
        assert got.summary(now) == want.summary(now)
        assert got.last_t == want.last_t
    for o in objectives:
        w = got._windows[o.name]
        assert w.bad == sum(1 for _, good in w.events if not good)
        assert len(w.events) <= max_events
    assert got._degraded == sum(1 for _, lvl in got._degradation if lvl > 0)
    assert len(got._degradation) <= max_events


class _NoWalk(deque):
    """A deque that cannot be walked: a read that lists or sums its
    entries raises."""

    def __iter__(self):
        raise AssertionError("the refresh walked the window")


def test_the_refresh_does_not_walk_the_window():
    t = SLOTracker()
    t._degradation = _NoWalk()
    for w in t._windows.values():
        w.events = _NoWalk()
    gauges = {}
    for step in range(10_000):
        now = step * 0.005                  # 50 s of 5 ms steps
        if step % 50 == 0:
            t.observe_request(now, ok=step % 100 == 0, ttft_s=0.5,
                              tpot_s=0.2)
        t.note_degradation(now, level=int(step % 4 == 0))
        gauges = t.gauges(now)
    assert len(t._degradation) == 10_000
    assert gauges == {"slo_ttft_burn_rate": 0.0,
                      "slo_tpot_burn_rate": 1.0 / (1.0 - 0.95),
                      "slo_availability_burn_rate": 0.5 / (1.0 - 0.999),
                      "slo_degraded_fraction": 0.25}
