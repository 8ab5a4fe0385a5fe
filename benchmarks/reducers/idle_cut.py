"""``idle_cut``: every idle gap of the device cut where the work
happens, not handed whole to the span at its midpoint.

A step cycle on the loop thread is: program N ends, the
``serve.device_wait`` that waited for it returns (**wake**), the host
does its turn (fetch, scatter, sample, metrics, passes, admission,
builds), the enqueue of program N+1 returns, the device starts it
(**launch**). For every idle gap ``[g0, g1]`` of the first chip of at
least ``MIN_GAP_S``:

1. ``e`` is the end of the enqueue span that issued the program after
   the gap, clipped to the gap: ``[e, g1]`` is **launch** (enqueued,
   not started). Programs (``trace.modules``) are paired with enqueue
   spans in order, a program to the oldest unpaired enqueue span that
   began before it: a program enqueued before the gap began (restore
   replays issued back to back, a pipelined loop) leaves the gap
   ``launch`` whole, and so does a gap inside one program. A program
   that no enqueue span issued (or a trace with no ``XLA Modules``
   line) takes the latest end of an enqueue span inside the gap, else
   ``g1``: no launch.
2. ``w`` is the end of the ``serve.device_wait`` span open at ``g0``,
   clipped to ``e``; none open: ``g0``. ``[g0, w]`` is **wake**: the
   program ended, the host not yet back.
3. ``[w, e]`` is the **host turn**, cut at every edge of the program's
   spans; each piece goes to the innermost span open over it, or to
   ``_no_span_``.

**The two clocks.** The profiler stamps the device's events with the
device's clock and the host's with the host's, and lines them up only
roughly: in this repository's traces of the v5e every program "began"
1.1-1.3 ms before the runtime's own ``DoEnqueueProgram`` event had
handed it to the device (PERF.md section 5). Cut as stamped, each gap
would give that lead to ``wake`` and take it from the enqueue span and
``launch``. So the gaps are moved onto the host's clock first, by
:func:`device_lead`: the least shift after which nine programs in ten
begin no earlier than the close of the enqueue span that issued them,
never more than the shift at which a ``serve.device_wait`` would
return before the program it waited for had ended (a trace with no
such span is cut as stamped). An estimate from
the program's spans alone (``trace/xplane.py`` keeps no event of the
runtime); ``idle_table --runtime`` prints it beside the bracket that
the runtime's ``DoEnqueueProgram`` and ``Execute=>Done`` events give.
A gap's length is the device's own and does not change.

The enqueue spans are the program's contract (``docs/observability.md``):
``ENQUEUE`` below; each closes when the enqueue has returned. Like
every reader of the program's spans this rests on the rule of
``README.md`` beside this file: only the thread that drives the device
opens them.

The bins are disjoint and, with the gaps under 20 us, add up to the
device's idle time exactly. Spec keys: ``bin`` (``wake``, ``launch``,
or a pattern on the leaf's name; ``_no_span_`` is a name) gives the
bin's seconds over the traced window in percent, ``0.0`` when empty;
``turn`` with ``how`` gives ``e - w`` per gap reduced by ``how``,
seconds. Nothing when the run has no trace, the trace no enqueue span,
or no gap to cut. ``python -m benchmarks.tools.idle_table`` prints the
whole cut of one trace.
"""

import bisect
import re
from dataclasses import dataclass

from ..stats import reduce_series
from ..trace.xplane import MIN_GAP_S, gaps, union

ENQUEUE = re.compile(r"^(serve\.(decode_dispatch|prefill_dispatch|"
                     r"spec_dispatch|fused_decode)|restore\.replay)$")
WAIT = "serve.device_wait"
#: the device's clock leads the host's by a millisecond or so (module
#: docstring): a program may seem to begin this long before the enqueue
#: span that issued it opened
CLOCK_SLACK_S = 2e-3
NO_SPAN = "_no_span_"
SHORT = "_gaps_under_20_us_"


@dataclass
class Gap:
    start: float
    end: float
    w: float            # the host is back
    e: float            # the next program is enqueued
    pieces: dict        # innermost span (or NO_SPAN) -> seconds of [w, e]
    ends_in: str        # the enqueue span that ends the turn, or None

    @property
    def wake(self):
        return self.w - self.start

    @property
    def launch(self):
        return self.end - self.e

    @property
    def turn(self):
        return self.e - self.w


@dataclass
class Cut:
    window_s: float
    lead_s: float       # the device's clock ahead of the host's
    gaps: list          # [Gap], every idle gap of at least MIN_GAP_S
    short_s: float      # the gaps under MIN_GAP_S together
    unissued: int       # programs that no enqueue span issued

    def bins(self):
        """``wake``, ``launch``, every leaf, ``NO_SPAN`` and ``SHORT``
        -> seconds: the device's idle time, each second once."""
        out = gap_bins(self.gaps)
        out[SHORT] = self.short_s
        return out


def gap_bins(found):
    """``wake``, ``launch``, every leaf and ``NO_SPAN`` -> seconds over
    the gaps ``found``."""
    out = {"wake": 0.0, "launch": 0.0}
    for gap in found:
        out["wake"] += gap.wake
        out["launch"] += gap.launch
        for name, seconds in gap.pieces.items():
            out[name] = out.get(name, 0.0) + seconds
    return out


def leaf_timeline(spans):
    """Disjoint ``[(start, end, name)]`` in time order: over each
    stretch the innermost (latest-started) span open, nothing where
    none is. Spans of one thread nest; where a child's clock outlasts
    its parent's by a tick the child keeps the tick."""
    order = sorted((s for s in spans if s.end > s.start),
                   key=lambda s: (s.start, -s.end))
    bounds = []
    for i, span in enumerate(order):
        bounds.append((span.start, 1, i))
        bounds.append((span.end, 0, i))
    bounds.sort()
    out, stack, ended, last = [], [], set(), None
    for t, is_start, i in bounds:
        while stack and stack[-1] in ended:
            ended.discard(stack.pop())
        if stack and t > last:
            out.append((last, t, order[stack[-1]].name))
        last = t
        if is_start:
            stack.append(i)
        else:
            ended.add(i)
    return out


def issuers(enqueues, modules):
    """For each program, in start order, the enqueue span that issued
    it: the oldest unpaired one that began before the program did, or
    ``None``. An enqueue span that issued nothing (a dispatch that
    raised) would pair with the next program and shift the rest:
    ``idle_table`` prints both counts."""
    out, i = [], 0
    for module in modules:
        if i < len(enqueues) and \
                enqueues[i].start - CLOCK_SLACK_S <= module.start:
            out.append(enqueues[i])
            i += 1
        else:
            out.append(None)
    return out


def device_lead(modules, issued_by, waits):
    """Seconds to add to the device's stamps to read them on the
    host's clock (module docstring); ``0.0`` where nothing says."""
    starts = sorted(span.end - module.start
                    for module, span in zip(modules, issued_by)
                    if span is not None)
    if not starts:
        return 0.0
    lead, bound = starts[int(0.9 * (len(starts) - 1))], None
    wait_starts = [w.start for w in waits]
    for module in modules:
        k = bisect.bisect_right(wait_starts, module.end) - 1
        if k >= 0 and waits[k].end > module.end:
            wake = waits[k].end - module.end
            bound = wake if bound is None else min(bound, wake)
    if bound is None:       # no wait to hold the estimate: none made
        return 0.0
    return max(min(lead, bound), 0.0)


def cut(trace):
    """The :class:`Cut` of ``trace``, or ``None`` where there is
    nothing to cut by: no device operation, or no enqueue span."""
    if not trace.chips:
        return None
    first = min(trace.chips)
    enqueues = sorted((s for s in trace.host if ENQUEUE.match(s.name)),
                      key=lambda s: s.start)
    if not enqueues:
        return None
    enqueue_ends = [s.end for s in enqueues]
    waits = sorted((s for s in trace.host if s.name == WAIT),
                   key=lambda s: s.start)
    wait_starts = [s.start for s in waits]
    modules = sorted(trace.modules.get(first, ()), key=lambda m: m.start)
    module_starts = [m.start for m in modules]
    issued_by = issuers(enqueues, modules)
    timeline = leaf_timeline(trace.host)
    timeline_starts = [seg[0] for seg in timeline]

    def pieces(a, b):
        out, covered = {}, 0.0
        k = max(bisect.bisect_right(timeline_starts, a) - 1, 0)
        while k < len(timeline) and timeline[k][0] < b:
            start, end, name = timeline[k]
            k += 1
            seconds = min(end, b) - max(start, a)
            if seconds > 0:
                out[name] = out.get(name, 0.0) + seconds
                covered += seconds
        if b - a - covered > 0:
            out[NO_SPAN] = b - a - covered
        return out

    lead = device_lead(modules, issued_by, waits)

    def enqueued(g0, g1):
        """``e`` of the gap ``[g0, g1]`` (the device's stamps), on the
        host's clock, and the enqueue span that ends its turn."""
        k = bisect.bisect_right(module_starts, g1) - 1
        if k >= 0 and modules[k].end > g1:      # the program after it
            if modules[k].start <= g0:          # a gap inside a program
                return g0 + lead, None
            if issued_by[k] is not None:
                return (min(max(issued_by[k].end, g0 + lead), g1 + lead),
                        issued_by[k].name)
        k = bisect.bisect_right(enqueue_ends, g1 + lead) - 1
        if k >= 0 and enqueue_ends[k] > g0 + lead:
            return enqueue_ends[k], enqueues[k].name
        return g1 + lead, None

    busy = union((op.start, op.end) for op in trace.chips[first])
    found, short = [], 0.0
    for g0, g1 in gaps(busy, trace.t_min, trace.t_max):
        if g1 - g0 < MIN_GAP_S:
            short += g1 - g0
            continue
        e, ends_in = enqueued(g0, g1)
        g0, g1 = g0 + lead, g1 + lead           # on the host's clock
        w = g0
        k = bisect.bisect_right(wait_starts, g0) - 1
        if k >= 0 and waits[k].end > g0:
            w = min(waits[k].end, e)
        found.append(Gap(g0, g1, w, e, pieces(w, e) if e > w else {},
                         ends_in))
    return Cut(window_s=trace.t_max - trace.t_min, lead_s=lead, gaps=found,
               short_s=short, unissued=sum(1 for s in issued_by
                                           if s is None))


def read(spec, evidence):
    reduction = evidence.get("trace")
    if reduction is None or not reduction.window_s:
        return None
    whole = cut(reduction.trace)
    if whole is None or not whole.gaps:
        return None
    if "turn" in spec:
        return reduce_series([gap.turn for gap in whole.gaps], spec["how"])
    bins = whole.bins()
    if spec["bin"] in ("wake", "launch"):
        seconds = bins[spec["bin"]]
    else:
        rx = re.compile(spec["bin"])
        seconds = sum(v for k, v in bins.items()
                      if k not in ("wake", "launch", SHORT) and rx.search(k))
    return 100.0 * seconds / whole.window_s
