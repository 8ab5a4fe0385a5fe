"""From a profiler trace (``.xplane.pb``) to busy, idle, op time, idle
gaps by host span, and kernel time.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A TPU
trace has one plane per chip (``/device:TPU:<n>``). Its ``XLA Ops`` line
holds one event per executed HLO operation, named by the operation's
whole HLO text (``%copy.121 = bf16[8,8,163840,128]{...} copy(...)``);
a ``while`` and the operations of its body nest, so an operation's own
time is its duration less its children's. ``XLA Modules`` holds one
event per executed program. The host plane (``/host:CPU``) has the
program's ``jax.profiler.TraceAnnotation`` spans (``sched.step``,
``hds.serve.put``, ...) by name. All planes share one clock.

Everything downstream works on :class:`Trace`, a plain record of
intervals, so the arithmetic is checked in tier-1 on a small recorded
trace and on hand-made intervals.
"""

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: idle gaps shorter than this are the spaces between back-to-back
#: operations of one program, not the host's doing
MIN_GAP_S = 20e-6
#: host spans a gap may be attributed to: the program's own annotations
HOST_SPAN = re.compile(r"^(sched|serve|hds|train|zero|restore)\.[\w.]+$")


@dataclass
class Op:
    text: str           # the operation's HLO text, as the trace names it
    label: str          # kind and result shape, stable across compiles
    start: float        # seconds
    end: float
    own: float = 0.0    # seconds not covered by operations nested in it


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    """Intervals in seconds on the trace's clock."""
    chips: dict = field(default_factory=dict)       # chip id -> [Op]
    modules: dict = field(default_factory=dict)     # chip id -> [Span]
    host: list = field(default_factory=list)        # [Span]
    t_min: float = 0.0
    t_max: float = 0.0


_HEAD = re.compile(
    r"\s*%?([\w\-]+?)(?:\.[\w.\-]*)?\s*=\s*\(?(\w+)\[([\d,]*)\]")
_NAME = re.compile(r"\s*%?([\w\-]+?)(?:\.[\w.\-]*)?\s*(=|$)")


def label_of(text):
    """``<kind>_<dtype>_<dims>_``: the operation's kind (its name less
    the compiler's numbering) and result shape, e.g.
    ``copy_bitcast_fusion_bf16_8_163840_128_``; of a tuple result, its
    first element's shape. Two compilations of one program number their
    operations differently and label them alike."""
    m = _HEAD.match(text)
    if m:
        return f"{m.group(1)}_{m.group(2)}_{m.group(3).replace(',', '_')}_"
    m = _NAME.match(text)
    return m.group(1) if m else text[:40]


def set_own_times(ops):
    """Fill ``own``: the seconds during which the operation is the
    latest-started one running. A ``while`` gets what its body does not
    cover, and where two operations overlap without nesting the later
    one is charged, so the ``own`` times of a chip add up to its busy
    time exactly."""
    bounds = []
    for i, op in enumerate(ops):
        op.own = 0.0
        bounds.append((op.start, 1, i))
        bounds.append((op.end, 0, i))
    bounds.sort()
    stack, ended, last = [], set(), None
    for t, is_start, i in bounds:
        while stack and stack[-1] in ended:
            ended.discard(stack.pop())
        if stack:
            ops[stack[-1]].own += t - last
        last = t
        if is_start:
            stack.append(i)
        else:
            ended.add(i)


def load(path):
    """Read ``path`` into a :class:`Trace`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    trace = Trace()
    lo, hi = float("inf"), float("-inf")
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name == OPS_LINE:
                ops = trace.chips.setdefault(int(dev.group(1)), [])
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    end = start + ev.duration_ns * 1e-9
                    ops.append(Op(ev.name, label_of(ev.name), start, end))
                    lo, hi = min(lo, start), max(hi, end)
            elif dev and line.name == MODULES_LINE:
                trace.modules[int(dev.group(1))] = [
                    Span(ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events]
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    end = start + ev.duration_ns * 1e-9
                    lo, hi = min(lo, start), max(hi, end)
                    if HOST_SPAN.match(ev.name):
                        trace.host.append(Span(ev.name, start, end))
    if lo > hi:
        lo = hi = 0.0
    trace.t_min, trace.t_max = lo, hi
    for ops in trace.chips.values():
        ops.sort(key=lambda op: op.start)
        set_own_times(ops)
    trace.host.sort(key=lambda s: s.start)
    return trace


def union(intervals):
    """Merged, sorted ``[(start, end)]``."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def covered(intervals):
    return sum(end - start for start, end in union(intervals))


def gaps(busy, t_min, t_max):
    """The idle intervals of ``[t_min, t_max]`` given merged busy
    intervals."""
    out, at = [], t_min
    for start, end in busy:
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if t_max > at:
        out.append((at, t_max))
    return out


def innermost(spans, t):
    """The name of the latest-started span open at ``t``, or ``None``."""
    best = None
    for span in spans:
        if span.start > t:
            break
        if span.end >= t and (best is None or span.start >= best.start):
            best = span
    return best.name if best else None


@dataclass
class Reduction:
    window_s: float
    busy_s: float                   # averaged over the chips traced
    busy_first_s: float             # the first chip's, beside op_seconds
    op_seconds: dict                # label -> own seconds, first chip
    gap_seconds: dict               # host span (or "_no_span_") -> seconds
    gap_lengths: list               # every idle gap over MIN_GAP_S
    trace: Trace = None

    @property
    def idle_share(self):
        return 1.0 - self.busy_s / self.window_s if self.window_s else None

    def breakdown(self, top=10):
        def head(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": head(self.op_seconds),
                "idle_gaps": head(self.gap_seconds)}

    def matching(self, pattern, on="label"):
        """The first chip's operations whose label (or, ``on="text"``,
        whole HLO text) matches ``pattern``."""
        rx = re.compile(pattern)
        ops = self.trace.chips[min(self.trace.chips)]
        return [op for op in ops if rx.search(getattr(op, on))]

    def seconds_matching(self, pattern, on="label"):
        return sum(op.own for op in self.matching(pattern, on))


def reduce(trace):
    if not trace.chips:
        raise ValueError("the trace holds no device operations: nothing "
                         "ran on a chip while it was taken")
    window = trace.t_max - trace.t_min
    busy_each = []
    for ops in trace.chips.values():
        busy_each.append(covered((op.start, op.end) for op in ops))
    first = trace.chips[min(trace.chips)]
    op_seconds = {}
    for op in first:
        op_seconds[op.label] = op_seconds.get(op.label, 0.0) + op.own
    merged = union((op.start, op.end) for op in first)
    gap_seconds, lengths = {}, []
    for start, end in gaps(merged, trace.t_min, trace.t_max):
        if end - start < MIN_GAP_S:
            key = "_gaps_under_20_us_"
        else:
            lengths.append(end - start)
            key = innermost(trace.host, (start + end) / 2.0) or "_no_span_"
        gap_seconds[key] = gap_seconds.get(key, 0.0) + (end - start)
    return Reduction(window_s=window,
                     busy_s=sum(busy_each) / len(busy_each),
                     busy_first_s=covered((op.start, op.end)
                                          for op in first),
                     op_seconds=op_seconds, gap_seconds=gap_seconds,
                     gap_lengths=lengths, trace=trace)


def reduce_file(path):
    return reduce(load(path))


def describe(path, limit=12):
    """What is in a trace file, for looking at one by hand."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        lines.append(f"plane {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            lines.append(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:limit]:
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in ev.stats}
                lines.append(f"    {ev.name[:60]!r} start {ev.start_ns} "
                             f"dur {ev.duration_ns} {stats}")
    return "\n".join(lines)
