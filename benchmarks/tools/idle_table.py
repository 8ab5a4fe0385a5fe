"""The whole cut of one trace's idle time: ``python -m
benchmarks.tools.idle_table <dir-or-file> [--runtime]``.

Prints what ``reducers/idle_cut.py`` makes of a ``.xplane.pb``: the
window, busy and idle; ``wake`` and ``launch`` apart; every leaf of the
host turn with its seconds, its share of the idle and its milliseconds
a ``sched.step`` (``*`` marks a span that has children: its own time,
which a leaf should hold); the same cut apart for the gaps before a
decode program, a slice program and the rest (by the enqueue span that
ends the turn); how the programs paired with the enqueue spans; and the
six bins of the ``idle_cut.*`` metrics beside the device's idle share.
It is how a phase that no leaf holds is found, and how PERF.md
section 5's host paragraphs are written.

``--runtime`` reads the file a second time for the runtime's own host
events, which ``trace/xplane.py`` does not keep: the bracket they put on
the device clock's lead (``reducers/idle_cut.py``, "The two clocks"),
and which of them were open during the ``wake`` and the ``launch``
pieces, by seconds of overlap: whether a wake is the runtime reading
its sync flag or running callbacks, a launch the executor's queue or
the device's own start.
"""

import bisect
import sys

from .. import contract
from ..reducers import idle_cut
from ..stats import percentile
from ..trace import xplane
from .describe_trace import find


def parents(spans):
    """The names of spans that have another span inside them."""
    out, stack = set(), []
    for span in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= span.start:
            stack.pop()
        if stack:
            out.add(stack[-1].name)
        stack.append(span)
    return out


def kind_of(gap):
    if gap.ends_in is None:
        return "no enqueue"
    return gap.ends_in.rsplit(".", 1)[-1]


def runtime_events(path):
    """``[(start, end, line, name)]`` of the host planes, the program's
    own spans apart, in start order: what ``trace/xplane.py`` drops."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not xplane.HOST_SPAN.match(ev.name):
                    start = ev.start_ns * 1e-9
                    out.append((start, start + ev.duration_ns * 1e-9,
                                line.name, ev.name))
    out.sort()
    return out


def runtime_overlaps(events, intervals):
    """``{line: event name -> seconds}`` of ``events`` inside the
    sorted disjoint ``intervals``."""
    out, ends = {}, [b for _, b in intervals]
    for start, end, line, name in events:
        k = bisect.bisect_right(ends, start)
        seconds = 0.0
        while k < len(intervals) and intervals[k][0] < end:
            seconds += min(end, intervals[k][1]) - max(start,
                                                       intervals[k][0])
            k += 1
        if seconds > 0:
            key = f"{line}: {name[:60]}"
            out[key] = out.get(key, 0.0) + seconds
    return out


def runtime_bracket(events, modules):
    """``(low, high)``: the device's lead over the host's clock as the
    runtime's own events bound it. No program began before its
    ``DoEnqueueProgram`` had ended, and none ended after its
    ``tpu::System::Execute=>Done`` began; events and programs are
    paired in time order. ``None`` for a bound with no such event."""
    enqueued = sorted(end for _, end, _, name in events
                      if name == "DoEnqueueProgram")
    done = [start for start, _, _, name in events
            if name == "tpu::System::Execute=>Done"]
    modules = sorted(modules, key=lambda m: m.start)

    def paired(stamps, edge):
        """Each program's stamp: the first not yet taken that lies
        within 20 ms of the program's ``edge``."""
        out, k = [], 0
        for module in modules:
            at = getattr(module, edge)
            while k < len(stamps) and stamps[k] < at - 0.02:
                k += 1
            if k < len(stamps) and stamps[k] < at + 0.02:
                out.append(stamps[k] - at)
                k += 1
        return out

    low, high = paired(enqueued, "start"), paired(done, "end")
    return (max(low) if low else None, min(high) if high else None)


def table(path, runtime=False):
    trace = xplane.load(path)
    reduction = xplane.reduce(trace)
    whole = idle_cut.cut(trace)
    lines = []
    say = lines.append
    idle = reduction.window_s - reduction.busy_first_s
    say(f"window {reduction.window_s:.3f} s, busy "
        f"{reduction.busy_first_s:.3f} s, idle {idle:.3f} s "
        f"({100 * idle / reduction.window_s:.2f}%)")
    if whole is None:
        say("no enqueue span in the trace: nothing to cut by")
        return "\n".join(lines)
    steps = sum(1 for s in trace.host if s.name == "sched.step") or 1
    first = min(trace.chips)
    enqueues = [s for s in trace.host if idle_cut.ENQUEUE.match(s.name)]
    modules = trace.modules.get(first, [])
    inside = sum(1 for g in whole.gaps
                 if g.e == g.start and g.ends_in is None)
    before = sum(1 for g in whole.gaps
                 if g.e == g.start and g.ends_in is not None)
    say(f"{steps} sched.step spans, {len(whole.gaps)} gaps of "
        f"{idle_cut.MIN_GAP_S * 1e6:.0f} us or more, {len(modules)} "
        f"programs, {len(enqueues)} enqueue spans; {whole.unissued} "
        f"programs that no enqueue span issued; {before} gaps whose "
        f"program was enqueued before they began, {inside} inside a "
        f"program")
    say(f"the device's clock leads the host's by "
        f"{1e3 * whole.lead_s:.3f} ms (idle_cut.device_lead, from the "
        f"spans); the gaps are cut on the host's clock")
    events = runtime_events(path) if runtime else []
    if runtime:
        low, high = runtime_bracket(events, modules)
        say("  the runtime's events bound the lead: at least "
            + ("?" if low is None else f"{1e3 * low:.3f}") +
            " ms (DoEnqueueProgram), at most "
            + ("?" if high is None else f"{1e3 * high:.3f}") +
            " ms (Execute=>Done)")
    bins = whole.bins()
    has_children = parents(trace.host)

    def rows(bins, idle_s, n_steps):
        order = ["wake", "launch"] + sorted(
            (k for k in bins if k not in ("wake", "launch")),
            key=lambda k: -bins[k])
        for name in order:
            mark = "*" if name in has_children else " "
            say(f"  {mark}{name:<28} {bins[name]:>9.4f} s "
                f"{100 * bins[name] / idle_s if idle_s else 0.0:>6.2f}% "
                f"{1e3 * bins[name] / n_steps:>8.3f} ms/step")

    say("the idle, each second once (* a parent's own time):")
    rows(bins, idle, steps)
    own = sum(v for k, v in bins.items()
              if k in has_children or k == idle_cut.NO_SPAN)
    say(f"  parents' own time and {idle_cut.NO_SPAN}: {own:.4f} s, "
        f"{100 * own / idle if idle else 0.0:.2f}% of the idle")
    turns = [g.turn for g in whole.gaps]
    say(f"host turn per gap: p50 {1e3 * percentile(turns, 50):.3f} ms, "
        f"p90 {1e3 * percentile(turns, 90):.3f} ms; wake p50 "
        f"{1e3 * percentile([g.wake for g in whole.gaps], 50):.3f} ms, "
        f"launch p50 "
        f"{1e3 * percentile([g.launch for g in whole.gaps], 50):.3f} ms")
    for kind in sorted({kind_of(g) for g in whole.gaps}):
        mine = [g for g in whole.gaps if kind_of(g) == kind]
        part = idle_cut.gap_bins(mine)
        total = sum(part.values())
        say(f"gaps before a {kind} program: {len(mine)}, {total:.4f} s; "
            f"per gap p50: gap "
            f"{1e3 * percentile([g.end - g.start for g in mine], 50):.3f}"
            f", wake {1e3 * percentile([g.wake for g in mine], 50):.3f}, "
            f"turn {1e3 * percentile([g.turn for g in mine], 50):.3f}, "
            f"launch {1e3 * percentile([g.launch for g in mine], 50):.3f}"
            f" ms (ms/step below is ms a gap)")
        rows(part, total, len(mine))
    say("the idle_cut.* metrics (% of the window):")
    total = 0.0
    for name, spec in sorted(contract.load_metric_specs().items()):
        if spec["reads"] == "idle_cut" and "bin" in spec:
            value = idle_cut.read(spec, {"trace": reduction})
            total += value
            say(f"  {name:<18} {value:>7.3f}")
    short = 100 * whole.short_s / whole.window_s
    say(f"  sum {total:.3f} + gaps under 20 us {short:.3f} = "
        f"{total + short:.3f}; device idle share "
        f"{100 * reduction.idle_share:.3f}")
    if runtime:
        for what, spans in (
                ("wake", [(g.start, g.w) for g in whole.gaps
                          if g.w > g.start]),
                ("launch", [(g.e, g.end) for g in whole.gaps
                            if g.end > g.e])):
            seconds = sum(b - a for a, b in spans)
            say(f"the runtime's host events open during {what} "
                f"({seconds:.4f} s):")
            found = runtime_overlaps(events, spans)
            for key, value in sorted(found.items(),
                                     key=lambda kv: -kv[1])[:12]:
                say(f"  {value:>9.4f} s {100 * value / seconds:>6.1f}%  "
                    f"{key}")
    return "\n".join(lines)


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--runtime"]
    print(table(find(args[0]), runtime="--runtime" in sys.argv[1:]))
