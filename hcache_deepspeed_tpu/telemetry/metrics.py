"""Derived step-metrics pipeline over the span stream.

Two consumers:

* :class:`StepMetrics` — live per-step emission (tokens/sec,
  samples/sec, step-time breakdown, MFU) through the existing
  ``monitor.MonitorMaster`` event path, so step telemetry lands in the
  same sinks (TensorBoard/W&B/CSV/in-memory) training metrics already
  use.
* :func:`summarize` / :func:`render_table` — offline reduction of a
  span stream (live tracer buffer or a loaded ``trace.json``) into a
  per-step breakdown (training steps, and per ``sched.step`` the
  serving loop's leaf spans, its wait for the device and the host's
  turn between two programs) plus comm-volume and HCache-restore
  attribution,
  including the restore-overlap ratio *computed from the explicit
  restore/decode span pair* the serving scheduler emits (not inferred
  from wall-clock adjacency).
"""

import statistics
from collections import OrderedDict
from typing import Dict, List, Optional

#: span names folded into the per-step phase columns
PHASE_SPANS = (
    "train.fwd", "train.bwd", "train.step", "train.fused_dispatch",
    "train.data", "train.offload_states", "train.reload_states",
)
#: the per-optimizer-step grouping span
STEP_SPAN = "train.train_batch"
#: serving restore attribution spans
RESTORE_SPAN = "serve.restore_kv"
RESTORE_STAGE_SPAN = "serve.restore.stage"
SCHED_RESTORE_SPAN = "sched.restore_issue"
SCHED_DISPATCH_SPAN = "sched.decode_dispatch"
#: the per-serving-step grouping span, the wait for the device, and
#: the spans that close when a program's enqueue has returned
#: (docs/observability.md, "The enqueue spans")
SERVE_STEP_SPAN = "sched.step"
DEVICE_WAIT_SPAN = "serve.device_wait"
ENQUEUE_SPANS = frozenset((
    "serve.decode_dispatch", "serve.prefill_dispatch",
    "serve.spec_dispatch", "serve.fused_decode", "restore.replay"))


class StepMetrics:
    """Per-step metric emission through a ``Monitor`` sink.

    ``flops_per_token`` is the portable 6N estimate by default (set it
    to an exact per-token cost when one is known, e.g. from the flops
    profiler's XLA cost analysis); ``peak_tflops`` is the published
    peak from ``platform.peak_tflops`` (times the mesh size). ``None``
    means the platform has no peak (the host CPU) and MFU is not a
    defined metric there; a peak of 0 is refused, it is not a spelling
    of "unknown".
    """

    def __init__(self, monitor=None, peak_tflops: Optional[float] = None,
                 flops_per_token: float = 0.0, prefix: str = "Train",
                 registry=None):
        self.monitor = monitor
        if peak_tflops is not None and not peak_tflops > 0:
            raise ValueError(
                f"peak_tflops={peak_tflops!r}: pass the published peak, "
                "or None on a platform that has none")
        self.peak_tflops = peak_tflops
        self.flops_per_token = float(flops_per_token)
        self.prefix = prefix
        #: optional ``telemetry.prometheus.MetricRegistry``: every
        #: emitted event also lands as a gauge (last value wins), so a
        #: scrape endpoint can expose training step metrics without a
        #: second emission path
        self.registry = registry

    def events(self, step: int, wall_s: float, tokens: int = 0,
               samples: int = 0, phase_s: Optional[Dict] = None):
        p = self.prefix
        out = [(f"{p}/step_time_ms", wall_s * 1e3, step)]
        if wall_s > 0:
            if tokens:
                out.append((f"{p}/tokens_per_sec", tokens / wall_s, step))
            if samples:
                out.append((f"{p}/samples_per_sec", samples / wall_s,
                            step))
            if tokens and self.flops_per_token and \
                    self.peak_tflops is not None:
                achieved = tokens * self.flops_per_token / wall_s / 1e12
                out.append((f"{p}/mfu", achieved / self.peak_tflops,
                            step))
        for phase, dur_s in sorted((phase_s or {}).items()):
            out.append((f"{p}/time_ms/{phase}", dur_s * 1e3, step))
        return out

    def emit(self, step: int, wall_s: float, tokens: int = 0,
             samples: int = 0, phase_s: Optional[Dict] = None):
        events = self.events(step, wall_s, tokens, samples, phase_s)
        if self.registry is not None:
            from .prometheus import sanitize_name
            for label, value, _ in events:
                self.registry.set_gauge(sanitize_name(label), value,
                                        help=label)
            self.registry.set_gauge("train_last_step", float(step))
        if self.monitor is None or not getattr(self.monitor, "enabled",
                                               True):
            return
        self.monitor.write_events(events)


# ------------------------------------------------------------------ #
# offline reduction
# ------------------------------------------------------------------ #
def _args(ev):
    return ev.get("args", {}) or {}


def step_breakdown(events) -> "OrderedDict":
    """step -> {"wall_ms", "tokens", "phases": {name: total_ms}} from
    every X span carrying a ``step`` attribute, ordered by step."""
    steps: Dict[int, Dict] = {}
    for ev in events:
        if ev.get("ph") != "X" or not ev["name"].startswith("train."):
            continue                 # serving spans keep their own axis
        step = _args(ev).get("step")
        if step is None:
            continue
        row = steps.setdefault(int(step), {"wall_ms": 0.0, "tokens": 0,
                                           "phases": {}})
        dur_ms = float(ev.get("dur", 0.0)) / 1e3
        name = ev["name"]
        if name == STEP_SPAN:
            row["wall_ms"] += dur_ms
            row["tokens"] += int(_args(ev).get("tokens", 0) or 0)
        else:
            row["phases"][name] = row["phases"].get(name, 0.0) + dur_ms
            if name == "train.fwd":
                row["tokens"] += int(_args(ev).get("tokens", 0) or 0)
    out = OrderedDict()
    for step in sorted(steps):
        row = steps[step]
        if row["wall_ms"] == 0.0 and row["phases"]:
            # micro-step API path: no grouping span — the step's wall is
            # the sum of its phase spans
            row["wall_ms"] = sum(row["phases"].values())
        out[step] = row
    return out


def _end(ev):
    return ev["ts"] + ev["dur"]


def _leaf_spans(spans):
    """Of one thread's X events, those that hold no other, in time
    order."""
    leaves, stack = [], []              # stack of [event, is a leaf]
    for ev in sorted(spans, key=lambda e: (e["ts"], -e["dur"])):
        while stack and _end(stack[-1][0]) <= ev["ts"]:
            done, is_leaf = stack.pop()
            if is_leaf:
                leaves.append(done)
        if stack:
            stack[-1][1] = False
        stack.append([ev, True])
    leaves.extend(done for done, is_leaf in stack if is_leaf)
    return sorted(leaves, key=lambda e: e["ts"])


def serve_step_breakdown(events) -> "OrderedDict":
    """``sched_step`` -> ``{"wall_ms", "leaves": {name: ms},
    "device_wait_ms", "host_turn_ms", "turns"}`` per ``sched.step``
    span of the loop thread, ordered by time: the step's length, its
    time by leaf span (the spans inside it that hold no other; the wait
    for the device apart, as ``device_wait_ms``), and the host's turns
    that *end* in the step, a turn being the stretch from a
    ``serve.device_wait``'s end to the end of the next enqueue span
    (:data:`ENQUEUE_SPANS`): what the host does between two programs
    while the device has none. A put that enqueues its programs back to
    back and then waits for each opens a turn at its last wait only
    (after the others the device still has a program).
    ``benchmarks/reducers/idle_cut.py`` reads the same two edges off a
    profiler trace."""
    steps = [ev for ev in events
             if ev.get("ph") == "X" and ev["name"] == SERVE_STEP_SPAN]
    out = OrderedDict()
    if not steps:
        return out
    tid = steps[0]["tid"]
    steps = sorted((ev for ev in steps if ev["tid"] == tid),
                   key=lambda ev: ev["ts"])
    rows = []
    for ev in steps:
        rows.append({"wall_ms": ev["dur"] / 1e3, "leaves": {},
                     "device_wait_ms": 0.0, "host_turn_ms": 0.0,
                     "turns": 0})
        out[int(_args(ev).get("sched_step", len(rows)))] = rows[-1]
    k, woke = 0, None
    for ev in _leaf_spans(e for e in events
                          if e.get("ph") == "X" and e["tid"] == tid):
        while k < len(steps) and _end(steps[k]) <= ev["ts"]:
            k += 1
        row = rows[k] if k < len(steps) and steps[k]["ts"] <= ev["ts"] \
            and ev is not steps[k] else None
        name, ms = ev["name"], ev["dur"] / 1e3
        if name == DEVICE_WAIT_SPAN:
            woke = _end(ev)
            if row is not None:
                row["device_wait_ms"] += ms
            continue
        if row is not None:
            row["leaves"][name] = row["leaves"].get(name, 0.0) + ms
        if name in ENQUEUE_SPANS and woke is not None:
            if row is not None:
                row["host_turn_ms"] += (_end(ev) - woke) / 1e3
                row["turns"] += 1
            woke = None
    return out


def _median(values) -> float:
    values = list(values)
    return round(statistics.median(values), 3) if values else 0.0


def serving_step_summary(events) -> Dict:
    """The serving block of :func:`summarize`: medians over the
    ``sched.step`` spans of :func:`serve_step_breakdown` (steps that
    dispatched nothing count in ``n_steps`` only), the mean
    milliseconds a dispatching step by leaf, and a turn's median."""
    rows = list(serve_step_breakdown(events).values())
    busy = [r for r in rows if r["device_wait_ms"] > 0.0]
    turns = [r["host_turn_ms"] / r["turns"] for r in rows if r["turns"]]
    leaves: Dict[str, float] = {}
    for row in busy:
        for name, ms in row["leaves"].items():
            leaves[name] = leaves.get(name, 0.0) + ms
    n = max(len(busy), 1)
    return {
        "n_steps": len(rows),
        "dispatching_steps": len(busy),
        "step_ms_p50": _median(r["wall_ms"] for r in busy),
        "device_wait_ms_p50": _median(r["device_wait_ms"] for r in busy),
        "host_turn_ms_p50": _median(turns),
        "turns": sum(r["turns"] for r in rows),
        "leaf_ms_mean": {k: round(v / n, 4)
                         for k, v in sorted(leaves.items())},
    }


def restore_summary(events) -> Dict:
    """HCache restore attribution: counts/bytes from the engine-level
    restore spans and per-chunk staging spans, and the overlap ratio
    from the scheduler's explicit span pair (``sched.restore_issue`` /
    ``sched.decode_dispatch`` with ``overlapped_restores``)."""
    restores = sched_restores = overlapped = chunks = 0
    sequences = 0
    bytes_shipped = 0
    stage_ms = 0.0
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev["name"]
        if name == RESTORE_SPAN:
            restores += 1
            sequences += int(_args(ev).get("sequences", 0) or 0)
        elif name == RESTORE_STAGE_SPAN:
            chunks += 1
            bytes_shipped += int(_args(ev).get("bytes", 0) or 0)
            stage_ms += float(ev.get("dur", 0.0)) / 1e3
        elif name == SCHED_RESTORE_SPAN:
            sched_restores += 1
        elif name == SCHED_DISPATCH_SPAN:
            overlapped += int(_args(ev).get("overlapped_restores", 0)
                              or 0)
    total = sched_restores or restores
    return {
        "restores": restores,
        "sequences": sequences,
        "chunks_issued": chunks,
        "bytes_shipped": bytes_shipped,
        "stage_ms": round(stage_ms, 3),
        "scheduler_restores": sched_restores,
        "overlapped": overlapped,
        "overlap_ratio": (overlapped / total) if total else 0.0,
    }


def comm_summary(events) -> Dict:
    """op -> {count, bytes} from the trace-time collective instants
    (``comm.<op>`` events CommsLogger emits)."""
    out: Dict[str, Dict] = {}
    for ev in events:
        if ev.get("ph") != "i" or not ev["name"].startswith("comm."):
            continue
        op = ev["name"][len("comm."):]
        rec = out.setdefault(op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += int(_args(ev).get("bytes", 0) or 0)
    return out


def serving_summary(events) -> Dict:
    """Request-lifecycle edge counts (``sched.*`` instants)."""
    out: Dict[str, int] = {}
    for ev in events:
        if ev.get("ph") == "i" and ev["name"].startswith("sched."):
            key = ev["name"][len("sched."):]
            out[key] = out.get(key, 0) + 1
    return out


def summarize(events) -> Dict:
    """Full reduction of a span stream (tracer buffer or loaded
    trace.json events) into the per-step breakdown + attribution
    blocks the CLI table and the bench JSONL ``extra`` payloads carry."""
    steps = step_breakdown(events)
    wall_ms = sum(r["wall_ms"] for r in steps.values())
    tokens = sum(r["tokens"] for r in steps.values())
    phase_totals: Dict[str, float] = {}
    for row in steps.values():
        for name, ms in row["phases"].items():
            phase_totals[name] = phase_totals.get(name, 0.0) + ms
    return {
        "steps": {s: {"wall_ms": round(r["wall_ms"], 3),
                      "tokens": r["tokens"],
                      "phases": {k: round(v, 3)
                                 for k, v in r["phases"].items()}}
                  for s, r in steps.items()},
        "n_steps": len(steps),
        "wall_ms": round(wall_ms, 3),
        "tokens": tokens,
        "tokens_per_sec": round(tokens / (wall_ms / 1e3), 2)
        if wall_ms > 0 and tokens else 0.0,
        "phase_totals_ms": {k: round(v, 3)
                            for k, v in sorted(phase_totals.items())},
        "restore": restore_summary(events),
        "comm": comm_summary(events),
        "serving": serving_summary(events),
        "serving_steps": serving_step_summary(events),
    }


def bench_extra(events) -> Dict:
    """The compact breakdown attached to bench JSONL ``extra`` payloads
    (totals only — per-step rows would bloat a one-line artifact)."""
    s = summarize(events)
    return {
        "n_steps": s["n_steps"],
        "wall_ms": s["wall_ms"],
        "tokens_per_sec": s["tokens_per_sec"],
        "phase_totals_ms": s["phase_totals_ms"],
        "restore": s["restore"],
        "comm": s["comm"],
    }


def render_table(summary: Dict) -> str:
    """Human-readable per-step breakdown (the ``telemetry summarize``
    CLI surface)."""
    lines: List[str] = []
    steps = summary.get("steps", {})
    phases = sorted({p for r in steps.values() for p in r["phases"]})
    short = {p: p.split(".", 1)[-1] for p in phases}
    header = f"{'step':>6} {'wall_ms':>10} {'tokens':>8}" + "".join(
        f" {short[p][:14]:>14}" for p in phases)
    lines.append(header)
    lines.append("-" * len(header))
    for step, row in steps.items():
        lines.append(
            f"{step:>6} {row['wall_ms']:>10.2f} {row['tokens']:>8}"
            + "".join(f" {row['phases'].get(p, 0.0):>14.2f}"
                      for p in phases))
    lines.append("-" * len(header))
    lines.append(f"steps={summary.get('n_steps', 0)} "
                 f"wall={summary.get('wall_ms', 0.0):.2f}ms "
                 f"tokens/sec={summary.get('tokens_per_sec', 0.0):.1f}")
    rs = summary.get("restore", {})
    if rs.get("restores") or rs.get("scheduler_restores"):
        lines.append(
            f"restore: {rs['restores']} restore_kv calls, "
            f"{rs['sequences']} seqs, {rs['chunks_issued']} chunks, "
            f"{rs['bytes_shipped']} B shipped, "
            f"stage={rs['stage_ms']:.2f}ms, "
            f"overlap_ratio={rs['overlap_ratio']:.3f} "
            f"({rs['overlapped']}/{rs['scheduler_restores'] or rs['restores']})")
    comm = summary.get("comm", {})
    if comm:
        lines.append("collectives:")
        for op, rec in sorted(comm.items()):
            lines.append(f"  {op:<28} count={rec['count']:<6} "
                         f"bytes={rec['bytes']}")
    serving = summary.get("serving", {})
    if serving:
        lines.append("serving edges: " + ", ".join(
            f"{k}={v}" for k, v in sorted(serving.items())))
    ss = summary.get("serving_steps", {})
    if ss.get("n_steps"):
        lines.append(
            f"serving steps: {ss['n_steps']} ({ss['dispatching_steps']} "
            f"dispatching), step p50={ss['step_ms_p50']:.3f}ms, "
            f"device_wait p50={ss['device_wait_ms_p50']:.3f}ms, "
            f"host_turn p50={ss['host_turn_ms_p50']:.3f}ms over "
            f"{ss['turns']} turns; mean ms a dispatching step by leaf:")
        for name, ms in sorted(ss["leaf_ms_mean"].items(),
                               key=lambda kv: -kv[1]):
            lines.append(f"  {name:<28} {ms:>9.4f}")
    return "\n".join(lines)
