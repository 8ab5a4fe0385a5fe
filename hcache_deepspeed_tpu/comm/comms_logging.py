"""Communication operation logging.

Reference analog: ``deepspeed/utils/comms_logging.py`` ``CommsLogger`` fed by
``@timed_op`` wrappers on every collective (``comm/comm.py:101-134``), and
``dist.log_summary()`` (``comm/comm.py:428``).

On TPU collectives are issued inside traced/compiled programs, so per-call
host-side wall timing is meaningless; instead we record, at *trace time*, the
op type, message size and mesh axes for every collective the facade emits, and
report aggregate counts/volumes. Wall-clock attribution comes from the XLA
profiler (``platform.profiler_start``), which names each collective.
"""

import math
from collections import defaultdict

from ..utils.logging import log_dist


def convert_size(size_bytes):
    if size_bytes == 0:
        return "0B"
    units = ("B", "KB", "MB", "GB", "TB", "PB")
    i = int(math.floor(math.log(size_bytes, 1024)))
    return f"{round(size_bytes / 1024 ** i, 2)} {units[i]}"


class CommsLogger:
    def __init__(self, enabled=False, verbose=False, prof_all=True,
                 prof_ops=None, debug=False):
        self.enabled = enabled
        self.verbose = verbose
        self.prof_all = prof_all
        self.prof_ops = prof_ops or []
        self.debug = debug
        # op_name -> msg_size -> [count, total_bytes]
        self.comms_dict = defaultdict(lambda: defaultdict(lambda: [0, 0]))
        # op_name -> op kind ("collective" | "latent_handoff"): which
        # transport carried the bytes (a mesh collective, or the
        # disaggregated serving handoff of serving/disagg.py)
        self.op_kinds = {}

    def configure(self, enabled=None, verbose=None, prof_all=None,
                  prof_ops=None, debug=None):
        if enabled is not None:
            self.enabled = enabled
        if verbose is not None:
            self.verbose = verbose
        if prof_all is not None:
            self.prof_all = prof_all
        if prof_ops is not None:
            self.prof_ops = prof_ops
        if debug is not None:
            self.debug = debug

    def should_log(self, op_name):
        if not self.enabled:
            return False
        return self.prof_all or op_name in self.prof_ops

    def log_collective(self, op_name, n_bytes, axes=(),
                       op_kind="collective"):
        """Byte attribution for a collective issued OUTSIDE the comm
        facade — the explicit ZeRO reduce-scatter and all-gather bucket
        sites (``runtime/zero/zeropp.py``: ``zero_reduce_scatter``,
        ``zero_bucket_reduce_scatter``, ``zero_bucket_all_gather``).
        Before these sites logged, only the gather/all-reduce paths
        were fully attributed and ``log_summary`` under-reported the
        reduce lane's wire volume. Convention: ``n_bytes`` is the
        per-device collective INPUT buffer (the same convention the
        facade's ``reduce_scatter``/``all_gather`` wrappers use), so
        bucketed and per-leaf programs report identical totals."""
        self.op_kinds[op_name] = op_kind
        self.append(op_name, tuple(axes), int(n_bytes))

    def log_quantized(self, op_name, wire_bytes, unquantized_equiv_bytes,
                      axes=(), op_kind="collective"):
        """Byte attribution for a QUANTIZED collective: record the
        actual wire volume under ``op_name`` and the volume the same
        collective would have carried full-width under
        ``op_name + "_unquantized_equiv"``. Every quantized wire site
        (qwZ gather, qgZ all-to-all, the bucketed quantized
        reduce-scatter, Domino's int8 all-reduce) reports through this
        single convention so ``wire_savings_summary`` — and the tests
        that gate attribution — can pair them mechanically."""
        if not self.should_log(op_name):
            return
        self.op_kinds[op_name] = op_kind
        self.append(op_name, tuple(axes), int(wire_bytes))
        self.append(op_name + "_unquantized_equiv", tuple(axes),
                    int(unquantized_equiv_bytes))

    def wire_savings_summary(self):
        """Pair each quantized op with its ``_unquantized_equiv``
        record: ``{op: {"wire_bytes", "unquantized_equiv_bytes",
        "saved_bytes", "fraction", "op_kind"}}``."""
        totals = {}
        for op, by_axis in self.axis_summary().items():
            totals[op] = sum(t for _, t in by_axis.values())
        out = {}
        for op, total in sorted(totals.items()):
            if op.endswith("_unquantized_equiv"):
                continue
            equiv = totals.get(op + "_unquantized_equiv")
            if equiv is None:
                continue
            out[op] = {
                "wire_bytes": total,
                "unquantized_equiv_bytes": equiv,
                "saved_bytes": equiv - total,
                "fraction": round(total / equiv, 4) if equiv else None,
                "op_kind": self.op_kinds.get(op, "collective"),
            }
        return out

    def append(self, op_name, axes, msg_size):
        if not self.should_log(op_name):
            return
        axis_group = ",".join(axes) if axes else "world"
        key = f"{op_name}@{axis_group}"
        rec = self.comms_dict[key][msg_size]
        rec[0] += 1
        rec[1] += msg_size
        # trace-time collective record -> telemetry span stream (the
        # nvtx-range analog; lazy import keeps comm importable first)
        from ..telemetry.tracer import get_tracer
        tracer = get_tracer()
        if tracer.enabled:
            tracer.instant(f"comm.{op_name}", bytes=int(msg_size),
                           axes=axis_group)
        if self.verbose:
            log_dist(f"comm op: {key} | msg size: {convert_size(msg_size)}",
                     ranks=[0])

    def log_all(self):
        if not self.comms_dict:
            log_dist("comms logger: no collectives recorded", ranks=[0])
            return
        lines = [f"{'Comm op (axis group)':<40} {'Message size':>14} "
                 f"{'Count':>8} {'Total volume':>14}"]
        for op, sizes in sorted(self.comms_dict.items()):
            for size, (count, total) in sorted(sizes.items()):
                lines.append(f"{op:<40} {convert_size(size):>14} {count:>8} "
                             f"{convert_size(total):>14}")
        log_dist("\n".join(lines), ranks=[0])

    def axis_summary(self):
        """Per-axis-group traffic breakdown
        ``{op_name: {axis_group: (count, total_bytes)}}`` — the
        partitioned-parameter profiler analog (reference:
        ``runtime/zero/partitioned_param_profiler.py`` EventCounter
        count/numel per event): how much gather/reduce volume each mesh
        axis carries, for the monitor and for hpZ-style wire-locality
        checks."""
        out = {}
        for key, sizes in self.comms_dict.items():
            op, _, axes = key.partition("@")
            count = sum(c for c, _ in sizes.values())
            total = sum(t for _, t in sizes.values())
            out.setdefault(op, {})[axes] = (count, total)
        return out

    def monitor_events(self, step: int):
        """``(tag, value, step)`` triples for ``monitor.write_events``:
        total bytes per collective per axis group."""
        return [(f"Comms/{op}@{axes}", float(total), step)
                for op, by_axis in sorted(self.axis_summary().items())
                for axes, (_, total) in sorted(by_axis.items())]

    def summary_events(self, step: int = 0):
        """The ``log_summary`` aggregate (op → count / total bytes) as
        monitor event triples, so comm volume lands in the same sink as
        step metrics instead of only the ``log_dist`` text table."""
        out = []
        for op, by_axis in sorted(self.axis_summary().items()):
            for axes, (count, total) in sorted(by_axis.items()):
                out.append((f"CommsSummary/{op}@{axes}/count",
                            float(count), step))
                out.append((f"CommsSummary/{op}@{axes}/bytes",
                            float(total), step))
        return out

    def log_summary(self, monitor=None, step: int = 0):
        """Print the aggregate table AND, when a monitor is given,
        route it through ``MonitorMaster.write_events``."""
        self.log_all()
        if monitor is not None and getattr(monitor, "enabled", True):
            events = self.summary_events(step)
            if events:
                monitor.write_events(events)

    def reset(self):
        self.comms_dict.clear()
        self.op_kinds.clear()


_comms_logger = CommsLogger()


def get_comms_logger() -> CommsLogger:
    return _comms_logger
