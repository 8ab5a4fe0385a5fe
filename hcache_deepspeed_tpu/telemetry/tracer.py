"""Structured span tracer.

Reference analog: the reference scatters attribution across
``wall_clock_breakdown`` timers (``utils/timer.py``), ``CommsLogger``
text tables and nvtx ranges (``utils/nvtx.py``) — three sinks that never
meet. Here one thread-safe ring buffer collects *spans* (named, timed,
attributed intervals), instant events, counters and async
(request-lifetime) intervals from every subsystem, and
``telemetry.export`` renders them as one Chrome/Perfetto
``trace_event`` timeline.

Design constraints:

* **~zero cost when disabled** — ``tracer.span(...)`` is one attribute
  check returning a shared no-op context manager; nothing allocates.
* **thread-safe** — the serving frontend traces from its worker thread
  while the monitor thread reads; the buffer is a ``deque`` (atomic
  appends) and snapshots copy under a lock.
* **bounded** — a ring buffer (``capacity`` events) so an always-on
  tracer in a long serving process cannot grow without bound.
* **device alignment** — on TPU each host span additionally opens the
  platform's XLA profiler trace annotation
  (``platform/tpu.py`` ``annotate``) with the attributes the span was
  opened with, so host spans line up with device traces captured via
  ``profiler_start`` and carry ``sched_step``, ``lanes``, ``bytes``
  there (attributes added later with ``set()`` stay in the ring buffer
  only); on CPU spans stand alone and the whole layer is tier-1
  testable.
* **read-only** — nothing in the program reads the buffer to decide
  anything: a traced run executes the same schedule as an untraced one.

Spans are recorded at *exit* time (when the duration is known); the
exporter sorts by start timestamp, so nesting never breaks per-thread
monotonicity.
"""

import functools
import os
import threading
import time
from collections import deque


class _NullSpan:
    """Shared no-op context manager returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NULL_SPAN = _NullSpan()


class _Span:
    """Live span context. ``set(**attrs)`` attaches attributes that are
    only known mid-span (e.g. bytes moved)."""

    __slots__ = ("_tracer", "name", "args", "_start", "_ann")

    def __init__(self, tracer, name, args):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._start = 0.0
        self._ann = None

    def set(self, **attrs):
        self.args.update(attrs)
        return self

    def __enter__(self):
        ann = self._tracer._annotation(self.name, self.args)
        if ann is not None:
            self._ann = ann
            ann.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._record("X", self.name, self._start, self.args,
                             dur=end - self._start)
        return False


class Tracer:
    def __init__(self, capacity: int = 65536):
        self.enabled = False
        self._capacity = capacity
        self._events = deque(maxlen=capacity)
        #: events silently displaced by the ring buffer since the last
        #: ``clear()`` — surfaced by the CLI/exporter/assembler so a
        #: trace with holes is never mistaken for a complete one
        self.dropped = 0
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self._tids = {}          # thread ident -> (small tid, name)
        self._pid = None         # resolved lazily (jax process index)
        # None = auto (open XLA annotations iff platform is TPU);
        # True/False force. Resolved to an annotate fn on first span.
        self._xla = None
        self._annotate_fn = 0    # 0 = unresolved, None = off

    # -------------------------------------------------------------- #
    # configuration
    # -------------------------------------------------------------- #
    def configure(self, enabled=None, capacity=None, xla=None):
        with self._lock:
            if capacity is not None and capacity != self._capacity:
                self._capacity = capacity
                self._events = deque(self._events, maxlen=capacity)
            if xla is not None:
                self._xla = bool(xla)
                self._annotate_fn = 0
            if enabled is not None:
                self.enabled = bool(enabled)
        return self

    def clear(self):
        with self._lock:
            self._events.clear()
            self.dropped = 0
            self._t0 = time.perf_counter()

    def now_us(self) -> float:
        """Current position on this tracer's timeline (µs since its
        ``_t0``). Each process's tracer has its own origin, so this is
        the anchor the cross-process clock-offset handshake exchanges:
        the parent stamps its ``now_us`` on a telemetry harvest
        request, the worker replies with its own, and the assembler
        shifts the worker's stream onto the parent timeline
        (``assemble.assemble_process_fleet_trace``)."""
        return (time.perf_counter() - self._t0) * 1e6

    # -------------------------------------------------------------- #
    # internals
    # -------------------------------------------------------------- #
    def _annotation(self, name, attrs):
        fn = self._annotate_fn
        if fn == 0:
            fn = self._resolve_annotate()
        return fn(name, **attrs) if fn is not None else None

    def _resolve_annotate(self):
        fn = None
        try:
            from ..platform import get_platform
            platform = get_platform()
            if self._xla or (self._xla is None and platform.name == "tpu"):
                fn = platform.annotate
        except Exception:
            fn = None
        # ``configure(xla=...)`` writes _annotate_fn under the lock;
        # resolving from a span on another thread must too, or a
        # concurrent reconfigure can be clobbered by a stale resolve
        with self._lock:
            self._annotate_fn = fn
        return fn

    def _tid(self):
        ident = threading.get_ident()
        entry = self._tids.get(ident)
        if entry is None:
            with self._lock:
                entry = self._tids.setdefault(
                    ident, (len(self._tids),
                            threading.current_thread().name))
        return entry[0]

    def _process_index(self):
        if self._pid is None:
            try:
                import jax
                self._pid = jax.process_index()
            except Exception:
                self._pid = int(os.environ.get("RANK", 0))
        return self._pid

    def _record(self, ph, name, t_abs, args, dur=None, **extra):
        ev = {
            "ph": ph,
            "name": name,
            "ts": (t_abs - self._t0) * 1e6,      # trace_event µs
            "pid": self._process_index(),
            "tid": self._tid(),
        }
        if dur is not None:
            ev["dur"] = dur * 1e6
        if args:
            ev["args"] = args
        ev.update(extra)
        # the lock-free hot path is the design; readers copy under
        # the lock (module docstring)
        if len(self._events) == self._events.maxlen:
            # the append below displaces the oldest event; count it —
            # a benign-race += is acceptable for a diagnostics counter
            # (GIL keeps it approximately exact, never negative)
            # hds: allow(HDS-L001) diagnostics counter, see above
            self.dropped += 1
        # hds: allow(HDS-L001) deque.append is atomic under the GIL
        self._events.append(ev)

    # -------------------------------------------------------------- #
    # recording API
    # -------------------------------------------------------------- #
    def span(self, name, **attrs):
        """Context manager timing a host interval. ~Zero-cost when the
        tracer is disabled (one attribute check, shared null object)."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def instant(self, name, **attrs):
        """Zero-duration marker (trace_event ``i``, thread scope)."""
        if not self.enabled:
            return
        self._record("i", name, time.perf_counter(), attrs, s="t")

    def counter(self, name, value, **attrs):
        """Time-series sample rendered as a counter track."""
        if not self.enabled:
            return
        args = {"value": float(value)}
        args.update(attrs)
        self._record("C", name, time.perf_counter(), args)

    def async_begin(self, name, aid, cat="req", **attrs):
        """Open an async interval (lives across threads/steps; paired by
        ``(cat, id, name)`` — the request-lifecycle primitive)."""
        if not self.enabled:
            return
        self._record("b", name, time.perf_counter(), attrs,
                     cat=cat, id=str(aid))

    def async_end(self, name, aid, cat="req", **attrs):
        if not self.enabled:
            return
        self._record("e", name, time.perf_counter(), attrs,
                     cat=cat, id=str(aid))

    # -------------------------------------------------------------- #
    # reading
    # -------------------------------------------------------------- #
    def events(self):
        """Snapshot (copy) of the buffered events, oldest first."""
        with self._lock:
            return list(self._events)

    def drain(self):
        """Snapshot and clear."""
        with self._lock:
            out = list(self._events)
            self._events.clear()
            return out

    def thread_names(self):
        """{tid: thread name} for the exporter's metadata events."""
        with self._lock:
            return {tid: name for tid, name in self._tids.values()}

    @property
    def buffered(self) -> int:
        """Events currently in the ring buffer (O(1), lock-free)."""
        return len(self._events)

    def export(self, path):
        """Write the current buffer as a Perfetto-loadable trace.
        A non-zero drop count rides into the trace as metadata and is
        warned about — an overflowed buffer is an incomplete trace."""
        from .export import write_trace
        return write_trace(self.events(), path,
                           thread_names=self.thread_names(),
                           pid=self._process_index(),
                           dropped=self.dropped)


_tracer = Tracer()
if os.environ.get("HDS_TRACE", "") not in ("", "0"):
    _tracer.enabled = True


def get_tracer() -> Tracer:
    return _tracer


def traced(name):
    """Decorator: every call of the function is one ``name`` span (an
    entry point whose whole body is the interval)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with _tracer.span(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco
