"""What both runners share: the run's context, phase timing, the device
line and the profiler around a traced stretch."""

import contextlib
import os
import shutil
import threading
import time
from dataclasses import dataclass, field


@dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float              # time.monotonic() at process start
    root: str                   # the checkout
    meter: object = None        # CompileMeter
    phases: dict = field(default_factory=dict)

    @contextlib.contextmanager
    def phase(self, name):
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.phases[name] = round(
                self.phases.get(name, 0.0) + time.monotonic() - t0, 3)


def device_line(devices, chips):
    """The ``device`` object of the result: as JAX reports it, with the
    peak on the fullest of the chips used."""
    peak = 0
    for dev in devices[:chips]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def fallback_count():
    """Kernels the program replaced by their reference so far
    (``ops.fallback_report()``), all reasons together."""
    from hcache_deepspeed_tpu import ops
    return sum(n for reasons in ops.fallback_report().values()
               for n in reasons.values())


class TracedStretch:
    """``jax.profiler`` around a stretch of the window, started and
    stopped from a thread of its own so that neither call holds up the
    thread that offers the load. ``path`` is the ``.xplane.pb`` once
    ``join`` returns."""

    def __init__(self, root, name):
        self.dir = os.path.join(root, ".bench_out", "trace", name)
        self.path = None
        self.t_begin = self.t_end = None
        self._thread = None
        self.error = None

    def run(self, t_begin, t_end):
        """Trace from ``t_begin`` to ``t_end`` (``time.monotonic``)."""
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self._thread = threading.Thread(
            target=self._work, args=(t_begin, t_end),
            name="bench-profiler", daemon=True)
        self._thread.start()

    def _work(self, t_begin, t_end):
        import jax
        try:
            time.sleep(max(0.0, t_begin - time.monotonic()))
            # the program's annotations and the device, not every Python
            # call: the Python tracer slows the host it is measuring
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.t_begin = time.monotonic()
            time.sleep(max(0.0, t_end - time.monotonic()))
            self.t_end = time.monotonic()
            jax.profiler.stop_trace()
        except Exception as exc:          # noqa: BLE001 — reported by join
            self.error = exc

    def join(self, timeout=300.0):
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop in "
                               f"{timeout:g}s")
        if self.error is not None:
            raise self.error
        for folder, _, files in os.walk(self.dir):
            for fname in files:
                if fname.endswith(".xplane.pb"):
                    self.path = os.path.join(folder, fname)
        if self.path is None:
            raise RuntimeError(f"no .xplane.pb under {self.dir}")
        return self.path
