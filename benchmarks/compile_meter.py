"""Programs built and fetched, from ``jax.monitoring`` (after
``chip_smoke.py CompileMeter``, PR 21)."""

import time


class CompileMeter:
    """Counts backend compilations (a persistent-cache hit also passes
    through ``backend_compile_duration``: it is the fetch) and cache
    hits since the last ``take()``."""

    def __init__(self):
        import jax
        self.programs = self.hits = 0
        self.seconds = 0.0
        self.stamps = []        # time.monotonic() of every program
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs
            self.stamps.append(time.monotonic())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def between(self, t_from, t_to):
        """Programs built or fetched in ``[t_from, t_to)``."""
        return sum(1 for t in self.stamps if t_from <= t < t_to)

    def take(self):
        out = {"programs": self.programs, "cache_hits": self.hits,
               "seconds": round(self.seconds, 3)}
        self.programs = self.hits = 0
        self.seconds = 0.0
        return out
