"""Backend compiles, from JAX's own monitoring events.

A load from the persistent compilation cache reports the compile it
replaced, so the compiles that overlap the measured window are the
programs that were not ready when the window started (it should be 0).
"""
from __future__ import annotations

import threading
import time


class CompileMeter:
    def __init__(self):
        import jax
        self.secs = 0.0
        self.n = 0
        self.hits = 0
        self.slow: list = []      # (function, seconds) of compiles >= 1 s
        self.spans: list = []     # (start, end) perf_counter s per compile
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, secs, fun_name="?", **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            end = time.perf_counter()
            with self._lock:
                self.secs += secs
                self.n += 1
                self.spans.append((end - secs, end))
                if secs >= 1.0:
                    self.slow.append((fun_name, round(secs, 3)))

    def _ev(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1

    def snap(self) -> tuple:
        with self._lock:
            return (self.secs, self.n, self.hits)

    def overlapping(self, t0: float, t1: float) -> int:
        """Compiles that ran at some time in [t0, t1), also one that
        started there and ended after it."""
        with self._lock:
            return sum(1 for a, b in self.spans if a < t1 and b > t0)
