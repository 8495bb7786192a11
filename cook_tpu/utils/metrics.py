"""Metric reporters: periodic snapshot publishers (reporter.clj:32-82).

The registry itself is `cook_tpu.obs.metrics.Registry`; `registry` here
is its process-wide instance, re-exported for legacy imports. The
reporters (console / JSONL file / Graphite — the JMX/Graphite/Riemann
role) publish any registry's `snapshot()`. Stdlib only.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Optional

from cook_tpu.obs.metrics import Registry, registry  # noqa: F401


class Reporter:
    """Periodic snapshot publisher (reporter.clj:32-82)."""

    def __init__(self, reg: Registry, interval_s: float = 60.0):
        self.registry = reg
        self.interval_s = interval_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def publish(self, snapshot: dict) -> None:
        raise NotImplementedError

    def start(self) -> "Reporter":
        def loop():
            while not self._stop.wait(self.interval_s):
                try:
                    self.publish(self.registry.snapshot())
                except Exception:
                    pass
        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()


class ConsoleReporter(Reporter):
    def publish(self, snapshot: dict) -> None:
        print(json.dumps({"ts": time.time(), "metrics": snapshot},
                         default=str))


class JsonlReporter(Reporter):
    """Append snapshots to a JSONL file."""

    def __init__(self, reg: Registry, path: str,
                 interval_s: float = 60.0):
        super().__init__(reg, interval_s)
        self.path = path

    def publish(self, snapshot: dict) -> None:
        with open(self.path, "a") as f:
            f.write(json.dumps({"ts": time.time(),
                                "metrics": snapshot}) + "\n")


class GraphiteReporter(Reporter):
    """Push snapshots over the Graphite plaintext protocol
    (`<prefix>.<name> <value> <unix-ts>\\n` per metric; the
    {:kind :graphite} sink of reporter.clj:44-59). One connection per
    flush; errors are swallowed by the Reporter loop and retried next
    interval."""

    def __init__(self, reg: Registry, host: str, port: int = 2003,
                 prefix: str = "cook", interval_s: float = 60.0):
        super().__init__(reg, interval_s)
        self.host, self.port, self.prefix = host, port, prefix

    @staticmethod
    def _flatten(prefix: str, val, out: list) -> None:
        if isinstance(val, dict):
            for k, v in val.items():
                if k == "type":      # metric-kind tag, not a value
                    continue
                # collapse {"value": v} so counters/gauges publish under
                # their own name, graphite-style
                sub = prefix if k == "value" else f"{prefix}.{k}"
                GraphiteReporter._flatten(sub, v, out)
        elif isinstance(val, (int, float)) and not isinstance(val, bool):
            out.append((prefix, float(val)))

    def publish(self, snapshot: dict) -> None:
        import socket

        lines: list = []
        ts = int(time.time())
        self._flatten(self.prefix, snapshot, lines)
        payload = "".join(
            f"{name.replace(' ', '_')} {value} {ts}\n"
            for name, value in lines)
        with socket.create_connection((self.host, self.port),
                                      timeout=5) as sock:
            sock.sendall(payload.encode())
