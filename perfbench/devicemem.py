"""Device facts a run reports: peak memory, and programs compiled while
the window was open."""
from __future__ import annotations

import threading


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no count)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


class CompileCounter:
    """Counts programs lowered (traced to a new executable) between
    ``start`` and ``stop``; a cache hit still lowers, so this counts
    every program the window had not seen before."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring

        self._n = 0
        self._on = False
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self._on and name == self.EVENTS[0]:
            with self._lock:
                self._n += 1

    def start(self) -> None:
        self._on = True

    def stop(self) -> int:
        self._on = False
        return self._n
