"""Set-up facts the harness records itself (copied from ``chip_smoke.py``,
which stays the program's own smoke test): compile seconds and
persistent-cache hits from ``jax.monitoring``, and the device as JAX
reports it."""

from __future__ import annotations

import jax


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileMeter:
    """Seconds spent in backend compilation (or in loading from the
    persistent cache), how many programs that was, and persistent-cache
    hits.  ``snapshot()`` lets the harness prove that the measured
    window compiled nothing."""

    def __init__(self) -> None:
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float]:
        return self.compiles, self.compile_s

    def __enter__(self) -> "CompileMeter":
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
