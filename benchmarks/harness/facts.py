"""What a run prints and counts besides its metrics: JSON fact lines, XLA
compile counts (jax.monitoring), peak device memory. CompileCounter and
peak_bytes are chip_smoke.py's, copied."""

from __future__ import annotations

import json
import time

T_PROCESS_START = time.perf_counter()


def emit(**facts) -> None:
    """One JSON fact line; `at_s` is seconds since the process started."""
    print(json.dumps(dict(facts, at_s=round(
        time.perf_counter() - T_PROCESS_START, 3))), flush=True)


class CompileCounter:
    """This process's XLA compiles. `requests`: every program handed to the
    backend, a persistent-cache hit included, a program jit still holds in
    memory not. `hits`: read back from the persistent cache. `writes`: JAX's
    "cache_misses" event, which fires when a program is WRITTEN to the cache,
    and JAX writes only compiles of a second or more."""

    def __init__(self):
        import jax.monitoring

        self.requests = self.hits = self.writes = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def _on_duration(self, event: str, _secs: float, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def facts(self) -> dict:
        return {"compile_requests": self.requests,
                "compile_cache_hits": self.hits,
                "compile_cache_writes": self.writes}


def peak_bytes(devices) -> list:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]
