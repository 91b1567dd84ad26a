"""XLA compiles of this process, from JAX's monitoring events.

A copy of ``chip_smoke.CompileClock``: how many backend compiles were
requested, the seconds they took, and the seconds the persistent cache
saved, and how many programs were loaded from that cache. The harness reads
it around set-up and around the window, where neither count should move.
"""
from __future__ import annotations


class CompileClock:
    def __init__(self) -> None:
        import jax.monitoring

        self.count, self.secs, self.saved, self.loads = 0, 0.0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.secs += secs
        elif name == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved += secs
            self.loads += 1

    def reading(self) -> dict:
        return {"compiles": self.count, "compile_s": self.secs, "saved_s": self.saved,
                "cache_loads": self.loads}
