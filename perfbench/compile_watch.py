"""Compile requests and persistent-cache traffic counted through
jax.monitoring (copied from chip_smoke.CompileWatch): "nothing compiles
inside the window" and "the second run finds every program in the cache"
are read off these, not guessed from wall time."""

from __future__ import annotations


class CompileWatch:
    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


_WATCH: CompileWatch | None = None


def compile_watch() -> CompileWatch:
    """The process's one watch: listeners cannot be unregistered."""
    global _WATCH
    if _WATCH is None:
        _WATCH = CompileWatch()
    return _WATCH
