"""jax's own compile events, to split set-up into compiling and the rest and
to prove that nothing compiled inside the measured window.  (Copied from
``chip_smoke.py``'s ``CompileLog``, the sound piece the repo already had.)"""

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Every XLA compile request (seconds; a persistent-cache hit is counted
    too, at its retrieval time) and every persistent-cache hit."""

    def __init__(self):
        import jax

        self.seconds = []
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.seconds.append(duration)

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.hits += 1

    def mark(self):
        return len(self.seconds), self.hits

    def since(self, mark=(0, 0)):
        n, hits = mark
        return {
            "compile_requests": len(self.seconds) - n,
            "compile_s": sum(self.seconds[n:]),
            "cache_hits": self.hits - hits,
        }
