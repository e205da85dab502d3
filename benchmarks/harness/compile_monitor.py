"""Counts XLA compile requests and persistent-cache hits through
``jax.monitoring`` (copied from chip_smoke.py's CompileMonitor: it sees the
placement-specialised recompiles a trace counter cannot)."""


class CompileMonitor:
    def __init__(self):
        import jax

        self.requests = self.cache_hits = self.cache_misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.compile_s += float(secs)

    def _on_event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return (self.requests, self.cache_hits, self.cache_misses,
                self.compile_s)

    def since(self, snap):
        r, h, m, s = self.snapshot()
        return {"xla_compiles": r - snap[0], "cache_hits": h - snap[1],
                "cache_misses": m - snap[2],
                "compile_s": round(s - snap[3], 3)}
