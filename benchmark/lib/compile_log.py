"""Backend compile seconds and persistent-cache traffic from jax's own
monitoring events. Copied from ``chip_smoke.py``'s ``CompileLog`` (PR 21),
with a time stamp on each compile so that those inside the window count."""

import time


class CompileLog:
    def __init__(self):
        import jax.monitoring as mon

        self.compiles = []  # (perf_counter at the end of the compile, fun, seconds)
        self.requests = self.hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(), kw.get("fun_name", "?"), secs))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def seconds(self) -> float:
        return sum(s for _, _, s in self.compiles)

    def inside(self, t0: float, t1: float) -> int:
        return sum(1 for t, _, _ in self.compiles if t0 <= t <= t1)

    def by_function(self, top: int = 8):
        tot = {}
        for _, name, s in self.compiles:
            tot[name] = tot.get(name, 0.0) + s
        return sorted(tot.items(), key=lambda kv: -kv[1])[:top]
