"""Counts XLA compilations (persistent-cache loads included) from JAX's
own monitoring events. Copied from the repository's chip smoke test."""
from __future__ import annotations

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    def __init__(self):
        self.n, self.secs, self.hits = 0, 0.0, 0

        def on_duration(name, secs, **_):
            if name == COMPILE_EVENT:
                self.n += 1
                self.secs += secs

        def on_event(name, **_):
            if name == CACHE_HIT_EVENT:
                self.hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.n, self.secs, self.hits
