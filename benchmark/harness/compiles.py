"""Counts what JAX compiles, from its own monitoring events.

With the persistent cache on, every program JAX needs is one
``compile_requests_use_cache`` event, and one ``cache_hits`` event if the
cache served it. Requests minus hits were compiled by this process.
"""

from __future__ import annotations

import collections

_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_HITS = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Listens from its creation on; JAX offers no way to stop listening,
    so a process makes one."""

    def __init__(self):
        import jax
        self._events: collections.Counter = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda name, **kw: self._events.update([name]))

    def snapshot(self) -> dict:
        return {"requests": self._events[_REQUESTS],
                "hits": self._events[_HITS]}


def between(before: dict, after: dict) -> dict:
    requests = after["requests"] - before["requests"]
    hits = after["hits"] - before["hits"]
    return {"requests": requests, "hits": hits, "compiled": requests - hits}
