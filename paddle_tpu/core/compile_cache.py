"""JAX's persistent compilation cache, at a place that can be chosen
from outside the program.

Every run on a fresh machine compiles the whole train step and every
serving program cold unless the compiled programs sit where the next
run finds them. The directory is part of the cache's key, so it must
never move: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
itself and this module sets no directory; where it is not, the cache
lives at one fixed path inside the checkout (``.jax_cache/``, listed in
``.gitignore``). No temporary name, process id or time ever names it.

Entry points (`chip_smoke.py`, `bench.py`) call `enable()` before their
first compile; the library never turns the cache on by itself.
"""
from __future__ import annotations

import os

__all__ = ["checkout_path", "enable", "CompileCounts"]

_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_HITS = "/jax/compilation_cache/cache_hits"


def checkout_path(*parts):
    """A path under the root of this checkout (the directory that holds
    the ``paddle_tpu`` package) — where run-time outputs that must not
    land in ``/tmp`` go (each is listed in ``.gitignore``)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, *parts)


class CompileCounts:
    """Counts, from JAX's own monitoring events, how many programs this
    process asked the compiler for and how many of those the persistent
    cache answered. `compiled` is the difference: what was built."""

    def __init__(self):
        self.requests = 0
        self.cache_hits = 0

    def _on_event(self, event, **_):
        if event == _REQUESTS:
            self.requests += 1
        elif event == _HITS:
            self.cache_hits += 1

    @property
    def compiled(self):
        return self.requests - self.cache_hits

    def as_dict(self):
        return {"requests": self.requests, "compiled": self.compiled,
                "read_from_cache": self.cache_hits}


def enable():
    """Turn the persistent compilation cache on for this process and
    return a `CompileCounts` that is already listening. Call before the
    first compile. Every program is cached, however quick to build: a
    serving pool is a dozen small programs, not one large one."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          checkout_path(".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counts = CompileCounts()
    jax.monitoring.register_event_listener(counts._on_event)
    return counts
