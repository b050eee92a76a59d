"""JAX's own compile and persistent-cache events (``jax.monitoring``).

Copied from ``chip_smoke.py::CompileLog`` (PR 21), which stays the smoke
test's; the benchmark keeps its own so that a later change to the program
cannot move the yardstick.  A window of the run can say how many programs
it compiled, how long that took without the cache reads, and how many
requests the cache answered.
"""

_COMPILE = "/jax/core/compile/backend_compile_duration"
_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileLog:
    def __init__(self, jax):
        self.events = []        # (name, seconds or None)
        jax.monitoring.register_event_listener(
            lambda name, **kw: self.events.append((name, None)))
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: self.events.append((name, secs)))

    def mark(self):
        return len(self.events)

    def between(self, start, end=None):
        return summarize(self.events[start:end])


def summarize(events):
    """Counts and seconds over a list of ``(name, seconds or None)``."""
    def secs(name):
        return [s for n, s in events if n == name]

    compiles = secs(_COMPILE)
    retrieval = sum(secs(_RETRIEVAL))
    requests = len(secs(_REQUEST))
    hits = len(secs(_HIT))
    return {
        "programs": len(compiles),
        # a backend-compile event encloses the cache lookup, hit or miss
        "compile_s": sum(compiles) - retrieval,
        "cache_retrieval_s": retrieval,
        "cache_requests": requests,
        "cache_hits": hits,
    }
