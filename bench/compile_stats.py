"""Count JAX compiles and persistent-cache hits, from ``jax.monitoring``.

Copied from ``chip_smoke._compile_stats`` so that the yardstick does not
depend on a script the program may change. The harness reads the counts
before and after the measured window: a compile inside it is a fault of
the benchmark's warm-up.
"""
from __future__ import annotations

import time

#: compiles at least this long are logged as they finish
LOG_COMPILE_S = 5.0


def compile_stats(t_start: float) -> dict:
    """Accumulate JAX's compile-time and persistent-cache events; log each
    compile of at least ``LOG_COMPILE_S`` as it finishes."""
    import jax

    stats = {"compile_s": 0.0, "compiles": 0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, duration, fun_name="?", **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            stats["compile_s"] += duration
            stats["compiles"] += 1
            if duration >= LOG_COMPILE_S:
                print(f"    [{time.perf_counter() - t_start:7.1f}s] compiled "
                      f"{fun_name} in {duration:.1f}s", flush=True)

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            stats["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            stats["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return stats
