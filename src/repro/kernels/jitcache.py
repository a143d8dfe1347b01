"""Shape-bucketing + trace accounting for the logzip kernels
(DESIGN.md §10.3), and the persistent compile cache the entry points
turn on.

``jax.jit`` caches compiled executables by input *shape* — streaming
chunks with drifting widths would re-trace (and on hardware recompile)
every call. The fix is static shape buckets: every dynamic dimension is
padded up to the next power of two (with a floor), so a 20-chunk session
lands on a handful of executables and chunks 2..N reuse them verbatim.

``record_trace`` runs inside the traced functions (Python side effects
execute at trace time only), so ``TRACE_COUNTS`` is exactly the number
of re-traces/compiles — the throughput benchmark exports it and
``tests/test_jitcache.py`` pins it down.
"""

from __future__ import annotations

import os
from collections import Counter
from pathlib import Path

TRACE_COUNTS: Counter = Counter()
CALL_COUNTS: Counter = Counter()
BUCKET_SHAPES: Counter = Counter()


def record_trace(name: str) -> None:
    """Call from inside a jitted function: counts one (re)trace."""
    TRACE_COUNTS[name] += 1


def record_call(name: str, shape: tuple) -> None:
    CALL_COUNTS[name] += 1
    BUCKET_SHAPES[(name,) + shape] += 1


def bucket(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor)."""
    b = max(int(n), int(floor), 1)
    return 1 << (b - 1).bit_length()


def bucket_stats() -> dict:
    """Snapshot for benchmarks: calls / traces per kernel plus the
    distinct padded shapes each kernel saw (>= traces; the gap is cache
    reuse across sessions)."""
    shapes: dict[str, dict[str, int]] = {}
    for key, c in BUCKET_SHAPES.items():
        name, shape = key[0], key[1:]
        shapes.setdefault(name, {})[str(tuple(shape))] = c
    return {
        "calls": dict(CALL_COUNTS),
        "traces": dict(TRACE_COUNTS),
        "bucket_shapes": shapes,
    }


def reset_counters() -> None:
    TRACE_COUNTS.clear()
    CALL_COUNTS.clear()
    BUCKET_SHAPES.clear()


# ------------------------------------------------ persistent compile cache

# the kernels compile in about a second on a TPU, just under JAX's
# default threshold (1 s) for writing an executable to the cache
CACHE_MIN_COMPILE_SECS = 0.1


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` at the
    root of this checkout (a fixed path: the directory is part of every
    cache key, so a moving one never hits)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    Called by the entry points (``launch/compress.py``, ``chip_smoke.py``),
    never at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has
    already read it and no other directory is set here."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      CACHE_MIN_COMPILE_SECS)
    return path
