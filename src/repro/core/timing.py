"""Per-stage wall-time accounting for the compression and query paths.

``StageTimer`` is the one entry point: ``compress(..., stage_times=dict)``
or ``QueryStats(stage_times=dict)`` threads one through the work, each
stage wraps itself in ``with tm("name"):``, and the sink — a dict of
inclusive seconds by name — is read by ``benchmarks/throughput.py``
(into ``BENCH_compress.json``) and by the chip benchmark's ``SpanSink``
(``benchmarks/chip/harness.py``), which keeps each update as a span
ending at the moment of the update.

An open span sits on a stack of the calling thread, with its sink, so:

- code far below the stage (``kernels/ops.py``) opens ``span(name)``
  into the sink of the innermost open span without being handed it;
- an XLA compile on that thread adds its seconds to
  ``<innermost span>.compile``, written when the compile ends;
- each span is also a ``jax.profiler.TraceAnnotation``, so a profile
  shows it on the host plane, with its thread, on the profiler's clock.

Every update of a sink is atomic against the other threads writing it
(the ``lzjs-pack`` worker and the feeding thread share one). With
``sink=None`` every context is the shared no-op: nothing is pushed, no
listener registered, and ``span`` costs one thread-local lookup.
"""

from __future__ import annotations

import functools
import threading
from contextlib import contextmanager, nullcontext
from time import perf_counter

# the XLA compile event JAX reports, on the compiling thread (jax.monitoring)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_NOOP = nullcontext()
_LOCAL = threading.local()   # .stack: [(span name, sink), ...], innermost last
_LOCK = threading.Lock()     # guards every sink update and the registration
_annotation = None           # jax.profiler.TraceAnnotation, once a sink is active


def _add(sink: dict, name: str, seconds: float) -> None:
    with _LOCK:
        sink[name] = sink.get(name, 0.0) + seconds


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event != COMPILE_EVENT:
        return
    stack = getattr(_LOCAL, "stack", None)
    if stack:
        name, sink = stack[-1]
        _add(sink, name + ".compile", duration)


def _activate() -> None:
    """Register the compile listener, once per process (so the first
    active sink imports jax)."""
    global _annotation
    if _annotation is not None:
        return
    import jax

    with _LOCK:
        if _annotation is None:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _annotation = jax.profiler.TraceAnnotation


@contextmanager
def _timed(name: str, sink: dict):
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    stack.append((name, sink))
    t0 = perf_counter()
    try:
        with _annotation(name):
            yield
    finally:
        dt = perf_counter() - t0
        stack.pop()
        _add(sink, name, dt)


class StageTimer:
    """Accumulates per-stage wall seconds into ``sink`` (None = disabled)."""

    def __init__(self, sink: dict | None):
        self.sink = sink
        if sink is not None:
            _activate()

    def __call__(self, name: str):
        if self.sink is None:
            return _NOOP
        return _timed(name, self.sink)


def span(name: str):
    """A span in the sink of the innermost open ``StageTimer`` span on
    this thread; the shared no-op where none is open."""
    stack = getattr(_LOCAL, "stack", None)
    if not stack:
        return _NOOP
    return _timed(name, stack[-1][1])


def spanned(name: str):
    """Decorator: the whole call runs in ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return call
    return wrap
