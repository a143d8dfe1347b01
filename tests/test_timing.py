"""The stage tracer (``repro.core.timing``): nested spans, the shared
no-op, atomic sink updates across threads, compile spans on the
compiling thread, and the kernel wrappers' dispatch spans."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import timing
from repro.core.timing import StageTimer
from repro.kernels import ops


def test_nested_spans_write_their_keys():
    sink: dict = {}
    tm = StageTimer(sink)
    with tm("stage"):
        time.sleep(0.01)
        with tm("stage.part"):
            time.sleep(0.01)
        with timing.span("dispatch.x"):
            time.sleep(0.01)
            with timing.span("dispatch.x.inner"):
                pass
    assert set(sink) == {"stage", "stage.part", "dispatch.x", "dispatch.x.inner"}
    # the stage's sum stays its own inclusive wall time
    assert sink["stage"] >= 0.03
    assert sink["stage"] >= sink["stage.part"] + sink["dispatch.x"]
    assert sink["dispatch.x"] >= sink["dispatch.x.inner"]
    assert not getattr(timing._LOCAL, "stack", [])


def test_stage_sums_keep_their_meaning(monkeypatch):
    """A stage with a dispatch span inside reads what it read without."""
    now = [0.0]
    monkeypatch.setattr(timing, "perf_counter", lambda: now[0])

    def run(inner):
        sink: dict = {}
        tm = StageTimer(sink)
        for _ in range(3):
            with tm("columns"):
                now[0] += 1.0
                with inner():
                    now[0] += 2.0
        return sink

    assert run(lambda: StageTimer(None)("nothing")) == {"columns": 9.0}
    assert run(lambda: timing.span("dispatch.colcodec_transform")) == {
        "columns": 9.0, "dispatch.colcodec_transform": 6.0}


def test_pipeline_adds_dispatch_keys_and_keeps_the_archive(hdfs_lines):
    from repro.core.codec import LogzipConfig, compress
    from repro.core.ise import ISEConfig
    from repro.data.loggen import DATASETS

    def cfg(use_kernel):
        return LogzipConfig(level=3, format=DATASETS["HDFS"]["format"],
                            ise=ISEConfig(min_sample=200, max_iters=2, use_kernel=use_kernel))

    lines = hdfs_lines[:400]
    host: dict = {}
    kernel: dict = {}
    blob = compress(lines, cfg(False), stage_times=host)
    assert compress(lines, cfg(True), stage_times=kernel) == blob
    assert compress(lines, cfg(True)) == blob
    assert not {k for k in host if k.startswith("dispatch.") or k.endswith(".compile")}
    new = set(kernel) - set(host)
    assert {"dispatch.wildcard_match", "dispatch.colcodec_transform"} <= new
    assert all(k.startswith("dispatch.") or k.endswith(".compile") for k in new)
    assert kernel["ise.match"] >= kernel["dispatch.wildcard_match"]
    assert kernel["columns"] >= kernel["dispatch.colcodec_transform"]


def test_no_sink_records_nothing_and_registers_nothing(monkeypatch):
    registered = []
    monkeypatch.setattr(timing, "_annotation", None)
    monkeypatch.setattr(jax.monitoring, "register_event_duration_secs_listener",
                        registered.append)
    tm = StageTimer(None)
    with tm("stage"):
        assert timing.span("dispatch.x") is tm("other") is timing._NOOP
        ops.delta_zigzag(np.arange(8, dtype=np.int32).reshape(2, 4),
                         np.array([4, 3]), np.array([1, 2]))
    assert tm.sink is None
    assert not getattr(timing._LOCAL, "stack", [])
    assert registered == [] and timing._annotation is None
    # the first active sink registers the one listener
    StageTimer({})
    StageTimer({})
    assert registered == [timing._on_duration]


class _SlowSink(dict):
    """A sink whose read yields the GIL before the write, as a sink that
    does work of its own in an update can."""

    def get(self, key, default=None):
        value = super().get(key, default)
        time.sleep(1e-5)
        return value


@pytest.mark.parametrize("n_threads,n_spans", [(2, 400), (4, 200), (8, 100)])
def test_threads_sharing_a_sink_lose_no_update(monkeypatch, n_threads, n_spans):
    clock = threading.local()

    def tick():  # every span lasts exactly 1.0 on its own thread's clock
        clock.t = getattr(clock, "t", 0.0) + 0.5
        return float(int(clock.t))

    monkeypatch.setattr(timing, "perf_counter", tick)
    sink = _SlowSink()
    start = threading.Barrier(n_threads)

    def work():
        tm = StageTimer(sink)
        start.wait()
        for _ in range(n_spans):
            with tm("pack"):
                pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sink["pack"] == n_threads * n_spans


def test_compile_lands_under_the_open_span_on_its_thread(monkeypatch):
    writes = []
    add = timing._add

    def spy(sink, name, seconds):
        writes.append((name, threading.get_ident()))
        add(sink, name, seconds)

    monkeypatch.setattr(timing, "_add", spy)
    sink: dict = {}
    seen = {}

    def work():
        seen["ident"] = threading.get_ident()
        with StageTimer(sink)("ise.match"):
            fresh = jax.jit(lambda x: x * 3 + 7)  # a new function: a new compile
            fresh(jnp.arange(11)).block_until_ready()

    t = threading.Thread(target=work)
    t.start()
    t.join()
    assert sink["ise.match.compile"] > 0
    assert sink["ise.match"] >= sink["ise.match.compile"]
    assert ("ise.match.compile", seen["ident"]) in writes
    # a compile on a thread with no span open records nothing
    n = len(writes)
    jax.jit(lambda x: x - 5)(jnp.arange(13)).block_until_ready()
    assert len(writes) == n


def test_kernel_wrappers_write_dispatch_spans():
    sink: dict = {}
    tm = StageTimer(sink)
    with tm("columns"):
        out = ops.delta_zigzag(np.arange(12, dtype=np.int32).reshape(3, 4),
                               np.array([4, 3, 2]), np.array([1, 2, 3]))
    with tm("ise.match"):
        m = ops.wildcard_match(np.array([[5, 6, 7], [5, 9, 9]], np.int32), np.array([3, 3]),
                               np.array([[5, 1, 7]], np.int32), np.array([3]))
    assert out.shape == (3, 4)
    assert m[:, 0].tolist() == [True, False]
    assert {"dispatch.colcodec_transform", "dispatch.wildcard_match"} <= set(sink)
    assert sink["columns"] >= sink["dispatch.colcodec_transform"]
    assert sink["ise.match"] >= sink["dispatch.wildcard_match"]
    # a compile inside a wrapper is the wrapper's
    assert not {k for k in sink if k.endswith(".compile")} - {
        "dispatch.colcodec_transform.compile", "dispatch.wildcard_match.compile"}
