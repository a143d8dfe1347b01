"""Bucketed jit cache + trace accounting (ISSUE 3): shapes that drift
within a bucket must NOT re-trace, ``wildcard_match_sharded`` must build
its shard_map'd callable once, and a 20-chunk kernel-path streaming
session must be recompile-free after warmup."""

import io
from contextlib import contextmanager

import jax
import numpy as np
import pytest

from repro.core.timing import COMPILE_EVENT
from repro.kernels import jitcache, ops


@contextmanager
def _backend_compiles():
    """-> list that collects one entry per XLA backend compile in the block."""
    seen: list[float] = []

    def on_duration(event, duration, **_kw):
        if event == COMPILE_EVENT:
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _case(rng, n, t, k, tt):
    logs = rng.integers(2, 10, (n, t)).astype(np.int32)
    lens = rng.integers(0, t + 1, n).astype(np.int32)
    for r in range(n):
        logs[r, lens[r]:] = 0
    tmpl = rng.integers(2, 10, (k, tt)).astype(np.int32)
    tlens = rng.integers(1, tt + 1, (k,)).astype(np.int32)
    for r in range(k):
        tmpl[r, tlens[r]:] = 0
    return logs, lens, tmpl, tlens


def test_bucketed_wildcard_match_equals_unbucketed():
    rng = np.random.default_rng(1)
    for n, t, k, tt in [(10, 5, 3, 4), (300, 17, 9, 6), (257, 12, 5, 5)]:
        logs, lens, tmpl, tlens = _case(rng, n, t, k, tt)
        a = np.asarray(ops.wildcard_match(logs, lens, tmpl, tlens, use_buckets=True))
        b = np.asarray(ops.wildcard_match(logs, lens, tmpl, tlens, use_buckets=False))
        np.testing.assert_array_equal(a, b)


def test_bucketed_overlength_lines_do_not_match():
    # padded width would otherwise let stars absorb PAD columns
    logs = np.array([[2, 1, 0]], np.int32)          # width 3
    lens = np.array([5], np.int32)                   # true length exceeds width
    tmpl = np.array([[2, 1]], np.int32)
    tlens = np.array([2], np.int32)
    out = np.asarray(ops.wildcard_match(logs, lens, tmpl, tlens, use_buckets=True))
    assert not out.any()


def test_wildcard_match_trace_count_stable_within_bucket():
    jitcache.reset_counters()
    rng = np.random.default_rng(2)
    base = jitcache.TRACE_COUNTS["wildcard_match"]
    # drifting shapes, same buckets: floors are (N 1024, T 32, K 128, Tt 32)
    for n, t, k, tt in [(100, 7, 3, 4), (180, 8, 5, 5), (256, 6, 8, 3), (31, 5, 2, 2)]:
        logs, lens, tmpl, tlens = _case(rng, n, t, k, tt)
        ops.wildcard_match(logs, lens, tmpl, tlens)
    assert jitcache.TRACE_COUNTS["wildcard_match"] - base <= 1


def test_match_extract_trace_count_stable_within_bucket():
    rng = np.random.default_rng(3)
    before = None
    for n, t in [(40, 7), (64, 8), (17, 5)]:
        logs, lens, tmpl, tlens = _case(rng, n, t, 3, 4)
        tpls = [tmpl[i, : tlens[i]] for i in range(len(tlens))]
        # equal star counts across calls -> same n_slots -> same executable
        tpls = [np.concatenate([tp, [1]]).astype(np.int32) for tp in tpls]
        ops.match_extract(logs, lens, tpls)
        if before is None:
            before = jitcache.TRACE_COUNTS["match_extract"]
    assert jitcache.TRACE_COUNTS["match_extract"] == before, "re-traced within bucket"


# each call helper returns the wrapper's results as a tuple of arrays

def _wildcard_call(rng, n, k, use_buckets):
    logs, lens, tmpl, tlens = _case(rng, n, 6, k, 4)
    return (ops.wildcard_match(logs, lens, tmpl, tlens, use_buckets=use_buckets),)


def _colcodec_call(rng, r, width, use_buckets):
    vals = rng.integers(-500, 500, (r, width)).astype(np.int32)
    lens = rng.integers(0, width + 1, r).astype(np.int32)
    mode = rng.integers(1, 4, r).astype(np.int32)
    return (ops.delta_zigzag(vals, lens, mode, use_buckets=use_buckets),)


def _match_extract_call(rng, n, k, use_buckets):
    logs, lens, tmpl, tlens = _case(rng, n, 6, k, 3)
    # one trailing star each: equal n_slots across calls
    tpls = [np.concatenate([tmpl[i, : tlens[i]], [1]]).astype(np.int32)
            for i in range(k)]
    return ops.match_extract(logs, lens, tpls, use_buckets=use_buckets)


@pytest.mark.parametrize("call, shapes", [
    # every shape of a case shares one bucket: wildcard_match (N 1024, K 128),
    # delta_zigzag (R 8, C 128), match_extract (N 64, K 16)
    (_wildcard_call, [(100, 3), (37, 9), (250, 16), (5, 1)]),
    (_colcodec_call, [(1, 40), (3, 97), (8, 128), (2, 5)]),
    (_match_extract_call, [(40, 3), (17, 5), (64, 2), (3, 1)]),
], ids=["wildcard_match", "delta_zigzag", "match_extract"])
def test_unpadded_shapes_within_bucket_compile_nothing(call, shapes):
    """A new unpadded shape inside a warm bucket costs no XLA compile:
    the wrappers trim the padded result on the host, never with an eager
    device slice (one ``dynamic_slice`` module per unpadded shape)."""
    rng = np.random.default_rng(5)
    call(rng, *shapes[0], True)                  # warm the bucket
    for shape in shapes[1:]:
        seed = int(rng.integers(1 << 30))
        with _backend_compiles() as compiles:
            got = call(np.random.default_rng(seed), *shape, True)
        assert compiles == [], (shape, len(compiles))
        want = call(np.random.default_rng(seed), *shape, False)
        for g, w in zip(got, want, strict=True):
            assert g.shape == w.shape
            np.testing.assert_array_equal(g, w)


def test_tokenizer_trace_count_stable_across_batch_sizes():
    # pack_lines buckets the ROW axis on the host: drifting batch sizes
    # must hit one compiled tokenizer executable per (rows, width) bucket
    ops.device_tokenize(["warm up, one two"])
    base = jitcache.TRACE_COUNTS["tokenize_hash"]
    for n in (100, 101, 173, 256):
        ops.device_tokenize([f"line {i} blk_{i}," for i in range(n)])
    assert jitcache.TRACE_COUNTS["tokenize_hash"] == base, "re-traced within bucket"


def test_sharded_matcher_traces_once():
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    rng = np.random.default_rng(4)
    logs, lens, tmpl, tlens = _case(rng, 32, 6, 3, 4)
    ops.wildcard_match_sharded(logs, lens, tmpl, tlens, mesh)
    base = jitcache.TRACE_COUNTS["wildcard_match_sharded"]
    for _ in range(3):  # identical shapes: the cached callable must not re-trace
        ops.wildcard_match_sharded(logs, lens, tmpl, tlens, mesh)
    assert jitcache.TRACE_COUNTS["wildcard_match_sharded"] == base
    assert base >= 1


def test_streaming_session_zero_recompiles_after_warmup():
    """20-chunk kernel-path session: zero re-traces and zero XLA backend
    compiles after the warmup chunks."""
    from repro.core.codec import LogzipConfig
    from repro.core.ise import ISEConfig
    from repro.core.stream import LZJSReader, StreamingCompressor
    from repro.data.loggen import generate_lines

    lines = list(generate_lines("HDFS", 4000, seed=13))
    cfg = LogzipConfig(
        level=3, format="<Date> <Time> <Pid> <Level> <Component>: <Content>",
        ise=ISEConfig(min_sample=120, max_iters=2, use_kernel=True))
    buf = io.BytesIO()
    traces_after_warmup = None
    compiles_per_chunk = []
    with StreamingCompressor(buf, cfg, chunk_lines=200, pipeline=False) as sc:
        for k in range(20):
            with _backend_compiles() as compiles:
                sc.feed(lines[k * 200:(k + 1) * 200])
                sc.flush_chunk()
            compiles_per_chunk.append(len(compiles))
            if k == 1:  # warmup = first two chunks (store still growing)
                traces_after_warmup = dict(jitcache.TRACE_COUNTS)
    assert dict(jitcache.TRACE_COUNTS) == traces_after_warmup, (
        "kernel re-traced after warmup", traces_after_warmup,
        dict(jitcache.TRACE_COUNTS))
    assert sum(compiles_per_chunk[2:]) == 0, (
        "XLA compiled after warmup", compiles_per_chunk)
    assert LZJSReader(io.BytesIO(buf.getvalue())).read_all() == lines


def test_compile_cache_dir_env_or_fixed_checkout_path(monkeypatch):
    from pathlib import Path

    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert jitcache.compile_cache_dir() == "/some/dir"
    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        # JAX's own reading of the variable wins: no other dir is set
        assert jitcache.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == \
            jitcache.CACHE_MIN_COMPILE_SECS
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = Path(__file__).resolve().parents[1]
    assert jitcache.compile_cache_dir() == str(checkout / ".jax_cache")
