"""Bucketed jit cache + trace accounting (ISSUE 3): shapes that drift
within a bucket must NOT re-trace, ``wildcard_match_sharded`` must build
its shard_map'd callable once, and a 20-chunk kernel-path streaming
session must be recompile-free after warmup."""

import io

import numpy as np

from repro.kernels import jitcache, ops


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
    # drifting shapes, same buckets: floors are (N 256, T 32, K 16, Tt 16)
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
    """ISSUE 3 acceptance: 20-chunk kernel-path session, zero re-traces
    after the warmup chunks."""
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
    with StreamingCompressor(buf, cfg, chunk_lines=200, pipeline=False) as sc:
        for k in range(20):
            sc.feed(lines[k * 200:(k + 1) * 200])
            sc.flush_chunk()
            if k == 1:  # warmup = first two chunks (store still growing)
                traces_after_warmup = dict(jitcache.TRACE_COUNTS)
    assert dict(jitcache.TRACE_COUNTS) == traces_after_warmup, (
        "kernel re-traced after warmup", traces_after_warmup,
        dict(jitcache.TRACE_COUNTS))
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
