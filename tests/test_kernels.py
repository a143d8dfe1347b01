"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.match import match_first
from repro.kernels import ops
from repro.kernels.ref import simcount_ref, wildcard_match_ref


def _rand_case(rng, n, t, k, tt, star_rate=0.25):
    logs = rng.integers(2, 24, (n, t)).astype(np.int32)
    lens = rng.integers(0, t + 1, (n,)).astype(np.int32)
    for r in range(n):
        logs[r, lens[r]:] = 0
    tmpl = rng.integers(2, 24, (k, tt)).astype(np.int32)
    stars = rng.random((k, tt)) < star_rate
    tmpl[stars] = 1
    tlens = rng.integers(1, tt + 1, (k,)).astype(np.int32)
    for r in range(k):
        tmpl[r, tlens[r]:] = 0
    return logs, lens, tmpl, tlens


@pytest.mark.parametrize("n,t,k,tt", [(7, 5, 3, 4), (64, 16, 9, 8), (300, 33, 17, 12), (257, 128, 129, 64)])
def test_simcount_matches_ref(n, t, k, tt):
    rng = np.random.default_rng(n)
    logs, lens, tmpl, tlens = _rand_case(rng, n, t, k, tt)
    got = np.asarray(ops.simcount(logs, tmpl))
    want = np.asarray(simcount_ref(jnp.asarray(logs), jnp.asarray(tmpl)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,t,k,tt", [(5, 6, 2, 4), (70, 12, 10, 6), (260, 24, 20, 10)])
def test_wildcard_match_matches_ref(n, t, k, tt):
    rng = np.random.default_rng(n * 7)
    logs, lens, tmpl, tlens = _rand_case(rng, n, t, k, tt)
    # plant guaranteed matches: log = template with stars -> 1-2 tokens
    for r in range(min(n, k)):
        row = []
        for j in range(tlens[r]):
            if tmpl[r, j] == 1:
                row.extend([int(rng.integers(2, 24))] * int(rng.integers(1, 3)))
            else:
                row.append(int(tmpl[r, j]))
        row = row[:t]
        logs[r, :] = 0
        logs[r, : len(row)] = row
        lens[r] = len(row)
    got = np.asarray(ops.wildcard_match(logs, lens, tmpl, tlens))
    want = np.asarray(
        wildcard_match_ref(jnp.asarray(logs), jnp.asarray(lens), jnp.asarray(tmpl), jnp.asarray(tlens))
    )
    np.testing.assert_array_equal(got, want)
    assert got.any(), "planted matches must register"


def _plant_matches(rng, logs, lens, tmpl, tlens):
    """Make line r an instance of template r (stars -> 1-2 tokens)."""
    n, t = logs.shape
    for r in range(min(n, len(tlens))):
        row = []
        for j in range(tlens[r]):
            if tmpl[r, j] == 1:
                row.extend([int(rng.integers(2, 24))] * int(rng.integers(1, 3)))
            else:
                row.append(int(tmpl[r, j]))
        row = row[:t]
        logs[r, :] = 0
        logs[r, : len(row)] = row
        lens[r] = len(row)


# multi-line records: the 256 and 512 token buckets (BK=8 tiles at 512)
@pytest.mark.parametrize("n,t,k,tt", [(130, 256, 20, 40), (70, 512, 11, 48), (20, 512, 3, 300)])
def test_wildcard_match_wide_matches_ref(n, t, k, tt):
    rng = np.random.default_rng(n * 11 + t)
    logs, lens, tmpl, tlens = _rand_case(rng, n, t, k, tt, star_rate=0.1)
    _plant_matches(rng, logs, lens, tmpl, tlens)
    got = np.asarray(ops.wildcard_match(logs, lens, tmpl, tlens))
    want = np.asarray(
        wildcard_match_ref(jnp.asarray(logs), jnp.asarray(lens), jnp.asarray(tmpl), jnp.asarray(tlens))
    )
    np.testing.assert_array_equal(got, want)
    assert got.any(), "planted matches must register"


@settings(max_examples=60, deadline=None)
@given(st.integers(1, 40), st.integers(1, 20), st.integers(1, 12), st.integers(1, 10), st.integers(0, 2**31 - 1))
def test_wildcard_match_property(n, t, k, tt, seed):
    rng = np.random.default_rng(seed)
    logs, lens, tmpl, tlens = _rand_case(rng, n, t, k, tt, star_rate=0.4)
    got = np.asarray(ops.wildcard_match(logs, lens, tmpl, tlens))
    want = np.asarray(
        wildcard_match_ref(jnp.asarray(logs), jnp.asarray(lens), jnp.asarray(tmpl), jnp.asarray(tlens))
    )
    np.testing.assert_array_equal(got, want)


def test_kernel_agrees_with_core_matcher():
    rng = np.random.default_rng(3)
    logs, lens, tmpl, tlens = _rand_case(rng, 120, 16, 7, 8)
    templates = [tmpl[i, : tlens[i]].copy() for i in range(len(tlens))]
    a_np = match_first(logs, lens, templates, use_kernel=False)
    a_k = match_first(logs, lens, templates, use_kernel=True)
    np.testing.assert_array_equal(a_np, a_k)


def test_pack_templates_empty():
    m, l = ops.pack_templates([])
    assert m.shape[0] == 0 and l.shape == (0,)


# -------- restructured-kernel parity on shapes off the tile boundaries --------

# wildcard_match tiles are (BN=256, BK=8); simcount (BN=128, BK=32) with
# T padded to 32 lanes — every case here straddles at least one boundary.
ODD_SHAPES = [(257, 33, 9, 6), (255, 31, 7, 5), (300, 128, 129, 64),
              (513, 17, 41, 12), (1, 1, 1, 1)]


@pytest.mark.parametrize("n,t,k,tt", ODD_SHAPES)
def test_simcount_odd_shapes(n, t, k, tt):
    rng = np.random.default_rng(n * 13 + tt)
    logs, lens, tmpl, tlens = _rand_case(rng, n, t, k, tt)
    got = np.asarray(ops.simcount(logs, tmpl))
    want = np.asarray(simcount_ref(jnp.asarray(logs), jnp.asarray(tmpl)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,t,k,tt", ODD_SHAPES)
def test_wildcard_match_odd_shapes(n, t, k, tt):
    rng = np.random.default_rng(n * 31 + tt)
    logs, lens, tmpl, tlens = _rand_case(rng, n, t, k, tt, star_rate=0.35)
    got = np.asarray(ops.wildcard_match(logs, lens, tmpl, tlens))
    want = np.asarray(
        wildcard_match_ref(jnp.asarray(logs), jnp.asarray(lens), jnp.asarray(tmpl), jnp.asarray(tlens))
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,t,k,tt", ODD_SHAPES + [(8, 128, 16, 16), (3, 512, 2, 300)])
def test_wildcard_match_numpy_twin_matches_ref(n, t, k, tt):
    """The numpy twin, the last tier of the ``wildcard_match`` dispatch
    chain, gives the kernel's oracle bits."""
    rng = np.random.default_rng(n * 17 + tt)
    logs, lens, tmpl, tlens = _rand_case(rng, n, t, k, tt, star_rate=0.35)
    _plant_matches(rng, logs, lens, tmpl, tlens)
    got = np.asarray(ops._wildcard_match_np(logs, lens, tmpl, tlens))
    want = np.asarray(
        wildcard_match_ref(jnp.asarray(logs), jnp.asarray(lens), jnp.asarray(tmpl), jnp.asarray(tlens))
    ).astype(bool)
    np.testing.assert_array_equal(got, want)


def test_pack_templates_overlength_sentinel():
    """A template longer than t_max is marked t_len = -1 and must match
    nothing — in the kernel AND in the oracle (host/kernel parity)."""
    tpls = [np.array([2, 3, 4, 5, 6], np.int32), np.array([2, 1], np.int32)]
    mat, lens = ops.pack_templates(tpls, t_max=3)
    assert lens.tolist() == [-1, 2]
    assert mat.shape == (2, 3)
    rng = np.random.default_rng(5)
    logs, llens, _, _ = _rand_case(rng, 70, 8, 1, 1)
    got = np.asarray(ops.wildcard_match(logs, llens, mat, lens))
    want = np.asarray(
        wildcard_match_ref(jnp.asarray(logs), jnp.asarray(llens), jnp.asarray(mat), jnp.asarray(lens))
    )
    np.testing.assert_array_equal(got, want)
    assert not got[:, 0].any(), "over-length template must match nothing"


def test_pack_templates_exact_fit_keeps_length():
    mat, lens = ops.pack_templates([np.array([2, 3, 4], np.int32)], t_max=3)
    assert lens.tolist() == [3]


def test_bucketed_kernel_path_matches_numpy():
    """The kernel path's dense launch gives the same assignment as the
    first-token bucketed numpy path, including star-first and empty
    templates."""
    rng = np.random.default_rng(11)
    logs, lens, tmpl, tlens = _rand_case(rng, 600, 12, 11, 6, star_rate=0.4)
    templates = [tmpl[i, : tlens[i]].copy() for i in range(len(tlens))]
    templates.append(np.zeros((0,), np.int32))  # empty template: matches nothing
    templates.append(np.array([1, 1], np.int32))  # star-first
    a_np = match_first(logs, lens, templates, use_kernel=False)
    a_k = match_first(logs, lens, templates, use_kernel=True)
    np.testing.assert_array_equal(a_np, a_k)


def test_match_first_dedup_rows_identical():
    """Row-dedup inside match_first must not change any assignment."""
    rng = np.random.default_rng(17)
    logs, lens, tmpl, tlens = _rand_case(rng, 200, 10, 5, 5)
    logs = np.tile(logs, (4, 1))[: 700]
    lens = np.tile(lens, 4)[: 700]
    templates = [tmpl[i, : tlens[i]].copy() for i in range(len(tlens))]
    a_dd = match_first(logs, lens, templates, dedup=True)
    a_no = match_first(logs, lens, templates, dedup=False)
    np.testing.assert_array_equal(a_dd, a_no)


# -------- one dense launch per token bucket (ops.match_first_dense) --------

def _templates_of(tmpl, tlens):
    return [tmpl[i, : tlens[i]].copy() for i in range(len(tlens))]


def _dense_planted(rng):
    logs, lens, tmpl, tlens = _rand_case(rng, 300, 12, 20, 6, star_rate=0.3)
    _plant_matches(rng, logs, lens, tmpl, tlens)
    return logs, lens, _templates_of(tmpl, tlens)


def _dense_empty_templates(rng):
    logs, lens, tpls = _dense_planted(rng)
    lens[:10] = 0  # an empty template must not take zero-length rows
    logs[:10] = 0
    empty = np.zeros((0,), np.int32)
    return logs, lens, [empty] + tpls[:7] + [empty] + tpls[7:]


def _dense_star_first(rng):
    logs, lens, tmpl, tlens = _rand_case(rng, 200, 10, 12, 5, star_rate=0.2)
    tmpl[:, 0] = 1  # every template starts with '*'
    _plant_matches(rng, logs, lens, tmpl, tlens)
    return logs, lens, _templates_of(tmpl, tlens) + [np.array([1], np.int32)]


def _dense_overlong_rows(rng):
    logs, lens, tpls = _dense_planted(rng)
    lens[::3] = logs.shape[1] + 2  # truncated at the width: match nothing
    return logs, lens, tpls + [np.array([1, 1], np.int32)]


def _dense_zero_length_rows(rng):
    logs, lens, tpls = _dense_planted(rng)
    logs[::2] = 0
    lens[::2] = 0
    return logs, lens, tpls + [np.array([1], np.int32)]


def _dense_shared_first_token(rng):
    # eight templates open with token 5; a row may match several of them
    logs, lens, tmpl, tlens = _rand_case(rng, 250, 10, 8, 5, star_rate=0.4)
    tmpl[:, 0] = 5
    tlens = np.maximum(tlens, 2)
    _plant_matches(rng, logs, lens, tmpl, tlens)
    tpls = _templates_of(tmpl, tlens) + [np.array([5, 1], np.int32)]
    return logs, lens, tpls


def _dense_crosses_template_bucket(rng):
    # 150 templates: past the launch's 128-template floor
    logs, lens, tmpl, tlens = _rand_case(rng, 300, 12, 150, 6, star_rate=0.0)
    tmpl[130:, 1] = np.where(tlens[130:] > 1, 1, tmpl[130:, 1])
    _plant_matches(rng, logs, lens, tmpl[130:], tlens[130:])  # rows 0-19
    return logs, lens, _templates_of(tmpl, tlens)


@pytest.mark.parametrize("make", [
    _dense_planted, _dense_empty_templates, _dense_star_first, _dense_overlong_rows,
    _dense_zero_length_rows, _dense_shared_first_token, _dense_crosses_template_bucket,
], ids=lambda f: f.__name__.removeprefix("_dense_"))
def test_match_first_kernel_path_equals_numpy(make):
    """One dense launch per token bucket assigns exactly what the numpy
    ``_match_first`` (first-token bucketed, template by template) does."""
    logs, lens, tpls = make(np.random.default_rng(23))
    want = match_first(logs, lens, tpls, use_kernel=False, dedup=False)
    got = match_first(logs, lens, tpls, use_kernel=True, dedup=False)
    np.testing.assert_array_equal(got, want)
    assert (want >= 0).any() and (want < 0).any()
    empty = [k for k, tp in enumerate(tpls) if len(tp) == 0]
    assert not np.isin(got, empty).any()
    assert (got[lens > logs.shape[1]] < 0).all()
    if make is _dense_crosses_template_bucket:
        assert (got >= 128).any()


def test_match_first_kernel_path_one_launch_per_width_group(monkeypatch):
    """Many first tokens, two token buckets: one ``wildcard_match`` launch
    per width group, and the numpy twin never runs."""
    from repro.core.match import width_groups
    from repro.kernels import jitcache

    rng = np.random.default_rng(29)
    n, width = 240, 256
    lens = np.where(np.arange(n) % 2, rng.integers(2, 40, n),
                    rng.integers(129, width, n)).astype(np.int32)
    ids = rng.integers(2, 60, (n, width)).astype(np.int32)
    ids[np.arange(width)[None, :] >= lens[:, None]] = 0
    tpls = [np.array([f, 1], np.int32) for f in range(2, 42)]  # 40 first tokens
    tpls.append(np.array([1, 7, 1], np.int32))
    host = []
    twin = ops._wildcard_match_np
    monkeypatch.setattr(ops, "_wildcard_match_np", lambda *a: host.append(1) or twin(*a))
    before = jitcache.CALL_COUNTS["wildcard_match"]
    got = match_first(ids, lens, tpls, use_kernel=True)
    launches = jitcache.CALL_COUNTS["wildcard_match"] - before
    assert len(np.unique(ids[:, 0])) > 40
    assert launches == len(width_groups(lens, width)) == 2
    assert host == []
    np.testing.assert_array_equal(got, match_first(ids, lens, tpls, use_kernel=False))


def test_match_first_kernel_path_cuts_large_calls_into_row_blocks():
    """A call of more than ``ops.MAX_LINES`` distinct lines (a batch
    ``pack``'s whole file) makes one launch per ``MAX_LINES`` lines, so
    the dense result stays bounded, and assigns what the numpy
    ``_match_first`` does, in the last block too."""
    from repro.core.match import _match_first
    from repro.kernels import jitcache

    rng = np.random.default_rng(37)
    n = ops.MAX_LINES + 300
    logs, lens, tmpl, tlens = _rand_case(rng, n, 12, 20, 6, star_rate=0.3)
    _plant_matches(rng, logs, lens, tmpl, tlens)
    logs[-20:], lens[-20:] = logs[:20], lens[:20]  # planted rows in the last block
    tpls = _templates_of(tmpl, tlens)
    before = jitcache.CALL_COUNTS["wildcard_match"]
    got = _match_first(logs, lens, tpls, True, False)
    assert jitcache.CALL_COUNTS["wildcard_match"] - before == 2
    want = _match_first(logs, lens, tpls, False, False)
    np.testing.assert_array_equal(got, want)
    assert (got[-20:] >= 0).any()


def test_stream_sessions_reuse_wildcard_match_shapes(monkeypatch):
    """A second stream session launches ``wildcard_match`` at no padded
    shape the first did not (the launch's floors absorb the drift of
    distinct lines and templates from chunk to chunk and seed to seed),
    so a warm-up session covers the window. No DP runs in the kernel:
    ``_dispatch`` answers each launch of the padded shape with the host
    matcher's lowest-id match, so the template store grows as it would."""
    import io

    from repro.core.codec import LogzipConfig
    from repro.core.ise import ISEConfig
    from repro.core.match import _match_first
    from repro.core.stream import StreamingCompressor
    from repro.data.loggen import generate_lines
    from repro.kernels import jitcache

    real = ops._dispatch

    def host_match(op, *args, **kw):
        if op != "wildcard_match":
            return real(op, *args, **kw)
        logs, lens, tmpl, t_lens = (np.asarray(a) for a in args)
        first = _match_first(logs, lens, [tp[:max(n, 0)] for tp, n in zip(tmpl, t_lens)],
                             False, True)
        out = np.zeros((len(logs), len(tmpl)), np.int8)
        out[np.flatnonzero(first >= 0), first[first >= 0]] = 1
        return out

    monkeypatch.setattr(ops, "_dispatch", host_match)
    cfg = LogzipConfig(level=3, ise=ISEConfig(use_kernel=True))

    def session_shapes(seed):
        before = dict(jitcache.BUCKET_SHAPES)
        with StreamingCompressor(io.BytesIO(), cfg, chunk_lines=600, pipeline=False) as sc:
            sc.feed(list(generate_lines("Spark", 2400, seed=seed)))
        return {key for key, c in jitcache.BUCKET_SHAPES.items()
                if key[0] == "wildcard_match" and c > before.get(key, 0)}

    first = session_shapes(31)
    assert first
    assert session_shapes(32) <= first


# -------- the platform picks the path; a TPU never demotes a kernel --------

@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_dispatch_demotes_only_off_tpu(monkeypatch, platform):
    def refused(*a):
        raise NotImplementedError("lowering refused")

    monkeypatch.setattr(ops, "platform", lambda: platform)
    monkeypatch.setitem(ops._CHAINS, "wildcard_match",
                        (("kernel", refused), ("ref", lambda *a: "ref ran")))
    ops.reset_backend_state()
    try:
        if platform == "tpu":
            with pytest.raises(NotImplementedError, match="lowering refused"):
                ops._dispatch("wildcard_match", 1)
            rep = ops.backend_report()["wildcard_match"]
            assert rep == {"backend": "kernel", "interpret": False, "fallbacks": []}
        else:
            assert ops._dispatch("wildcard_match", 1) == "ref ran"
            rep = ops.backend_report()["wildcard_match"]
            assert rep["backend"] == "ref" and rep["interpret"]
            assert rep["fallbacks"][0]["error"].startswith("NotImplementedError")
    finally:
        ops.reset_backend_state()


@pytest.mark.parametrize("platform,kernel_path", [("cpu", False), ("tpu", True)])
def test_default_use_kernel_follows_platform(monkeypatch, platform, kernel_path):
    from repro.core import coltypes

    calls = []
    monkeypatch.setattr(ops, "platform", lambda: platform)
    monkeypatch.setattr(ops, "match_first_dense",
                        lambda ids, lens, tpls: calls.append("match") or
                        np.zeros(len(ids), np.int32))
    monkeypatch.setattr(ops, "delta_zigzag",
                        lambda v, ln, m: calls.append("delta") or
                        np.zeros(v.shape, np.uint32))
    rng = np.random.default_rng(2)
    logs, lens, tmpl, tlens = _rand_case(rng, 20, 6, 3, 4)
    match_first(logs, lens, [tmpl[i, : tlens[i]] for i in range(3)],
                use_kernel=None, dedup=False)
    coltypes._transformed_stream(list(range(5, 5 + coltypes.KERNEL_MIN_VALUES)),
                                 coltypes.MONOTONE_INT, None)
    # a column shorter than the kernel's narrowest bucket stays on the host
    coltypes._transformed_stream([5, 7, 9], coltypes.MONOTONE_INT, None)
    assert calls == (["match", "delta"] if kernel_path else [])
