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
    """The twin ``match_first_bucketed`` runs for a first-token bucket of
    at most ``HOST_MAX_LINES`` lines gives the kernel's oracle bits."""
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
    """First-token bucketing in the kernel path: same assignment as the
    (bucketed) numpy path, including star-first and empty templates."""
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
    monkeypatch.setattr(ops, "match_first_bucketed",
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
