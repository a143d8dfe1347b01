"""Fused match+extract and byte-tokenizer Pallas kernels vs their
references (ISSUE 3 satellites): random token grids including
all-wildcard / zero-length / over-length-template edges, and the device
tokenizer's exact ``reassemble`` round trip on delimiter-heavy lines."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.tokenizer import Vocab, reassemble, tokenize
from repro.kernels import ops
from repro.kernels.match_extract import match_extract as me_kernel
from repro.kernels.tokenize import hash_powers, tokenize_hash

DELIMS = " \t,;:="


def _case(rng, n, t, k, tt, star_rate=0.4):
    logs = rng.integers(2, 16, (n, t)).astype(np.int32)
    lens = rng.integers(0, t + 2, n).astype(np.int32)  # incl. over-length lines
    for r in range(n):
        logs[r, min(int(lens[r]), t):] = 0
    tpls = []
    for _ in range(k):
        m = int(rng.integers(0, tt + 1))
        tp = rng.integers(2, 16, m).astype(np.int32)
        tp[rng.random(m) < star_rate] = 1
        tpls.append(tp)
    return logs, lens, tpls


def _check(logs, lens, tpls):
    a_dev, sp_dev = ops.match_extract(logs, lens, tpls)
    tmpl, tlens = ops.pack_templates(tpls)
    a_ref, sp_ref = ops.match_extract_ref(logs, lens, tmpl, tlens, sp_dev.shape[1])
    np.testing.assert_array_equal(a_dev, a_ref)
    m = a_dev >= 0
    np.testing.assert_array_equal(sp_dev[m], sp_ref[m])
    return a_dev


@pytest.mark.parametrize("n,t,k,tt", [(7, 5, 3, 4), (64, 9, 6, 6), (130, 12, 5, 8), (1, 1, 1, 1)])
def test_match_extract_kernel_matches_ref(n, t, k, tt):
    rng = np.random.default_rng(n * 11 + tt)
    logs, lens, tpls = _case(rng, n, t, k, tt)
    # plant guaranteed matches so the span path is exercised
    for r in range(0, n, 3):
        tp = tpls[r % k]
        row = []
        for tok in tp:
            if tok == 1:
                row.extend(rng.integers(2, 16, int(rng.integers(1, 3))).tolist())
            else:
                row.append(int(tok))
        row = row[:t]
        logs[r, :] = 0
        logs[r, : len(row)] = row
        lens[r] = len(row)
    a = _check(logs, lens, tpls)
    assert (a >= 0).any(), "planted matches must register"


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 24), st.integers(1, 8), st.integers(0, 4),
       st.integers(1, 6), st.integers(0, 2**31 - 1))
def test_match_extract_kernel_property(n, t, k, tt, seed):
    rng = np.random.default_rng(seed)
    logs, lens, tpls = _case(rng, n, t, k, tt, star_rate=0.5)
    _check(logs, lens, tpls)


def test_match_extract_kernel_edges():
    logs = np.array([[2, 3, 4, 0], [5, 0, 0, 0], [0, 0, 0, 0]], np.int32)
    lens = np.array([3, 1, 0], np.int32)
    tpls = [np.zeros(0, np.int32),              # zero-length template
            np.array([1, 1, 1], np.int32),      # all-wildcard
            np.array([1], np.int32)]
    a = _check(logs, lens, tpls)
    assert a.tolist() == [1, 2, 0]               # lowest-id wins; empty matches len==0


def test_match_extract_overlength_template_sentinel():
    rng = np.random.default_rng(5)
    logs, lens, _ = _case(rng, 40, 6, 1, 1)
    tmpl, tlens = ops.pack_templates([np.array([2, 3, 4, 5, 6], np.int32)], t_max=3)
    assert tlens.tolist() == [-1]
    a, _sp = me_kernel(jnp.asarray(logs), jnp.asarray(lens), jnp.asarray(tmpl),
                       jnp.asarray(tlens), n_slots=1, interpret=ops.interpret())
    assert (np.asarray(a) == -1).all(), "over-length sentinel must match nothing"


def test_match_extract_agrees_with_match_first():
    rng = np.random.default_rng(9)
    logs, lens, tpls = _case(rng, 200, 10, 6, 6)
    from repro.core.match import extract_spans, match_first

    a_dev, sp_dev = ops.match_extract(logs, lens, tpls)
    a_host = match_first(logs, lens, tpls, use_kernel=False)
    np.testing.assert_array_equal(a_dev, a_host)
    for g in set(a_host[a_host >= 0].tolist()):
        rows = np.flatnonzero(a_host == g)
        sp = extract_spans(logs[rows], lens[rows], tpls[g])
        np.testing.assert_array_equal(sp_dev[rows, : sp.shape[1]], sp)


# ------------------------------------------------------- device tokenizer

DELIM_HEAVY = [
    "", " ", ",,,;;;===", "a b,c;;x==1:  y", " lead", "trail ",
    "=a=b=c=", "::::", "x\ty\tz", "a" * 90 + ",b", "one", "* a *",
]


def test_device_tokenizer_roundtrips_reassemble():
    for line, (toks, delims) in zip(DELIM_HEAVY, ops.device_tokenize(DELIM_HEAVY)):
        assert reassemble(toks, delims) == line
        rt, rd = tokenize(line)
        assert toks == rt and delims == rd


@settings(max_examples=40, deadline=None)
@given(st.lists(st.text(alphabet=" ,;:=abXY\t", max_size=20), min_size=1, max_size=8))
def test_device_tokenizer_property(lines):
    for line, (toks, delims) in zip(lines, ops.device_tokenize(lines)):
        assert reassemble(toks, delims) == line


def test_tokenize_hash_kernel_matches_ref():
    lines = DELIM_HEAVY + ["blk_%d x" % i for i in range(300)]
    blocks, blens, _ = ops.pack_lines(lines)
    pws = hash_powers(blocks.shape[1])
    delims = tuple(ord(c) for c in DELIMS)
    got = tokenize_hash(jnp.asarray(blocks), jnp.asarray(blens),
                        jnp.asarray(pws[0][0]), jnp.asarray(pws[1][0]), delims=delims,
                        interpret=ops.interpret())
    want = ops.tokenize_hash_ref(blocks, blens, pws[0][0], pws[1][0], delims)
    for g, w, name in zip(got, want, ["mask", "starts", "pref1", "pref2"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)


def test_device_encode_batch_matches_vocab():
    contents = DELIM_HEAVY + ["a b c", "* star", "blk_1 blk_2 blk_1"]
    v1, v2 = Vocab(), Vocab()
    ids_h, lens_h = v1.encode_batch([tokenize(c)[0] for c in contents], 16, tight=True)
    ids_d, lens_d = ops.device_encode_batch(contents, v2, 16)
    np.testing.assert_array_equal(ids_h, ids_d)
    np.testing.assert_array_equal(lens_h, lens_d)
    assert v1._to_str == v2._to_str
