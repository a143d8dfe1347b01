"""Device-resident hot path (ISSUE 3): batched tokenizer grid, fast
header parse, fused anchor match+extract — each property-tested against
its scalar / DP reference, plus archive byte-identity across the
serial-vs-pipelined container writers."""

import io

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.encode import ColumnCodec, ParamDict, factorize, split_subfields, esc
from repro.core.match import (
    extract_spans,
    extract_spans_dp,
    match_extract_one,
    match_first,
    match_one_template,
    match_one_template_dp,
)
from repro.core.tokenizer import (
    DEFAULT_DELIMITERS,
    LOG_FORMATS,
    TokenGrid,
    Vocab,
    reassemble,
    tokenize,
    tokenize_batch,
    _tokenize_batch_reference,
)

# ---------------------------------------------------------- tokenize grid

TRICKY = [
    "", " ", "   ", "a", "a b,c;;x==1:  y", "blk_123 , end=",
    "* star * x", "\\esc\x02ape\r", "café =:= naïve", "a" * 200 + " tail",
    "=,;: =", "lead  ", "  trail", "\t\ttabs\tx", "solo",
]


def _grids_equal(g1: TokenGrid, g2: TokenGrid, n: int) -> bool:
    if not (np.array_equal(g1.ids, g2.ids) and np.array_equal(g1.lens, g2.lens)):
        return False
    for u in range(n):
        w = min(int(g1.lens[u]), g1.ids.shape[1])  # clipped rows: compare kept cols
        if ([g1.delim_table[i] for i in g1.delim_ids[u, :w + 1]]
                != [g2.delim_table[i] for i in g2.delim_ids[u, :w + 1]]):
            return False
    return True


def test_tokenize_batch_matches_scalar_reference():
    v1, v2 = Vocab(), Vocab()
    g = tokenize_batch(TRICKY, v1, 64)
    r = _tokenize_batch_reference(TRICKY, v2, 64, delimiters=" \t,;:=", tight=True)
    assert v1._to_str == v2._to_str, "vocab id assignment diverged"
    assert _grids_equal(g, r, len(TRICKY))
    # token round trip: tokens+delims reassemble each content exactly
    for u, c in enumerate(TRICKY):
        t = int(g.lens[u])
        toks = [v1.token(int(g.ids[u, j])) for j in range(min(t, g.ids.shape[1]))]
        if t <= g.ids.shape[1]:
            assert reassemble(toks, g.line_delims(u)) == c


def test_tokenize_batch_newline_content_falls_back():
    # a newline is a delimiter (multi-line records) on the batched path;
    # a NUL, which the batch joins contents with, takes the scalar path
    for contents in (["a b", "with\nnewline", "c,d"], ["a b", "with\x00nul", "c\n,d"]):
        v1, v2 = Vocab(), Vocab()
        g = tokenize_batch(contents, v1, 32)
        r = _tokenize_batch_reference(contents, v2, 32, delimiters=DEFAULT_DELIMITERS,
                                      tight=True)
        assert v1._to_str == v2._to_str
        assert _grids_equal(g, r, len(contents))


def test_tokenize_batch_substring_matches_param_join():
    contents = ["a b c d", "x == y ;; z w", "one,two,three four"]
    v = Vocab()
    g = tokenize_batch(contents, v, 32)
    for u, c in enumerate(contents):
        toks, delims = tokenize(c)
        for s in range(len(toks)):
            for e in range(s + 1, len(toks) + 1):
                want = toks[s] + "".join(delims[i] + toks[i] for i in range(s + 1, e))
                assert g.substring(u, s, e) == want


@settings(max_examples=60, deadline=None)
@given(st.lists(st.text(alphabet=" ,;:=abXY*\t\\\x02é", max_size=24), min_size=1, max_size=12))
def test_tokenize_batch_property(contents):
    v1, v2 = Vocab(), Vocab()
    g = tokenize_batch(contents, v1, 16)
    r = _tokenize_batch_reference(contents, v2, 16, delimiters=" \t,;:=", tight=True)
    assert v1._to_str == v2._to_str
    assert _grids_equal(g, r, len(contents))


def test_tokenize_batch_overlong_rows_clip_like_encode_batch():
    contents = ["t" + str(i) for i in range(3)] + [" ".join(f"w{i}" for i in range(40))]
    v1, v2 = Vocab(), Vocab()
    g = tokenize_batch(contents, v1, 8)  # width budget 8 << 40 tokens
    toks = [tokenize(c)[0] for c in contents]
    ids, lens = v2.encode_batch(toks, 8, tight=True)
    assert v1._to_str == v2._to_str
    assert np.array_equal(g.ids, ids) and np.array_equal(g.lens, lens)


# ------------------------------------------------------------- fast parse

BAD_HEADERS = [
    "081109 203615 148 INFO dfs.DataNode$PacketResponder: ok line",
    "081109  203615 148 INFO dfs.X: double space",
    " 081109 203615 148 INFO dfs.X: leading space",
    "081109 203615 148 INFO nocolon missing",
    "081109 203615 148 INFO dfs.X:no space after colon",
    "081109\t203615 148 INFO dfs.X: tab separator",
    "too few",
    "",
    "081109 203615 148 INFO dfs.X: trailing ",
    "081109 203615 148 INFO dfs.X: colon: inside content",
    "081109 203615 x\x01y INFO dfs.X: control char in field",
    "081109 203615 148 INFO café.X: unicode field",
    "081109 203615 148 INFO dfs.X\xa0: nbsp in field",
]


@pytest.mark.parametrize("name", list(LOG_FORMATS))
def test_parse_fast_agrees_with_regex(name):
    from repro.data.loggen import generate_lines

    fmt = LOG_FORMATS[name]
    lines = list(generate_lines(name, 600, seed=5)) + BAD_HEADERS
    fast = fmt.parse(lines, fast=True)
    slow = fmt.parse(lines, fast=False)
    assert fast == slow


def test_parse_fast_path_is_active_for_paper_formats():
    for name, fmt in LOG_FORMATS.items():
        assert fmt._fast_cores is not None, name


# ----------------------------------------------- fused anchor match/spans

def _rand_grid(rng, n, t, star_rate=0.4):
    ids = rng.integers(2, 9, (n, t)).astype(np.int32)
    lens = rng.integers(0, t + 2, n).astype(np.int32)
    for r in range(n):
        ids[r, min(int(lens[r]), t):] = 0
    m = int(rng.integers(0, t + 3))
    tpl = rng.integers(2, 9, m).astype(np.int32)
    tpl[rng.random(m) < star_rate] = 1
    return ids, lens, tpl


@settings(max_examples=150, deadline=None)
@given(st.integers(1, 40), st.integers(1, 14), st.integers(0, 2**31 - 1))
def test_fused_match_equals_dp(n, t, seed):
    rng = np.random.default_rng(seed)
    ids, lens, tpl = _rand_grid(rng, n, t)
    ok, spans = match_extract_one(ids, lens, tpl, want_spans=True)
    assert np.array_equal(ok, match_one_template_dp(ids, lens, tpl))
    rows = np.flatnonzero(ok)
    if len(rows) and int((tpl == 1).sum()):
        assert np.array_equal(spans[rows], extract_spans_dp(ids[rows], lens[rows], tpl))


def test_fused_match_edge_templates():
    rng = np.random.default_rng(0)
    ids = rng.integers(2, 6, (20, 6)).astype(np.int32)
    lens = rng.integers(0, 7, 20).astype(np.int32)
    for r in range(20):
        ids[r, min(int(lens[r]), 6):] = 0
    for tpl in (np.zeros(0, np.int32),            # zero-length: len==0 only
                np.array([1], np.int32),          # lone star
                np.array([1, 1, 1], np.int32),    # all-wildcard
                np.array([2] * 9, np.int32)):     # longer than any line
        ok = match_one_template(ids, lens, tpl)
        assert np.array_equal(ok, match_one_template_dp(ids, lens, tpl)), tpl
        sp = extract_spans(ids[ok], lens[ok], tpl)
        if ok.any():
            assert np.array_equal(sp, extract_spans_dp(ids[ok], lens[ok], tpl))


def test_match_first_void_dedup_identical():
    rng = np.random.default_rng(3)
    ids = np.tile(rng.integers(2, 6, (300, 8)).astype(np.int32), (3, 1))
    lens = np.tile(rng.integers(0, 9, 300).astype(np.int32), 3)
    tpls = [np.array([2, 1], np.int32), np.array([1, 3], np.int32),
            np.array([2, 1, 4], np.int32)]
    assert np.array_equal(match_first(ids, lens, tpls, dedup=True),
                          match_first(ids, lens, tpls, dedup=False))


# ------------------------------------------------- ColumnCodec vs scalar

def _column_codec_reference(name, values, paradict=None):
    """The pre-vectorization per-value loop (kept verbatim as oracle)."""
    from repro.core.encode import encode_varints, join_column

    inv, uvals = factorize(values)
    patterns, pat_list, uparts = {}, [], []
    upid = np.empty(len(uvals), np.int64)
    for j, v in enumerate(uvals):
        pattern, parts = split_subfields(esc(v))
        pid = patterns.setdefault(pattern, len(pat_list))
        if pid == len(pat_list):
            pat_list.append(pattern)
        upid[j] = pid
        uparts.append(parts)
    pat_ids = upid[inv] if len(values) else np.zeros(0, np.int64)
    objs = {f"{name}.pat": join_column(pat_list), f"{name}.pid": encode_varints(pat_ids)}
    order = np.argsort(pat_ids, kind="stable")
    counts = np.bincount(pat_ids, minlength=len(pat_list)).astype(np.int64)
    gs = 0
    for pid in range(len(pat_list)):
        c = int(counts[pid])
        us = inv[order[gs:gs + c]]
        gs += c
        n_slots = len(uparts[int(us[0])])
        if n_slots == 0:
            continue
        g_inv, g_uniq = factorize(us)
        for k in range(n_slots):
            col_u = [uparts[u][k] for u in g_uniq]
            if paradict is not None:
                uids = np.fromiter((paradict.id(p) for p in col_u), np.int64, len(col_u))
                objs[f"{name}.p{pid}s{k}"] = encode_varints(uids[g_inv])
            else:
                objs[f"{name}.p{pid}s{k}"] = join_column(
                    [col_u[g] for g in g_inv], already_safe=True)
    return objs


@settings(max_examples=40, deadline=None)
@given(st.lists(st.text(alphabet="ab1._-:\\\n\x00é ", max_size=14), max_size=30))
def test_column_codec_batch_matches_reference(values):
    assert ColumnCodec("c").encode(values) == _column_codec_reference("c", values)
    pd1, pd2 = ParamDict(), ParamDict()
    assert (ColumnCodec("c", pd1).encode(values)
            == _column_codec_reference("c", values, pd2))
    assert pd1.values == pd2.values


def test_column_codec_roundtrip_after_vectorization():
    vals = ["a.1", "a.2", "b-3", "", "a.1", "x:y:z", "é.9", "\\esc\n"]
    for pd in (None, ParamDict()):
        codec = ColumnCodec("h", pd)
        objs = codec.encode(vals)
        out = ColumnCodec("h").decode(objs, len(vals),
                                      pd.values if pd is not None else None)
        assert out == vals


# ------------------------------------------- pipelined container identity

def test_stream_pipeline_bytes_identical(hdfs_lines):
    from repro.core.codec import LogzipConfig
    from repro.core.ise import ISEConfig
    from repro.core.stream import LZJSReader, StreamingCompressor

    cfg = LogzipConfig(level=3, format=LOG_FORMATS["HDFS"].format,
                       ise=ISEConfig(min_sample=200, max_iters=3))
    blobs = []
    for pl in (False, True):
        buf = io.BytesIO()
        with StreamingCompressor(buf, cfg, chunk_lines=400, pipeline=pl) as sc:
            sc.feed(hdfs_lines)
        blobs.append(buf.getvalue())
    assert blobs[0] == blobs[1]
    assert LZJSReader(io.BytesIO(blobs[1])).read_all() == hdfs_lines


def test_parallel_single_worker_pipelined_roundtrip(spark_lines):
    from repro.core.codec import LogzipConfig
    from repro.core.ise import ISEConfig
    from repro.core.parallel import compress_parallel, decompress_parallel

    cfg = LogzipConfig(level=3, format=LOG_FORMATS["Spark"].format,
                       ise=ISEConfig(min_sample=200, max_iters=3))
    blob = compress_parallel(spark_lines, cfg, n_workers=1, chunk_lines=500)
    assert decompress_parallel(blob) == spark_lines
