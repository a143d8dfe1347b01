"""Round-trip property/fuzz suite: ``decompress(compress(x)) == x`` for
every level x container x dedup setting over adversarial corpora (empty
lines, delimiter-only lines, NUL / multibyte text, 10k-char lines, CRLF),
and ``search(blob, Substring(s))`` agreement with a plain-Python grep."""

import io
import re

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import query as Q
from repro.core.codec import LogzipConfig, compress, decompress
from repro.core.ise import ISEConfig
from repro.core.parallel import compress_parallel, decompress_parallel
from repro.core.stream import StreamingCompressor, decompress_lzjs
from repro.data.loggen import DATASETS, generate_lines
from strategies import SPARK_FORMAT, records

CFG_FAST = ISEConfig(min_sample=30, max_iters=2)

EDGE_CORPORA = {
    "empty_lines": ["", "", ""],
    "delims_only": [" ", "\t\t", " ,;:= ", "::::", "=", ",", ""],
    "nul_bytes": ["a\x00b", "\x00", "x y \x00\x00 z", "end\x00"],
    "multibyte": ["héllo wörld", "日本語 ログ 行 123", "emoji 🙂 end", "mixé=ü"],
    "long_lines": ["T " + "x" * 10000, "y" * 10000 + " tail",
                   ("tok " * 3000).rstrip()],
    "crlf": ["line one\r", "\rline two", "a\rb", "trailing \r\r"],
    "star_escape": ["* literal star *", "a * b", "**"],
    "mixed": ["", " ", "héllo", "x" * 10000, "a\x00b", "normal line 123",
              "\t", "* star"],
}

CONTAINERS = ["lzjf", "lzjm", "lzjs"]


def roundtrip(lines, cfg, container):
    if container == "lzjf":
        blob = compress(lines, cfg)
        return blob, decompress(blob)
    if container == "lzjm":
        blob = compress_parallel(lines, cfg, n_workers=1, chunk_lines=3)
        return blob, decompress_parallel(blob)
    buf = io.BytesIO()
    with StreamingCompressor(buf, cfg, chunk_lines=3) as sc:
        sc.feed(lines)
    blob = buf.getvalue()
    return blob, decompress_lzjs(blob)


@pytest.mark.parametrize("container", CONTAINERS)
@pytest.mark.parametrize("level", [1, 2, 3])
@pytest.mark.parametrize("name", sorted(EDGE_CORPORA))
def test_edge_corpora_roundtrip(name, level, container):
    lines = EDGE_CORPORA[name]
    for dedup in (True, False):
        cfg = LogzipConfig(level=level, format=None, ise=CFG_FAST, dedup=dedup)
        blob, back = roundtrip(lines, cfg, container)
        assert back == lines, (name, level, container, dedup)


@pytest.mark.parametrize("container", CONTAINERS)
def test_edge_corpora_with_format(container):
    """Edge lines never parse the HDFS header -> verbatim channel; mixed
    with parsing lines they exercise both paths per chunk."""
    parsing = list(generate_lines("HDFS", 12, seed=1))
    lines = []
    for i, edge in enumerate(sorted(EDGE_CORPORA)):
        lines.extend(EDGE_CORPORA[edge])
        lines.extend(parsing[i:i + 2])
    cfg = LogzipConfig(level=3, format=DATASETS["HDFS"]["format"], ise=CFG_FAST)
    blob, back = roundtrip(lines, cfg, container)
    assert back == lines


@pytest.mark.parametrize("container", CONTAINERS)
def test_edge_corpora_search_agrees_with_grep(container):
    cfg = LogzipConfig(level=3, format=None, ise=CFG_FAST)
    for name, lines in sorted(EDGE_CORPORA.items()):
        blob, _ = roundtrip(lines, cfg, container)
        needles = {"", " ", "x", "\x00", "🙂", "xx", "tok t", "*"}
        needles.update(l[:3] for l in lines)
        for s in sorted(needles):
            got = list(Q.search(blob, Q.Substring(s)))
            want = [(i, l) for i, l in enumerate(lines) if s in l]
            assert got == want, (name, container, repr(s))


# ------------------------------------------------------------- properties

line_text = st.text(
    alphabet=st.characters(blacklist_categories=("Cs",)), max_size=60
).filter(lambda s: "\n" not in s)

lines_strategy = st.lists(line_text, max_size=25)
records_strategy = st.lists(records(line_text.filter(lambda s: len(s) < 30)), max_size=20)


@settings(max_examples=25, deadline=None)
@given(lines_strategy, st.integers(1, 3), st.integers(0, 2), st.integers(0, 1))
def test_roundtrip_property(lines, level, container_i, dedup_i):
    cfg = LogzipConfig(level=level, format=None, ise=CFG_FAST,
                       dedup=bool(dedup_i))
    blob, back = roundtrip(lines, cfg, CONTAINERS[container_i])
    assert back == lines


@settings(max_examples=25, deadline=None)
@given(lines_strategy, st.integers(1, 3), st.integers(0, 1))
def test_roundtrip_property_with_format(lines, level, dedup_i):
    """Random lines against a real header format: whatever parses must
    render back; whatever doesn't goes verbatim — either way lossless."""
    cfg = LogzipConfig(level=level, format=DATASETS["Spark"]["format"],
                       ise=CFG_FAST, dedup=bool(dedup_i))
    blob, back = roundtrip(lines, cfg, "lzjs")
    assert back == lines


@settings(max_examples=25, deadline=None)
@given(records_strategy, st.integers(1, 3), st.integers(0, 1))
def test_roundtrip_property_records(records_, level, dedup_i):
    """Multi-line records against the Spark format: the header from the
    first line, the rest in the content — lossless either way."""
    cfg = LogzipConfig(level=level, format=SPARK_FORMAT, ise=CFG_FAST, dedup=bool(dedup_i))
    blob, back = roundtrip(records_, cfg, "lzjs")
    assert back == records_


@settings(max_examples=25, deadline=None)
@given(records_strategy, st.one_of(line_text, st.sampled_from(["\n\tat", "more\n", "INFO", "\n"])))
def test_search_agrees_with_grep_records_property(records_, needle):
    """A record matches when its text does, a needle across its lines too."""
    cfg = LogzipConfig(level=3, format=SPARK_FORMAT, ise=CFG_FAST)
    blob, _ = roundtrip(records_, cfg, "lzjs")
    got = list(Q.search(blob, Q.Substring(needle)))
    assert got == [(i, r) for i, r in enumerate(records_) if needle in r]


@settings(max_examples=30, deadline=None)
@given(lines_strategy, line_text, st.integers(0, 2))
def test_search_agrees_with_grep_property(lines, needle, container_i):
    cfg = LogzipConfig(level=3, format=None, ise=CFG_FAST)
    blob, _ = roundtrip(lines, cfg, CONTAINERS[container_i])
    got = list(Q.search(blob, Q.Substring(needle)))
    assert got == [(i, l) for i, l in enumerate(lines) if needle in l]


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10**6), st.integers(0, 40), st.integers(1, 12))
def test_search_agrees_on_real_corpus(seed, start, ln):
    """Needles cut from the corpus itself (params, header fragments,
    cross-token spans) against an HDFS-format LZJS session."""
    lines = list(generate_lines("HDFS", 120, seed=seed % 7))
    src = lines[seed % len(lines)]
    needle = src[start % max(len(src), 1):][:ln]
    cfg = LogzipConfig(level=3, format=DATASETS["HDFS"]["format"], ise=CFG_FAST)
    buf = io.BytesIO()
    with StreamingCompressor(buf, cfg, chunk_lines=30) as sc:
        sc.feed(lines)
    blob = buf.getvalue()
    got = list(Q.search(blob, Q.Substring(needle)))
    assert got == [(i, l) for i, l in enumerate(lines) if needle in l]
    rx = re.escape(needle)
    got_rx = list(Q.search(blob, Q.Regex(rx)))
    assert got_rx == [(i, l) for i, l in enumerate(lines) if re.search(rx, l)]
