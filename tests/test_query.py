"""Compressed-domain query engine: hit-set equality with decompress-then-
grep on every container kind, template classification, chunk skipping via
LZJS manifests, the param-dictionary screen, and the count/sample fast
paths."""

import dataclasses
import io
import json
import re
import zlib

import pytest

from repro.core import query as Q
from repro.core.codec import LogzipConfig, compress
from repro.core.ise import ISEConfig
from repro.core.parallel import compress_parallel
from repro.core.stream import FOOTER_MAGIC, StreamingCompressor
from repro.core.templates import compile_template_regex, template_regex
from repro.data.loggen import DATASETS

CFG_FAST = ISEConfig(min_sample=200, max_iters=2)
FMT = DATASETS["HDFS"]["format"]

BURST = [
    f"081109 203545 99 INFO dfs.FSNamesystem: Starting decommission of "
    f"node /10.9.{i % 7}.{i % 11} remaining {i}"
    for i in range(60)
]


@pytest.fixture(scope="module")
def corpus(hdfs_lines):
    """HDFS corpus with a localized rare-template burst (the 'track a
    security incident' workload from the paper's motivation)."""
    lines = list(hdfs_lines)
    lines[1700:1700] = BURST
    return lines


@pytest.fixture(scope="module")
def archives(corpus):
    cfg = LogzipConfig(level=3, format=FMT, ise=CFG_FAST)
    lzjf = compress(corpus, cfg)
    lzjm = compress_parallel(corpus, cfg, n_workers=1, chunk_lines=500)
    buf = io.BytesIO()
    with StreamingCompressor(buf, cfg, chunk_lines=320) as sc:
        sc.feed(corpus)
    return {"lzjf": lzjf, "lzjm": lzjm, "lzjs": buf.getvalue()}


def grep(lines, needle):
    return [(i, l) for i, l in enumerate(lines) if needle in l]


# --------------------------------------------------- hit-set equivalence

@pytest.mark.parametrize("kind", ["lzjf", "lzjm", "lzjs"])
@pytest.mark.parametrize("needle", [
    "terminating",            # template literal -> ALWAYS
    "decommission",           # rare-template literal
    "blk_",                   # parameter prefix -> MAYBE everywhere
    "WARN",                   # header field value
    "### corrupt",            # verbatim line
    "no-such-needle-xyzzy",   # empty hit set
    "",                       # matches everything
    "size 1024 from",         # spans tokens and delimiters
])
def test_substring_matches_grep(archives, corpus, kind, needle):
    assert list(Q.search(archives[kind], Q.Substring(needle))) == grep(corpus, needle)


@pytest.mark.parametrize("kind", ["lzjf", "lzjm", "lzjs"])
@pytest.mark.parametrize("pattern", [
    r"blk_(-?\d+) terminating",
    r"decommission of node /10\.9\.\d+",
    r"^081109 2035\d\d 99 ",
    r"src: /10\.\d+\.\d+\.\d+:\d+",
])
def test_regex_matches_grep(archives, corpus, kind, pattern):
    want = [(i, l) for i, l in enumerate(corpus) if re.search(pattern, l)]
    assert list(Q.search(archives[kind], Q.Regex(pattern))) == want


@pytest.mark.parametrize("kind", ["lzjf", "lzjm", "lzjs"])
def test_field_eq_matches_parse(archives, corpus, kind):
    from repro.core.tokenizer import LogFormat

    fmt = LogFormat(FMT)
    want = []
    for i, l in enumerate(corpus):
        vals = fmt._parse_regex_line(l)
        if vals is not None and dict(zip(fmt.fields, vals))["Level"] == "WARN":
            want.append((i, l))
    assert list(Q.search(archives[kind], Q.FieldEq("Level", "WARN"))) == want


def test_line_range_and_conjunction(archives, corpus):
    q = Q.And(Q.LineRange(400, 1200), Q.Substring("blk_"))
    want = [(i, l) for i, l in enumerate(corpus) if 400 <= i < 1200 and "blk_" in l]
    assert list(Q.search(archives["lzjs"], q)) == want
    assert list(Q.search(archives["lzjs"], Q.LineRange(0, 3))) == \
        [(i, l) for i, l in enumerate(corpus[:3])]


def test_event_is_matches_structured(archives, corpus):
    from repro.core.stream import LZJSReader

    rd = LZJSReader(io.BytesIO(archives["lzjs"]))
    target = next(g for g, t in enumerate(rd.templates) if "terminating" in t)
    hits = list(Q.search(archives["lzjs"], Q.EventIs(target)))
    assert hits and all("terminating" in l for _, l in hits)
    assert len(hits) == sum(
        int((rd.read_events(k) == target).sum()) for k in range(len(rd)))


# ----------------------------------------------------------- work bounds

def test_rare_template_query_skips_chunks(archives):
    st = Q.QueryStats()
    hits = list(Q.search(archives["lzjs"], Q.Substring("decommission"), stats=st))
    assert len(hits) == len(BURST)
    assert st.chunks_total >= 8
    # the burst spans at most 2 chunks; everything else is proven clean
    # from the footer manifests alone
    assert st.chunks_opened <= 2
    assert st.chunks_skipped == st.chunks_total - st.chunks_opened


def test_absent_needle_skips_all_chunks(archives):
    st = Q.QueryStats()
    assert list(Q.search(archives["lzjs"], Q.Substring("no-such-needle-xyzzy"),
                         stats=st)) == []
    assert st.chunks_opened == 0
    assert st.chunks_skipped == st.chunks_total


def test_count_fast_path_materializes_nothing(archives, corpus):
    st = Q.QueryStats()
    n = Q.count(archives["lzjs"], Q.Substring("terminating"), stats=st)
    assert n == len(grep(corpus, "terminating"))
    # ALWAYS-classified templates + verbatim manifest: counting needs no
    # line assembly at all
    assert st.rows_materialized == 0


def test_sample_stops_early(archives, corpus):
    st = Q.QueryStats()
    got = Q.sample(archives["lzjs"], Q.Substring("blk_"), 5, stats=st)
    assert got == grep(corpus, "blk_")[:5]
    assert st.chunks_opened <= 2  # lazy: later chunks never touched


def test_param_query_prunes_materialization(archives, corpus):
    # one specific block id: hit rows only are materialized
    needle = next(tok for l in corpus for tok in l.split() if tok.startswith("blk_"))
    st = Q.QueryStats()
    hits = list(Q.search(archives["lzjs"], Q.Substring(needle), stats=st))
    assert hits == grep(corpus, needle)
    assert st.rows_materialized <= max(4 * len(hits), 8)


def test_search_accepts_paths(tmp_path, archives, corpus):
    for kind in ("lzjf", "lzjm", "lzjs"):
        p = tmp_path / f"a.{kind}"
        p.write_bytes(archives[kind])
        assert list(Q.search(str(p), Q.Substring("decommission"))) == \
            grep(corpus, "decommission")


def test_manifest_free_container_still_correct(archives, corpus):
    """Containers written before manifests existed (PR 2) must still
    query correctly — just without chunk skipping."""
    blob = archives["lzjs"]
    flen = int.from_bytes(blob[-16:-8], "little")
    from repro.core import integrity

    # v3 footer layout: [fb][crc4][len8][magic8] — resign after splicing
    cut = -16 - integrity.CRC_LEN - flen
    footer = json.loads(zlib.decompress(blob[cut:cut + flen]).decode("utf-8"))
    for e in footer["chunks"]:
        e.pop("manifest", None)
    fb = zlib.compress(json.dumps(footer).encode("utf-8"))
    stripped = blob[:cut] + fb + integrity.trailer(fb) \
        + len(fb).to_bytes(8, "little") + FOOTER_MAGIC
    st = Q.QueryStats()
    assert list(Q.search(stripped, Q.Substring("decommission"), stats=st)) == \
        grep(corpus, "decommission")
    assert st.chunks_skipped == 0  # nothing to prove with -> everything opened


# ------------------------------------------------------- classification

def test_classify_template_cases():
    tpl = ("PacketResponder", None, "for", "block", None, "terminating")
    assert Q.classify_template("terminating", tpl) == Q.ALWAYS
    assert Q.classify_template("Responder", tpl) == Q.ALWAYS  # inside a literal
    assert Q.classify_template("blk_123", tpl) == Q.MAYBE     # param-dependent
    no_star = ("Starting", "TrustedInstaller", "initialization.")
    assert Q.classify_template("Trusted", no_star) == Q.ALWAYS
    assert Q.classify_template("nope", no_star) == Q.NEVER
    # spanning: feasible alignment vs infeasible one
    assert Q.classify_template("Starting TrustedInstaller", no_star) == Q.MAYBE
    assert Q.classify_template("TrustedInstaller Starting", no_star) == Q.NEVER
    assert Q.classify_template("ing TrustedInstaller", no_star) == Q.MAYBE
    assert Q.classify_template("xing TrustedInstaller", no_star) == Q.NEVER


def test_template_regex_matches_instantiations():
    tpl = ("Deleting", "block", None, "file", None)
    rx = compile_template_regex(tpl)
    assert rx.match("Deleting block blk_1 file /data/part-00001")
    assert rx.match("  Deleting  block , blk_1 x y file /d  ")  # multi-token star
    assert not rx.match("Deleting block file /data")            # star needs >= 1 token
    assert not rx.match("Deleting block blk_1 file")
    assert "Deleting" in template_regex(tpl)


def test_required_literals_extraction():
    lits = Q._required_literals(r"blk_(-?\d+) terminating")
    assert "blk_" in lits and "terminating" in lits
    assert Q._required_literals(r"(?i)Block") == []  # case-insensitive: bail
    assert Q._required_literals(r"(?i:TERM)inating") == []  # scoped flag: bail
    assert Q._required_literals(r"a|b") == []
    assert "need" in Q._required_literals(r"(?:x|y)*need(ed)?z?")


def test_case_insensitive_regex_matches_grep(archives, corpus):
    """(?i:...) must defeat literal pruning, not produce false misses."""
    pattern = r"(?i:TERMINATING)"
    want = [(i, l) for i, l in enumerate(corpus) if re.search(pattern, l)]
    assert want  # the corpus really has lowercase hits
    assert list(Q.search(archives["lzjs"], Q.Regex(pattern))) == want


def test_invalid_regex_reports_the_pattern(archives):
    with pytest.raises(ValueError, match="invalid regex"):
        list(Q.search(archives["lzjs"], Q.Regex("(")))


def test_explain_reports_classes(archives):
    rows = Q.explain(archives["lzjs"], Q.Substring("terminating"))
    by_class = {r["class"] for r in rows}
    assert "always" in by_class
    term = next(r for r in rows if r["class"] == "always")
    assert "terminating" in term["template"]
    assert re.match(term["regex"], "PacketResponder 1 for block blk_2 terminating")


# ------------------------------------------------------------ edge cases

def test_field_eq_unknown_field_raises(archives):
    with pytest.raises(ValueError, match="unknown header field"):
        list(Q.search(archives["lzjs"], Q.FieldEq("Nope", "x")))


def test_not_an_archive_raises():
    with pytest.raises(ValueError, match="not a logzip archive"):
        list(Q.search(b"XXXXjunk", Q.Substring("a")))


def test_query_without_format(spark_lines):
    """Content-only archives (format=None): full-line == content."""
    lines = spark_lines[:400]
    cfg = LogzipConfig(level=3, format=None, ise=CFG_FAST)
    blob = compress(lines, cfg)
    for needle in ("Found block", "rdd_", "xyzzy"):
        assert list(Q.search(blob, Q.Substring(needle))) == grep(lines, needle)


def test_query_level_1_and_2(corpus):
    lines = corpus[:600]
    for level in (1, 2):
        cfg = LogzipConfig(level=level, format=FMT, ise=CFG_FAST)
        buf = io.BytesIO()
        with StreamingCompressor(buf, cfg, chunk_lines=200) as sc:
            sc.feed(lines)
        for needle in ("terminating", "blk_", "xyzzy"):
            assert list(Q.search(buf.getvalue(), Q.Substring(needle))) == \
                grep(lines, needle)


def test_extract_records_roundtrip(archives, corpus):
    recs = list(Q.extract_records(archives["lzjs"], line_range=(100, 300)))
    assert recs and all(100 <= r["line"] < 300 for r in recs)
    assert [r["line"] for r in recs] == sorted(r["line"] for r in recs)
    for r in recs[:20]:
        # params really are the line's parameter values
        for p in r["params"]:
            assert p in corpus[r["line"]]
    by_event = list(Q.extract_records(archives["lzjs"], event=recs[0]["event"]))
    assert all(r["event"] == recs[0]["event"] for r in by_event)


# --------------------------------------------------------- phase timing

PHASES = {"query.screen", "query.decode", "query.filter", "query.materialize",
          "query.aggregate"}


def _counts(st):
    return {k: v for k, v in dataclasses.asdict(st).items() if k != "stage_times"}


@pytest.mark.parametrize("kind", ["lzjf", "lzjm", "lzjs"])
def test_stage_times_fill_the_query_phases(archives, kind):
    times: dict = {}
    timed = Q.QueryStats(stage_times=times)
    hits = list(Q.search(archives[kind], Q.Substring("blk_"), stats=timed))
    top = Q.top_k(archives[kind], "Level", k=3, stats=timed)
    assert PHASES <= set(times)
    assert all(v >= 0 for v in times.values())

    plain = Q.QueryStats()
    assert list(Q.search(archives[kind], Q.Substring("blk_"), stats=plain)) == hits
    assert Q.top_k(archives[kind], "Level", k=3, stats=plain) == top
    assert plain.stage_times is None
    assert _counts(plain) == _counts(timed)


@pytest.mark.parametrize("agg", [
    lambda a, st: Q.count_by_template(a, stats=st),
    lambda a, st: Q.time_histogram(a, "Time", bucket=100, stats=st),
    lambda a, st: list(Q.extract_records(a, line_range=(100, 900), stats=st)),
    lambda a, st: Q.top_k(a, event=0, star=0, stats=st),
    lambda a, st: Q.count(a, Q.Substring("terminating"), stats=st),
], ids=["by_template", "histogram", "extract", "top_param", "count"])
def test_stage_times_leave_aggregations_unchanged(archives, agg):
    for kind in ("lzjf", "lzjs"):
        times: dict = {}
        timed = Q.QueryStats(stage_times=times)
        plain = Q.QueryStats()
        assert agg(archives[kind], timed) == agg(archives[kind], plain)
        assert times and set(times) <= PHASES | {"dispatch.distinct_counts"}
        assert plain.stage_times is None
        assert _counts(plain) == _counts(timed)
