"""Multi-line log4j records (a header line and its stack trace) on the
stream path: lossless sessions and CLI round trips, queries per record
against the benchmark's plain reference, the session counters, and no
clustering of records over the token budget."""

import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import ise as ise_mod
from repro.core import query as Q
from repro.core.codec import LogzipConfig
from repro.core.ise import ISEConfig
from repro.core.match import (
    NARROW_TOKENS,
    extract_spans,
    extract_spans_dp,
    match_first,
    match_one_template_dp,
)
from repro.core.stream import LZJSReader, StreamingCompressor
from repro.core.tokenizer import LogFormat, Vocab, tokenize, tokenize_batch
from repro.launch.compress import main

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks" / "chip"))
from reference import Reference  # noqa: E402

FMT = "<Date> <Time> <Level> <Component>: <Content>"
CFG = LogzipConfig(level=3, format=FMT, ise=ISEConfig(min_sample=200, max_iters=3))
FRAMES = [
    "org.apache.spark.rdd.MapPartitionsRDD.compute(MapPartitionsRDD.scala:38)",
    "org.apache.spark.rdd.RDD.computeOrReadCheckpoint(RDD.scala:323)",
    "org.apache.spark.rdd.RDD.iterator(RDD.scala:287)",
]
TAIL = ["org.apache.spark.scheduler.Task.run(Task.scala:99)",
        "java.lang.Thread.run(Thread.java:745)"]
# (exception line, frames): 3 tokens a frame; the last is over the budget
TRACES = [("java.io.IOException: Failed to connect to {}", 30),
          ("java.io.FileNotFoundException: {} (No such file or directory)", 60),
          ("java.lang.OutOfMemoryError: Unable to acquire {} bytes", 150),
          ("org.apache.spark.SparkException: Task failed while writing rows to {}", 200)]
# a logging statement has one logger and one level, as in log4j
ONE_LINE = [("Found block rdd_{}_{} locally", "INFO storage.BlockManager"),
            ("Running task {}.0 in stage {}.0 (TID {})", "INFO executor.Executor"),
            ("Removing RDD {} from persistence list", "WARN storage.BlockManager"),
            ("Memory usage is {} MB, threshold {} MB", "INFO storage.memory.MemoryStore")]


def _trace(rng, exc: str, depth: int) -> str:
    frames = [FRAMES[i % 3] for i in range(depth - len(TAIL))] + TAIL
    head = (f"Exception in task {rng.integers(1000)}.0 in stage {rng.integers(50)}.0 "
            f"(TID {rng.integers(10**5)})")
    return "\n".join([head, exc.format(f"/data/part-{rng.integers(4096):05d}")]
                     + ["\tat " + f for f in frames] + [f"\t... {rng.integers(90)} more"])


def make_records(n: int, seed: int, trace_every: int = 25) -> list[str]:
    """``n`` Spark records from ``seed``; every ``trace_every``-th carries
    a stack trace (logged at ERROR by the executor), each trace kind in
    turn; about one in a hundred is a bare line with no header."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        clock = f"17/06/09 20:{(i // 60) % 60:02d}:{i % 60:02d}"
        if i % trace_every == trace_every - 1:
            exc, depth = TRACES[(i // trace_every) % len(TRACES)]
            out.append(f"{clock} ERROR executor.Executor: " + _trace(rng, exc, depth))
        elif i % 97 == 50:
            out.append("### corrupt entry ###")
        else:
            stmt, logger = ONE_LINE[int(rng.integers(len(ONE_LINE)))]
            out.append(f"{clock} {logger}: " + stmt.format(*rng.integers(1, 500, stmt.count("{}"))))
    return out


def n_tokens(record: str) -> int:
    return len(tokenize(record.split(": ", 1)[1])[0]) if ": " in record else 0


def plain_records(text: str) -> list[str]:
    """A record splitter written from the format alone: a line opens a
    record when it has four space-free fields, single spaces between
    them, then ": "; the lines before the first one are one record."""
    out: list[list[str]] = []
    for line in text.split("\n"):
        parts = line.split(" ", 4)
        header = (len(parts) == 5 and parts[3].endswith(":") and len(parts[3]) > 1
                  and all(p and not any(c.isspace() for c in p) for p in parts[:4]))
        if header or not out:
            out.append([line])
        else:
            out[-1].append(line)
    return ["\n".join(r) for r in out]


def _session(records, path, **kw):
    with StreamingCompressor(str(path), CFG, chunk_lines=500, **kw) as sc:
        for r in records:
            sc.feed_line(r)
        summary = sc.close()
    return summary


@pytest.fixture(scope="module")
def archive(tmp_path_factory):
    records = ["a stray line before any header\n\tat x.y(Z.java:1)"] + make_records(1600, seed=2**33 + 5)
    path = tmp_path_factory.mktemp("ml") / "a.lzjs"
    summary = _session(records, path)
    return records, path, summary


def test_session_round_trip(archive):
    records, path, _ = archive
    widths = [n_tokens(r) for r in records]
    assert max(widths) > 512 and any(NARROW_TOKENS < w <= 256 for w in widths)
    assert any(256 < w <= 512 for w in widths)
    rd = LZJSReader(str(path))
    try:
        assert rd.read_all() == records
        assert rd.read_range(900, 40) == records[900:940]
    finally:
        rd.close()


def test_counters_no_record_within_budget_goes_verbatim(tmp_path):
    """Once the first chunk has put each statement in the store, only the
    token budget and a missing header send a record to the verbatim
    channel. (In the first chunk ISE may leave a record whose (Level,
    Component) group holds no other of its kind, as for any statement.)"""
    records = make_records(2000, seed=2**33 + 5)
    sc = StreamingCompressor(str(tmp_path / "c.lzjs"), CFG, chunk_lines=500)
    for r in records[:500]:
        sc.feed_line(r)
    first = sc.stats()
    for r in records[500:]:
        sc.feed_line(r)
    summary = sc.close()
    rest = records[500:]
    widths = [n_tokens(r) for r in rest]
    headerless = sum(1 for r in rest if not LogFormat(FMT).is_header(r.split("\n")[0]))
    assert summary["verbatim_records"] - first["verbatim_records"] == \
        headerless + sum(1 for w in widths if w > 512)
    assert summary["wide_records_templated"] - first["wide_records_templated"] == \
        sum(1 for w in widths if NARROW_TOKENS < w <= 512)
    assert first["wide_records_templated"] > 0


def test_over_budget_rows_are_never_clustered(tmp_path, monkeypatch):
    seen: list[np.ndarray] = []
    orig = ise_mod.cluster_sample

    def spy(ids, lens, *a):
        seen.append(np.asarray(lens).copy())
        return orig(ids, lens, *a)

    monkeypatch.setattr(ise_mod, "cluster_sample", spy)
    records = make_records(2000, seed=11, trace_every=10)
    over = [r for r in records if n_tokens(r) > 512]
    assert len(over) >= 40
    summary = _session(records, tmp_path / "o.lzjs")
    assert seen and all((lens <= 512).all() for lens in seen)
    assert summary["verbatim_records"] >= len(over)
    rd = LZJSReader(str(tmp_path / "o.lzjs"))
    try:
        assert rd.read_all() == records
    finally:
        rd.close()


def test_stream_unpack_physical_lines(tmp_path, monkeypatch, capsys):
    # the file starts with continuation lines and ends with a newline
    text = "\tat a.b(C.java:1)\n\t... 3 more\n" + "\n".join(make_records(700, seed=3)) + "\n"
    src, arc, back = tmp_path / "in.log", tmp_path / "a.lzjs", tmp_path / "back.log"
    src.write_bytes(text.encode("utf-8"))
    for argv in (["stream", str(src), str(arc), "--format", FMT, "--chunk-lines", "200"],
                 ["unpack", str(arc), str(back)]):
        monkeypatch.setattr(sys, "argv", ["compress"] + argv)
        main()
    capsys.readouterr()
    assert back.read_bytes() == text.encode("utf-8")
    rd = LZJSReader(str(arc))
    try:
        assert rd.read_all() == plain_records(text)
    finally:
        rd.close()


def _queries(records):
    qs = [("search", ("substring", "IOException")), ("count", ("substring", "\n\tat org")),
          ("count", ("substring", "RDD.scala:287)\n\tat")), ("search", ("regex", r"TID 4[0-9]+\)")),
          ("count", ("regex", r"more$")), ("count", ("substring", "corrupt")),
          ("search", ("field", "Level", "ERROR")),
          ("search", ("and", ("field", "Level", "ERROR"), ("substring", "FileNotFound"))),
          ("search", ("field", "Level", "WARN")),
          ("top_k", "Level", 3), ("top_k", "Component", 2), ("time_histogram", "Time", 100)]
    return qs


def _ask(path, query):
    def pred(p):
        if p[0] == "and":
            return Q.And(*(pred(x) for x in p[1:]))
        return {"substring": Q.Substring, "regex": Q.Regex, "field": Q.FieldEq}[p[0]](*p[1:])

    op = query[0]
    if op == "search":
        return list(Q.search(str(path), pred(query[1])))
    if op == "count":
        return Q.count(str(path), pred(query[1]))
    if op == "top_k":
        return Q.top_k(str(path), query[1], k=query[2])
    return Q.time_histogram(str(path), query[1], bucket=query[2])


@pytest.mark.parametrize("query", _queries(None), ids=lambda q: "-".join(map(str, q))[:40])
def test_queries_equal_the_plain_reference(archive, query):
    records, path, _ = archive
    ref = Reference(records, FMT)
    assert _ask(path, query) == ref.answer(query)


def test_wide_match_kernel_equals_host():
    """Token buckets 256 and 512 through the kernel path (interpret mode
    here) give the host matcher's assignment."""
    records = make_records(300, seed=9, trace_every=6)
    contents = [r.split(": ", 1)[1] for r in records if ": " in r]
    vocab = Vocab()
    grid = tokenize_batch(contents, vocab, 512)
    ids, lens = grid.ids, grid.lens
    wide = lens > NARROW_TOKENS
    templates = [ids[i, :lens[i]].copy() for i in np.flatnonzero(wide & (lens <= 512))[:6]]
    templates += [ids[i, :lens[i]].copy() for i in np.flatnonzero(~wide)[:4]]
    star = templates[0].copy()
    star[3] = 1  # a star in place of the task id
    templates.append(star)
    a_host = match_first(ids, lens, templates, use_kernel=False)
    a_kern = match_first(ids, lens, templates, use_kernel=True)
    np.testing.assert_array_equal(a_host, a_kern)
    assert (a_host[wide] >= 0).sum() >= 6
    # the anchor pass over long literal runs gives the DP backtrack's spans
    rows = np.flatnonzero(match_one_template_dp(ids, lens, star))
    assert len(rows) >= 1
    np.testing.assert_array_equal(extract_spans(ids[rows], lens[rows], star),
                                  extract_spans_dp(ids[rows], lens[rows], star))
