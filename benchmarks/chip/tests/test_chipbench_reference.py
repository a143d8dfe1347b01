"""The plain query reference agrees with the program's query engine on a
small archive of each deployment."""

import _chipbench_paths  # noqa: F401
import pytest

import corpus
import harness
from reference import Reference

SEED = 2**35 + 7


def ask(path, query):
    from repro.core import query as Q

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


@pytest.fixture(scope="module", params=["hdfs", "thunderbird"])
def archive(request, tmp_path_factory):
    from repro.core.codec import LogzipConfig
    from repro.core.stream import StreamingCompressor

    cfg = harness.load_json(harness.HERE / "configs" / f"{request.param}.json")
    lines = corpus.generate(cfg, SEED, 6000)
    path = tmp_path_factory.mktemp("arch") / "a.lzjs"
    with StreamingCompressor(str(path), LogzipConfig(format=cfg["format"]),
                             chunk_lines=1024) as sc:
        for line in lines:
            sc.feed_line(line)
    return cfg, lines, path


def queries(cfg, lines):
    from reference import Header

    f = Header(cfg["format"]).fields(lines[100])
    word = lines[100].split(": ", 1)[1].split()[0]
    qs = [("search", ("substring", word)), ("count", ("substring", word)),
          ("search", ("regex", r"[0-9]{6}")), ("search", ("substring", "no such text")),
          ("count", ("regex", r"0x[0-9a-f]{8}"))]
    for name, value in f.items():
        qs += [("search", ("field", name, value)), ("top_k", name, 3)]
        qs.append(("search", ("and", ("field", name, value), ("substring", word))))
    qs += [("time_histogram", "Time", 100)]
    if "Timestamp" in f:
        qs += [("time_histogram", "Timestamp", 60)]
    return qs


def test_reference_equals_program(archive):
    cfg, lines, path = archive
    ref = Reference(lines, cfg["format"])
    for q in queries(cfg, lines):
        assert ask(path, q) == ref.answer(q), q


def test_malformed_lines_have_no_fields():
    from reference import Header

    h = Header("<Date> <Time> <Level>: <Content>")
    assert h.fields("a b c: d e") == {"Date": "a", "Time": "b", "Level": "c"}
    for bad in ("", "\t", "### corrupt entry ###", "raw dump: 0x1", "a  b c: d", "a b c:d"):
        assert h.fields(bad) is None
    with pytest.raises(ValueError):
        Header("<Date>|<Content>")
