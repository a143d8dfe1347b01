"""CLI surface: pack (bounded buffering, stdin), stream -> LZJS, unpack
with range random access, inspect aggregation for all three magics."""

import io
import sys

import pytest

from repro.data.loggen import DATASETS, generate_lines
from repro.launch.compress import _iter_lines, main

FMT = DATASETS["Spark"]["format"]


@pytest.fixture(scope="module")
def log_file(tmp_path_factory):
    p = tmp_path_factory.mktemp("cli") / "in.log"
    p.write_text("\n".join(generate_lines("Spark", 1200, seed=13)),
                 encoding="utf-8")
    return str(p)


def _run(argv, monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["compress"] + argv)
    main()
    return capsys.readouterr().out


def test_iter_lines_matches_read_split(tmp_path):
    p = tmp_path / "x.log"
    for content in ["", "a", "a\nb", "a\nb\n", "\n\n", "x" * 3000 + "\ny"]:
        p.write_text(content, encoding="utf-8")
        with open(p, encoding="utf-8") as f:
            streamed = list(_iter_lines(f, bufsize=7))  # tiny buffer: cross-block carry
        assert streamed == content.split("\n"), repr(content)


def test_pack_unpack_roundtrip(log_file, tmp_path, monkeypatch, capsys):
    lzj = str(tmp_path / "out.lzj")
    back = str(tmp_path / "back.log")
    out = _run(["pack", log_file, lzj, "--format", FMT], monkeypatch, capsys)
    assert "CR" in out
    _run(["unpack", lzj, back], monkeypatch, capsys)
    assert open(back, encoding="utf-8").read() == open(log_file, encoding="utf-8").read()


def test_pack_chunked_bounded_roundtrip(log_file, tmp_path, monkeypatch, capsys):
    lzj = str(tmp_path / "out.lzjm")
    back = str(tmp_path / "back.log")
    _run(["pack", log_file, lzj, "--format", FMT, "--chunk-lines", "300"],
         monkeypatch, capsys)
    assert open(lzj, "rb").read(4) == b"LZJM"
    _run(["unpack", lzj, back], monkeypatch, capsys)
    assert open(back, encoding="utf-8").read() == open(log_file, encoding="utf-8").read()


def test_pack_from_stdin(log_file, tmp_path, monkeypatch, capsys):
    lzj = str(tmp_path / "out.lzj")
    back = str(tmp_path / "back.log")
    data = open(log_file, "rb").read()
    monkeypatch.setattr(sys, "stdin",
                        type("S", (), {"buffer": io.BytesIO(data)})())
    _run(["pack", "-", lzj, "--format", FMT, "--chunk-lines", "500"],
         monkeypatch, capsys)
    _run(["unpack", lzj, back], monkeypatch, capsys)
    assert open(back, "rb").read() == data


def test_stream_unpack_and_range(log_file, tmp_path, monkeypatch, capsys):
    lzjs = str(tmp_path / "out.lzjs")
    back = str(tmp_path / "back.log")
    _run(["stream", log_file, lzjs, "--format", FMT, "--chunk-lines", "250"],
         monkeypatch, capsys)
    assert open(lzjs, "rb").read(4) == b"LZJS"
    _run(["unpack", lzjs, back], monkeypatch, capsys)
    want = open(log_file, encoding="utf-8").read()
    assert open(back, encoding="utf-8").read() == want

    ranged = str(tmp_path / "range.log")
    out = _run(["unpack", lzjs, ranged, "--range", "300:400"], monkeypatch, capsys)
    assert "decoded 2/5 chunks" in out
    assert open(ranged, encoding="utf-8").read() == "\n".join(want.split("\n")[300:700])


def test_stream_append_cli(log_file, tmp_path, monkeypatch, capsys):
    lzjs = str(tmp_path / "out.lzjs")
    back = str(tmp_path / "back.log")
    _run(["stream", log_file, lzjs, "--format", FMT, "--chunk-lines", "400"],
         monkeypatch, capsys)
    _run(["stream", log_file, lzjs, "--append", "--chunk-lines", "400"],
         monkeypatch, capsys)
    _run(["unpack", lzjs, back], monkeypatch, capsys)
    want = open(log_file, encoding="utf-8").read()
    assert open(back, encoding="utf-8").read() == want + "\n" + want


def _run_fail(argv, monkeypatch, capsys):
    """Run a CLI invocation expected to fail operationally: returns
    (exit_code, stderr)."""
    monkeypatch.setattr(sys, "argv", ["compress"] + argv)
    with pytest.raises(SystemExit) as ei:
        main()
    err = capsys.readouterr().err
    return ei.value.code, err


@pytest.mark.parametrize("argv", [
    ["pack", "{missing}", "{out}"],
    ["stream", "{missing}", "{out}", "--format", FMT],
    ["unpack", "{missing}", "{out}"],
    ["inspect", "{missing}"],
    ["grep", "{missing}", "ERROR"],
    ["agg", "{missing}", "--by-template"],
    ["extract", "{missing}"],
    ["fsck", "{missing}"],
    ["repair", "{missing}"],
])
def test_missing_input_exits_2_one_line(argv, tmp_path, monkeypatch, capsys):
    sub = {"missing": str(tmp_path / "nope.lzjs"), "out": str(tmp_path / "o")}
    code, err = _run_fail([sub.get(a.strip("{}"), a) for a in argv],
                          monkeypatch, capsys)
    assert code == 2
    assert err.startswith("error: ") and err.count("\n") == 1
    assert "Traceback" not in err


@pytest.mark.parametrize("argv", [
    ["unpack", "{junk}", "{out}"],
    ["inspect", "{junk}"],
    ["grep", "{junk}", "ERROR"],
    ["agg", "{junk}", "--by-template"],
    ["fsck", "{junk}"],
    ["repair", "{junk}"],
])
def test_bad_magic_exits_2_one_line(argv, tmp_path, monkeypatch, capsys):
    junk = tmp_path / "junk.bin"
    junk.write_bytes(b"definitely not a logzip archive\n" * 4)
    sub = {"junk": str(junk), "out": str(tmp_path / "o")}
    code, err = _run_fail([sub.get(a.strip("{}"), a) for a in argv],
                          monkeypatch, capsys)
    assert code == 2
    assert err.startswith("error: ") and err.count("\n") == 1
    assert "magic" in err
    assert "Traceback" not in err


def test_append_onto_non_lzjs_exits_2(tmp_path, monkeypatch, capsys):
    target = tmp_path / "plain.log"
    target.write_text("not an archive\n", encoding="utf-8")
    src = tmp_path / "in.log"
    src.write_text("one line", encoding="utf-8")
    code, err = _run_fail(["stream", str(src), str(target), "--append"],
                          monkeypatch, capsys)
    assert code == 2
    assert err.startswith("error: ") and "LZJS" in err and err.count("\n") == 1
    assert "Traceback" not in err
    # the target was not clobbered by the failed append
    assert target.read_text(encoding="utf-8") == "not an archive\n"


def test_inspect_all_three_magics(log_file, tmp_path, monkeypatch, capsys):
    lzj = str(tmp_path / "a.lzj")
    lzjm = str(tmp_path / "a.lzjm")
    lzjs = str(tmp_path / "a.lzjs")
    _run(["pack", log_file, lzj, "--format", FMT], monkeypatch, capsys)
    _run(["pack", log_file, lzjm, "--format", FMT, "--chunk-lines", "300"],
         monkeypatch, capsys)
    _run(["stream", log_file, lzjs, "--format", FMT, "--chunk-lines", "300"],
         monkeypatch, capsys)

    # pack without --chunk-lines still frames one chunk in LZJM
    out = _run(["inspect", lzj], monkeypatch, capsys)
    assert "LZJM multi-chunk archive: 1200 lines in 1 chunks" in out

    out = _run(["inspect", lzjm], monkeypatch, capsys)
    assert "LZJM multi-chunk archive: 1200 lines in 4 chunks" in out
    assert "line-weighted match_rate" in out
    assert "chunk   0: 300 lines" in out

    out = _run(["inspect", lzjs], monkeypatch, capsys)
    assert "LZJS stream: 1200 lines in 4 chunks" in out
    assert "session store:" in out
    assert "chunk   0" in out


@pytest.mark.parametrize("argv,phases", [
    (["grep", "{lzjs}", "rdd_7", "--stats"],
     ("screen", "decode", "filter", "materialize")),
    (["agg", "{lzjs}", "--top", "Level", "--stats"], ("decode", "aggregate")),
])
def test_query_stats_print_phase_seconds(log_file, tmp_path, monkeypatch, capsys, argv, phases):
    lzjs = str(tmp_path / "a.lzjs")
    _run(["stream", log_file, lzjs, "--format", FMT, "--chunk-lines", "300"],
         monkeypatch, capsys)
    monkeypatch.setattr(sys, "argv", ["compress"] + [a.format(lzjs=lzjs) for a in argv])
    main()
    err = capsys.readouterr().err
    line = next(l for l in err.splitlines() if l.startswith("query: seconds by phase -> "))
    got = dict(p.rsplit(" ", 1) for p in line.split(" -> ", 1)[1].split(", "))
    assert set(phases) <= set(got)
    assert all(float(v) >= 0 for v in got.values())
    assert "query: " in err and " hits; decoded " in err  # the counts still print
