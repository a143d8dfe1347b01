"""The ``spark`` corpus: log4j records, 2% of them with a stack trace that
makes about half the bytes, in the 128, 256 and 512 token buckets."""

import re

import _chipbench_paths  # noqa: F401
import pytest

import corpus
import harness

SEED = 2**40 + 977  # larger than 32 bits, as the benchmark's seeds are
# the program's default delimiters (tokenizer.DEFAULT_DELIMITERS), copied
TOKEN = re.compile(r"[^ \t,;:=\n]+")


@pytest.fixture(scope="module")
def cfg():
    return harness.load_json(harness.HERE / "configs" / "spark.json")


@pytest.fixture(scope="module")
def records(cfg):
    return corpus.generate(cfg, SEED, 20_000)


def test_same_seed_same_records(cfg, records):
    assert corpus.generate(cfg, SEED, 20_000) == records
    assert corpus.generate(cfg, SEED + 1, 20_000) != records


def test_statement_universe(cfg):
    stmts = corpus.statements(cfg["corpus"])
    assert len(stmts) == cfg["statement_count"] == 36
    assert sum("\n\tat " in t for t, _ in stmts) == 8


def test_trace_share_of_records_and_bytes(records):
    # the stated shares (configs/spark.json, assumed.trace_share): about
    # 2% of records, about half the bytes; within +-20% of each
    traced = [r for r in records if "\n\tat " in r]
    share = len(traced) / len(records)
    byte_share = corpus.line_bytes(traced) / corpus.line_bytes(records)
    assert 0.016 <= share <= 0.024, share
    assert 0.40 <= byte_share <= 0.60, byte_share


def test_widths_reach_the_wide_token_buckets(records):
    widths = {len(TOKEN.findall(r.split(": ", 1)[1])) for r in records if "\n\tat " in r}
    assert any(w <= 128 for w in widths)
    assert any(128 < w <= 256 for w in widths)
    assert any(256 < w <= 512 for w in widths)
    assert max(widths) <= 512  # every trace fits the token budget


def test_lines_are_as_wide_as_the_source(cfg, records):
    # per physical line, as LogHub counts the dataset's 83 B a line
    lines = sum(r.count("\n") + 1 for r in records)
    ratio = corpus.line_bytes(records) / lines / cfg["dataset_bytes_per_line"]
    assert 0.97 < ratio < 1.08, ratio


def test_records_follow_the_format(cfg, records):
    from reference import Header

    hdr = Header(cfg["format"])
    parsed = [hdr.fields(r) for r in records]
    assert 0 < sum(p is None for p in parsed) < 0.01 * len(records)
    assert {p["Level"] for p in parsed if p} == {"INFO", "WARN", "ERROR"}
