"""The benchmark's corpus generator: a pure function of (config, seed, n)."""

import re

import _chipbench_paths  # noqa: F401
import pytest

import corpus
import harness

SEED = 2**40 + 12345  # larger than 32 bits, as the benchmark's seeds are


@pytest.fixture(scope="module", params=["hdfs", "thunderbird"])
def cfg(request):
    return harness.load_json(harness.HERE / "configs" / f"{request.param}.json")


def test_same_seed_same_bytes(cfg):
    assert corpus.generate(cfg, SEED, 3000) == corpus.generate(cfg, SEED, 3000)


def test_other_seed_other_bytes(cfg):
    a, b = corpus.generate(cfg, SEED, 3000), corpus.generate(cfg, SEED + 1, 3000)
    assert a != b
    assert len(a) == len(b) == 3000


def test_statement_universe_does_not_depend_on_seed(cfg):
    stmts = corpus.statements(cfg["corpus"])
    assert stmts == corpus.statements(cfg["corpus"])
    assert len(stmts) == len({t for t, _ in stmts})
    if cfg["name"] == "thunderbird":
        assert len(stmts) == cfg["statement_count"] == 149


def test_lines_follow_the_format(cfg):
    from reference import Header

    lines = corpus.generate(cfg, SEED, 5000)
    hdr = Header(cfg["format"])
    parsed = [hdr.fields(line) for line in lines]
    # only the configured share of malformed lines fails to parse
    assert 0 < sum(p is None for p in parsed) < 0.01 * len(lines)
    assert 100 < corpus.line_bytes(lines) / len(lines) < 160


def test_hdfs_block_ids_stay_local():
    cfg = harness.load_json(harness.HERE / "configs" / "hdfs.json")
    lines = corpus.generate(cfg, SEED, 40_000)
    pos: dict = {}
    for i, line in enumerate(lines):
        for tok in line.split():
            if tok.startswith("blk_"):
                pos.setdefault(tok, []).append(i)
    spans = sorted(max(v) - min(v) for v in pos.values() if len(v) > 1)
    # a block's lines lie within +-locality_lines of each other
    assert spans[len(spans) // 2] <= 2 * cfg["corpus"]["sessions"]["locality_lines"] + 8


@pytest.mark.parametrize("name", ["hdfs", "thunderbird"])
def test_lines_are_as_wide_as_the_source(name):
    cfg = harness.load_json(harness.HERE / "configs" / f"{name}.json")
    lines = corpus.generate(cfg, SEED, 20_000)
    ratio = corpus.line_bytes(lines) / len(lines) / cfg["dataset_bytes_per_line"]
    # the record width is the source's (never cut); within a few percent
    assert 0.97 < ratio < 1.06, ratio


def test_thunderbird_header_has_the_source_forms():
    cfg = harness.load_json(harness.HERE / "configs" / "thunderbird.json")
    from reference import Header

    hdr = Header(cfg["format"])
    fields = [f for f in (hdr.fields(s) for s in corpus.generate(cfg, SEED, 4000)) if f]
    assert all(f["Location"] == f"{f['User']}/{f['User']}" for f in fields)
    with_pid = [f for f in fields if f["Component"] != "kernel"]
    assert with_pid and all(re.fullmatch(r"\S+\[[0-9]+\]", f["Component"]) for f in with_pid)
    assert all(f["Component"] == "kernel" or f in with_pid for f in fields)


def test_warmup_stream_repeats_no_measured_lines(cfg):
    main = corpus.generate(cfg, SEED, 8000)
    warm = corpus.generate(cfg, SEED, 8000, stream=1)
    assert warm == corpus.generate(cfg, SEED, 8000, stream=1)
    # neither the values nor the statement sequence are stream 0's
    # (only bare malformed lines such as "" can coincide)
    assert len(set(main) & set(warm)) <= 4
    assert main[:8000] == corpus.generate(cfg, SEED, 8000, stream=0)
