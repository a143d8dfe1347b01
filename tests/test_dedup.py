"""Dedup fast path == non-dedup path, byte for byte, plus round-trips on
the corpora the fast path has to survive: duplicate-heavy, near-duplicate,
format-mismatch, over-length."""

import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.codec import LogzipConfig, compress, decompress
from repro.core.ise import ISEConfig
from repro.data.loggen import DATASETS, generate_lines
from strategies import SPARK_FORMAT, records

FMT = "<Date> <Time> <Level> <Component>: <Content>"
CFG_FAST = ISEConfig(min_sample=100, max_iters=3)


def _both(lines: list[str], cfg: LogzipConfig) -> tuple[bytes, bytes]:
    return compress(lines, cfg), compress(lines, dataclasses.replace(cfg, dedup=False))


@pytest.mark.parametrize("level", [2, 3])
def test_dedup_identity_synthetic(level, spark_lines):
    cfg = LogzipConfig(level=level, kernel="none", format=DATASETS["Spark"]["format"],
                       ise=CFG_FAST)
    a, b = _both(spark_lines[:1200], cfg)
    assert a == b
    assert decompress(a) == spark_lines[:1200]


def test_dedup_identity_duplicate_heavy():
    base = list(generate_lines("HDFS", 120, seed=3))
    lines = base * 12  # 92% exact duplicates
    rng = np.random.default_rng(0)
    lines = [lines[i] for i in rng.permutation(len(lines))]
    cfg = LogzipConfig(level=3, kernel="none", format=DATASETS["HDFS"]["format"],
                       ise=CFG_FAST)
    a, b = _both(lines, cfg)
    assert a == b
    assert decompress(a) == lines


def test_dedup_identity_adversarial_mix():
    """Near-duplicates (shared prefixes, one token differs), format
    mismatches, over-length lines, empties — all through both paths."""
    lines = []
    for i in range(40):
        lines.append(f"17/06/09 20:10:{i % 60:02d} INFO a.b: block blk_{i % 4} ok")
        lines.append(f"17/06/09 20:10:{i % 60:02d} INFO a.b: block blk_{i % 4} ok")  # exact dup
        lines.append(f"17/06/09 20:10:{i % 60:02d} INFO a.b: block blk_{i % 4} lost")  # near-dup
    lines += ["no format here", "", "* * *", "x " * 300, "x" * 4000, "\x02\x00 ctl", "日志"] * 3
    cfg = LogzipConfig(level=3, kernel="none", format=FMT,
                       ise=ISEConfig(min_sample=30, max_iters=2), max_tokens=64)
    a, b = _both(lines, cfg)
    assert a == b
    assert decompress(a) == lines


line_text = st.text(alphabet=st.characters(blacklist_categories=("Cs",)), max_size=60).filter(
    lambda s: "\n" not in s
)


@settings(max_examples=20, deadline=None)
@given(st.lists(line_text, max_size=30), st.integers(1, 6))
def test_dedup_identity_property(lines, dup_factor):
    """Arbitrary text times an arbitrary duplication factor: the two paths
    must agree byte-for-byte and the archive must round-trip."""
    lines = (lines * dup_factor)[:90]
    cfg = LogzipConfig(level=3, kernel="none", format=FMT,
                       ise=ISEConfig(min_sample=20, max_iters=2))
    a, b = _both(lines, cfg)
    assert a == b
    assert decompress(a) == lines


record_text = records(line_text.filter(lambda s: len(s) < 40))


@settings(max_examples=20, deadline=None)
@given(st.lists(record_text, max_size=20), st.integers(1, 6))
def test_dedup_identity_records_property(records_, dup_factor):
    """Multi-line records: the dedup and reference paths agree byte for
    byte, and the archive round-trips."""
    records_ = (records_ * dup_factor)[:60]
    cfg = LogzipConfig(level=3, kernel="none", format=SPARK_FORMAT,
                       ise=ISEConfig(min_sample=20, max_iters=2))
    a, b = _both(records_, cfg)
    assert a == b
    assert decompress(a) == records_


def test_dedup_speedup_observable():
    """On a duplicate-heavy corpus the fast path must actually skip work:
    distinct-content processing only (whitebox: tokenize cache hits)."""

    base = list(generate_lines("Spark", 300, seed=1))
    lines = base * 10
    cfg = LogzipConfig(level=3, kernel="none", format=DATASETS["Spark"]["format"],
                       ise=CFG_FAST)
    st_on: dict = {}
    st_off: dict = {}
    compress(lines, cfg, stage_times=st_on)
    compress(lines, dataclasses.replace(cfg, dedup=False), stage_times=st_off)
    # 10x duplication -> the distinct-only stages should be markedly
    # cheaper; use a loose 2x bound to stay timing-robust in CI
    assert st_on["tokenize"] < st_off["tokenize"] / 2 + 0.05
