"""Roofline counts come from the logical problem: padding never counts."""

import _chipbench_paths  # noqa: F401
import numpy as np
import pytest

import harness


def counter(kernel):
    return harness.roofline_counter(kernel)


def test_wildcard_match_counts_tokens_not_padding():
    c = counter("wildcard_match")
    logs = np.full((3, 8), 5, np.int32)
    lens = np.array([2, 5, 8], np.int32)
    tmpl = np.full((2, 4), 5, np.int32)
    tl = np.array([3, 4], np.int32)
    rec = c.record(logs, lens, tmpl, tl)
    ops, nbytes = c.count(rec)
    assert ops == 2 * 15 * 7
    assert nbytes == 4 * (15 + 3 + 7 + 2) + 3 * 2
    # wider token and template axes, and a sentinel template, change nothing
    padded = c.record(np.pad(logs, ((0, 0), (0, 24))), lens,
                      np.pad(np.vstack([tmpl, tmpl[:1]]), ((0, 0), (0, 12))),
                      np.array([3, 4, -1], np.int32))
    assert c.count(padded) == (ops, nbytes)


def test_colcodec_counts_values_not_padding():
    c = counter("colcodec_transform")
    vals = np.zeros((2, 100), np.int32)
    rec = c.record(vals, np.array([10, 30]), np.array([1, 2]))
    assert c.count(rec) == (6 * 40, 8 * 40 + 24)
    assert c.count(c.record(np.zeros((2, 4096), np.int32), np.array([10, 30]),
                            np.array([1, 2]))) == c.count(rec)


def test_distinct_counts_counts_rows_times_bins():
    c = counter("distinct_counts")
    assert c.count(c.record(np.zeros(1000, np.int64), 7)) == (2 * 1000 * 7, 8 * 1000 + 4 * 7)


@pytest.mark.parametrize("kernel", ["wildcard_match", "colcodec_transform", "distinct_counts"])
def test_every_kernel_names_its_peak_and_trace_name(kernel):
    c = counter(kernel)
    assert c.PEAK_OPS in harness.peaks("TPU v5 lite")
    assert kernel in c.TRACE_NAMES


def test_unknown_device_has_no_peaks():
    with pytest.raises(harness.CellError):
        harness.peaks("TPU v9 imaginary")


def test_share_is_the_least_time_over_kernel_time():
    pk = harness.peaks("TPU v5 lite")
    calls = {"distinct_counts": [{"rows": 8192, "bins": 4}]}
    least = (8 * 8192 + 16) / (pk["hbm_gb_s"] * 1e9)  # bytes bound
    rec = harness.Record("c", {}, {}, {}, {"kernel_s": {"distinct_counts": least * 4}},
                         calls, pk)
    assert rec.roofline_pct("distinct_counts") == pytest.approx(25.0)
    rec.trace = {"kernel_s": {"distinct_counts": 0.0}}
    assert rec.roofline_pct("distinct_counts") is None


def test_kernel_calls_record_each_entry_point_while_installed():
    from repro.kernels import ops

    kc = harness.KernelCalls()
    orig = ops.distinct_counts
    kc.install()
    try:
        ops.distinct_counts(np.zeros(100, np.int64), 5)
    finally:
        kc.uninstall()
    assert ops.distinct_counts is orig
    ops.distinct_counts(np.zeros(10, np.int64), 5)  # not recorded
    assert kc.calls["distinct_counts"] == [{"rows": 100, "bins": 5}]
    assert set(kc.calls) == {"wildcard_match", "colcodec_transform", "distinct_counts"}
