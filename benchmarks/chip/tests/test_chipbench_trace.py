"""trace_reduce on a small trace recorded on a TPU v5e: three kernels
(``wildcard_match``, ``colcodec_transform``, ``distinct_counts``) called
three times each inside ``bench.window``."""

import _chipbench_paths  # noqa: F401
import pytest

import harness
import trace_reduce

FIXTURE = harness.HERE / "tests" / "fixtures" / "v5e_kernels.xplane.pb"
WINDOW_START = 531.967820592  # perf_counter when the window opened


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(FIXTURE, harness.HERE,
                               spans=[("outer", WINDOW_START - 1, WINDOW_START + 1),
                                      ("inner", WINDOW_START, WINDOW_START + 0.004)],
                               window_start=WINDOW_START)


def test_busy_and_window(reduced):
    assert reduced["devices"] == 1
    assert 0.02 < reduced["window_s"] < 0.05
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_each_kernel_has_device_time(reduced):
    k = reduced["kernel_s"]
    assert set(k) == {"wildcard_match", "colcodec_transform", "distinct_counts"}
    assert all(v > 0 for v in k.values())
    assert sum(k.values()) <= reduced["busy_s"]
    # only the custom call counts, not the fusion that reads its output
    assert k["wildcard_match"] == pytest.approx(reduced["op_s"]["wildcard_match"])
    assert "convert_bitcast_fusion" in reduced["op_s"]


def test_breakdown(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert ops[0][0] == "wildcard_match"
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert set(gaps) <= {"outer", "inner"}
    assert sum(gaps.values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"])


def test_op_name():
    assert trace_reduce.op_name("%wildcard_match.1 = s32[8] custom-call(x)") == "wildcard_match"
    assert trace_reduce.op_name("%copy-start = (s32[8]) copy-start(x)") == "copy-start"
