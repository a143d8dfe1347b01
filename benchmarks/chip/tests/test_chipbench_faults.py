"""Every run drives the rest of the harness past its look for a chip; a
sound run comes out correct, and the control and each planted fault
come out not correct. Small sizes, on the CPU."""

import time

import _chipbench_paths  # noqa: F401
import pytest

import control
import harness

SEED = 2**36 + 99
SIZES = {"thunderbird.ingest": {"session_lines": 2048, "corpus_sessions": 3,
                                "warmup_lines": 512, "check_every": 64},
         "hdfs.query": {"session_lines": 4096, "max_blocks": 1}}


def small(name):
    cell = harness.find_cell(name)
    cell.config = dict(cell.config, chunk_lines=1024)
    return cell


@pytest.mark.parametrize("name", sorted(SIZES))
def test_sound_run_is_correct(name):
    out = harness.run_cell(small(name), SEED, 1.0, False, time.perf_counter(),
                           overrides=SIZES[name], on_chip=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "setup_s" in out["metrics"]


@pytest.mark.parametrize("name,fault", [(n, f) for n in sorted(SIZES)
                                        for f in control.FAULTS[small(n).traffic["driver"]]])
def test_control_and_faults_are_caught(name, fault):
    out = control.run_faulty(small(name), fault, SEED, 1.0, overrides=SIZES[name],
                             on_chip=False)
    assert not out["correct"], (fault, out["checks"])
    assert any(c["value"] > c["limit"] for c in out["checks"].values()
               if c["must_be"] == "<=")
