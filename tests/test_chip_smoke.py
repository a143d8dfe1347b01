"""chip_smoke.py on the CPU: its phases run (kernels in interpret mode)
and give the plain references' answers; its entry point refuses a host
without a TPU and prints no result."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def smoke_run(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("smoke"))
    src = chip_smoke.phase_generate(out, 2000, seed=0)
    return out, src, chip_smoke._read_lines(src), chip_smoke.phase_stream(src, out)


def test_phases_round_trip_and_answer(smoke_run):
    out, src, lines, arch = smoke_run
    assert len(lines) == 2000
    chip_smoke.phase_unpack(src, arch, out)
    counts = chip_smoke.phase_grep(lines, arch)
    assert len(counts) == 2 and all(counts.values())
    top = chip_smoke.phase_agg(lines, arch)
    assert top[0][0] == "INFO" and sum(c for _, c in top) <= len(lines)


def test_kernel_parity_phase(smoke_run):
    out, _, lines, _ = smoke_run
    stages = chip_smoke.phase_kernel_parity(lines, out, n_lines=2000)
    assert stages["kernel"]["ise.match"] > 0 and stages["numpy"]["pack"] > 0


def test_wrong_answer_is_a_failure(smoke_run, monkeypatch):
    _, _, lines, arch = smoke_run
    monkeypatch.setattr(chip_smoke, "SUBSTRING", "no such text")
    with pytest.raises(chip_smoke.SmokeError, match="plain count"):
        chip_smoke.phase_grep(lines[:-1] + [lines[-1] + " no such text"], arch)


def test_device_path_check():
    report = {op: {"backend": "kernel", "interpret": False, "fallbacks": []}
              for op in chip_smoke.MAIN_PATH_OPS}
    stats = {"calls": {op: 1 for op in chip_smoke.MAIN_PATH_CALLS}}
    chip_smoke.check_device_path(report, stats)
    report["wildcard_match"]["interpret"] = True
    with pytest.raises(chip_smoke.SmokeError, match="wildcard_match"):
        chip_smoke.check_device_path(report, stats)


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main(["--lines", "10"]) == 2
    out, err = capsys.readouterr()
    assert "needs a TPU" in err and "cpu" in err
    assert '"ok"' not in out and "Traceback" not in err
