"""The query cell's archive is built by the program once per checkout
and then read from the cache; its key follows what its bytes depend on."""

import time

import _chipbench_paths  # noqa: F401

import harness

SEED = 2**37 + 5
SMALL = {"session_lines": 4096, "max_blocks": 1}


def small_cell():
    cell = harness.find_cell("hdfs.query")
    cell.config = dict(cell.config, chunk_lines=1024)
    return cell


def test_second_run_reads_the_cached_archive(tmp_path, monkeypatch):
    cell = small_cell()
    drv = cell.driver()
    monkeypatch.setattr(drv.System, "archives", tmp_path / "archives")
    outs = [harness.run_cell(cell, SEED + i, 1.0, False, time.perf_counter(),
                             system=drv.System(), overrides=SMALL, on_chip=False)
            for i in range(2)]
    assert [o["correct"] for o in outs] == [True, True], [o["checks"] for o in outs]
    assert len(list((tmp_path / "archives").glob("*.lzjs"))) == 1
    assert outs[0]["metrics"]["cr"] == outs[1]["metrics"]["cr"]


def test_archive_key_follows_config_length_and_platform():
    drv = small_cell().driver()
    cfg = small_cell().config
    k = drv.archive_key(cfg, 4096, "tpu")
    assert k == drv.archive_key(dict(cfg), 4096, "tpu")
    assert k != drv.archive_key(cfg, 4097, "tpu")
    assert k != drv.archive_key(cfg, 4096, "cpu")
    assert k != drv.archive_key(dict(cfg, chunk_lines=2048), 4096, "tpu")


def test_archive_key_follows_the_program_source(tmp_path, monkeypatch):
    drv = small_cell().driver()
    cfg = small_cell().config
    src = tmp_path / "src" / "repro"
    src.mkdir(parents=True)
    (src / "stream.py").write_text("A = 1\n")
    monkeypatch.setattr(drv, "ROOT", tmp_path)
    k = drv.archive_key(cfg, 4096, "tpu")
    (src / "stream.py").write_text("A = 2\n")
    assert drv.archive_key(cfg, 4096, "tpu") != k
