"""A configuration, a traffic mix and a per-layer metric that exist only
as new files (and new BENCHMARK.json entries) are found by name and run,
with no file of the benchmark edited."""

import json
import shutil
import time

import _chipbench_paths  # noqa: F401

import harness


def test_new_files_are_found_and_run(tmp_path):
    base = tmp_path / "bench"
    shutil.copytree(harness.HERE, base, ignore=shutil.ignore_patterns("tests", ".cache"))
    cfg = harness.load_json(base / "configs" / "hdfs.json")
    cfg.update(name="hdfs_small", session_lines=2048, chunk_lines=1024)
    (base / "configs" / "hdfs_small.json").write_text(json.dumps(cfg))
    (base / "traffic" / "ingest_tiny.json").write_text(json.dumps(
        {"driver": "ingest", "corpus_sessions": 3, "warmup_lines": 512, "check_every": 64}))
    (base / "metrics" / "pipeline.parse_ms_per_mb.py").write_text(
        "def read(rec):\n"
        "    return 1000.0 * rec.stage_s['parse'] / rec.counters['input_mb']\n")
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    bench["workloads"].append({"name": "hdfs_small.ingest_tiny", "config": "hdfs_small",
                               "traffic": "ingest_tiny", "chips": 1, "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ingest_mb_s", "cr"):
            m["workloads"].append("hdfs_small.ingest_tiny")
    bench["per_layer"].append({"name": "pipeline.parse_ms_per_mb", "unit": "ms/MB",
                               "better": "lower", "source": "program_span",
                               "layer": "chunk pipeline (core/stages.py)",
                               "moves": "ingest_mb_s",
                               "workloads": ["hdfs_small.ingest_tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.find_cell("hdfs_small.ingest_tiny", tmp_path / "BENCHMARK.json", base)
    assert cell.config["session_lines"] == 2048
    assert cell.traffic["corpus_sessions"] == 3
    assert {m["name"] for m in cell.end_to_end} == {"ingest_mb_s", "cr", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["pipeline.parse_ms_per_mb"]
    rec = harness.Record(cell.name, {"input_mb": 2.0}, {"parse": 0.5}, {}, None, {}, None, base)
    assert harness.metric_reader("pipeline.parse_ms_per_mb", base).read(rec) == 250.0

    out = harness.run_cell(cell, 2**33 + 1, 1.0, False, time.perf_counter(), on_chip=False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"ingest_mb_s", "cr", "setup_s"}


def test_missing_parts_are_errors(tmp_path):
    import pytest

    (tmp_path / "BENCHMARK.json").write_text(json.dumps(
        {"workloads": [{"name": "x.y", "config": "x", "traffic": "y", "chips": 1}],
         "end_to_end": [], "per_layer": []}))
    with pytest.raises(harness.CellError):
        harness.find_cell("x.y", tmp_path / "BENCHMARK.json", tmp_path)
    with pytest.raises(harness.CellError):
        harness.find_cell("nope", tmp_path / "BENCHMARK.json", tmp_path)
