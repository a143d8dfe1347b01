"""The readers of the program's dispatch spans: ``dispatch.compile_ms_per_mb``
and ``dispatch.launch_ms_per_mb`` over known sums, None where the program
records no dispatch span; and a small ``thunderbird.ingest`` run whose
program writes those spans stays correct. On the CPU."""

import time

import _chipbench_paths  # noqa: F401
import pytest

import harness

SEED = 2**36 + 99
STAGES = {"ise.match": 4.0, "columns": 1.5, "ise.match.compile": 0.25}
DISPATCH = {"dispatch.wildcard_match": 3.0, "dispatch.wildcard_match.compile": 2.0,
            "dispatch.colcodec_transform": 1.0, "dispatch.colcodec_transform.compile": 0.5}


def read(name, stage_s, input_mb=2.0):
    rec = harness.Record("thunderbird.ingest", {"input_mb": input_mb}, stage_s,
                         {}, None, {}, None)
    return harness.metric_reader(name).read(rec)


def test_compile_ms_per_mb_sums_every_compile_span():
    # dispatch compiles 2.5 s and one outside any wrapper 0.25 s, over 2 MB
    assert read("dispatch.compile_ms_per_mb", {**STAGES, **DISPATCH}) == \
        pytest.approx(1375.0)
    # the dispatch spans without a compile: nothing compiled
    calls = {k: v for k, v in DISPATCH.items() if not k.endswith(".compile")}
    assert read("dispatch.compile_ms_per_mb", {"columns": 1.5, **calls}) == 0.0


def test_launch_ms_per_mb_leaves_the_compiles_out():
    # (3.0 + 1.0) - (2.0 + 0.5) s of dispatch without compiles, over 2 MB
    assert read("dispatch.launch_ms_per_mb", {**STAGES, **DISPATCH}) == pytest.approx(750.0)


@pytest.mark.parametrize("name", ["dispatch.compile_ms_per_mb", "dispatch.launch_ms_per_mb"])
@pytest.mark.parametrize("stage_s,input_mb", [
    (STAGES, 2.0),           # a program without the dispatch spans
    ({}, 2.0),               # no program spans at all
    ({**STAGES, **DISPATCH}, 0.0),  # nothing fed
])
def test_readers_find_nothing_without_the_keys(name, stage_s, input_mb):
    assert read(name, stage_s, input_mb) is None


def test_ingest_run_with_dispatch_spans_is_correct(monkeypatch):
    """The kernel path (interpreted here) writes the dispatch spans into
    the program's sink, as a traced chip run hands it one; the sessions
    still read back line for line."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "interpret", lambda: True)
    cell = harness.find_cell("thunderbird.ingest")
    cell.config = dict(cell.config, chunk_lines=1024)
    drv = cell.driver()
    sink = harness.SpanSink()

    class Spanned(drv.System):
        def open_session(self, path, cfg, stage_times=None):
            return super().open_session(path, cfg, sink)

    # one look at the clock per session: exactly one full session
    sizes = {"session_lines": 2048, "corpus_sessions": 3, "warmup_lines": 512,
             "check_every": 2048}
    out = harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(), system=Spanned(),
                           overrides=sizes, on_chip=False)
    assert out["correct"], out["checks"]
    assert out["checks"]["full_sessions_sealed"]["value"] == 1
    assert {"dispatch.wildcard_match", "dispatch.colcodec_transform"} <= set(sink)
    assert {"ise.match", "columns"} <= set(sink)
    assert {name for name, *_ in sink.spans} == set(sink)
    for name in ("dispatch.compile_ms_per_mb", "dispatch.launch_ms_per_mb"):
        assert read(name, dict(sink)) >= 0
