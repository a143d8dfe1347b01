"""Ingest driver: one closed-loop producer feeds the corpus to logzip's
``stream`` path as fast as it takes lines, and seals one LZJS session
per ``session_lines`` lines (the rotation unit), each into a new file at
the next offset of the corpus.

Traffic parameters (``traffic/<name>.json``): ``corpus_sessions`` (the
corpus holds that many sessions of lines and wraps only beyond them; the
first is the deployment's reference rotation, the same in every run, and
the rest come from the seed),
``warmup_lines`` (a throwaway session compressed in set-up, of lines from
another stream of the generator, which the window never feeds),
``check_every`` (lines fed between looks at the clock).

End-to-end metrics: ``ingest_mb_s``, the raw bytes (newline included)
of every line fed in the window over the time from the window's start
until the last session's ``close()`` has returned; and ``cr``, the raw
bytes of the window's first sealed session (the reference rotation) over
its archive bytes (a run that seals no full session is not correct).

``correct``: every session sealed in the window is read back after the
window and compared line for line with its slice of the corpus.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import corpus  # noqa: E402
from harness import Check, compile_log  # noqa: E402

MAIN_PATH_OPS = ("wildcard_match", "colcodec_transform")


class System:
    """The program under test: ``stream`` sessions and ``unpack``."""

    def open_session(self, path, cfg: dict, stage_times=None):
        from repro.core.codec import LogzipConfig
        from repro.core.stream import StreamingCompressor

        return StreamingCompressor(
            str(path), LogzipConfig(level=cfg["level"], kernel=cfg["kernel"],
                                    format=cfg["format"]),
            chunk_lines=cfg["chunk_lines"], stage_times=stage_times)

    def read_session(self, path) -> list[str]:
        from repro.core.stream import LZJSReader

        rd = LZJSReader(str(path))
        try:
            return rd.read_all()
        finally:
            rd.close()


def _slice(lines: list[str], start: int, stop: int) -> list[str]:
    """``lines[start:stop]`` of the corpus repeated end to end."""
    n = len(lines)
    out: list[str] = []
    while start < stop:
        a = start % n
        b = min(n, a + stop - start)
        out.extend(lines[a:b])
        start += b - a
    return out


def setup(run) -> dict:
    cfg = run.cell.config
    s_lines = run.param("session_lines")
    with run.span("generate"):
        lines = corpus.reference(cfg, s_lines) + corpus.generate(
            cfg, run.seed, (run.param("corpus_sessions") - 1) * s_lines)
    with run.span("warmup"):
        sc = run.system.open_session(run.workdir / "warmup.lzjs", cfg)
        for line in corpus.generate(cfg, run.seed, run.param("warmup_lines"), stream=1):
            sc.feed_line(line)
        sc.close()
        os.remove(run.workdir / "warmup.lzjs")
    return {"lines": lines, "session_lines": s_lines, "sessions": []}


def window(run, state) -> dict:
    lines, s_lines = state["lines"], state["session_lines"]
    cfg = run.cell.config
    every = run.param("check_every")
    sessions = state["sessions"]
    log = []  # per session: lines, seconds into the window at its seal, compiles
    comp = compile_log()
    c_last = comp.snapshot()
    pos = 0
    t_start = time.perf_counter()
    deadline = t_start + run.seconds
    done = False
    while not done:
        path = run.workdir / f"session_{len(sessions):04d}.lzjs"
        sc = run.system.open_session(path, cfg, run.stage_sink)
        start = i = pos
        with run.span("feed"):
            while i < start + s_lines:
                j = min(start + s_lines, i + every)
                for line in _slice(lines, i, j):
                    sc.feed_line(line)
                i = j
                if time.perf_counter() >= deadline:
                    done = True
                    break
        with run.span("seal"):
            sc.close()
        sessions.append((path, start, i - start))
        pos = i
        c_now = comp.snapshot()
        log.append({"lines": i - start, "sealed_at_s": time.perf_counter() - t_start,
                    **{k: c_now[k] - c_last[k] for k in c_now}})
        c_last = c_now
    t_end = time.perf_counter()

    raw = sum(corpus.line_bytes(_slice(lines, a, a + n)) for _, a, n in sessions)
    first_path, first_start, first_n = sessions[0]
    cr = None
    if first_n == s_lines:
        cr = corpus.line_bytes(_slice(lines, first_start, first_start + first_n)) \
            / os.path.getsize(first_path)
    run.counters.update(input_mb=raw / 1e6, lines=pos, sessions=len(sessions),
                        full_sessions=sum(n == s_lines for *_, n in sessions),
                        corpus_lines=len(lines), window_s=t_end - t_start,
                        session_log=log)
    return {"metrics": {"ingest_mb_s": raw / 1e6 / (t_end - t_start), "cr": cr},
            "attempted": pos, "failed": 0}


def check(run, state) -> list[Check]:
    lines, sessions = state["lines"], state["sessions"]
    bad = 0
    for path, start, n in sessions:
        want = _slice(lines, start, start + n)
        try:
            got = run.system.read_session(path)
        except Exception as e:  # noqa: BLE001 - an unreadable session is all wrong
            print(f"session {path.name}: cannot be read back: {e!r}", file=sys.stderr)
            bad += n
            continue
        bad += sum(a != b for a, b in zip(got, want)) + abs(len(got) - len(want))
    run.counters["lines_not_read_back"] = run.counters["failed"] = bad
    state.clear()
    return [Check("lines_not_read_back", bad, 0),
            Check("full_sessions_sealed", run.counters["full_sessions"], 1, "min")]
