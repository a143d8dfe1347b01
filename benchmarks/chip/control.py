#!/usr/bin/env python3
"""The control and the planted faults that ``correct`` has to catch.

    python3 benchmarks/chip/control.py --workload <cell> --fault <name> \
        --seeds 11,12,13 --seconds 10

runs the cell once per seed, in this one process, with the system under
test replaced or broken, and prints each run's compared numbers; every
run has to come out not correct. The benchmark's own runs never do
this. Faults:

- ``control``: the plain reference in the program's place, with the
  configuration's guarantee broken the way a typed column would break
  it: every decimal run loses its leading zeros (``081109`` ->
  ``81109``). For ``ingest`` the sessions are that text, zlib-compressed;
  for ``query`` the answers are the reference's over that text.
- ``alter_line`` (ingest): one line in 997 altered as it is fed.
- ``drop_half`` (ingest): every other line left out.
- ``state_unchanged`` (ingest): a chunk flush that leaves the session as
  it was (nothing is written).
- ``alter_answer`` (query): one hit dropped, or one count off by one,
  where the answer is produced.
- ``drop_half_chunks`` (query): every other chunk of the archive never
  visited.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import re
import sys
import time
import types
import zlib

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
from reference import Reference  # noqa: E402

FAULTS = {"ingest": ("control", "alter_line", "drop_half", "state_unchanged"),
          "query": ("control", "alter_answer", "drop_half_chunks")}
_LEADING_ZEROS = re.compile(r"(?<![0-9])0+(?=[0-9])")


def lossy(line: str) -> str:
    return _LEADING_ZEROS.sub("", line)


class _LossyWriter:
    def __init__(self, path):
        self.path, self.lines = path, []

    def feed_line(self, line: str) -> None:
        self.lines.append(lossy(line))

    def close(self) -> None:
        with open(self.path, "wb") as f:
            f.write(zlib.compress("\n".join(self.lines).encode("utf-8")))


class LossyIngest:
    """The reference compressor (zlib of the lines), lossy on numbers."""

    def open_session(self, path, cfg, stage_times=None):
        return _LossyWriter(path)

    def read_session(self, path) -> list[str]:
        with open(path, "rb") as f:
            return zlib.decompress(f.read()).decode("utf-8").split("\n")


class LossyQuery:
    """The reference query engine over the lossy text."""

    def __init__(self):
        self.refs: dict = {}

    def build_archive(self, path, lines, cfg) -> None:
        w = _LossyWriter(path)
        for line in lines:
            w.feed_line(line)
        w.close()
        self.refs[str(path)] = Reference(LossyIngest().read_session(path), cfg["format"])

    def new_stats(self):
        return types.SimpleNamespace(chunks_opened=0, chunks_total=0, rows_materialized=0)

    def ask(self, path, query, stats):
        return self.refs[str(path)].answer(query)


def control_system(driver: str):
    return LossyIngest() if driver == "ingest" else LossyQuery()


@contextlib.contextmanager
def _patched(obj, attr, new):
    old = getattr(obj, attr)
    setattr(obj, attr, new)
    try:
        yield
    finally:
        setattr(obj, attr, old)


@contextlib.contextmanager
def planted(fault: str):
    """Break the program underneath the timed path."""
    from repro.core import query as Q
    from repro.core.stream import StreamingCompressor as SC

    if fault in ("alter_line", "drop_half"):
        feed = SC.feed_line

        def broken(self, line):
            n = self.__dict__.setdefault("_fault_n", 0)
            self._fault_n = n + 1
            if fault == "drop_half":
                if n % 2 == 0:
                    feed(self, line)
            else:
                feed(self, line + "#" if n % 997 == 0 else line)

        with _patched(SC, "feed_line", broken):
            yield
    elif fault == "state_unchanged":
        with _patched(SC, "flush_chunk", lambda self: None):
            yield
    elif fault == "alter_answer":
        search, count, top_k, hist = Q.search, Q.count, Q.top_k, Q.time_histogram

        def b_search(*a, **k):
            return list(search(*a, **k))[:-1] or [(-1, "")]

        def b_top_k(*a, **k):
            out = top_k(*a, **k)
            return [(out[0][0], out[0][1] + 1)] + out[1:]

        def b_hist(*a, **k):
            out = hist(*a, **k)
            first = next(iter(out))
            return {**out, first: out[first] + 1}

        with _patched(Q, "search", b_search), \
                _patched(Q, "count", lambda *a, **k: count(*a, **k) + 1), \
                _patched(Q, "top_k", b_top_k), _patched(Q, "time_histogram", b_hist):
            yield
    elif fault == "drop_half_chunks":
        chunks = Q._ArchiveChunks.chunks

        def every_other(self):
            for i, c in enumerate(chunks(self)):
                if i % 2 == 0:
                    yield c

        with _patched(Q._ArchiveChunks, "chunks", every_other):
            yield
    else:
        raise ValueError(f"unknown fault {fault!r}")


def run_faulty(cell: harness.Cell, fault: str, seed: int, seconds: float, *,
               overrides=None, on_chip: bool = True) -> dict:
    """One run of ``cell`` with ``fault``; -> the result (``correct``
    should be false)."""
    kind = cell.traffic["driver"]
    if fault not in FAULTS[kind]:
        raise ValueError(f"fault {fault!r} does not apply to a {kind} cell")
    t0 = time.perf_counter()
    if fault == "control":
        return harness.run_cell(cell, seed, seconds, False, t0, overrides=overrides,
                                system=control_system(kind), on_chip=False)
    with planted(fault):
        return harness.run_cell(cell, seed, seconds, False, t0, overrides=overrides,
                                on_chip=on_chip)


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    harness.prepare_process()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_faulty(cell, args.fault, seed, args.seconds)
        caught &= not out["correct"]
        print(f"control {args.workload} fault={args.fault} seed={seed} "
              f"correct={out['correct']} checks={out['checks']}", flush=True)
    print(f"control {args.workload} fault={args.fault}: "
          f"{'every run caught' if caught else 'NOT CAUGHT'}")
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
