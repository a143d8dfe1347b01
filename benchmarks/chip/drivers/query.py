"""Query driver: one closed-loop client runs a seeded sequence of
queries (``grep``/``agg``: ``core.query.search``, ``count``, ``top_k``,
``time_histogram``) over one sealed LZJS archive of ``session_lines``
lines, which the program under test built from the deployment's
reference rotation (the same lines in every run). The built archive is
kept in ``.cache/archives/``, keyed by the configuration, the length,
the platform and a hash of the program's and the generator's source, so
that only a checkout's first run builds it; each run queries a copy of
it in its own directory.

Traffic parameters (``traffic/<name>.json``): ``max_blocks`` (blocks of
queries drawn; the window stops early if it runs out, and says so) and
``classes``: each with a ``count`` per block, an
``op`` and its arguments. Every block holds exactly those counts, spread
evenly in one fixed order, with parameters drawn from the seed, so every
seed does the same mix of work; ``warmup`` lists the classes asked once in set-up. A
predicate is ``{"substring": s}``, ``{"regex": p}``, ``{"field": name,
"value": v}``, ``{"all": [pred, ...]}`` (a conjunction), or drawn per
query from the archive's
lines: ``{"substring_of": kind}`` (a value of that kind found in a
random line), ``{"absent_of": kind}`` (a value of that kind found in no
line) or ``{"field_of": name}`` (the field of a random line).

End-to-end metrics: ``query_per_s``, the queries completed in the window
over the time until the last one returned; ``query_p95_ms``, the 95th
percentile of all their latencies; ``cr``, the archive's raw bytes over
its bytes.

``correct``: after the window, every answer of the window is compared
with the plain reference (``reference.py``) over the raw lines.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import corpus  # noqa: E402
from harness import CACHE, ROOT, Check  # noqa: E402
from reference import Header, Reference  # noqa: E402

MAIN_PATH_OPS = ("distinct_counts",)

# what a value of a parameter kind looks like in a line
KIND_RE = {"blk": re.compile(r"blk_-?[0-9]+"),
           "ip": re.compile(r"10\.[0-9]+\.[0-9]+\.[0-9]+")}


def archive_key(cfg: dict, n_lines: int, platform: str) -> str:
    """What the archive's bytes depend on: the configuration, the length,
    the platform, the program's source and the generator's."""
    h = hashlib.sha256(json.dumps([cfg, n_lines, platform], sort_keys=True).encode())
    src = ROOT / "src"
    files = [(str(p.relative_to(src)), p) for p in sorted(src.rglob("*.py"))]
    for name, p in files + [("corpus.py", Path(corpus.__file__))]:
        h.update(name.encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:32]


class System:
    """The program under test: ``stream`` to build, ``grep``/``agg`` to ask."""

    archives = CACHE / "archives"

    def build_archive(self, path, lines: list[str], cfg: dict) -> bool:
        """Put the archive of ``lines`` at ``path``: a copy of the cached
        one, or built by ``stream`` (and then cached). -> from the cache."""
        import jax

        kept = self.archives / f"{cfg['name']}-{archive_key(cfg, len(lines), jax.default_backend())}.lzjs"
        if not kept.is_file():
            from repro.core.codec import LogzipConfig
            from repro.core.stream import StreamingCompressor

            with StreamingCompressor(str(path), LogzipConfig(
                    level=cfg["level"], kernel=cfg["kernel"], format=cfg["format"]),
                    chunk_lines=cfg["chunk_lines"]) as sc:
                for line in lines:
                    sc.feed_line(line)
            self.archives.mkdir(parents=True, exist_ok=True)
            tmp = kept.with_name(f"{kept.name}.{os.getpid()}.part")
            shutil.copyfile(path, tmp)
            os.replace(tmp, kept)
            return False
        shutil.copyfile(kept, path)
        return True

    def new_stats(self):
        from repro.core.query import QueryStats

        return QueryStats()

    def ask(self, path, query: tuple, stats):
        from repro.core import query as Q

        op = query[0]
        if op in ("search", "count"):
            pred = _predicate(Q, query[1])
            if op == "count":
                return Q.count(str(path), pred, stats=stats)
            return list(Q.search(str(path), pred, stats=stats))
        if op == "top_k":
            return Q.top_k(str(path), query[1], k=query[2], stats=stats)
        if op == "time_histogram":
            return Q.time_histogram(str(path), query[1], bucket=query[2], stats=stats)
        raise ValueError(f"unknown query {query!r}")


def _predicate(Q, pred: tuple):
    kind, *arg = pred
    if kind == "and":
        return Q.And(*(_predicate(Q, p) for p in arg))
    return {"substring": Q.Substring, "regex": Q.Regex, "field": Q.FieldEq}[kind](*arg)


def percentile(xs: list[float], q: float) -> float:
    """The ``q`` quantile of ``xs`` by the lower nearest rank (copied from
    the program's soak benchmark: ``s[int(q * (n - 1))]``)."""
    s = sorted(xs)
    return s[min(len(s) - 1, int(q * (len(s) - 1)))]


class _Picker:
    """Draws query parameters from the archive's raw lines."""

    def __init__(self, lines: list[str], fmt: str, rng: np.random.Generator):
        self.lines, self.rng = lines, rng
        self.header = Header(fmt)
        self._rows: dict = {}
        self._text: str | None = None

    def rows_with(self, kind: str) -> list[int]:
        if kind not in self._rows:
            rx = KIND_RE[kind]
            self._rows[kind] = [i for i, line in enumerate(self.lines) if rx.search(line)]
        return self._rows[kind]

    def pred(self, spec: dict) -> tuple:
        if "substring" in spec:
            return ("substring", spec["substring"])
        if "regex" in spec:
            return ("regex", spec["regex"])
        if "field" in spec:
            return ("field", spec["field"], spec["value"])
        if "all" in spec:
            return ("and",) + tuple(self.pred(s) for s in spec["all"])
        if "substring_of" in spec:
            kind = spec["substring_of"]
            rows = self.rows_with(kind)
            line = self.lines[rows[int(self.rng.integers(len(rows)))]]
            found = KIND_RE[kind].findall(line)
            return ("substring", found[int(self.rng.integers(len(found)))])
        if "absent_of" in spec:
            kind = spec["absent_of"]
            while True:  # a value of the kind's shape that no line holds
                if kind == "blk":
                    v = f"blk_{int(self.rng.integers(-2**63, 2**63 - 1))}"
                else:
                    v = "10.{}.{}.{}".format(*self.rng.integers(256, size=3))
                if self._text is None:
                    self._text = "\n".join(self.lines)
                if v not in self._text:
                    return ("substring", v)
        if "field_of" in spec:
            name = spec["field_of"]
            while True:
                f = self.header.fields(self.lines[int(self.rng.integers(len(self.lines)))])
                if f is not None:
                    return ("field", name, f[name])
        raise ValueError(f"unknown predicate spec {spec!r}")

    def query(self, cls: dict) -> tuple:
        op = cls["op"]
        if op in ("search", "count"):
            return (op, self.pred(cls["pred"]))
        if op == "top_k":
            return (op, cls["field"], cls["k"])
        if op == "time_histogram":
            return (op, cls["field"], cls["bucket"])
        raise ValueError(f"unknown op {op!r}")


def schedule(run, lines: list[str], n_blocks: int, salt: int) -> list[tuple[str, tuple]]:
    """``n_blocks`` blocks of (class name, query). Every block holds each
    class's ``count`` queries, spread evenly through the block in the same
    order for every seed; the seed draws each query's parameters."""
    rng = np.random.default_rng([run.seed & (2**64 - 1), run.seed >> 64, salt])
    picker = _Picker(lines, run.cell.config["format"], rng)
    classes = run.param("classes")
    block = [c for _, _, c in sorted(((j + 0.5) / c["count"], k, c)
                                     for k, c in enumerate(classes)
                                     for j in range(c["count"]))]
    return [(c["name"], picker.query(c)) for _ in range(n_blocks) for c in block]


def setup(run) -> dict:
    cfg = run.cell.config
    with run.span("generate"):
        lines = corpus.reference(cfg, run.param("session_lines"))
    archive = run.workdir / "archive.lzjs"
    with run.span("build"):
        run.counters["archive_from_cache"] = bool(run.system.build_archive(archive, lines, cfg))
    with run.span("schedule"):
        queries = schedule(run, lines, run.param("max_blocks"), 0x51)
        warm = schedule(run, lines, 1, 0x5A)
    with run.span("warmup"):
        todo = set(run.param("warmup"))
        for name, q in warm:  # each listed class once
            if name in todo:
                todo.discard(name)
                run.system.ask(archive, q, run.system.new_stats())
    return {"lines": lines, "archive": archive, "queries": queries, "answers": []}


def window(run, state) -> dict:
    archive, queries, answers = state["archive"], state["queries"], state["answers"]
    lat: list[float] = []
    stats = run.system.new_stats()
    ask = run.system.ask
    t_start = time.perf_counter()
    deadline = t_start + run.seconds
    t_end = t_start
    while t_end < deadline and len(answers) < len(queries):
        name, q = queries[len(answers)]
        with run.span(f"query.{name}"):
            t0 = time.perf_counter()
            try:
                ans = ask(archive, q, stats)
            except Exception as e:  # noqa: BLE001 - a failed query is counted, not fatal
                ans = e
            t_end = time.perf_counter()
        lat.append(t_end - t0)
        answers.append((name, q, ans))
    k = len(answers)
    if k == len(queries):
        print(f"query driver: the schedule of {k} queries ran out inside the window",
              file=sys.stderr)
    by_class: dict = {}
    for (name, *_), t in zip(answers, lat):
        by_class.setdefault(name, []).append(t)
    run.counters.update(
        queries=k, window_s=t_end - t_start, chunks_opened=stats.chunks_opened,
        chunks_total=stats.chunks_total, rows_materialized=stats.rows_materialized,
        class_ms={n: {"n": len(v), "median": 1000 * percentile(v, 0.5),
                      "max": 1000 * max(v)} for n, v in sorted(by_class.items())},
        loop="closed, one client (no schedule, so no lateness)")
    return {"metrics": {"query_per_s": k / (t_end - t_start),
                        "query_p95_ms": 1000 * percentile(lat, 0.95),
                        "cr": corpus.line_bytes(state["lines"]) / os.path.getsize(archive)},
            "attempted": k, "failed": 0}


def check(run, state) -> list[Check]:
    ref = Reference(state["lines"], run.cell.config["format"])
    wrong = raised = 0
    for name, q, ans in state["answers"]:
        if isinstance(ans, Exception):
            raised += 1
            print(f"query {name} {q!r} raised {ans!r}", file=sys.stderr)
        elif ans != ref.answer(q):
            wrong += 1
            if wrong <= 3:
                print(f"query {name} {q!r}: answer differs from the reference",
                      file=sys.stderr)
    run.counters["failed"] = wrong + raised
    state.clear()
    return [Check("wrong_answers", wrong, 0), Check("failed_queries", raised, 0)]
