"""The benchmark harness: finds a cell's parts by name, runs it, and
reduces what it measured to the result line.

Everything a cell needs is found by the names in ``BENCHMARK.json``,
so a later cell, configuration, traffic mix or metric is a new file and
a new entry, never an edit:

- ``configs/<config>.json``: the deployment (format, session size,
  corpus parameters, guarantees, ``reduced`` and ``assumed``);
- ``traffic/<traffic>.json``: the mix, a data file whose ``driver``
  names the general driver that reads it (``drivers/<driver>.py``);
- ``metrics/<metric>.py``: one reader per per-layer metric, ``read(rec)``
  over the run's record; it returns None where it finds nothing to read;
- ``roofline/<kernel>.py``: the operations and bytes of one kernel call
  from its logical shapes;
- ``peaks.json``: the chip's peaks by ``device_kind``.

A driver module has ``setup(run)``, ``window(run, state)`` and
``check(run, state)``; see ``drivers/ingest.py``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE = HERE / ".cache"
COMPILE_CACHE = CACHE / "jax"
TRACE_DIR = CACHE / "trace"  # a traced run's profile, deleted once it is read
WINDOW = "bench.window"  # the host annotation around a traced window

# the XLA compile and jaxpr trace events JAX reports (jax.monitoring)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class CellError(Exception):
    """The cell, or one of its parts, cannot be found or read."""


# ------------------------------------------------------------ discovery

def load_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file by path (its name is the path's stem)."""
    if not path.is_file():
        raise CellError(f"no such file: {path}")
    name = "chipbench_" + "_".join(path.relative_to(path.parents[1]).with_suffix("").parts)
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    base: Path

    def driver(self):
        return load_module(self.base / "drivers" / f"{self.traffic['driver']}.py")


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench_path: Path | None = None, base: Path = HERE) -> Cell:
    try:
        bench = load_json(bench_path or ROOT / "BENCHMARK.json")
    except OSError as e:
        raise CellError(f"cannot read BENCHMARK.json: {e}") from e
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    try:
        config = load_json(base / "configs" / f"{w['config']}.json")
        traffic = load_json(base / "traffic" / f"{w['traffic']}.json")
    except OSError as e:
        raise CellError(str(e)) from e
    e2e = [m for m in bench["end_to_end"] if reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in e2e_names and reports(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer, base)


def peaks(kind: str, base: Path = HERE) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    table = load_json(base / "peaks.json")["devices"]
    if kind not in table:
        raise CellError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def roofline_counter(kernel: str, base: Path = HERE):
    return load_module(base / "roofline" / f"{kernel}.py")


def metric_reader(name: str, base: Path = HERE):
    return load_module(base / "metrics" / f"{name}.py")


# ------------------------------------------------------------ the run

@dataclasses.dataclass
class Check:
    """One number compared to decide ``correct``: ``value`` must be at
    most (``kind="max"``) or at least (``kind="min"``) ``limit``."""

    name: str
    value: float
    limit: float
    kind: str = "max"

    @property
    def ok(self) -> bool:
        return self.value <= self.limit if self.kind == "max" else self.value >= self.limit


class SpanSink(dict):
    """A ``stage_times`` sink for the program's ``StageTimer``: besides
    the per-stage sums it keeps each stage's span (name, start, end) on
    the host clock, from the duration each update adds."""

    def __init__(self):
        super().__init__()
        self.spans: list[tuple[str, float, float]] = []

    def __setitem__(self, key, value):
        end = time.perf_counter()
        self.spans.append((key, end - (value - self.get(key, 0.0)), end))
        super().__setitem__(key, value)


@dataclasses.dataclass
class Run:
    """What a driver is given, and what it fills in for the readers."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    workdir: Path
    system: object = None          # the system under test (drivers/<name>.System by default)
    overrides: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    stage_sink: SpanSink | None = None

    def param(self, key: str, default=None):
        """A size or setting: the override (tests), else the traffic's,
        else the configuration's."""
        for src in (self.overrides, self.cell.traffic, self.cell.config):
            if key in src:
                return src[key]
        if default is not None:
            return default
        raise CellError(f"no {key!r} in the cell's traffic or configuration")

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span of the harness's own, kept for the trace's idle
        gaps (only in a traced run)."""
        if not self.trace:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))


class CompileLog:
    """Counts XLA compiles and jaxpr traces, with their seconds; apart,
    the compiles long enough for the persistent cache to keep
    (``slow_threshold_s``), which a later process finds there."""

    def __init__(self, slow_threshold_s: float = 0.0):
        self.n = {COMPILE_EVENT: 0, TRACE_EVENT: 0}
        self.s = {COMPILE_EVENT: 0.0, TRACE_EVENT: 0.0}
        self.slow_threshold_s = slow_threshold_s
        self.n_slow, self.s_slow = 0, 0.0

    def __call__(self, event, duration, **_kw):
        if event in self.n:
            self.n[event] += 1
            self.s[event] += duration
            if event == COMPILE_EVENT and duration >= self.slow_threshold_s:
                self.n_slow += 1
                self.s_slow += duration

    def snapshot(self) -> dict:
        return {"compiles": self.n[COMPILE_EVENT], "compile_s": self.s[COMPILE_EVENT],
                "cacheable_compiles": self.n_slow, "cacheable_compile_s": self.s_slow,
                "traces": self.n[TRACE_EVENT]}


_COMPILES: CompileLog | None = None


def compile_log() -> CompileLog:
    global _COMPILES
    if _COMPILES is None:
        import jax

        _COMPILES = CompileLog(float(jax.config.jax_persistent_cache_min_compile_time_secs))
        jax.monitoring.register_event_duration_secs_listener(_COMPILES)
    return _COMPILES


class KernelCalls:
    """Records the logical inputs of every call of each kernel's entry
    point in ``repro.kernels.ops`` (``ENTRY`` of ``roofline/<kernel>.py``)
    while installed (traced runs only), for the roofline counts."""

    def __init__(self, base: Path = HERE):
        self.counters = {p.stem: load_module(p) for p in sorted((base / "roofline").glob("*.py"))}
        self.calls: dict[str, list] = {k: [] for k in self.counters}
        self._saved: dict = {}

    def install(self) -> None:
        from repro.kernels import ops

        for kernel, counter in self.counters.items():
            orig = getattr(ops, counter.ENTRY)
            self._saved[counter.ENTRY] = orig

            def wrapped(*a, _orig=orig, _calls=self.calls[kernel], _c=counter, **kw):
                out = _orig(*a, **kw)
                _calls.append(_c.record(*a, **kw))
                return out

            setattr(ops, counter.ENTRY, wrapped)

    def uninstall(self) -> None:
        from repro.kernels import ops

        for attr, orig in self._saved.items():
            setattr(ops, attr, orig)
        self._saved.clear()


@dataclasses.dataclass
class Record:
    """Everything the per-layer readers may read."""

    cell: str
    counters: dict
    stage_s: dict
    calls_delta: dict            # jitcache calls per kernel in the window
    trace: dict | None           # trace_reduce.reduce(...) of the window
    kernel_calls: dict           # kernel -> [logical call records]
    peaks: dict | None
    base: Path = HERE

    def roofline_pct(self, kernel: str) -> float | None:
        """Share of the kernel's roofline over the window: the least time
        the chip needs for the work of every call (the larger of ops over
        peak ops and bytes over peak bandwidth), over the kernel's device
        time in the trace. None where there is no call or no time."""
        calls = self.kernel_calls.get(kernel) or []
        t = (self.trace or {}).get("kernel_s", {}).get(kernel, 0.0)
        if not calls or t <= 0 or not self.peaks:
            return None
        counter = roofline_counter(kernel, self.base)
        least = 0.0
        for rec in calls:
            ops, nbytes = counter.count(rec)
            least += max(ops / (self.peaks[counter.PEAK_OPS] * 1e12),
                         nbytes / (self.peaks["hbm_gb_s"] * 1e9))
        return 100.0 * least / t


def device_info(devices) -> dict:
    d = devices[0]
    peak = None
    for dev in devices:
        try:
            stats = dev.memory_stats() or {}
        except Exception:  # noqa: BLE001 - a backend without memory stats
            stats = {}
        if "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def backend_problems(ops_needed) -> list[str]:
    """Main-path ops that do not run compiled on the kernel tier."""
    from repro.kernels import ops

    rep = ops.backend_report()
    return [op for op in ops_needed
            if rep[op]["backend"] != "kernel" or rep[op]["interpret"] or rep[op]["fallbacks"]]


def print_report(name: str, obj) -> None:
    print(f"{name}: {json.dumps(obj, sort_keys=True, default=str)}", flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float, *,
             system=None, overrides: dict | None = None, on_chip: bool = True) -> dict:
    """Set up, measure and check one run of ``cell``; -> the result line
    (a dict), with the compared numbers under ``checks``."""
    import jax

    from repro.kernels import ops
    from repro.kernels.jitcache import bucket_stats, enable_compile_cache

    enable_compile_cache()
    comp = compile_log()
    devices = jax.devices()
    drv = cell.driver()
    with tempfile.TemporaryDirectory(prefix="chipbench_") as tmp:
        run = Run(cell, int(seed), float(seconds), bool(trace), Path(tmp),
                  system=system or drv.System(), overrides=dict(overrides or {}))
        if trace:
            run.stage_sink = SpanSink()
        state = drv.setup(run)
        setup_s = time.perf_counter() - t0
        b0, comp0 = bucket_stats(), comp.snapshot()
        print_report("setup", {"setup_s": setup_s, **comp0})

        kcalls = KernelCalls(cell.base) if trace else None
        if trace:
            kcalls.install()
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        try:
            with (jax.profiler.TraceAnnotation(WINDOW) if trace else contextlib.nullcontext()):
                tw0 = time.perf_counter()
                res = drv.window(run, state)
                tw1 = time.perf_counter()
        finally:
            if trace:
                jax.profiler.stop_trace()
                kcalls.uninstall()
        b1, comp1 = bucket_stats(), comp.snapshot()
        calls_delta, traces_delta = ({k: v - b0[part].get(k, 0) for k, v in b1[part].items()
                                      if v - b0[part].get(k, 0)} for part in ("calls", "traces"))
        device = device_info(devices)
        in_window = {k: comp1[k] - comp0[k] for k in comp1}
        print_report("window", {"seconds": tw1 - tw0, "kernel_calls": calls_delta,
                                "jitcache_traces_in_window": traces_delta,
                                **{f"{k}_in_window": v for k, v in in_window.items()}})
        print_report("backend_report", {
            op: {"backend": r["backend"], "interpret": r["interpret"],
                 "fallbacks": len(r["fallbacks"])}
            for op, r in ops.backend_report().items()})
        print_report("bucket_stats", bucket_stats())
        print_report("counters", run.counters)

        checks = drv.check(run, state)
        del state
        if on_chip:
            bad = backend_problems(drv.MAIN_PATH_OPS)
            checks.append(Check("main_path_ops_not_compiled", len(bad), 0))
            if bad:
                print(f"not compiled on the chip: {bad}", file=sys.stderr)

        out = {"correct": all(c.ok for c in checks) and res["attempted"] > 0,
               "attempted": res["attempted"],
               "failed": run.counters.get("failed", res["failed"])}
        units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
        metrics: dict = {}
        if not trace:
            values = dict(res["metrics"], setup_s=setup_s)
            for m in cell.end_to_end:
                if values.get(m["name"]) is not None:
                    metrics[m["name"]] = {"value": values[m["name"]], "unit": units[m["name"]]}
        else:
            import trace_reduce

            spans = list(run.spans) + list(run.stage_sink.spans)
            tr = trace_reduce.reduce(trace_reduce.find_trace(TRACE_DIR), cell.base, spans=spans,
                                     window_start=tw0)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            pk = peaks(device["kind"], cell.base) if on_chip else None
            rec = Record(cell.name, run.counters, dict(run.stage_sink),
                         calls_delta, tr, kcalls.calls, pk, cell.base)
            for m in cell.per_layer:
                v = metric_reader(m["name"], cell.base).read(rec)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            out["breakdown"] = tr["breakdown"]
        out["metrics"] = metrics
        out["device"] = device
        out["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                                  "must_be": "<=" if c.kind == "max" else ">="}
                         for c in checks}
        return out


def print_result(out: dict) -> None:
    """The checks as the last lines of standard error, then the result
    as the last line of standard output (``checks`` last in it)."""
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (must be {c['must_be']} {c['limit']})",
              file=sys.stderr)
    print(f"correct = {out['correct']}", file=sys.stderr, flush=True)
    line = {k: v for k, v in out.items() if k != "checks"}
    line["checks"] = out["checks"]
    print(json.dumps(line), flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one logzip benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare_process() -> None:
    """Before JAX is imported: its compile cache at a fixed path inside
    the checkout (JAX reads it at import; a moving path never hits), the
    TPU runtime's logs inside the checkout too (unless the environment
    places them), and the program on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    sys.path.insert(0, str(ROOT / "src"))


def main(argv, t0: float) -> int:
    args = parse_args(argv)
    try:
        cell = find_cell(args.workload)
    except CellError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    prepare_process()
    try:
        import jax

        import repro.kernels.ops  # noqa: F401
    except ImportError as e:
        print(f"benchmark: cannot import the program ({e}); run from the root "
              "of a logzip checkout", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"benchmark: the cell needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)", file=sys.stderr)
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0)
    print_result(out)
    return 0
