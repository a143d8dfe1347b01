"""GB-scale soak harness (DESIGN.md §17, ROADMAP item 4).

Streams a parametric workload (`repro.data.loggen.WorkloadSpec`) through
the real write paths — `StreamingCompressor` directly, and/or the ingest
daemon over its socket protocol — while sampling what ≤40k-line
benchmarks cannot observe: RSS over time (bounded memory under template
drift + cardinality ramps), per-batch latency percentiles, and
TemplateStore/ParamDict growth curves. Emits `BENCH_soak.json`;
`scripts/check_soak_gate.py` turns the curves into pass/fail.

    PYTHONPATH=src python -m benchmarks.soak --smoke            # ~100 MB
    PYTHONPATH=src python -m benchmarks.soak --mb 1024          # nightly
    PYTHONPATH=src python -m benchmarks.soak --smoke --daemon   # + socket path

Corpora are deterministic in `(spec, seed)` and generated lazily — a
multi-GB soak never materializes its input.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import platform
import resource
import tempfile
import time

from repro.core.stages import ISEConfig, LogzipConfig
from repro.core.stream import StreamingCompressor
from repro.data.loggen import WorkloadSpec, generate_workload, generate_workload_multitenant

# same fast-ISE settings as benchmarks/throughput.py: soak measures the
# production sampling regime, not exhaustive clustering
ISE_FAST = ISEConfig(sample_rate=0.01, min_sample=400, max_iters=4)

DEFAULT_REPORT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_soak.json")

# the default soak spec leans on every stressor at once: drift rotates
# the statement universe, the ramp streams never-seen parameter values,
# bursts exercise the Markov path, malformed lines hit the verbatim
# channel. Rates are chosen so a 100 MB smoke sees hundreds of drift
# events yet TemplateStore growth stays far below lines (the gate).
SOAK_SPEC = WorkloadSpec(
    n_templates=64, zipf_s=1.1, pool_size=4096, param_reuse=0.6,
    cardinality_ramp=0.25, burstiness=0.6, malformed_rate=0.002,
    drift_rate=0.0005, mutate_fraction=0.5,
)


def _rss_mb() -> float:
    """Current resident set (VmRSS), MB — /proc on linux, peak-RSS
    fallback elsewhere. No new deps (stdlib only)."""
    try:
        with open("/proc/self/status") as f:
            for ln in f:
                if ln.startswith("VmRSS:"):
                    return int(ln.split()[1]) / 1024.0
    except OSError:
        pass
    return _peak_rss_mb()


def _peak_rss_mb() -> float:
    """High-water resident set, MB (`ru_maxrss` is KB on linux)."""
    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return ru / 1024.0 if platform.system() == "Linux" else ru / (1024.0 ** 2)


def _percentiles(xs: list[float]) -> dict:
    if not xs:
        return {"p50": 0.0, "p95": 0.0, "p99": 0.0, "max": 0.0}
    s = sorted(xs)
    pick = lambda q: s[min(len(s) - 1, int(q * (len(s) - 1)))]  # noqa: E731
    return {"p50": round(pick(0.50), 3), "p95": round(pick(0.95), 3),
            "p99": round(pick(0.99), 3), "max": round(s[-1], 3)}


def _growth_metrics(curve: list[dict], n_lines: int) -> dict:
    """Sublinearity of TemplateStore growth: templates learned in the
    second half of the stream vs the first. A store tracking distinct
    *statements* (drift events) stays well under 1.0 — the first half
    also absorbs the whole initial active set; a store growing with
    *lines* (parse regression: params leaking into templates) pushes
    toward 1.0 and blows the per-1k-lines density cap."""
    if not curve:
        return {}
    t_end = curve[-1]["templates"]
    mid_lines = n_lines / 2
    t_mid = curve[0]["templates"]
    for pt in curve:
        if pt["lines"] <= mid_lines:
            t_mid = pt["templates"]
    out = {
        "templates_final": t_end,
        "params_final": curve[-1]["params"],
        "templates_per_1k_lines": round(t_end / max(1.0, n_lines / 1000.0), 4),
    }
    # store counts advance at chunk cuts; if no chunk landed by the
    # midpoint (tiny daemon soaks) the ratio has no resolution — omit it
    # rather than emit a wild number (the gate skips, density still caps)
    if t_mid > 0:
        out["template_growth_ratio"] = round((t_end - t_mid) / t_mid, 4)
    return out


def _host() -> dict:
    return {"platform": platform.platform(), "python": platform.python_version()}


def _backends() -> dict:
    from repro.kernels import ops

    rep = ops.backend_report()
    return {"interpret_mode": ops.interpret(),
            "backends": {op: info["backend"] for op, info in rep.items()}}


def soak_stream(target_bytes: int, *, spec: WorkloadSpec = SOAK_SPEC,
                seed: int = 0, batch_lines: int = 2048,
                chunk_lines: int = 8192, n_samples: int = 64,
                progress=None) -> dict:
    """Stream ~``target_bytes`` of workload through a
    ``StreamingCompressor`` session. Per-batch latency = wall time to
    feed ``batch_lines`` lines (chunk cuts land inside some batches —
    p99 captures those spikes); RSS/store growth sampled ~``n_samples``
    times across the run."""
    fmt_cfg = LogzipConfig(level=3, kernel="gzip", format=spec.format,
                           ise=ISE_FAST)
    gen = iter(generate_workload(spec, None, seed=seed))
    lat_s: list[float] = []
    curve: list[dict] = []
    rss_start = _rss_mb()
    raw = 0
    n_lines = 0
    # sample cadence from the expected line count (bytes / ~90 B-line)
    sample_every = max(1, int(target_bytes / 90 / batch_lines / max(1, n_samples)))
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "soak.lzjs")
        t0 = time.perf_counter()
        with StreamingCompressor(path, fmt_cfg, chunk_lines=chunk_lines) as sc:
            batch_no = 0
            while raw < target_bytes:
                batch = []
                for _ in range(batch_lines):
                    ln = next(gen)
                    raw += len(ln) + 1
                    batch.append(ln)
                tb = time.perf_counter()
                sc.feed(batch)
                lat_s.append(time.perf_counter() - tb)
                n_lines += len(batch)
                batch_no += 1
                if batch_no % sample_every == 0:
                    st = sc.stats()
                    curve.append({
                        "lines": n_lines, "templates": st["n_templates"],
                        "params": st["n_params"],
                        "bytes_written": st["bytes_written"],
                        "rss_mb": round(_rss_mb(), 1),
                    })
                    if progress is not None:
                        progress(n_lines, raw, curve[-1])
            # final point AFTER close: the tail buffer flushes there, and
            # store counts only advance at chunk cuts
            summary = sc.close()
            st = sc.stats()
            curve.append({"lines": n_lines, "templates": st["n_templates"],
                          "params": st["n_params"],
                          "bytes_written": st["bytes_written"],
                          "rss_mb": round(_rss_mb(), 1)})
        wall = time.perf_counter() - t0
        compressed = os.path.getsize(path)
    out = {
        "mode": "stream",
        "n_lines": n_lines,
        "raw_bytes": raw,
        "compressed_bytes": compressed,
        "compression_ratio": round(raw / compressed, 3),
        "wall_s": round(wall, 2),
        "lines_per_sec": round(n_lines / wall, 1),
        "mb_per_sec": round(raw / 1e6 / wall, 2),
        "batch_lines": batch_lines,
        "chunk_lines": chunk_lines,
        "n_chunks": summary["n_chunks"],
        "latency_ms": _percentiles([s * 1000 for s in lat_s]),
        "rss_mb": {"start": round(rss_start, 1), "end": round(_rss_mb(), 1),
                   "peak": round(_peak_rss_mb(), 1)},
        "growth": _growth_metrics(curve, n_lines),
        "curve": curve,
    }
    out.update(_backends())
    return out


def soak_daemon(target_bytes: int, *, spec: WorkloadSpec = SOAK_SPEC,
                seed: int = 0, n_tenants: int = 4, batch_lines: int = 512,
                chunk_lines: int = 4096, n_samples: int = 32,
                progress=None) -> dict:
    """Drive ~``target_bytes`` through the ingest daemon over its unix
    socket: ``n_tenants`` interleaved workload streams, one client each.
    Per-batch latency = send ``batch_lines`` lines then block on the
    durability ACK (`wait_ack`) — i.e. the fsync-group-commit round
    trip, the daemon's operational latency number."""
    from repro.ingest import IngestClient
    from repro.ingest.service import IngestDaemon

    tenants = [(f"t{k}", spec) for k in range(n_tenants)]
    # expected lines ~ bytes / 90; interleave is line-count driven
    est_lines = max(batch_lines * n_tenants, int(target_bytes / 90))
    gen = iter(generate_workload_multitenant(tenants, est_lines, seed=seed,
                                             burstiness=0.5))
    lat_s: list[float] = []
    curve: list[dict] = []
    rss_start = _rss_mb()
    raw = 0
    n_lines = 0
    sample_every = max(1, est_lines // batch_lines // max(1, n_samples))
    with tempfile.TemporaryDirectory() as d:
        daemon = IngestDaemon(d, cfg=LogzipConfig(level=3, kernel="gzip",
                                                  format=spec.format,
                                                  ise=ISE_FAST),
                              chunk_lines=chunk_lines,
                              max_tenants=n_tenants + 1).start()
        clients = {tid: IngestClient(daemon.address, tid) for tid, _ in tenants}
        try:
            t0 = time.perf_counter()
            batch_no = 0
            done = False
            while raw < target_bytes and not done:
                last_seq: dict[str, int] = {}
                for _ in range(batch_lines * n_tenants):
                    try:
                        tid, ln = next(gen)
                    except StopIteration:
                        done = True
                        break
                    raw += len(ln) + 1
                    last_seq[tid] = clients[tid].send(ln)
                    n_lines += 1
                tb = time.perf_counter()
                for tid, seq in last_seq.items():
                    clients[tid].wait_ack(seq)
                lat_s.append(time.perf_counter() - tb)
                batch_no += 1
                if batch_no % sample_every == 0:
                    stats = daemon.stats()
                    agg = _agg_tenants(stats)
                    agg.update({"lines": n_lines, "rss_mb": round(_rss_mb(), 1)})
                    curve.append(agg)
                    if progress is not None:
                        progress(n_lines, raw, agg)
            for c in clients.values():
                c.flush()
            stats = daemon.stats()
            agg = _agg_tenants(stats)
            agg.update({"lines": n_lines, "rss_mb": round(_rss_mb(), 1)})
            curve.append(agg)
            wall = time.perf_counter() - t0
        finally:
            for c in clients.values():
                c.close()
            daemon.shutdown()
        compressed = sum(
            os.path.getsize(os.path.join(r, f))
            for r, _dirs, files in os.walk(d) for f in files
            if f.endswith(".lzjs"))
    out = {
        "mode": "daemon",
        "n_tenants": n_tenants,
        "n_lines": n_lines,
        "raw_bytes": raw,
        "compressed_bytes": compressed,
        "compression_ratio": round(raw / max(1, compressed), 3),
        "wall_s": round(wall, 2),
        "lines_per_sec": round(n_lines / wall, 1),
        "mb_per_sec": round(raw / 1e6 / wall, 2),
        "batch_lines": batch_lines,
        "chunk_lines": chunk_lines,
        "latency_ms": _percentiles([s * 1000 for s in lat_s]),
        "rss_mb": {"start": round(rss_start, 1), "end": round(_rss_mb(), 1),
                   "peak": round(_peak_rss_mb(), 1)},
        "growth": _growth_metrics(curve, n_lines),
        "curve": curve,
    }
    out.update(_backends())
    return out


def _agg_tenants(stats: dict) -> dict:
    """Collapse per-tenant daemon stats into one curve point (stores are
    per-tenant: sum sizes — the RSS cap sees their union anyway)."""
    return {
        "templates": sum(s["n_templates"] for s in stats.values()),
        "params": sum(s["n_params"] for s in stats.values()),
        "bytes_written": sum(s["bytes_written"] for s in stats.values()),
        "queue_depth": sum(s["queue_depth"] for s in stats.values()),
    }


def run(target_bytes: int, *, daemon: bool = False,
        daemon_bytes: int | None = None, spec: WorkloadSpec = SOAK_SPEC,
        seed: int = 0, verbose: bool = False) -> dict:
    """Full soak report: always the stream path; optionally the daemon
    path at ``daemon_bytes`` (defaults to a quarter of the stream size —
    socket round trips dominate its wall clock)."""
    prog = None
    if verbose:
        def prog(lines, raw, pt):
            print(f"  {lines:>10,} lines  {raw / 1e6:7.1f} MB  "
                  f"templates {pt.get('templates', '?'):>5}  "
                  f"rss {pt.get('rss_mb', '?')} MB", flush=True)
    report = {
        "benchmark": "soak",
        "host": _host(),
        "spec": dataclasses.asdict(spec),
        "seed": seed,
        "target_mb": round(target_bytes / 1e6, 1),
        "runs": {},
    }
    if verbose:
        print(f"stream soak: {target_bytes / 1e6:.0f} MB target", flush=True)
    report["runs"]["stream"] = soak_stream(target_bytes, spec=spec, seed=seed,
                                           progress=prog)
    if daemon:
        db = daemon_bytes if daemon_bytes is not None else target_bytes // 4
        if verbose:
            print(f"daemon soak: {db / 1e6:.0f} MB target", flush=True)
        report["runs"]["daemon"] = soak_daemon(db, spec=spec, seed=seed,
                                               progress=prog)
    return report


def write_report(report: dict, path: str | None = None) -> str:
    out = os.path.abspath(path or DEFAULT_REPORT_PATH)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="~100 MB stream soak (the required CI job)")
    ap.add_argument("--mb", type=float, default=None,
                    help="stream soak size in MB (nightly: >= 1024)")
    ap.add_argument("--daemon", action="store_true",
                    help="also soak the ingest daemon over its socket")
    ap.add_argument("--daemon-mb", type=float, default=None,
                    help="daemon soak size in MB (default: stream/4)")
    ap.add_argument("--drift", type=float, default=SOAK_SPEC.drift_rate)
    ap.add_argument("--ramp", type=float, default=SOAK_SPEC.cardinality_ramp)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=DEFAULT_REPORT_PATH)
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args()
    mb = args.mb if args.mb is not None else (100.0 if args.smoke else 100.0)
    spec = dataclasses.replace(SOAK_SPEC, drift_rate=args.drift,
                               cardinality_ramp=args.ramp)
    report = run(int(mb * 1e6), daemon=args.daemon,
                 daemon_bytes=None if args.daemon_mb is None
                 else int(args.daemon_mb * 1e6),
                 spec=spec, seed=args.seed, verbose=not args.quiet)
    out = write_report(report, args.out)
    for mode, r in report["runs"].items():
        g = r["growth"]
        print(f"{mode:7s} {r['n_lines']:>10,} lines  {r['mb_per_sec']:6.2f} MB/s  "
              f"CR {r['compression_ratio']:5.2f}  "
              f"p99 {r['latency_ms']['p99']:7.1f} ms  "
              f"rss peak {r['rss_mb']['peak']:6.1f} MB  "
              f"templates {g['templates_final']} "
              f"(growth ratio {g.get('template_growth_ratio', 'n/a')})")
    print(f"report: {out}")


if __name__ == "__main__":
    main()
