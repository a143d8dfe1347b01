"""Compression throughput benchmark -> ``BENCH_compress.json``.

Measures ``compress()`` end-to-end (lines/sec, MB/s) with the per-stage
wall-time breakdown from ``codec.StageTimer`` (parse / dedup / tokenize /
encode / ise.cluster / ise.match / spans / columns / pack / kernel), on:

- the 40k-line synthetic HDFS corpus (level 3, gzip kernel) — the
  recorded perf trajectory every PR appends to;
- the same corpus with the dedup fast path disabled (ablation);
- a duplicate-heavy variant (each distinct line repeated ~10x, the
  regime real logs live in — LogShrink/LogLite's observation) where the
  dedup stage collapses most of the work;
- a streaming-session scenario (``bench_streaming``): single-archive vs
  per-chunk-independent vs shared-store ``StreamingCompressor`` CR (the
  session must close >= half the chunking CR gap), plus a footer-index
  random-access check (a 1k-line range decodes only covering chunks);
- a ``device_pipeline`` scenario (ISSUE 3): a 20-chunk streaming session
  through the Pallas kernel matcher with bucketed shapes, recording the
  per-bucket call counts and the recompile (re-trace) counter after
  warmup — the jit-cache contract is zero, and ``check_perf_gate.py``
  fails CI if it regresses. On CPU the kernels run in interpret mode, so
  this scenario's lines/sec calibrates *relative* cost only;
- a ``query`` scenario (ISSUE 4): compressed-domain grep over an LZJS
  session with a rare-template burst — selective literal/regex queries, a
  point param query and a field-equality query, each verified hit-for-hit
  against decompress-then-grep, reporting matched-lines/s, the fraction
  of chunks decoded and the speedup vs the baseline (gated by
  ``check_perf_gate.py``: selective queries must decode <50% of chunks
  and beat the baseline wall clock).

``SEED_REFERENCE`` is the seed-tree measurement of the same 40k-line
HDFS / level-3 / gzip configuration in this container, recorded when the
fast path landed; ``speedup_vs_seed`` in the JSON is computed against it.

PYTHONPATH=src python -m benchmarks.throughput [--quick] [--lines N] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import time

import numpy as np

from repro.core.codec import LogzipConfig, compress, decompress
from repro.core.ise import ISEConfig
from repro.data.loggen import generate_lines

ISE_FAST = ISEConfig(sample_rate=0.01, min_sample=400, max_iters=4)

# seed compress() on this exact benchmark (40k-line synthetic HDFS,
# level 3, gzip kernel), measured in this container at commit 9e78cd3
# before the dedup/vectorization fast path landed.
SEED_REFERENCE = {"lines_per_sec": 3050.0, "wall_s": 13.11, "commit": "9e78cd3"}


def _dup_heavy(name: str, n_lines: int, factor: int = 10, seed: int = 0) -> list[str]:
    """~n_lines lines with each distinct line repeated ``factor``x, shuffled
    deterministically — the exact-duplicate regime of production logs."""
    base = list(generate_lines(name, max(1, n_lines // factor), seed))
    lines = base * factor
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(lines))
    return [lines[i] for i in order]


def bench_one(lines: list[str], cfg: LogzipConfig, label: str, *, verify: bool = True,
              scenario: str | None = None) -> dict:
    raw_bytes = sum(len(l.encode("utf-8", "surrogateescape")) + 1 for l in lines) - 1
    stages: dict[str, float] = {}
    t0 = time.perf_counter()
    blob = compress(lines, cfg, stage_times=stages)
    wall = time.perf_counter() - t0
    if verify:
        assert decompress(blob) == lines, f"{label}: lossless round-trip FAILED"
    return {
        "label": label,
        "scenario": scenario,
        "n_lines": len(lines),
        "raw_mb": raw_bytes / 1e6,
        "level": cfg.level,
        "kernel": cfg.kernel,
        "dedup": cfg.dedup,
        "wall_s": round(wall, 4),
        "lines_per_sec": round(len(lines) / wall, 1),
        "mb_per_sec": round(raw_bytes / 1e6 / wall, 3),
        "compressed_bytes": len(blob),
        "compression_ratio": round(raw_bytes / len(blob), 3),
        "stages_s": {k: round(v, 4) for k, v in sorted(stages.items())},
    }


def bench_streaming(lines: list[str], cfg: LogzipConfig, cr_single: float,
                    chunk_lines: int) -> dict:
    """Streaming-session scenario (ISSUE 2 acceptance): shared-store
    chunked compression must close >= half the CR gap between
    per-chunk-independent and single-archive compression, within 10% of
    the chunked path's lines/sec; random access must decode only the
    chunks covering the requested range."""
    import dataclasses
    import io

    from repro.core.parallel import compress_parallel, decompress_parallel
    from repro.core.stream import LZJSReader, StreamingCompressor

    n = len(lines)
    raw_bytes = sum(len(l.encode("utf-8", "surrogateescape")) + 1 for l in lines) - 1

    t0 = time.perf_counter()
    chunked = compress_parallel(lines, cfg, n_workers=1, chunk_lines=chunk_lines)
    wall_chunked = time.perf_counter() - t0
    assert decompress_parallel(chunked) == lines, "chunked round-trip FAILED"

    # like-for-like CR: the chunked LZJM baseline has no screen frames,
    # so the gap-closure metric excludes them too (their size is measured
    # and <1%-gated in the query scenario, where they earn their keep)
    cfg = dataclasses.replace(cfg, screens=False)
    buf = io.BytesIO()
    t0 = time.perf_counter()
    with StreamingCompressor(buf, cfg, chunk_lines=chunk_lines) as sc:
        sc.feed(lines)
        summary = sc.close()
    wall_stream = time.perf_counter() - t0
    blob = buf.getvalue()

    rd = LZJSReader(io.BytesIO(blob))
    assert rd.read_all() == lines, "streaming round-trip FAILED"

    # random access: a 1k-line range must only decode covering chunks
    # (start clamped so tiny --lines runs still verify a non-empty range)
    start = min(n // 2 + 137, max(n - 1, 0))
    count = min(1000, n - start)
    rd2 = LZJSReader(io.BytesIO(blob))
    got = rd2.read_range(start, count)
    covering = rd2.covering_chunks(start, count)
    ra_ok = (count > 0 and got == lines[start:start + count]
             and rd2.chunks_decoded == len(covering))

    cr_chunked = raw_bytes / len(chunked)
    cr_stream = raw_bytes / len(blob)
    gap = cr_single - cr_chunked
    return {
        "chunk_lines": chunk_lines,
        "n_chunks": summary["n_chunks"],
        "n_templates": summary["n_templates"],
        "cr_single": round(cr_single, 3),
        "cr_chunked": round(cr_chunked, 3),
        "cr_streaming": round(cr_stream, 3),
        "cr_gap_closed": round((cr_stream - cr_chunked) / gap, 3) if gap > 0 else 1.0,
        "chunked_lines_per_sec": round(n / wall_chunked, 1),
        "streaming_lines_per_sec": round(n / wall_stream, 1),
        "throughput_vs_chunked": round(wall_chunked / wall_stream, 3),
        "random_access": {
            "start": start, "count": count,
            "chunks_total": len(rd2), "chunks_covering": len(covering),
            "chunks_decoded": rd2.chunks_decoded, "ok": bool(ra_ok),
        },
    }


def bench_query(lines: list[str], cfg: LogzipConfig, chunk_lines: int) -> dict:
    """Compressed-domain query scenario (ISSUE 4 + ISSUE 7 acceptance):
    hit sets must be byte-identical to decompress-then-grep; the
    selective query must decode <50% of LZJS chunks and beat the
    baseline wall clock; with chunk screens, the point query must open
    O(1) chunks and the aggregations must beat decompress-then-compute
    with zero rows materialized.

    The corpus gets a localized rare-template burst (a "deployment
    event": lines that exist only in a narrow region of the stream) —
    the paper's own motivation for archiving logs is tracing exactly such
    recurrent problems / security incidents later."""
    import io
    import re as _re
    from collections import Counter

    from repro.core import query as Q
    from repro.core.parallel import decompress_parallel
    from repro.core.stream import StreamingCompressor
    from repro.core.tokenizer import LogFormat

    n0 = len(lines)
    at = (n0 * 7) // 10
    burst = [
        f"081109 203545 99 INFO dfs.FSNamesystem: Starting decommission of "
        f"node /10.9.{i % 7}.{i % 11} remaining {i}"
        for i in range(max(60, n0 // 400))
    ]
    lines = lines[:at] + burst + lines[at:]

    buf = io.BytesIO()
    with StreamingCompressor(buf, cfg, chunk_lines=chunk_lines) as sc:
        sc.feed(lines)
    blob = buf.getvalue()

    t0 = time.perf_counter()
    decoded = decompress_parallel(blob)
    t_decompress = time.perf_counter() - t0
    assert decoded == lines, "query benchmark: decode mismatch"

    # a parameter value occurring on as few lines as possible (point query)
    blk_counts = Counter(t for l in lines for t in l.split() if t.startswith("blk_"))
    min_count = min(blk_counts.values())
    rare_blk = min(t for t, c in blk_counts.items() if c == min_count)

    fmt = LogFormat(cfg.format)
    cols, ok_idx, _ = fmt.parse(lines)

    def base_field_eq(field, value):
        return [(i, lines[i]) for r, i in enumerate(ok_idx)
                if cols[field][r] == value]

    # field_eq targets the burst timestamp: Time is monotone, so the
    # manifest field-bound screens confine it to the burst chunks plus
    # the one organic region sharing the value (the ISSUE 7 gate).
    # field_eq_hot (Level=WARN) is everywhere by construction —
    # unprunable, kept as an agreement/throughput row only.
    queries = [
        ("selective_literal", Q.Substring("decommission"),
         lambda: [(i, l) for i, l in enumerate(lines) if "decommission" in l]),
        ("selective_regex", Q.Regex(r"decommission of node /10\.9\.\d+"),
         lambda: [(i, l) for i, l in enumerate(lines)
                  if _re.search(r"decommission of node /10\.9\.\d+", l)]),
        ("param_value", Q.Substring(rare_blk),
         lambda: [(i, l) for i, l in enumerate(lines) if rare_blk in l]),
        ("field_eq", Q.FieldEq("Time", "203545"),
         lambda: base_field_eq("Time", "203545")),
        ("field_eq_hot", Q.FieldEq("Level", "WARN"),
         lambda: base_field_eq("Level", "WARN")),
    ]
    rows = []
    for name, q, base_fn in queries:
        st = Q.QueryStats()
        t0 = time.perf_counter()
        hits = list(Q.search(blob, q, stats=st))
        wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        base_hits = base_fn()
        t_scan = time.perf_counter() - t0
        base_wall = t_decompress + t_scan
        rows.append({
            "query": name,
            "hits": len(hits),
            "hits_agree": hits == base_hits,
            "wall_s": round(wall, 4),
            "matched_lines_per_sec": round(len(hits) / wall, 1) if wall else None,
            "chunks_opened": st.chunks_opened,
            "chunks_total": st.chunks_total,
            "fraction_chunks_decoded": round(st.fraction_chunks_decoded, 4),
            "rows_materialized": st.rows_materialized,
            "chunks_skipped_by": dict(st.chunks_skipped_by),
            "bloom_probes": st.bloom_probes,
            "bloom_passes": st.bloom_passes,
            "bloom_false_positives": st.bloom_false_positives,
            "baseline_wall_s": round(base_wall, 4),
            "speedup_vs_baseline": round(base_wall / wall, 2) if wall else None,
        })

    st = Q.QueryStats()
    t0 = time.perf_counter()
    n_term = Q.count(blob, Q.Substring("terminating"), stats=st)
    count_wall = time.perf_counter() - t0
    assert n_term == sum(1 for l in lines if "terminating" in l)

    # aggregations (ISSUE 7): answers must agree with decompress-then-
    # compute while never materializing a row of text
    from collections import Counter as _Counter
    aggs = []

    def agg_row(name, run_fn, base_fn):
        stq = Q.QueryStats()
        t0 = time.perf_counter()
        got = run_fn(stq)
        wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        want = base_fn(decoded)
        t_compute = time.perf_counter() - t0
        base_wall = t_decompress + t_compute
        aggs.append({
            "agg": name,
            "agree": got == want,
            "wall_s": round(wall, 4),
            "rows_materialized": stq.rows_materialized,
            "chunks_opened": stq.chunks_opened,
            "chunks_counted_from_manifest": stq.chunks_counted_from_manifest,
            "baseline_wall_s": round(base_wall, 4),
            "speedup_vs_baseline": round(base_wall / wall, 2) if wall else None,
        })

    ev_truth = _Counter(r["event"] for r in Q.extract_records(blob))
    agg_row("count_by_template",
            lambda stq: Q.count_by_template(blob, stats=stq),
            lambda ls: dict(ev_truth))
    agg_row("top_k_level",
            lambda stq: Q.top_k(blob, "Level", k=5, stats=stq),
            lambda ls: sorted(
                _Counter(cols["Level"][r] for r in range(len(ok_idx))).items(),
                key=lambda kv: (-kv[1], kv[0]))[:5])
    agg_row("time_histogram",
            lambda stq: Q.time_histogram(blob, "Time", bucket=60, stats=stq),
            lambda ls: dict(sorted(_Counter(
                int(cols["Time"][r]) // 60 for r in range(len(ok_idx))).items())))

    # screen frame overhead, CR-gated at < 1% of the archive
    from repro.core.stream import LZJSReader
    rd = LZJSReader(io.BytesIO(blob))
    screen_bytes = sum(e["sc"][1] for e in rd.index if "sc" in e)
    rd.close()

    return {
        "n_lines": len(lines),
        "chunk_lines": chunk_lines,
        "baseline_decompress_s": round(t_decompress, 4),
        "screen_bytes": screen_bytes,
        "screen_bytes_fraction": round(screen_bytes / len(blob), 5),
        "queries": rows,
        "aggregations": aggs,
        "count_fast_path": {
            "query": "count(terminating)", "hits": n_term,
            "wall_s": round(count_wall, 4),
            "rows_materialized": st.rows_materialized,
            "chunks_opened": st.chunks_opened,
            "chunks_counted_from_manifest": st.chunks_counted_from_manifest,
        },
    }


# the per-dataset CR table always runs at this size, in BOTH quick and
# full runs: the CI gate compares fresh-vs-committed per-dataset CR at a
# 2% tolerance, which is only meaningful like-for-like (CR grows with
# corpus size, so a quick-vs-40k comparison would need sloppy slack)
DATASET_CR_LINES = 8000


def bench_datasets(n_lines: int = DATASET_CR_LINES) -> dict:
    """Per-dataset CR: typed columns (v2) vs the v1 text layout vs the
    checksummed v3 framing on every synthetic corpus (ISSUES 5/6).
    ``check_cr_gate.py`` fails CI if any dataset's typed CR regresses >2%
    vs the committed baseline, stops beating its own v1 baseline, or the
    v3 integrity overhead exceeds 0.5% of CR."""
    from repro.data.loggen import DATASETS

    variants = {"v3": (True, True), "typed": (True, False), "v1": (False, False)}
    rows = []
    for name, spec in DATASETS.items():
        lines = list(generate_lines(name, n_lines, seed=0))
        raw = sum(len(l.encode("utf-8", "surrogateescape")) + 1 for l in lines) - 1
        sizes = {}
        for key, (typed, integrity) in variants.items():
            cfg = LogzipConfig(level=3, kernel="gzip", format=spec["format"],
                               ise=ISE_FAST)
            cfg.typed_columns = typed
            cfg.integrity = integrity
            blob = compress(lines, cfg)
            assert decompress(blob) == lines, f"{name}: round-trip FAILED"
            sizes[key] = len(blob)
        rows.append({
            "dataset": name,
            "raw_mb": round(raw / 1e6, 3),
            "cr_typed": round(raw / sizes["typed"], 3),
            "cr_v1": round(raw / sizes["v1"], 3),
            "cr_v3": round(raw / sizes["v3"], 3),
            "typed_gain": round(sizes["v1"] / sizes["typed"] - 1, 4),
            "v3_overhead": round(sizes["v3"] / sizes["typed"] - 1, 4),
        })
    return {"n_lines": n_lines, "rows": rows}


def bench_device_pipeline(lines: list[str], fmt: str, n_chunks: int = 20) -> dict:
    """Kernel-path streaming session: bucketed static shapes must make
    chunks 3..n reuse compiled executables (zero re-traces after the
    2-chunk warmup while the template store settles)."""
    import io

    from repro.core.stream import StreamingCompressor
    from repro.kernels import jitcache, ops

    n = len(lines)
    chunk = max(50, n // n_chunks)
    cfg = LogzipConfig(level=3, kernel="gzip", format=fmt,
                       ise=ISEConfig(min_sample=120, max_iters=2, use_kernel=True))
    jitcache.reset_counters()
    buf = io.BytesIO()
    warm_traces: dict | None = None
    t0 = time.perf_counter()
    with StreamingCompressor(buf, cfg, chunk_lines=chunk) as sc:
        k = 0
        for s in range(0, n, chunk):
            sc.feed(lines[s:s + chunk])
            sc.flush_chunk()
            k += 1
            if k == 2:
                warm_traces = dict(jitcache.TRACE_COUNTS)
    wall = time.perf_counter() - t0
    stats = jitcache.bucket_stats()
    recompiles = sum(stats["traces"].values()) - sum((warm_traces or {}).values())
    # record what actually ran, not what was intended: the resolved
    # backend per op (kernel / ref / host after any sticky demotions)
    # and the real interpret flag — check_perf_gate.py annotates
    # interpret-mode numbers so they are never read as accelerator perf
    report = ops.backend_report()
    return {
        "n_lines": n,
        "n_chunks": (n + chunk - 1) // chunk,
        "lines_per_sec": round(n / wall, 1),
        "interpret_mode": ops.interpret(),
        "backends": {op: info["backend"] for op, info in report.items()},
        "backend_fallbacks": {op: info["fallbacks"]
                              for op, info in report.items() if info["fallbacks"]},
        "recompiles_after_warmup": int(recompiles),
        "kernel_calls": stats["calls"],
        "kernel_traces": stats["traces"],
        "bucket_shapes": stats["bucket_shapes"],
    }


def bench_compaction(n_lines: int, dataset: str = "HDFS") -> dict:
    """Lifecycle compaction (DESIGN.md §16): merge three dup-heavy
    tenant sessions — same template universe, per-tenant parameter
    streams — into one sealed archive and measure the win against the
    summed sealed inputs plus the recompression throughput. Gated by
    ``check_cr_gate.py``: the compacted archive must be strictly
    smaller than the inputs it replaced, and fsck-clean."""
    import tempfile

    from repro.core import recover
    from repro.core.stream import StreamingCompressor
    from repro.data.loggen import DATASETS
    from repro.lifecycle import compact

    fmt = DATASETS[dataset]["format"]
    per_tenant = max(n_lines // 3, 600)
    cfg = LogzipConfig(level=3, kernel="gzip", format=fmt, ise=ISE_FAST)
    with tempfile.TemporaryDirectory() as d:
        paths = []
        for i in range(3):
            p = os.path.join(d, f"tenant{i}.lzjs")
            with StreamingCompressor(p, cfg,
                                     chunk_lines=max(500, per_tenant // 8)) as sc:
                sc.feed(_dup_heavy(dataset, per_tenant, seed=i))
            paths.append(p)
        out = os.path.join(d, "merged.lzjs")
        t0 = time.perf_counter()
        rep = compact(paths, out)
        wall = time.perf_counter() - t0
        fsck_clean = bool(recover.fsck(out)["clean"])
    return {
        "n_inputs": len(paths),
        "n_lines": rep.n_lines,
        "bytes_in": rep.bytes_in,
        "bytes_out": rep.bytes_out,
        "ratio_vs_inputs": round(rep.bytes_in / rep.bytes_out, 3),
        "templates_in": rep.recluster["templates_in"],
        "templates_out": rep.recluster["templates_out"],
        "wall_s": round(wall, 3),
        "lines_per_sec": round(rep.n_lines / wall, 1),
        "fsck_clean": fsck_clean,
    }


ALL_PARTS = ("nodedup", "dupheavy", "streaming", "device", "query",
             "datasets", "compaction")


def run(n_lines: int = 40000, dataset: str = "HDFS", parts=None) -> dict:
    """Full report, or a subset: ``parts`` names the optional sections
    (``ALL_PARTS``; the "main" scenario always runs — streaming needs its
    CR as the baseline). Skipped sections are ``None`` in the report —
    only write a *full* run to the tracked BENCH artifact."""
    from repro.data.loggen import DATASETS

    sel = set(ALL_PARTS) if parts is None else set(parts)
    unknown = sel - set(ALL_PARTS)
    if unknown:
        raise ValueError(f"unknown part(s) {sorted(unknown)}; "
                         f"available: {list(ALL_PARTS)}")

    fmt = DATASETS[dataset]["format"]
    cfg = LogzipConfig(level=3, kernel="gzip", format=fmt, ise=ISE_FAST)
    cfg_nodedup = LogzipConfig(level=3, kernel="gzip", format=fmt, ise=ISE_FAST, dedup=False)

    lines = list(generate_lines(dataset, n_lines, seed=0))
    results = [bench_one(lines, cfg, f"{dataset}-{n_lines}", scenario="main")]
    if "nodedup" in sel:
        results.append(bench_one(lines, cfg_nodedup, f"{dataset}-{n_lines}-nodedup",
                                 scenario="nodedup"))
    if "dupheavy" in sel:
        results.append(bench_one(_dup_heavy(dataset, n_lines), cfg,
                                 f"{dataset}-{n_lines}-dupheavy",
                                 scenario="dupheavy"))
    fast = results[0]
    streaming = bench_streaming(lines, cfg, fast["compression_ratio"],
                                chunk_lines=max(500, n_lines // 20)) \
        if "streaming" in sel else None
    # interpret-mode kernels are slow on CPU: a small slice exercises the
    # bucketed jit cache without dominating the benchmark wall clock
    device = bench_device_pipeline(lines[: min(n_lines, 4000)], fmt) \
        if "device" in sel else None
    query = bench_query(lines, cfg, chunk_lines=max(500, n_lines // 20)) \
        if "query" in sel else None
    report = {
        "benchmark": "compress_throughput",
        "host": {"platform": platform.platform(), "python": platform.python_version()},
        "config": {"dataset": dataset, "n_lines": n_lines, "level": 3, "kernel": "gzip"},
        "seed_reference": SEED_REFERENCE,
        "speedup_vs_seed": round(fast["lines_per_sec"] / SEED_REFERENCE["lines_per_sec"], 2)
        if n_lines == 40000 and dataset == "HDFS" else None,
        "results": results,
        "streaming": streaming,
        "device_pipeline": device,
        "query": query,
        "datasets": bench_datasets() if "datasets" in sel else None,
        "compaction": bench_compaction(n_lines, dataset)
        if "compaction" in sel else None,
    }
    return report


DEFAULT_REPORT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_compress.json")


def write_report(report: dict, path: str | None = None) -> str:
    """Serialize the report to ``BENCH_compress.json`` (single writer —
    both ``benchmarks.throughput`` and ``benchmarks.run`` go through
    here so the CI artifact never diverges between entry points)."""
    out = os.path.abspath(path or DEFAULT_REPORT_PATH)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lines", type=int, default=40000)
    ap.add_argument("--quick", action="store_true", help="tiny sizes (CI smoke)")
    ap.add_argument("--out", default=DEFAULT_REPORT_PATH)
    args = ap.parse_args()
    report = run(4000 if args.quick else args.lines)
    out = write_report(report, args.out)
    for r in report["results"]:
        print(f"{r['label']:28s} {r['lines_per_sec']:>10.0f} lines/s  "
              f"{r['mb_per_sec']:>7.2f} MB/s  CR {r['compression_ratio']:.2f}")
    if report["speedup_vs_seed"]:
        print(f"speedup vs seed ({SEED_REFERENCE['lines_per_sec']:.0f} lines/s): "
              f"{report['speedup_vs_seed']:.2f}x")
    s = report["streaming"]
    print(f"streaming ({s['n_chunks']} chunks x {s['chunk_lines']} lines): "
          f"CR {s['cr_streaming']:.2f} vs chunked {s['cr_chunked']:.2f} / "
          f"single {s['cr_single']:.2f} -> gap closed {s['cr_gap_closed']:.0%}; "
          f"{s['streaming_lines_per_sec']:.0f} lines/s "
          f"({s['throughput_vs_chunked']:.2f}x chunked)")
    ra = s["random_access"]
    print(f"random access [{ra['start']}:{ra['start']+ra['count']}]: decoded "
          f"{ra['chunks_decoded']}/{ra['chunks_total']} chunks "
          f"(covering {ra['chunks_covering']}) ok={ra['ok']}")
    d = report["device_pipeline"]
    mode = "interpret" if d["interpret_mode"] else "compiled"
    print(f"device pipeline ({mode}, {d['n_chunks']} chunks): "
          f"{d['lines_per_sec']:.0f} lines/s, traces {d['kernel_traces']}, "
          f"recompiles after warmup {d['recompiles_after_warmup']}, "
          f"backends {d['backends']}")
    qy = report["query"]
    for r in qy["queries"]:
        print(f"query[{r['query']:18s}] {r['hits']:5d} hits in {r['wall_s']:.3f}s  "
              f"decoded {r['chunks_opened']}/{r['chunks_total']} chunks "
              f"({r['fraction_chunks_decoded']:.0%})  "
              f"{r['speedup_vs_baseline']:.1f}x vs decompress-then-grep  "
              f"agree={r['hits_agree']}")
    for r in qy["aggregations"]:
        print(f"agg[{r['agg']:20s}] {r['wall_s']:.3f}s  "
              f"opened {r['chunks_opened']} chunks "
              f"(manifest-counted {r['chunks_counted_from_manifest']})  "
              f"{r['speedup_vs_baseline']:.1f}x vs decompress-then-compute  "
              f"rows_mat={r['rows_materialized']}  agree={r['agree']}")
    cf = qy["count_fast_path"]
    print(f"query[count fast path ] {cf['hits']:5d} hits in {cf['wall_s']:.3f}s  "
          f"materialized {cf['rows_materialized']} lines, opened "
          f"{cf['chunks_opened']} chunks "
          f"(manifest-counted {cf['chunks_counted_from_manifest']})")
    print(f"screens: {qy['screen_bytes']}B "
          f"({qy['screen_bytes_fraction']:.2%} of the archive)")
    ds = report["datasets"]
    for r in ds["rows"]:
        print(f"dataset[{r['dataset']:12s}] CR typed {r['cr_typed']:6.2f} vs "
              f"v1 {r['cr_v1']:6.2f}  (+{r['typed_gain']:.1%})  "
              f"v3 {r['cr_v3']:6.2f} (crc cost {r['v3_overhead']:.2%})")
    cp = report["compaction"]
    print(f"compaction: {cp['n_inputs']} sessions ({cp['n_lines']} lines) -> "
          f"{cp['bytes_in']} -> {cp['bytes_out']} B "
          f"({cp['ratio_vs_inputs']:.2f}x vs summed inputs)  "
          f"templates {cp['templates_in']} -> {cp['templates_out']}  "
          f"{cp['lines_per_sec']:.0f} lines/s  fsck_clean={cp['fsck_clean']}")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
