"""Throughput regression gate for CI (ISSUE 3 satellite).

Compares a freshly-measured throughput report against the committed
``BENCH_compress.json`` trajectory artifact:

- per-scenario ``lines_per_sec`` must stay above ``(1 - slack)`` x the
  recorded value. CI's smoke job runs quick sizes on shared runners, so
  its slack is generous (gross regressions — an accidental O(n^2) loop,
  a dead fast path — not single-percent drift);
- no single pipeline *stage* may grow its share of the wall clock by
  more than ``--stage-slack`` (relative) vs the recorded breakdown.
  Fractions, not absolute seconds, so quick-size runs are comparable;
  stages under ``--stage-floor`` of the wall are ignored (noise);
- if the fresh report carries a ``device_pipeline`` scenario, its
  recompile counter after warmup must be zero (the bucketed jit cache
  contract). Interpret-mode runs and runtime backend demotions are
  *annotated* (never gated) so their numbers are not mistaken for
  accelerator performance;
- if the fresh report carries a ``query`` scenario (ISSUE 4), every
  query's hit set must agree with the decompress-then-grep baseline, and
  the *selective* queries must decode under ``--query-decode-cap`` of the
  LZJS chunks while beating the baseline wall clock (template pushdown
  actually pushing down);
- query v2 (ISSUE 7, chunk screens + aggregations): the ``param_value``
  point query may open at most ``--point-chunk-cap`` chunks (O(1), not
  O(n)); the gated ``field_eq`` query must decode under the same
  ``--query-decode-cap`` fraction; every aggregation must agree with
  decompress-then-compute, materialize zero rows, and beat the baseline
  wall clock; the count fast path must materialize zero rows.

Exit code 1 with a per-check report on any violation.

    PYTHONPATH=src python scripts/check_perf_gate.py \
        --report BENCH_compress.quick.json --baseline BENCH_compress.json
"""

from __future__ import annotations

import argparse
import json
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--report", required=True, help="fresh run (e.g. quick smoke)")
    ap.add_argument("--baseline", required=True, help="committed BENCH_compress.json")
    ap.add_argument("--slack", type=float, default=0.15,
                    help="allowed lines/sec regression per scenario "
                         "(0.15 = fail below 85%% of recorded)")
    ap.add_argument("--stage-slack", type=float, default=0.30,
                    help="allowed relative growth of any stage's share of wall")
    ap.add_argument("--stage-floor", type=float, default=0.05,
                    help="ignore stages below this fraction of recorded wall")
    ap.add_argument("--query-decode-cap", type=float, default=0.5,
                    help="max fraction of LZJS chunks a selective query may decode")
    ap.add_argument("--point-chunk-cap", type=int, default=3,
                    help="max chunks the param_value point query may open "
                         "(screens make it O(1) in archive length)")
    ap.add_argument("--require-compiled", action="store_true",
                    help="fail (not just annotate) when device_pipeline ran "
                         "in Pallas interpret mode — for environments that "
                         "promise a real accelerator")
    args = ap.parse_args()

    with open(args.report) as f:
        fresh = json.load(f)
    with open(args.baseline) as f:
        base = json.load(f)

    failures: list[str] = []
    checks: list[str] = []

    base_by_scenario = {r.get("scenario"): r for r in base["results"] if r.get("scenario")}
    for r in fresh["results"]:
        b = base_by_scenario.get(r.get("scenario"))
        if b is None:
            continue
        floor = (1.0 - args.slack) * b["lines_per_sec"]
        line = (f"lines/sec[{r['scenario']}]: fresh {r['lines_per_sec']:.0f} vs "
                f"recorded {b['lines_per_sec']:.0f} (floor {floor:.0f})")
        checks.append(line)
        if r["lines_per_sec"] < floor:
            failures.append(line)

        bw, fw = b.get("wall_s", 0), r.get("wall_s", 0)
        if not (bw and fw) or r.get("n_lines") != b.get("n_lines"):
            # stage shares shift systematically with corpus size — only
            # compare like-for-like runs (CI quick runs gate lines/sec only)
            continue
        for stage, bs in b.get("stages_s", {}).items():
            bfrac = bs / bw
            if bfrac < args.stage_floor:
                continue
            ffrac = r.get("stages_s", {}).get(stage, 0.0) / fw
            cap = bfrac * (1.0 + args.stage_slack)
            line = (f"stage[{r['scenario']}/{stage}]: share {ffrac:.2f} vs "
                    f"recorded {bfrac:.2f} (cap {cap:.2f})")
            checks.append(line)
            if ffrac > cap:
                failures.append(line)

    dp = fresh.get("device_pipeline")
    if dp is not None:
        line = (f"device_pipeline recompiles after warmup: "
                f"{dp.get('recompiles_after_warmup')}")
        checks.append(line)
        if dp.get("recompiles_after_warmup", 0) != 0:
            failures.append(line)
        # benchmark honesty: annotate interpret-mode numbers so they are
        # not mistaken for accelerator performance; --require-compiled
        # escalates the annotation to a failure. Under GitHub Actions the
        # ``::warning`` line becomes a run-summary annotation (visible on
        # every nightly without opening the markdown table); elsewhere it
        # is just a printed line.
        if dp.get("interpret_mode"):
            print("::warning title=Pallas interpret mode::device_pipeline "
                  "numbers were measured in Pallas interpret mode "
                  f"(backends: {dp.get('backends', {})}) — relative cost "
                  "only, not accelerator performance")
        if args.require_compiled:
            line = (f"device_pipeline compiled (interpret_mode="
                    f"{bool(dp.get('interpret_mode'))}, required compiled)")
            checks.append(line)
            if dp.get("interpret_mode"):
                failures.append(line)
        elif dp.get("interpret_mode"):
            print("note  device_pipeline ran in Pallas interpret mode "
                  f"(backends: {dp.get('backends', {})}) — its lines/sec "
                  "calibrates relative cost only, not accelerator perf")
        if dp.get("backend_fallbacks"):
            print("note  kernel backends demoted at runtime: "
                  f"{dp['backend_fallbacks']}")

    qy = fresh.get("query")
    if qy is not None:
        for r in qy.get("queries", []):
            line = f"query[{r['query']}] hit set == decompress-then-grep"
            checks.append(line)
            if not r.get("hits_agree"):
                failures.append(line)
            if not r["query"].startswith("selective"):
                continue
            frac = r.get("fraction_chunks_decoded", 1.0)
            line = (f"query[{r['query']}] chunks decoded {frac:.0%} "
                    f"(cap {args.query_decode_cap:.0%})")
            checks.append(line)
            if frac >= args.query_decode_cap:
                failures.append(line)
            spd = r.get("speedup_vs_baseline") or 0.0
            line = f"query[{r['query']}] speedup vs baseline {spd:.2f}x (floor 1.00x)"
            checks.append(line)
            if spd <= 1.0:
                failures.append(line)

        # --- query v2 (ISSUE 7): screens + aggregations -------------
        by_name = {r["query"]: r for r in qy.get("queries", [])}
        pv = by_name.get("param_value")
        if pv is not None:
            line = (f"query[param_value] opened {pv['chunks_opened']}/"
                    f"{pv['chunks_total']} chunks (cap {args.point_chunk_cap})")
            checks.append(line)
            if pv["chunks_opened"] > args.point_chunk_cap:
                failures.append(line)
        fe = by_name.get("field_eq")
        if fe is not None:
            frac = fe.get("fraction_chunks_decoded", 1.0)
            line = (f"query[field_eq] chunks decoded {frac:.0%} "
                    f"(cap {args.query_decode_cap:.0%})")
            checks.append(line)
            if frac >= args.query_decode_cap:
                failures.append(line)
        for a in qy.get("aggregations", []):
            line = f"agg[{a['agg']}] == decompress-then-compute"
            checks.append(line)
            if not a.get("agree"):
                failures.append(line)
            line = f"agg[{a['agg']}] rows materialized {a['rows_materialized']} (must be 0)"
            checks.append(line)
            if a.get("rows_materialized", 1) != 0:
                failures.append(line)
            spd = a.get("speedup_vs_baseline") or 0.0
            line = f"agg[{a['agg']}] speedup vs baseline {spd:.2f}x (floor 1.00x)"
            checks.append(line)
            if spd <= 1.0:
                failures.append(line)
        cf = qy.get("count_fast_path")
        if cf is not None:
            line = (f"count fast path rows materialized "
                    f"{cf['rows_materialized']} (must be 0)")
            checks.append(line)
            if cf.get("rows_materialized", 1) != 0:
                failures.append(line)

    for c in checks:
        print(("FAIL  " if c in failures else "ok    ") + c)
    if failures:
        print(f"\nperf gate: {len(failures)} check(s) failed", file=sys.stderr)
        return 1
    print("\nperf gate: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
