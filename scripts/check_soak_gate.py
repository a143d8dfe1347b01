"""Soak gate for CI (ISSUE 10, DESIGN.md §17).

Turns a ``BENCH_soak.json`` report (``benchmarks/soak.py``) into
pass/fail. Per run mode (stream / daemon):

- **RSS ceiling** — peak resident set must stay under ``--rss-cap-mb``.
  The generator is O(templates) and the session is bounded-memory by
  design; a drifting, cardinality-ramping soak whose RSS climbs past the
  cap means something (TemplateStore, ParamDict, screens, WAL, pack
  queue) retains per-line state.
- **p99 latency cap** — per-batch feed/ack latency p99 under
  ``--p99-cap-ms``. Catches stalls the mean hides: a chunk cut that
  blocks on an unbounded queue, a pathological clustering pass.
- **CR floor** — compression ratio at soak scale must stay above
  ``--cr-floor``. Drift + ramps reduce CR vs the closed-world LogHub
  mimics; the floor catches a collapse (templates leaking params).
- **Sublinear TemplateStore growth** — final ``templates_per_1k_lines``
  under ``--templates-per-1k-cap`` (the primary linear-in-lines
  tripwire: a store tracking distinct *statements* sits around 1.2/1k
  at smoke scale, a store growing with *lines* sits near 1000/1k), and
  ``template_growth_ratio`` (templates learned in the stream's second
  half / first half) under ``--growth-ratio-cap``. Under compounding
  mutation drift the measured ratio is ~1.67, not <1: statements
  accrete slots over time and the sampled clustering learns the tail
  lazily, so discovery *accelerates* mildly while density stays flat.
  The ratio cap therefore only catches runaway acceleration.

Thresholds are calibrated for the CI smoke soak (~100 MB, default
``SOAK_SPEC``); re-baseline them per DESIGN.md §17 when the spec or
scale changes deliberately. Exit 1 with a per-check report on any
violation.

    PYTHONPATH=src python scripts/check_soak_gate.py --report BENCH_soak.json
"""

from __future__ import annotations

import argparse
import json
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--report", required=True, help="BENCH_soak.json from benchmarks/soak.py")
    ap.add_argument("--rss-cap-mb", type=float, default=2048.0,
                    help="peak RSS ceiling (MB); jax/numpy baseline is "
                         "several hundred MB before the first line")
    ap.add_argument("--p99-cap-ms", type=float, default=5000.0,
                    help="per-batch latency p99 cap (ms); batches that "
                         "absorb a chunk cut spike well above the median")
    ap.add_argument("--cr-floor", type=float, default=6.0,
                    help="compression ratio floor at soak scale")
    ap.add_argument("--growth-ratio-cap", type=float, default=2.5,
                    help="max (2nd-half / 1st-half) template growth. The "
                         "100 MB smoke measures ~1.67: mutation drift "
                         "compounds (statements accrete slots) and the "
                         "sampled clustering learns the tail lazily, so "
                         "discovery accelerates mildly even though density "
                         "stays flat. The cap catches runaway acceleration; "
                         "--templates-per-1k-cap is the linear-in-lines "
                         "tripwire")
    ap.add_argument("--templates-per-1k-cap", type=float, default=2.0,
                    help="max final templates per 1k lines")
    args = ap.parse_args()

    with open(args.report) as f:
        rep = json.load(f)

    runs = rep.get("runs", {})
    if not runs:
        print("soak gate: report has no runs", file=sys.stderr)
        return 1

    failures: list[str] = []
    checks: list[str] = []

    def check(line: str, bad: bool) -> None:
        checks.append(line)
        if bad:
            failures.append(line)

    for mode, r in runs.items():
        rss = r.get("rss_mb", {})
        peak = rss.get("peak", float("inf"))
        check(f"[{mode}] peak RSS {peak:.0f} MB (cap {args.rss_cap_mb:.0f})",
              peak > args.rss_cap_mb)
        p99 = r.get("latency_ms", {}).get("p99", float("inf"))
        check(f"[{mode}] batch latency p99 {p99:.1f} ms (cap {args.p99_cap_ms:.0f})",
              p99 > args.p99_cap_ms)
        cr = r.get("compression_ratio", 0.0)
        check(f"[{mode}] compression ratio {cr:.2f} (floor {args.cr_floor:.2f})",
              cr < args.cr_floor)
        g = r.get("growth", {})
        if not g:
            check(f"[{mode}] growth curve present", True)
        else:
            ratio = g.get("template_growth_ratio")
            if ratio is None:
                # store counts advance at chunk cuts; a soak too small to
                # land a chunk before its midpoint has no ratio resolution
                print(f"note  [{mode}] growth ratio unavailable "
                      "(no chunk landed before stream midpoint) — "
                      "density cap still applies")
            else:
                check(f"[{mode}] template growth ratio {ratio:.3f} "
                      f"(cap {args.growth_ratio_cap:.2f}; 1.0 = linear)",
                      ratio > args.growth_ratio_cap)
            # daemon soaks run one independent store per tenant — each
            # re-learns the statement universe, so density scales by N
            cap = args.templates_per_1k_cap * r.get("n_tenants", 1)
            per1k = g.get("templates_per_1k_lines", float("inf"))
            check(f"[{mode}] templates per 1k lines {per1k:.3f} "
                  f"(cap {cap:.2f})", per1k > cap)
        if r.get("interpret_mode"):
            print("::warning title=Pallas interpret mode::soak "
                  f"[{mode}] throughput/latency measured in Pallas interpret mode — "
                  "relative cost only, not accelerator performance")

    for c in checks:
        print(("FAIL  " if c in failures else "ok    ") + c)
    if failures:
        print(f"\nsoak gate: {len(failures)} check(s) failed", file=sys.stderr)
        return 1
    print("\nsoak gate: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
