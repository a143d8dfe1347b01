#!/usr/bin/env python3
"""Chip smoke test: drive logzip's main path once on one TPU chip.

    python chip_smoke.py [--seed 0] [--lines 1000000] [--out DIR]

Everything runs in this one process, which owns the chip:

  a. write ``--lines`` HDFS-style lines (``data/loggen.py``) from the seed;
  b. ``stream`` them through the CLI into an LZJS session (HDFS format,
     default ``--chunk-lines``), matching and typed-column transforms on
     the compiled Pallas kernels;
  c. ``unpack`` it: the output must be byte-identical to the input;
  d. ``grep --count`` one substring and one ``blk_`` point query: each
     count must equal a plain Python count over the raw lines;
  e. ``agg --top Level -k 3``: must equal a ``Counter`` over the raw
     lines' Level field;
  f. on a 50,000-line slice, one library session on the kernels and one
     on the numpy path: the two archives must be byte-identical.

It prints the kernel backend report, the shape-bucket call counts, the
device and each phase's wall time (smoke timings, not benchmark
metrics). The last line is one JSON object, ``{"ok": true, "device":
{...}}``, printed only when every phase and check passed. Without a TPU
it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import sys
import tempfile
import time
from collections import Counter

ROOT = os.path.dirname(os.path.abspath(__file__))
FORMAT = "<Date> <Time> <Pid> <Level> <Component>: <Content>"
SUBSTRING = "PacketResponder"
PARITY_LINES = 50_000
# the ops of the main path that must run compiled on the chip
MAIN_PATH_OPS = ("wildcard_match", "colcodec_transform", "distinct_counts")
MAIN_PATH_CALLS = ("wildcard_match", "delta_zigzag", "distinct_counts")

# an independent reading of FORMAT: five space-separated header fields,
# then ": " and the content
_HEADER_RE = re.compile(r"^(\S+) (\S+) (\S+) (\S+) (\S+): (.*)$")
_BLOCK_RE = re.compile(r"blk_-?\d+")


class SmokeError(Exception):
    """A phase produced a wrong answer."""


def _cli(*argv: str) -> str:
    """Run one ``repro.launch.compress`` verb in this process; -> stdout."""
    from repro.launch.compress import main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        main(list(argv))
    return buf.getvalue()


def _read_lines(path: str) -> list[str]:
    with open(path, encoding="utf-8") as f:
        return f.read().split("\n")


def phase_generate(out: str, n_lines: int, seed: int) -> str:
    from repro.data.loggen import write_dataset

    path = os.path.join(out, "hdfs.log")
    write_dataset("HDFS", path, n_lines, seed=seed)
    return path


def phase_stream(src: str, out: str) -> str:
    arch = os.path.join(out, "hdfs.lzjs")
    _cli("stream", src, arch, "--format", FORMAT)
    return arch


def phase_unpack(src: str, arch: str, out: str) -> None:
    back = os.path.join(out, "back.log")
    _cli("unpack", arch, back)
    with open(src, "rb") as a, open(back, "rb") as b:
        if a.read() != b.read():
            raise SmokeError("unpack is not byte-identical to the input")


def phase_grep(lines: list[str], arch: str) -> dict:
    block = next(m.group() for line in lines[len(lines) // 2:]
                 if (m := _BLOCK_RE.search(line)))
    got = {}
    for pattern in (SUBSTRING, block):
        count = int(_cli("grep", arch, pattern, "--count").strip())
        want = sum(pattern in line for line in lines)
        if count != want:
            raise SmokeError(f"grep --count {pattern!r}: {count}, "
                             f"plain count {want}")
        got[pattern] = count
    return got


def phase_agg(lines: list[str], arch: str) -> list:
    out = _cli("agg", arch, "--top", "Level", "-k", "3", "--json")
    got = [(r["value"], r["count"]) for r in map(json.loads, out.splitlines())]
    levels = Counter(m.group(4) for line in lines if (m := _HEADER_RE.match(line)))
    want = sorted(levels.items(), key=lambda kv: (-kv[1], kv[0]))[:3]
    if got != want:
        raise SmokeError(f"agg --top Level: {got}, plain Counter {want}")
    return got


def phase_kernel_parity(lines: list[str], out: str, n_lines: int = PARITY_LINES) -> dict:
    """-> {"kernel"|"numpy": per-stage seconds of that session} (smoke
    timings); raises unless both sessions wrote the same bytes."""
    from repro.core.codec import LogzipConfig
    from repro.core.ise import ISEConfig
    from repro.core.stream import StreamingCompressor

    blobs, stages = [], {}
    for name, use_kernel in (("kernel", True), ("numpy", False)):
        path = os.path.join(out, f"parity_{name}.lzjs")
        cfg = LogzipConfig(format=FORMAT, ise=ISEConfig(use_kernel=use_kernel))
        stages[name] = {}
        with StreamingCompressor(path, cfg, stage_times=stages[name]) as sc:
            for line in lines[:n_lines]:
                sc.feed_line(line)
        with open(path, "rb") as f:
            blobs.append(f.read())
    if blobs[0] != blobs[1]:
        raise SmokeError("kernel and numpy sessions wrote different archives "
                         f"({len(blobs[0])} vs {len(blobs[1])} bytes)")
    return stages


def check_device_path(report: dict, stats: dict) -> None:
    """Every main-path op ran compiled on the chip, with no demotion."""
    for op in MAIN_PATH_OPS:
        r = report[op]
        if r["backend"] != "kernel" or r["interpret"] or r["fallbacks"]:
            raise SmokeError(f"{op} did not run compiled: {r}")
    for op in MAIN_PATH_CALLS:
        if not stats["calls"].get(op):
            raise SmokeError(f"{op} was never called on the device")


def run(args, out: str) -> dict:
    import jax

    from repro.kernels import ops
    from repro.kernels.jitcache import bucket_stats

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)
    times: dict[str, float] = {}

    def timed(name, fn, *a):
        t0 = time.perf_counter()
        res = fn(*a)
        times[name] = time.perf_counter() - t0
        print(f"phase {name}: ok ({times[name]:.3f} s smoke wall time)", flush=True)
        return res

    os.makedirs(out, exist_ok=True)
    src = timed("a_generate", phase_generate, out, args.lines, args.seed)
    arch = timed("b_stream", phase_stream, src, out)
    timed("c_unpack", phase_unpack, src, arch, out)
    lines = _read_lines(src)
    grep = timed("d_grep", phase_grep, lines, arch)
    agg = timed("e_agg", phase_agg, lines, arch)
    stages = timed("f_kernel_parity", phase_kernel_parity, lines, out)

    report, stats = ops.backend_report(), bucket_stats()
    print("backend_report:", json.dumps(
        {op: {k: r[k] for k in ("backend", "interpret")} | {"fallbacks": len(r["fallbacks"])}
         for op, r in report.items()}))
    print("bucket_stats:", json.dumps(stats))
    print(f"grep counts: {grep}; agg top Level: {agg}")
    print("parity session stage seconds (smoke timings):", json.dumps(stages))
    print("smoke wall times (s, not benchmark metrics):", json.dumps(times))
    check_device_path(report, stats)
    return device


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lines", type=int, default=1_000_000)
    ap.add_argument("--out", default=None,
                    help="working directory (default: a new temporary one)")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import jax

        from repro.kernels.jitcache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run it from "
              "the root of a logzip checkout", file=sys.stderr)
        return 2
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform {platform!r}",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()  # before the first compile
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            device = run(args, args.out or tmp)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    n_cached = sum(len(files) for _, _, files in os.walk(cache))
    print(f"compile cache: {cache} ({n_cached} files)")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
