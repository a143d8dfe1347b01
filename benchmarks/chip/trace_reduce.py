"""Reduce a profiler trace (``.xplane.pb``) of one run's window to the
numbers the benchmark reports: the device's busy time, each kernel's
device time, the device operations that took most time, and the idle
gaps, labelled by what the host was doing in them.

- Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds
  one event per operation the device ran.
- The window is the host event ``bench.window`` that the harness opens
  around it; without one, the span of the device's events.
- Busy time is the union of the device's op intervals inside the
  window, averaged over the devices that ran any.
- Each event is named by the HLO instruction it ran, without its ``%``
  and numeric suffix. A kernel's time is the sum of the durations of
  the Pallas custom calls (``tpu_custom_call``) whose instruction is one
  of the ``TRACE_NAMES`` of ``roofline/<kernel>.py``.
- An idle gap takes the label of the innermost host span (the harness's
  own spans and the program's ``StageTimer`` stages, both on the host's
  ``perf_counter`` clock, aligned at the start of ``bench.window``)
  around its middle; gaps are summed per label.
"""

from __future__ import annotations

import glob
import heapq
import os
import re
from pathlib import Path

from harness import WINDOW as WINDOW_EVENT
from harness import load_module

OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\.\d+$")
KERNEL_CALL = "tpu_custom_call"  # a Pallas kernel's custom-call target
TOP = 10


def find_trace(d) -> Path:
    found = sorted(glob.glob(os.path.join(str(d), "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {d}")
    return Path(found[-1])


def kernel_names(base: Path) -> dict[str, tuple[str, ...]]:
    """{kernel: TRACE_NAMES} of every ``roofline/<kernel>.py``."""
    out = {}
    for p in sorted((base / "roofline").glob("*.py")):
        out[p.stem] = tuple(load_module(p).TRACE_NAMES)
    return out


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_name(text: str) -> str:
    """The HLO instruction an ``XLA Ops`` event names, without its ``%``
    and numeric suffix: ``%wildcard_match.1 = s32[...] custom-call(...)``
    -> ``wildcard_match``."""
    return _SUFFIX.sub("", text.split(" = ", 1)[0].lstrip("%"))


def reduce(path, base: Path, *, spans=(), window_start: float | None = None) -> dict:
    """-> {busy_s, window_s, kernel_s: {kernel: s}, op_s: {name: s},
    breakdown: {device_ops, idle_gaps}, devices}."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    names = kernel_names(base)
    win = None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_EVENT:
                        win = (int(ev.start_ns), int(ev.end_ns))
    per_device = []
    for plane in pd.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        evs = [ev for line in plane.lines if line.name == OPS_LINE for ev in line.events]
        if evs:
            per_device.append(evs)
    if not per_device:
        raise ValueError(f"no device operations in {path}")
    if win is None:
        win = (min(int(e.start_ns) for evs in per_device for e in evs),
               max(int(e.end_ns) for evs in per_device for e in evs))
    w0, w1 = win
    busy_ns = 0
    kernel_ns = dict.fromkeys(names, 0)
    op_ns: dict[str, int] = {}
    gaps: list[tuple[int, int]] = []
    for evs in per_device:
        iv = []
        for ev in evs:
            a, b = max(int(ev.start_ns), w0), min(int(ev.end_ns), w1)
            if b <= a:
                continue
            iv.append((a, b))
            name = op_name(ev.name)
            op_ns[name] = op_ns.get(name, 0) + (b - a)
            if KERNEL_CALL in ev.name:
                for k, pats in names.items():
                    if name in pats:
                        kernel_ns[k] += b - a
                        break
        busy = _union(iv)
        busy_ns += sum(b - a for a, b in busy)
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = len(per_device)

    # host spans on the trace's clock
    offset = w0 - window_start * 1e9 if window_start is not None else None
    hs = sorted(((a * 1e9 + offset, b * 1e9 + offset, name) for name, a, b in spans),
                key=lambda s: s[0]) if offset is not None else []
    idle: dict[str, float] = {}
    live: list = []  # heap of (-start, end, name): the latest start on top
    j = 0
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (a + b) / 2
        while j < len(hs) and hs[j][0] <= mid:
            heapq.heappush(live, (-hs[j][0], hs[j][1], hs[j][2]))
            j += 1
        while live and live[0][1] < mid:  # ended before this gap: never again
            heapq.heappop(live)
        label = live[0][2] if live else "other"
        idle[label] = idle.get(label, 0.0) + (b - a) / 1e9 / n_dev

    def top(d: dict, scale: float) -> list:
        return [[k, v * scale] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "busy_s": busy_ns / 1e9 / n_dev,
        "window_s": (w1 - w0) / 1e9,
        "devices": n_dev,
        "kernel_s": {k: v / 1e9 / n_dev for k, v in kernel_ns.items()},
        "op_s": {k: v / 1e9 / n_dev for k, v in op_ns.items()},
        "breakdown": {"device_ops": top(op_ns, 1e-9 / n_dev), "idle_gaps": top(idle, 1.0)},
    }
