#!/usr/bin/env python3
"""Run one benchmark cell of logzip on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run it from the root of a logzip checkout. The cell is an entry of
``BENCHMARK.json``'s ``workloads``; its configuration, traffic mix and
per-layer metrics are found by name under this directory (see
``harness.py``). Everything runs in this one process, which owns the
chip. The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``;
every number compared to decide ``correct`` is printed beside its limit
as the last lines of standard error. Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""

import time

T0 = time.perf_counter()  # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=T0))
