"""Milliseconds of XLA compiles in the window per MB of input: the sum
of the program's ``<span>.compile`` StageTimer keys (each compile added
to the innermost span open on the compiling thread) over the window.

None where the program records no ``dispatch.<kernel>`` span, as a
program without the dispatch spans does not; with them, 0 where nothing
compiled."""


def read(rec):
    mb = rec.counters.get("input_mb")
    if not mb or not any(k.startswith("dispatch.") for k in rec.stage_s):
        return None
    return 1000.0 * sum(v for k, v in rec.stage_s.items() if k.endswith(".compile")) / mb
