"""Milliseconds of the ``ise.match.wide`` span (matching of the rows whose
token bucket exceeds 128, device calls included; the program's StageTimer
sum over the window) per MB of input. None where the program has no such
span."""


def read(rec):
    mb = rec.counters.get("input_mb")
    t = rec.stage_s.get("ise.match.wide")
    return None if not mb or t is None else 1000.0 * t / mb
