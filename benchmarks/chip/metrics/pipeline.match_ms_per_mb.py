"""Milliseconds of the ``ise.match`` stage (the program's StageTimer sum
over the window) per MB of input."""


def read(rec):
    mb = rec.counters.get("input_mb")
    t = rec.stage_s.get("ise.match")
    return None if not mb or t is None else 1000.0 * t / mb
