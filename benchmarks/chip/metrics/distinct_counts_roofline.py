"""Share of ``distinct_counts``'s roofline over the window (%): the least time
the chip needs for the logical work of every call
(``roofline/distinct_counts.py``, ``peaks.json``) over the kernel's device time
in the trace."""


def read(rec):
    return rec.roofline_pct("distinct_counts")
