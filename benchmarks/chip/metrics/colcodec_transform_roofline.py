"""Share of ``colcodec_transform``'s roofline over the window (%): the least time
the chip needs for the logical work of every call
(``roofline/colcodec_transform.py``, ``peaks.json``) over the kernel's device time
in the trace."""


def read(rec):
    return rec.roofline_pct("colcodec_transform")
