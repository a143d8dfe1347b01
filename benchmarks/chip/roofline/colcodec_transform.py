"""Operations and bytes of one ``colcodec_transform`` call (reached
through ``ops.delta_zigzag``), from its logical problem: the values of
the integer columns under transform.

Per value the transform is at most two differences, a shift, an XOR and
two selects (delta, delta-of-delta with zigzag, or frame of reference):
6 operations. The least bytes read each value once (int32) and write
each payload value once (uint32), plus each column's length, mode and
reference (int32). Positions past a column's length, and padding, are
never counted. Integer work on the vector unit: the bound is bytes.
"""

import numpy as np

PEAK_OPS = "int8_tops"
# the entry point in ``repro.kernels.ops`` whose calls are counted
ENTRY = "delta_zigzag"
# the HLO instruction the chip's trace gives this kernel's custom call
TRACE_NAMES = ("colcodec_transform",)


def record(vals, lens, mode, **_kw) -> dict:
    width = np.asarray(vals).shape[1]
    lens = np.minimum(np.asarray(lens, np.int64), width)
    return {"columns": int(lens.size), "values": int(lens.sum())}


def count(rec: dict) -> tuple[int, int]:
    return 6 * rec["values"], 8 * rec["values"] + 12 * rec["columns"]
