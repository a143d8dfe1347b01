"""Operations and bytes of one ``distinct_counts`` call, from its
logical problem: a weighted histogram of ``rows`` inverse-index entries
into ``bins`` bins.

As the aggregation computes it, every row is compared with every bin
and the weight added where they agree: ``2 * rows * bins`` operations.
The least bytes read each row's index and weight once (int32) and write
each bin once (int32). Padding rows and bins are never counted. Integer
work on the vector unit: the bound is bytes unless the bins run into
the thousands.
"""

import numpy as np

PEAK_OPS = "int8_tops"
# the entry point in ``repro.kernels.ops`` whose calls are counted
ENTRY = "distinct_counts"
# the HLO instruction the chip's trace gives this kernel's custom call
TRACE_NAMES = ("distinct_counts",)


def record(inv, n_bins, weights=None, **_kw) -> dict:
    return {"rows": int(np.asarray(inv).shape[0]), "bins": int(n_bins)}


def count(rec: dict) -> tuple[int, int]:
    return 2 * rec["rows"] * rec["bins"], 8 * rec["rows"] + 4 * rec["bins"]
