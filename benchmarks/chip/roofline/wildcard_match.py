"""Operations and bytes of one ``wildcard_match`` call, from its logical
problem: the lines' tokens against the templates' tokens.

The matcher is the reachability DP of a wildcard template over a token
sequence: one compare and one AND for every pair of (line token,
template token), so ``2 * sum(line lengths) * sum(template lengths)``
operations. The least bytes move each input once and each output once:
the lines' token ids and lengths (int32), the templates' token ids and
lengths (int32), and one byte per (line, template) verdict. Padding
(token width, template width, over-length templates marked by a
negative length) is never counted. The work is integer work on the
vector unit, which the published (matrix-unit) int8 peak overstates, so
the bound is almost always bytes.
"""

import numpy as np

PEAK_OPS = "int8_tops"
# the entry point in ``repro.kernels.ops`` whose calls are counted
ENTRY = "wildcard_match"
# the HLO instruction the chip's trace gives this kernel's custom call
TRACE_NAMES = ("wildcard_match",)


def record(logs, lens, templates, t_lens, **_kw) -> dict:
    width = np.asarray(logs).shape[1]
    lens = np.minimum(np.asarray(lens, np.int64), width)
    t_lens = np.asarray(t_lens, np.int64)
    real = t_lens[t_lens >= 0]
    return {"lines": int(lens.size), "tokens": int(lens.sum()),
            "templates": int(real.size), "template_tokens": int(real.sum())}


def count(rec: dict) -> tuple[int, int]:
    ops = 2 * rec["tokens"] * rec["template_tokens"]
    nbytes = (4 * (rec["tokens"] + rec["lines"] + rec["template_tokens"] + rec["templates"])
              + rec["lines"] * rec["templates"])
    return ops, nbytes
