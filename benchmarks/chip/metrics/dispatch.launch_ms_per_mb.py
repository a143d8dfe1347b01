"""Host milliseconds of the kernel wrappers without their compiles, per
MB of input: the program's ``dispatch.<kernel>`` StageTimer sums
(padding, transfer, launch, device-side trim and copy back) less their
``dispatch.<kernel>.compile`` sums, over the window.

None where the program records no ``dispatch.<kernel>`` span."""


def read(rec):
    mb = rec.counters.get("input_mb")
    spans = {k: v for k, v in rec.stage_s.items() if k.startswith("dispatch.")}
    if not mb or not spans:
        return None
    compiles = sum(v for k, v in spans.items() if k.endswith(".compile"))
    calls = sum(v for k, v in spans.items() if not k.endswith(".compile"))
    return 1000.0 * (calls - compiles) / mb
