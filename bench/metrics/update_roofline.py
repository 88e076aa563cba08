"""update_roofline (trailing update, %): the least time the chip could
take for the call's trailing updates (counts.py, from their shapes; per
update the larger of operations over peak rate and bytes over peak
bandwidth, peaks.json by device kind) over the kernel's device time."""
import json
from pathlib import Path

import counts
from metrics import update_kernel_s

PEAKS = json.loads((Path(__file__).parent.parent / "peaks.json").read_text())


def read(ctx):
    kernel_s = update_kernel_s.read(ctx)
    if kernel_s is None:
        return None
    if ctx["kind"] not in PEAKS["devices"]:
        raise KeyError(f"no peaks for device kind {ctx['kind']!r} in "
                       f"peaks.json")
    peak = PEAKS["devices"][ctx["kind"]]
    least = counts.least_seconds(ctx["entry"].updates(ctx["cfg"]),
                                 peak["flops_per_s"], peak["bytes_per_s"])
    return 100.0 * least / kernel_s
