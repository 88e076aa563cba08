"""update_kernel_s (trailing update, s): device seconds per call of the
Pallas posit GEMM's events, found by their op metadata (the
pattern in kernels.json)."""
import json
from pathlib import Path

import trace_reduce

PATTERN = json.loads((Path(__file__).parent / "kernels.json").read_text())


def read(ctx):
    s = trace_reduce.kernel_seconds(ctx["trace"], PATTERN["update_kernel"])
    return s / ctx["calls"] if s > 0 else None
