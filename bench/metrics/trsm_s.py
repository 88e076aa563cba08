"""trsm_s: device seconds per call of the ops whose innermost named frame
layers.json lists under "trsm_s"."""
import trace_reduce


def read(ctx):
    s = trace_reduce.seconds_by_layer(ctx["trace"], ctx["layers"]).get("trsm_s")
    return s / ctx["calls"] if s else None
