"""panel_s: device seconds per call of the ops whose innermost named frame
layers.json lists under "panel_s"."""
import trace_reduce


def read(ctx):
    s = trace_reduce.seconds_by_layer(ctx["trace"], ctx["layers"]).get("panel_s")
    return s / ctx["calls"] if s else None
