"""idle_share (device, %): 1 - busy / window over the traced calls, busy
being the union of the device-op intervals in the window."""
import trace_reduce


def read(ctx):
    busy_s, window_s = trace_reduce.busy_and_window(ctx["trace"])
    if busy_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s / window_s)
