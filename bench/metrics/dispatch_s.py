"""dispatch_s (drivers, s): host seconds per call from entering the
entry point to its return with the work enqueued, by the benchmark's own
clock: the sum over the run's calls over their number."""


def read(ctx):
    d = ctx["dispatch_s"]
    return sum(d) / len(d) if d else None
