"""Share of the step time in which no operation ran on the device,
averaged over the cell's devices.  The device seconds come from the
traced window (``harness/trace.py``); the wall they are set against is
what the same walk positions took in the measured window, since the
profiler slows the host several-fold and would count its own cost as
idle device time."""


def read(ctx):
    if ctx.trace["idle_share"] is None:      # no device plane in the trace
        return None
    wall = ctx.untraced_seconds()
    if not wall:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / wall)
