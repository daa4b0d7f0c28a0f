"""Mean host milliseconds per step that the program waits for the delta
gate's stats to reach the host: the self time of its ``gate_readback``
span, from the spans window (the program's spans on, no profiler).  It
holds the device work queued before the gate and the gate itself."""
from harness.spans import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.mean_ms(("gate_readback",))
