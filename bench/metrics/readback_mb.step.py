"""Mean megabytes (1e6 B) per step the program pulls to the host: its
``readback_bytes`` counter, gate stats plus head maps, over the spans
window's steps."""
from harness.spans import window


def read(ctx):
    w = window(ctx)
    if w is None or w.readback_bytes is None or not w.spans:
        return None
    return w.readback_bytes / len(w.spans) / 1e6
