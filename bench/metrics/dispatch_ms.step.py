"""Mean host milliseconds per step handing work to the device: the self
times of the program's ``stage``, ``gate``, ``conv_dispatch`` and
``ref_advance`` spans (tables and frames staged, the gate, the conv
chain and the cache, canvas and reference updates enqueued), from the
spans window."""
from harness.spans import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.mean_ms(
        ("stage", "gate", "conv_dispatch", "ref_advance"))
