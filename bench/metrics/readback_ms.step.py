"""Mean host milliseconds per step handing the head maps out: the self
time of the program's ``heads_out`` span (device slices on the single
device path; the whole head canvas pulled to the host and split on the
sharded path), from the spans window."""
from harness.spans import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.mean_ms(("heads_out",))
