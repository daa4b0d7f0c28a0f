"""Mean host milliseconds per step of reuse planning: the self time of
the program's ``reuse_plan`` span (gate thresholding, dilation,
compaction, power-of-two buckets; per shard on the sharded path), from
the spans window."""
from harness.spans import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.mean_ms(("reuse_plan",))
