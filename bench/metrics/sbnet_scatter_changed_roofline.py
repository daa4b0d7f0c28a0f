"""``sbnet_scatter_changed``'s share of its roofline
(``bench/harness/roofline.py``)."""
from harness.roofline import share


def read(ctx):
    return share(ctx, "sbnet_scatter_changed")
