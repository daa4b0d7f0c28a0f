"""``tile_delta_gate``'s share of its roofline
(``bench/harness/roofline.py``)."""
from harness.roofline import share


def read(ctx):
    return share(ctx, "tile_delta_gate")
