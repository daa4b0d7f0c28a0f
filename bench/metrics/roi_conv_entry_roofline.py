"""``roi_conv_entry``'s share of its roofline
(``bench/harness/roofline.py``)."""
from harness.roofline import share


def read(ctx):
    return share(ctx, "roi_conv_entry")
