"""Work of the changed-only scatter: the head rows of the useful tiles
(the outputs of the layers of role ``sbnet_scatter_changed`` in the
reference's layer list, at their own stride) read once and written once
into the persistent head canvas.  The head's matmul itself runs outside
the kernel, in XLA: the kernel does no FLOPs."""
from harness import layers as ly

TRACE_NAMES = (
    r"^%sbnet_scatter_changed(\.\d+)? = .*custom-call\(",
)


def work(step, dims):
    heads = ly.of_role(dims["layers"], "sbnet_scatter_changed")
    rows = sum(ly.px_per_tile(dims["tile"], ly.stride_out(h)) * h["cout"]
               for h in heads)
    return 0.0, 4.0 * 2 * step["useful"] * rows
