"""Work of the entry kernel: its layers (role ``roi_conv_entry`` in the
reference's layer list: the first conv and its ReLU) over the useful
tiles, reading each tile's haloed input window of the first layer's
input and writing the last layer's activations, each at its own
stride."""
from harness import layers as ly

TRACE_NAMES = (
    r"^%_roi_conv_entry_jit(\.\d+)? = .*custom-call\(",
)


def work(step, dims):
    mine = ly.of_role(dims["layers"], "roi_conv_entry")
    if not mine:
        return 0.0, 0.0
    u, t = step["useful"], dims["tile"]
    first, last = mine[0], mine[-1]
    # the input rows one tile's output pixels read under SAME padding
    side = (t / ly.stride_out(first) - 1) * first["stride"] + first["k"]
    window = side ** 2 * first["cin"]
    out = ly.px_per_tile(t, ly.stride_out(last)) * last["cout"]
    flops = u * sum(ly.flops_per_tile(layer, t) for layer in mine)
    nbytes = 4 * (u * (window + out) + sum(map(ly.weights, mine)))
    return flops, nbytes
