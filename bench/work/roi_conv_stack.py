"""Work of the layer-stack megakernel: its layers (role
``roi_conv_stack`` in the reference's layer list: every conv after the
first and its ReLU) over the useful tiles, reading the first layer's
input activations and writing the last layer's (the layers between stay
on chip), each at its own stride."""
from harness import layers as ly

TRACE_NAMES = (
    r"^%_roi_conv_stack_jit(\.\d+)? = .*custom-call\(",
)


def work(step, dims):
    mine = ly.of_role(dims["layers"], "roi_conv_stack")
    if not mine:
        return 0.0, 0.0
    u, t = step["useful"], dims["tile"]
    first, last = mine[0], mine[-1]
    io = (ly.px_per_tile(t, first["stride_in"]) * first["cin"]
          + ly.px_per_tile(t, ly.stride_out(last)) * last["cout"])
    flops = u * sum(ly.flops_per_tile(layer, t) for layer in mine)
    nbytes = 4 * (u * io + sum(map(ly.weights, mine)))
    return flops, nbytes
