"""Work of the layer-stack megakernel: every 3x3 conv after the first
and its ReLU over the useful tiles, reading the entry's activations and
writing the last layer's (the layers between stay on chip)."""

TRACE_NAMES = (
    r"^%_roi_conv_stack_jit(\.\d+)? = .*custom-call\(",
)


def work(step, dims):
    u, t, ch = step["useful"], dims["tile"], list(dims["channels"])
    pairs = list(zip(ch[:-1], ch[1:]))
    flops = u * t * t * sum(2 * 9 * a * b for a, b in pairs)
    nbytes = 4 * (u * t * t * (ch[0] + ch[-1])
                  + sum(9 * a * b for a, b in pairs))
    return flops, nbytes
