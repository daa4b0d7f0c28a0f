"""Work of the entry kernel: the first 3x3 conv (3 -> C1) and its ReLU
over the useful tiles, reading each tile's haloed input window and
writing its C1-channel activations."""

TRACE_NAMES = (
    r"^%_roi_conv_entry_jit(\.\d+)? = .*custom-call\(",
)


def work(step, dims):
    u, t, cin, c1 = step["useful"], dims["tile"], dims["cin"], \
        dims["channels"][0]
    flops = u * t * t * 2 * 9 * cin * c1
    nbytes = 4 * (u * ((t + 2) ** 2 * cin + t * t * c1) + 9 * cin * c1)
    return flops, nbytes
