"""Work of the delta gate: every active tile's haloed (t+2)^2 window of
the current frame and of its reference, compared element by element,
and one row of stats out per tile (8 int32 words).  Logical float32
shapes with the first layer's input channels."""

TRACE_NAMES = (
    r"^%_tile_delta_gate_canvas_jit(\.\d+)? = .*custom-call\(",
)


def work(step, dims):
    n = step["n_active"]
    win = (dims["tile"] + 2) ** 2 * dims["layers"][0]["cin"]
    flops = 2 * n * win                   # a difference and a test each
    nbytes = 4 * (2 * n * win + 8 * n)
    return flops, nbytes
