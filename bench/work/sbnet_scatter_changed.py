"""Work of the changed-only scatter: the useful tiles' head rows (A
channels) read once and written once into the persistent head canvas.
The 1x1 head itself runs outside the kernel, in XLA."""

TRACE_NAMES = (
    r"^%sbnet_scatter_changed(\.\d+)? = .*custom-call\(",
)


def work(step, dims):
    u, t, a = step["useful"], dims["tile"], dims["heads"]
    return 0.0, 4.0 * 2 * u * t * t * a
