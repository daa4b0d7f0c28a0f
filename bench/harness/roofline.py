"""A kernel's share of its roofline over the traced window.

The least time the chip could take for the work the kernel's role needs
is the larger of its FLOPs over the peak FLOP/s and its bytes over the
peak HBM bandwidth; the share is that least time over the kernel's
device seconds in the trace, summed over the cell's chips.  The work is
counted by ``bench/work/<kernel>.py`` on the role's minimal tile set and
logical shapes, never on the rows the program launched, so the share
cannot pass 100% unless the work or the time is counted wrong.
"""


def bound(ctx, kernel):
    """(share %, "compute" or "memory"), or None when the trace holds no
    time for the kernel or the cell's layer list gives its role no
    work."""
    seconds = ctx.kernel_seconds(kernel)
    if seconds <= 0:
        return None
    flops, nbytes = ctx.work(kernel)
    if flops <= 0 and nbytes <= 0:
        return None
    t_flops = flops / ctx.peak["bf16_flops_per_s"]
    t_bytes = nbytes / ctx.peak["hbm_bytes_per_s"]
    which = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, which


def share(ctx, kernel):
    b = bound(ctx, kernel)
    return None if b is None else b[0]
