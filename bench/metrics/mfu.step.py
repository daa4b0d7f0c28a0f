"""Useful model FLOPs over the window as a share of the cell's chips'
bf16 peak.  Useful FLOPs are the detector's FLOPs per pixel (every 3x3
conv layer and the 1x1 head, as ``RoIDetector.flops`` counts them) over
the useful tiles of each step: active tiles whose head output depends on
a changed input pixel, as the generator reports them."""


def read(ctx):
    d = ctx.dims
    chans = [d["cin"]] + list(d["channels"])
    per_px = sum(2 * 9 * a * b for a, b in zip(chans[:-1], chans[1:]))
    per_px += 2 * chans[-1] * d["heads"]
    flops = sum(s["useful"] for s in ctx.steps) * d["tile"] ** 2 * per_px
    if flops <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peak["bf16_flops_per_s"])
