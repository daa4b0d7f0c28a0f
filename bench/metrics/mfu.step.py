"""Useful model FLOPs over the window as a share of the cell's chips'
bf16 peak.  Useful FLOPs are the detector's matmul FLOPs per detector
tile (every ``conv`` and ``head`` layer of the reference's layer list,
each at its own output stride: ``harness/layers.py``) over the useful
tiles of each step: active tiles whose head output depends on a changed
input pixel, as the generator reports them."""
from harness import layers as ly


def read(ctx):
    d = ctx.dims
    per_tile = sum(ly.flops_per_tile(layer, d["tile"])
                   for layer in d["layers"])
    flops = sum(s["useful"] for s in ctx.steps) * per_tile
    if flops <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peak["bf16_flops_per_s"])
