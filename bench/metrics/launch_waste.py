"""Tiles the conv launches convolved (the program's
``ReuseStats.launched`` / ``ShardedReuseStats.launched``: the compute
set with its dilation margin, padded to its power-of-two bucket, and on
the sharded path every shard padded to the largest) over the useful
tiles, summed over the window."""


def read(ctx):
    useful = sum(s["useful"] for s in ctx.steps)
    if useful <= 0:
        return None
    return sum(s["launched"] for s in ctx.steps) / useful
