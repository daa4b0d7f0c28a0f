"""Mean host time inside the fleet-step call, before the wait for its
heads: host planning, the gate readback sync and dispatch.  Read from
the benchmark's own span around the call.  Where the program blocks on
its heads inside the call (the sharded entry), the span holds the wait
too."""


def read(ctx):
    if not ctx.steps:
        return None
    return 1e3 * sum(s["host_s"] for s in ctx.steps) / len(ctx.steps)
