"""Programs the cold step and one period of the traffic need: lowerings
(jit cache misses, JAX's ``jaxpr_to_mlir_module`` monitoring event)
during the warm-up, whether the persistent cache then serves them or the
backend compiles.  It counts the shapes the traffic forces on the kernel
wrappers, so it is the same with a cold or a warm cache; every one is
loaded or compiled in set-up."""


def read(ctx):
    return ctx.warmup_lowerings
