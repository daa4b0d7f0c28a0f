"""Lowerings during the window: every jit cache miss, whether the
persistent cache then serves it or the backend compiles (JAX's
``jaxpr_to_mlir_module`` monitoring event)."""


def read(ctx):
    return ctx.lowerings_in_window
