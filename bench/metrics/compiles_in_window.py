"""Programs JAX compiled, or loaded from its persistent cache, while the
measured window ran (its ``backend_compile`` events).  Set-up warms every
shape the traffic uses, so this is expected to be 0."""


def read(ctx):
    ws, we = ctx.window
    return float(sum(ws <= t < we for t in ctx.compiles))
