"""Share of the traced stretch in which no operation ran on the device, %:
1 - (union of the operations' intervals) / (length of the stretch)."""


def read(ctx):
    w0, w1 = ctx.red.window
    if w1 <= w0 or not ctx.red.leaves():
        return None
    return 100.0 * (1.0 - ctx.red.busy() / (w1 - w0))
