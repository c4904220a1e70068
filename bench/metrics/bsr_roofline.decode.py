"""``bsr_matmul_packed``'s share of its roofline in the serving step, %:
the least time of the packed projections' required work (live blocks at
M = the engine's slots, the family's ``packed_projections``) over the
device time of the Pallas kernel launches inside the step programs."""


def read(ctx):
    if ctx.keep is None:
        return None
    steps = ctx.red.of("step")
    kt = sum(o.end - o.start for p in steps for o in ctx.red.ops_in(p, "kernel"))
    if kt <= 0:
        return None
    least = len(steps) * ctx.least(
        ctx.model.packed_projections(ctx.cfg, ctx.keep, ctx.n_slots))
    return 100.0 * least / kt
