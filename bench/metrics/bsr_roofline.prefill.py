"""``bsr_matmul_packed``'s share of its roofline in the admission prefill,
%: the least time of the packed projections' required work at M = the
prompt length, over the device time of the Pallas kernel launches inside
the prefill programs."""


def read(ctx):
    if ctx.keep is None:
        return None
    matched = ctx.prefill_lengths()
    if not matched:
        return None
    kt = sum(o.end - o.start for p, _ in matched
             for o in ctx.red.ops_in(p, "kernel"))
    if kt <= 0:
        return None
    least = sum(ctx.least(ctx.model.packed_projections(ctx.cfg, ctx.keep, P))
                for _, P in matched)
    return 100.0 * least / kt
