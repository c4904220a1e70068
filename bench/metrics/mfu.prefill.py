"""The admission prefill's share of the chip's peak, %: the least time of
each prefill's required work at the peaks (the family's ``prefill`` at its
prompt length) over the device time of its program."""


def read(ctx):
    matched = ctx.prefill_lengths()
    if not matched:
        return None
    least = sum(ctx.least(ctx.model.prefill(ctx.cfg, P, ctx.keep))
                for _, P in matched)
    return 100.0 * least / sum(p.end - p.start for p, _ in matched)
