"""The decode cycle's share of the chip's peak, %: the least time the
required work of each serving step takes at the peaks (the family's
``decode_step`` over its active slots' cache lengths), over the wall
interval from its start to the next step's start.  Taken over the cycles
with no prefill in them, so it reads the decode path as it runs between
admissions."""


def read(ctx):
    steps = ctx.red.of("step")
    pre = ctx.red.of("prefill")
    least = wall = 0.0
    for a, b in zip(steps, steps[1:]):
        if any(a.start <= p.start < b.start for p in pre):
            continue
        hs = ctx.host_step(a)
        lengths = None if hs is None else ctx.slot_lengths(hs)
        if not lengths:
            continue
        least += ctx.least(ctx.model.decode_step(ctx.cfg, lengths, ctx.keep))
        wall += b.start - a.start
    return 100.0 * least / wall if wall > 0 else None
