"""The decode cycle's share of the chip's peak, %: the least time the
required work of each serving step takes at the peaks (``work.decode_step``
for its active slots), over the wall interval from its start to the next
step's start.  Taken over the cycles with no prefill in them, so it reads
the decode path as it runs between admissions."""
from bench import work


def read(ctx):
    steps = ctx.red.of("step")
    pre = ctx.red.of("prefill")
    least = wall = 0.0
    for a, b in zip(steps, steps[1:]):
        if any(a.start <= p.start < b.start for p in pre):
            continue
        hs = ctx.host_step(a)
        if hs is None or hs[2] == 0:
            continue
        least += ctx.least(work.decode_step(ctx.cfg, hs[2], ctx.keep))
        wall += b.start - a.start
    return 100.0 * least / wall if wall > 0 else None
