"""Mean time the device sat idle between two consecutive serving-step
programs, ms: the gap from one step's end to the next one's start, less
the device time of whatever else ran in it (prefills, slot writes)."""


def read(ctx):
    steps = ctx.red.of("step")
    if len(steps) < 2:
        return None
    gaps = []
    for a, b in zip(steps, steps[1:]):
        other = sum(p.end - p.start for p in ctx.red.programs
                    if p.start >= a.end and p.end <= b.start)
        gaps.append(max(b.start - a.end - other, 0.0))
    return 1e3 * sum(gaps) / len(gaps)
