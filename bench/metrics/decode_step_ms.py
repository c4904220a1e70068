"""Device time of the engine's serving-step program, mean per step, ms."""


def read(ctx):
    steps = ctx.red.of("step")
    if not steps:
        return None
    return 1e3 * sum(p.end - p.start for p in steps) / len(steps)
