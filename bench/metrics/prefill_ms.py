"""Device time of the admission prefill program, mean per admitted
request, ms (prompts of every length of the mix together)."""


def read(ctx):
    pre = ctx.red.of("prefill")
    if not pre:
        return None
    return 1e3 * sum(p.end - p.start for p in pre) / len(pre)
