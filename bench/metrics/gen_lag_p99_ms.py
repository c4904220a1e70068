"""How late the driver submitted the requests due in the measured window,
99th percentile, in ms (host clock).  A starved load generator shows here
before it is read as a fast server."""
from bench.stats import percentile


def read(ctx):
    return percentile(ctx.gen_lag_ms(), 99)
