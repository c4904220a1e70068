"""End-to-end metrics from the host-clock record of one run.

Every metric is taken over all the work of the measured window:
``ttft_p90_ms`` over every request due in it, ``itl_*`` over every gap
between two consecutive tokens of a request whose later token came in it,
``tok_s`` over every token that came in it.  A request due in the window
that failed, or had no first token when the driver stopped, is a failure:
it counts as attempted and failed, and enters the TTFT tail with the wait
it had had when the driver stopped, a lower bound of its latency.
"""
from __future__ import annotations

import numpy as np

ENDED_OK = ("finished", "queued", "running", "deferred")


def percentile(values, q):
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default); None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, float), q))


def window_entries(record, window):
    ws, we = window
    return [e for e in record.entries if ws <= e.due.due < we]


def failed(e):
    return not e.times or e.status not in ENDED_OK


def end_to_end(record, window, seconds):
    """{metric: value} for the window, plus the counts the result line
    carries (attempted, failed)."""
    ws, we = window
    wins = window_entries(record, window)
    ttft = [(e.times[0] if e.times else record.end) - e.due.due
            for e in wins]
    gaps = [b - a for e in record.entries
            for a, b in zip(e.times, e.times[1:]) if ws <= b < we]
    tokens = sum(ws <= t < we for e in record.entries for t in e.times)
    return {
        "ttft_p90_ms": _ms(percentile(ttft, 90)),
        "itl_p50_ms": _ms(percentile(gaps, 50)),
        "itl_p99_ms": _ms(percentile(gaps, 99)),
        "tok_s": tokens / seconds,
    }, {"attempted": len(wins), "failed": sum(map(failed, wins)),
        "ttft_n": len(ttft), "itl_n": len(gaps), "tokens": tokens}


def gen_lag_ms(record, window):
    """How late the driver submitted each request due in the window."""
    return [1e3 * (e.submit - e.due.due)
            for e in window_entries(record, window)]


def _ms(s):
    return None if s is None else 1e3 * s
