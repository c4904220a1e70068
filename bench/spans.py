"""Device-idle time between serving steps, split by what the engine's host
thread was doing: its ``serve.*`` spans (``repro.serve.trace``).

The idle intervals are those ``host_gap_ms.decode`` reads: for each pair
of consecutive serving-step programs, from the first one's end to the
second one's start, less the programs wholly inside.  Each piece of idle
time goes to the innermost ``serve.*`` span that covers it:

    caller   inside no span: the code that calls the engine, here the
             harness's load generator
    submit   ``serve.submit``
    admit    ``serve.admit``
    launch   ``serve.step`` outside admit and harvest: the sweep, the
             operand transfers, the dispatch and the readback
    harvest  ``serve.harvest``

so that, summed over the phases, the split is ``host_gap_ms.decode``.
The spans are read from the same profile as ``trace.Reduced`` and share
the device planes' clock.
"""
from __future__ import annotations

import dataclasses

from bench import trace

PHASES = ("caller", "submit", "admit", "launch", "harvest")
PHASE_OF = {"serve.submit": "submit", "serve.admit": "admit",
            "serve.step": "launch", "serve.harvest": "harvest"}


@dataclasses.dataclass
class HostSpan:
    name: str
    start: float            # seconds, on the trace's clock
    end: float
    args: dict


def collect(pd, window):
    """Every host event of ``pd`` whose name starts with ``serve.``, with
    its arguments, clipped to ``window`` (``Reduced.window``)."""
    w0, w1 = window
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith("serve."):
                    continue
                s = ev.start_ns * 1e-9
                e = (ev.start_ns + ev.duration_ns) * 1e-9
                if e > w0 and s < w1:
                    out.append(HostSpan(ev.name, max(s, w0), min(e, w1),
                                        trace._stats(ev)))
    return sorted(out, key=lambda s: (s.start, -s.end))


def idle_intervals(red):
    """For each pair of consecutive serving-step programs, the pieces of
    [end of the first, start of the second] in which none of the programs
    wholly inside it ran."""
    steps = red.of("step")
    gaps = []
    for a, b in zip(steps, steps[1:]):
        inside = sorted((p for p in red.programs
                         if p.start >= a.end and p.end <= b.start),
                        key=lambda p: p.start)
        pieces, t = [], a.end
        for p in inside:
            if p.start > t:
                pieces.append((t, p.start))
            t = max(t, p.end)
        if b.start > t:
            pieces.append((t, b.start))
        gaps.append(pieces)
    return gaps


def _split(spans, t0, t1, totals):
    """Add each piece of [t0, t1] to the phase of the innermost span that
    covers it (the latest to start; of two that start together, the first
    to end)."""
    over = [s for s in spans if s.start < t1 and s.end > t0]
    cuts = sorted({t0, t1} | {t for s in over for t in (s.start, s.end)
                              if t0 < t < t1})
    for u, v in zip(cuts, cuts[1:]):
        mid = (u + v) / 2
        cover = [s for s in over if s.start <= mid <= s.end]
        inner = max(cover, key=lambda s: (s.start, -s.end), default=None)
        totals[PHASE_OF[inner.name] if inner else "caller"] += v - u


def split(red, spans):
    """(number of gaps, {phase: idle seconds summed over the gaps})."""
    spans = [s for s in spans if s.name in PHASE_OF]
    totals = dict.fromkeys(PHASES, 0.0)
    gaps = idle_intervals(red)
    for pieces in gaps:
        for t0, t1 in pieces:
            _split(spans, t0, t1, totals)
    return len(gaps), totals


def gap_ms(red, spans):
    """{phase: idle ms per gap}, which sum to ``host_gap_ms.decode``; None
    where the trace holds no ``serve.*`` span (a program that records
    none) or fewer than two serving steps."""
    if not any(s.name in PHASE_OF for s in spans):
        return None
    n, totals = split(red, spans)
    if n == 0:
        return None
    return {k: 1e3 * v / n for k, v in totals.items()}
