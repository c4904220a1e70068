"""What a per-layer metric's reader gets: the reduced trace, the host
record, and the cell's family module (``model``), which counts the work,
with the lookups several readers share.

A reader is ``bench/metrics/<metric>.py`` with ``read(ctx) -> float |
None``; None means it found nothing to read, and the metric is left out
of the result line.
"""
from __future__ import annotations

import bisect
import dataclasses

from bench import stats, work


@dataclasses.dataclass
class Context:
    cfg: dict                 # the configuration file
    model: object             # its family module (bench/families)
    keep: dict | None         # live blocks (reference masks), or None
    peaks: dict               # the device's peaks (bench/peaks.json)
    n_slots: int
    red: object               # trace.Reduced of the traced stretch
    record: object            # driver.Record of the run (host clock)
    window: tuple             # (start, end) of the measured window
    compiles: list            # host times of JAX compile events

    def host_step(self, prog):
        """(start, end, active slots) of the engine step, on the host
        clock, in which the device program ``prog`` ran; None if not
        found."""
        st = self.red.step_of(prog)
        if st is None or not 0 <= st.index < len(self.record.steps):
            return None
        return self.record.steps[st.index]

    def slot_lengths(self, host_step):
        """The cache length of each slot the engine stepped in
        ``host_step``, the step's own entry included: the request's prompt,
        the tokens it received before the step, and the one the step feeds
        back.  A stepped request received one token when the step returned
        (two if the step admitted it, the prefill's first)."""
        t_end = host_step[1]
        out = []
        for e in self.record.entries:
            n = bisect.bisect_right(e.times, t_end)
            fed = n - bisect.bisect_left(e.times, t_end)
            if e.times and e.times[0] == t_end:
                fed -= 1                    # the prefill's token, not fed
            if fed > 0:
                out.append(e.due.prompt_len + n - 1)
        return out

    def admitted_in(self, host_step):
        """Prompt lengths of the requests admitted in ``host_step``, in
        admission order (FIFO: order of due time)."""
        t_end = host_step[1]
        return [e.due.prompt_len for e in self.record.entries
                if e.times and e.times[0] == t_end]

    def prefill_lengths(self):
        """[(program, prompt length)] for every prefill program traced,
        matched in order to the admissions of the step it ran in; None
        where the match fails."""
        out, by_step = [], {}
        for prog in self.red.of("prefill"):
            hs = self.host_step(prog)
            if hs is None:
                return None
            by_step.setdefault(hs, []).append(prog)
        for hs, progs in by_step.items():
            lens = self.admitted_in(hs)
            if len(lens) != len(progs):
                return None
            out += list(zip(progs, lens))
        return out

    def least(self, flops_bytes):
        return work.least_seconds(*flops_bytes, self.peaks)[0]

    def gen_lag_ms(self):
        return stats.gen_lag_ms(self.record, self.window)
