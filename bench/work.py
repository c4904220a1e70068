"""The least time the chip could take for a step's required work.

The work itself, operations and bytes from the shapes alone, is counted by
the cell's family module (``bench/families/<family>.py``: ``prefill``,
``decode_step``, ``packed_projections``), in the served dtype.
"""
from __future__ import annotations

ELT = 2                   # bytes of a bfloat16


def least_seconds(flops, byts, peaks):
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = byts / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
