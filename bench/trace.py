"""Profiler trace of a traced stretch, reduced to what the readers need.

The harness wraps the stretch in a host span ``bench.window`` and each
engine step in ``bench.step`` (``jax.profiler.TraceAnnotation``); the
device planes give the programs (line ``XLA Modules``) and the operations
(line ``XLA Ops``).  Only the first device is read: every cell so far runs
on one chip.

Programs are told apart by their JAX names (the program does not name
them yet): ``jit_step`` is the engine's serving step, and the admission
prefill is the jitted lambda ``_jit_prefill`` builds.  An operation's
event carries its whole HLO instruction as its name; it is a Pallas
kernel launch when that names the ``tpu_custom_call`` target.  Control
flow (``while``, ``conditional``, ``call``) spans the operations of its
body, so it counts towards busy time but is no operation of its own.
"""
from __future__ import annotations

import dataclasses
import glob
import re
import shutil
from pathlib import Path

PROGRAMS = (("step", re.compile(r"^jit_step\b|^jit_step\(")),
            ("prefill", re.compile(r"^jit__lambda|^jit\(<lambda>\)")))
KERNEL = "tpu_custom_call"
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Span:
    name: str
    start: float            # seconds, on the trace's clock
    end: float
    kind: str = ""          # program kind, or "kernel" for a kernel launch
    index: int = -1         # a ``bench.step``'s step number


@dataclasses.dataclass
class Reduced:
    window: tuple           # (start, end) of ``bench.window``
    steps: list             # host ``bench.step`` spans
    programs: list          # device programs inside the window
    ops: list               # device operations inside the window

    def leaves(self):
        """The operations, without the control flow that spans them."""
        return [o for o in self.ops if o.kind != "container"]

    def busy(self):
        """Seconds in the window with an operation running on the device
        (the union of the operations' intervals)."""
        return _union(self.leaves())

    def of(self, kind):
        return [p for p in self.programs if p.kind == kind]

    def step_of(self, span):
        """The ``bench.step`` span that holds ``span``'s midpoint."""
        mid = (span.start + span.end) / 2
        return next((st for st in self.steps if st.start <= mid <= st.end),
                    None)

    def ops_in(self, prog, kind=None):
        """The operations that ran inside ``prog``."""
        return [o for o in self.ops if o.start >= prog.start
                and o.end <= prog.end and (kind is None or o.kind == kind)]


def _union(spans):
    total, end = 0.0, float("-inf")
    for s in sorted(spans, key=lambda s: s.start):
        if s.end > end:
            total += s.end - max(s.start, end)
            end = s.end
    return total


def program_kind(name):
    for kind, pat in PROGRAMS:
        if pat.search(name):
            return kind
    return "other"


def short_name(hlo):
    """``%bsr_matmul.223 = bf16[...] custom-call(...)`` -> ``bsr_matmul``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def _stats(ev):
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def load(trace_dir):
    """The newest profile under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def reduce(pd):
    """The traced stretch as ``Reduced``: spans in seconds, clipped to the
    ``bench.window`` span."""
    window, steps = None, []
    dev = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and dev is None:
            dev = plane
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench.window":
                    window = (ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
                elif ev.name == "bench.step":
                    steps.append(Span(ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9,
                                      index=int(_stats(ev).get("index", -1))))
    if window is None:
        raise ValueError("the trace holds no bench.window span")
    if dev is None:
        raise ValueError("the trace holds no TPU device plane")
    w0, w1 = window
    programs, ops = [], []
    for line in dev.lines:
        if line.name not in ("XLA Modules", "XLA Ops"):
            continue
        for ev in line.events:
            s, e = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
            if s < w0 or e > w1:
                continue
            if line.name == "XLA Modules":
                programs.append(Span(ev.name, s, e, program_kind(ev.name)))
            else:
                name = short_name(ev.name)
                kind = ("kernel" if KERNEL in ev.name else
                        "container" if name.startswith(CONTAINERS) else "")
                ops.append(Span(name, s, e, kind))
    steps = [st for st in steps if st.start >= w0 and st.end <= w1]
    return Reduced(window, sorted(steps, key=lambda x: x.start),
                   sorted(programs, key=lambda x: x.start),
                   sorted(ops, key=lambda x: x.start))


def breakdown(red, top=10):
    """The device operations that took most time, and the longest idle
    gaps by what the host was doing (``bench.step`` or waiting)."""
    by_op = {}
    for o in red.leaves():
        by_op[o.name] = by_op.get(o.name, 0.0) + (o.end - o.start)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps, end = [], red.window[0]
    for o in red.leaves():
        if o.start > end:
            gaps.append((end, o.start))
        end = max(end, o.end)
    if red.window[1] > end:
        gaps.append((end, red.window[1]))
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        host = next(("in engine step" for st in red.steps
                     if st.start <= mid <= st.end), "between engine steps")
        named.append([host, b - a])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}


def remove(trace_dir):
    shutil.rmtree(Path(trace_dir), ignore_errors=True)
