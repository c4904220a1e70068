"""The split of ``host_gap_ms.decode`` by the engine's ``serve.*`` spans
(``bench/spans.py``): on synthetic programs and spans, and on a small
trace recorded on one v5e chip with the spans in it (the bsr
configuration cut to one layer, 4096-token prompts at 6 req/s, 0.6 s
traced: ``data/serve_spans_trace.xplane.pb.gz``)."""
import gzip
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import spans, spec, trace
from bench.trace import Reduced, Span

DATA = Path(__file__).parent / "data" / "serve_spans_trace.xplane.pb.gz"
PROJECTIONS = ("wq", "wk", "wv", "wo", "gate", "up", "down")


def _red(programs):
    return Reduced((0.0, 10.0), [], sorted(programs, key=lambda p: p.start),
                   [])


def _prog(kind, start, end):
    return Span(f"jit_{kind}", start, end, kind)


def _span(name, start, end, **args):
    return spans.HostSpan(name, start, end, args)


# gap 1: [1, 2]; gap 2: [3, 5] less a slot write at [3.5, 4]
PROGRAMS = [_prog("step", 0, 1), _prog("step", 2, 3), _prog("other", 3.5, 4),
            _prog("step", 5, 6)]


def test_nested_spans_take_the_innermost():
    sp = [_span("serve.step", 0.9, 2.1, index=0, active=2),
          _span("serve.harvest", 1.0, 1.2, active=2),
          _span("serve.admit", 1.5, 1.8, rid=3, slot=1, prompt_len=8)]
    n, t = spans.split(_red(PROGRAMS[:2]), sp)
    assert n == 1
    assert t == pytest.approx({"caller": 0.0, "submit": 0.0, "admit": 0.3,
                               "launch": 0.5, "harvest": 0.2})


def test_overlapping_spans_and_programs_inside_the_gap():
    """A span that starts inside the gap and ends past it, and one that
    straddles a program inside the gap: only idle time is split."""
    sp = [_span("serve.submit", 3.2, 3.7, rid=4, prompt_len=8),
          _span("serve.step", 4.5, 6.1, index=1, active=1)]
    n, t = spans.split(_red(PROGRAMS[1:]), sp)
    assert n == 1
    # idle [3, 3.5] and [4, 5]
    assert t == pytest.approx({"caller": 0.2 + 0.5, "submit": 0.3,
                               "admit": 0.0, "launch": 0.5, "harvest": 0.0})


def test_absent_spans():
    """Gaps no span covers go to the caller; a trace with no ``serve.*``
    span at all, as from a program that records none, reads None."""
    red = _red(PROGRAMS)
    sp = [_span("serve.harvest", 1.0, 1.2, active=2),
          _span("serve.other", 1.2, 2.0)]
    n, t = spans.split(red, sp)
    assert n == 2
    assert t == pytest.approx({"caller": 0.8 + 1.5, "submit": 0.0,
                               "admit": 0.0, "launch": 0.0, "harvest": 0.2})
    assert spans.gap_ms(red, []) is None
    assert spans.gap_ms(red, [_span("bench.step", 0, 6)]) is None
    assert spans.gap_ms(_red(PROGRAMS[:1]), sp) is None


def test_split_sums_to_host_gap():
    red = _red(PROGRAMS)
    sp = [_span("serve.step", 0.5, 2.5, index=0, active=2),
          _span("serve.admit", 1.1, 1.4, rid=0, slot=0, prompt_len=8)]
    ms = spans.gap_ms(red, sp)
    gap = spec.reader("host_gap_ms.decode")(SimpleNamespace(red=red))
    assert sum(ms.values()) == pytest.approx(gap, abs=1e-9)
    assert ms["admit"] == pytest.approx(1e3 * 0.3 / 2)


# -- the recorded trace ------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(gzip.decompress(DATA.read_bytes()))
    red = trace.reduce(pd)
    return red, spans.collect(pd, red.window)


def _holding(sp, prog):
    return [s for s in sp if s.start <= prog.start and prog.end <= s.end]


def test_recorded_spans(recorded):
    red, sp = recorded
    names = {s.name for s in sp}
    assert names == set(spans.PHASE_OF)
    w0, w1 = red.window
    assert all(w0 <= s.start <= s.end <= w1 for s in sp)
    steps = [s for s in sp if s.name == "serve.step"]
    idx = [s.args["index"] for s in steps]
    assert idx == list(range(idx[0], idx[0] + len(idx)))


def test_clocks_align(recorded):
    """Every serving-step program ran inside the ``serve.step`` span of
    the engine step that launched it, which lies inside the harness's
    ``bench.step`` of the same step, and every prefill program inside a
    ``serve.admit``: host spans and device programs share one clock."""
    red, sp = recorded
    offsets = set()
    for prog in red.of("step"):
        held = [s for s in _holding(sp, prog) if s.name == "serve.step"]
        assert len(held) == 1, prog
        outer = red.step_of(prog)
        assert outer.start <= held[0].start and held[0].end <= outer.end
        offsets.add(held[0].args["index"] - outer.index)
    # the engine counts the warm-up's steps too; the harness does not
    assert len(offsets) == 1
    for prog in red.of("prefill"):
        held = [s for s in _holding(sp, prog) if s.name == "serve.admit"]
        assert len(held) == 1, prog
        assert held[0].args["prompt_len"] == 4096


def test_recorded_split_sums_to_host_gap(recorded):
    red, sp = recorded
    ms = spans.gap_ms(red, sp)
    assert set(ms) == set(spans.PHASES)
    assert all(v >= 0 for v in ms.values())
    gap = spec.reader("host_gap_ms.decode")(SimpleNamespace(red=red))
    assert sum(ms.values()) == pytest.approx(gap, abs=1e-6)


@pytest.mark.parametrize("kind", ["step", "prefill"])
def test_recorded_launches_are_named(recorded, kind):
    red, _ = recorded
    names = {k.name for p in red.of(kind) for k in red.ops_in(p, "kernel")}
    assert names == {f"bsr_matmul_{p}" for p in PROJECTIONS}


@pytest.mark.parametrize("metric,value", [
    ("host_gap_ms.decode", 3.8483631000000025),
    ("decode_step_ms", 2.7510788571428457),
    ("prefill_ms", 67.8240795),
    ("idle_share", 15.164624930783654)])
def test_readers_repeat_the_recording_run(recorded, metric, value):
    """The readers that need only the trace give, from the recorded file,
    what the run that recorded it reported (one v5e chip)."""
    red, _ = recorded
    assert spec.reader(metric)(SimpleNamespace(red=red)) == \
        pytest.approx(value, rel=1e-9)


def test_split_repeats_the_recording_run(recorded):
    """The split, from the recorded file, as the run that recorded it
    computed it (ms per gap, one v5e chip)."""
    assert spans.gap_ms(*recorded) == pytest.approx(
        {"caller": 0.07449714999998927, "submit": 0.17405855000000026,
         "admit": 0.7549495000000032, "launch": 2.7274754000000074,
         "harvest": 0.11738250000000242}, rel=1e-9)
