"""The trace reducer on a small recorded trace: one v5e chip, the bsr
configuration cut to one layer, 4096-token prompts at 6 req/s, 0.6 s
traced (``data/small_trace.xplane.pb.gz``)."""
import gzip
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import spec, trace

DATA = Path(__file__).parent / "data" / "small_trace.xplane.pb.gz"


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData
    return trace.reduce(ProfileData.from_serialized_xspace(
        gzip.decompress(DATA.read_bytes())))


def test_window_and_steps(red):
    w0, w1 = red.window
    assert 0.5 < w1 - w0 < 1.0
    assert red.steps and all(w0 <= s.start < s.end <= w1 for s in red.steps)
    idx = [s.index for s in red.steps]
    assert idx == sorted(idx) and idx[0] >= 0


def test_programs_are_told_apart(red):
    steps, pre = red.of("step"), red.of("prefill")
    assert steps and pre
    # every serving-step program ran inside one engine step's host span
    assert all(red.step_of(p) is not None for p in steps + pre)
    assert all(p.end - p.start > 0 for p in steps + pre)


def test_kernels_inside_both_programs(red):
    for kind in ("step", "prefill"):
        p = red.of(kind)[0]
        ks = red.ops_in(p, "kernel")
        assert ks and all(k.name == "bsr_matmul" for k in ks)


def test_busy_and_breakdown(red):
    w0, w1 = red.window
    busy = red.busy()
    assert 0 < busy <= w1 - w0
    assert busy >= sum(p.end - p.start for p in red.programs) * 0.9
    b = trace.breakdown(red)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(" = " not in name for name, _ in b["device_ops"])
    assert sum(t for _, t in b["idle_gaps"]) <= (w1 - w0) - busy + 1e-9


def test_short_name():
    assert trace.short_name('%bsr_matmul.223 = bf16[4096,1024] custom-call('
                            's32[8,58] %fusion.186), custom_call_target='
                            '"tpu_custom_call"') == "bsr_matmul"
    assert trace.short_name("%while.40 = (s32[]) while(...)") == "while"
    assert trace.program_kind("jit_step(5196324822809038106)") == "step"
    assert trace.program_kind("jit__lambda(12619966746685830002)") == \
        "prefill"
    assert trace.program_kind("jit__scatter_kv(777)") == "other"


@pytest.mark.parametrize("metric,value", [
    ("decode_step_ms", 2.766554058823518),
    ("prefill_ms", 67.50249360000001),
    ("host_gap_ms.decode", 3.381504212121222),
    ("idle_share", 21.407879236376893)])
def test_readers_repeat_the_chip_run(red, metric, value):
    """The readers that need only the trace give, from the recorded file,
    what the run that recorded it reported (measured on one v5e chip)."""
    from bench import spec
    from types import SimpleNamespace
    assert spec.reader(metric)(SimpleNamespace(red=red)) == \
        pytest.approx(value, rel=1e-9)
    assert red.busy() == pytest.approx(0.4387029990000007, rel=1e-9)
