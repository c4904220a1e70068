"""The family contract: a non-dense architecture reaches the harness as new
files alone, and the generic harness names no family.

A stub family (``ssm_family``, the program's ``ssm`` family at a smoke
size) is registered as ``bench.families.ssm_stub``; its configuration,
mix and cell are files under a temporary checkout root.  The cell loads
through ``spec.load``, builds and warms through ``system.build`` /
``system.warm`` (pruned and packed, interpret mode on the CPU), serves a
small recorded run, and ``mfu.decode`` reads its work through the stub's
``decode_step`` over the cache lengths the host record gives.
"""
import json
import re
import sys
from pathlib import Path

import pytest

from bench import context, run, spec, system, trace
from bench.tests import ssm_family

CONFIG = {"family": "ssm_stub", "model_type": "ssm-stub",
          "hidden_size": 64, "expand": 2, "state_size": 16, "head_dim": 16,
          "conv_kernel": 4, "vocab_size": 256, "num_hidden_layers": 2,
          "initializer_range": 0.02,
          "pruning": {"block": [16, 8], "rate": 0.5}}
MIX = {"block_requests": 5, "prompt_lengths": [[64, 0.8], [128, 0.2]],
       "output": {"median": 16, "sigma": 0.5, "min": 8, "max": 32},
       "n_slots": 4, "seq_cap": 128, "warmup_s": 1, "drain_s": 30}
PEAKS = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8}


@pytest.fixture()
def cell(tmp_path, monkeypatch):
    from repro.serve import engine as E
    monkeypatch.setitem(sys.modules, "bench.families.ssm_stub", ssm_family)
    monkeypatch.setattr(E, "_JIT_CACHE", type(E._JIT_CACHE)())
    for d in ("configs", "traffic", "workloads"):
        (tmp_path / "bench" / d).mkdir(parents=True)
    (tmp_path / "bench/configs/ssm.json").write_text(json.dumps(CONFIG))
    (tmp_path / "bench/traffic/ssm.json").write_text(json.dumps(MIX))
    (tmp_path / "bench/workloads/ssm.cell.json").write_text(json.dumps(
        {"rate_per_s": 16.0, "limits": {"unpacked_projections": 0}}))
    b = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    b["configs"] = [{"name": "ssm", "file": "bench/configs/ssm.json"}]
    b["workloads"] = [{"name": "ssm.cell", "config": "ssm", "traffic": "ssm",
                       "chips": 1}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return spec.load("ssm.cell", tmp_path)


def _recorded(record):
    """The trace a run would record, made from its host record: each engine
    step that stepped a slot as a ``bench.step`` span holding one serving
    step program."""
    steps = [trace.Span("bench.step", s, e, index=i)
             for i, (s, e, n) in enumerate(record.steps) if n]
    progs = [trace.Span("jit_step", s.start, s.end, "step") for s in steps]
    return trace.Reduced((record.steps[0][0], record.steps[-1][1]), steps,
                         progs, [])


def test_a_new_family_needs_only_new_files(cell, monkeypatch):
    assert cell.family() is ssm_family
    eng, counts = system.build(cell, 2**33 + 9, {})
    assert eng.cfg.family == "ssm" and counts == {"unpacked_projections": 0}
    system.warm(eng, cell.traffic, {})

    # the keys each stepped slot's query attends, from the engine itself
    first, seen = eng.stats["steps"], {}
    real = eng._step_fn

    def step_fn(p, tok, cache, pos, cap):
        seen[eng.stats["steps"] - 1 - first] = sorted(
            int(eng.pos[s, 0]) + 1 for s, _ in eng.sched.active())
        return real(p, tok, cache, pos, cap)
    eng._step_fn = step_fn
    record, window, _ = run.serve(cell, eng, 2**33 + 9, 2.0, 0)
    assert seen and any(len(v) > 1 for v in seen.values())

    calls, work = [], ssm_family.decode_step

    def decode_step(cfg, lengths, keep=None):
        calls.append(sorted(lengths))
        return work(cfg, lengths, keep)
    monkeypatch.setattr(ssm_family, "decode_step", decode_step)
    red = _recorded(record)
    ctx = context.Context(
        cfg=cell.config, model=cell.family(), keep=None, peaks=PEAKS,
        n_slots=MIX["n_slots"], red=red, record=record, window=window,
        compiles=[])
    got = spec.reader("mfu.decode")(ctx)

    # every cycle but the last, which has no next step to end it
    want = [seen[s.index] for s in red.steps[:-1]]
    assert calls == want
    least = sum(ctx.least(work(cell.config, ls))
                for ls in want)
    wall = red.steps[-1].start - red.steps[0].start
    assert got == pytest.approx(100.0 * least / wall, rel=1e-12)


GENERIC = [spec.BENCH / f"{m}.py" for m in (
    "system", "work", "context", "run", "check", "control", "sweep",
    "spec")] + sorted((spec.BENCH / "metrics").glob("*.py"))
MODEL_NAMES = re.compile(r'attn/w|ffn/|sliding_window|intermediate_size|'
                         r'num_key_value_heads|family="dense"')


@pytest.mark.parametrize("path", GENERIC, ids=lambda p: p.name)
def test_generic_modules_name_no_family(path):
    hits = [f"{i}: {line}" for i, line in enumerate(
        Path(path).read_text().splitlines(), 1) if MODEL_NAMES.search(line)]
    assert not hits, hits
