"""BENCHMARK.json and the files it names: every cell finds its
configuration, mix, cell file and metric readers by name; a run without a
TPU prints no result."""
import json
import os
import subprocess
import sys

import pytest

from bench import context, spec, trace
from bench.driver import Record

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_finds_its_files(name):
    cell = spec.load(name)
    assert cell.reference().logits
    assert cell.family().arch_config(cell.config)
    assert {"rate_per_s", "limits"} <= set(cell.params)
    assert cell.per_layer and cell.end_to_end
    for m in cell.per_layer:
        assert callable(spec.reader(m["name"]))


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_reader_finds_nothing_in_an_empty_trace(metric):
    red = trace.Reduced((0.0, 1.0), [], [], [])
    cell = spec.load(CELLS[0])
    ctx = context.Context(
        cfg=cell.config, model=cell.family(), keep=None,
        peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}, n_slots=16,
        red=red, record=Record([], [], 0.0, 0.0), window=(0.0, 1.0),
        compiles=[])
    v = spec.reader(metric)(ctx)
    assert v is None or metric == "compiles_in_window"


@pytest.mark.parametrize("moves,e2e_cells", [("no_such_metric", None),
                                             ("tok_s", ["another.cell"])])
def test_metric_must_move_a_reported_metric(tmp_path, moves, e2e_cells):
    """A per-layer metric whose ``moves`` names no end-to-end metric, or
    one the cell does not report, is an error, never dropped."""
    b = json.loads(json.dumps(BENCH))
    b["per_layer"][0]["moves"] = moves
    b["per_layer"][0].pop("workloads", None)
    if e2e_cells:
        for m in b["end_to_end"]:
            if m["name"] == moves:
                m["workloads"] = e2e_cells
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    with pytest.raises(spec.SpecError, match="moves|report"):
        spec.load(CELLS[0], tmp_path)


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(spec.SpecError):
        spec.peaks("some other chip")


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2 and p.stdout == ""
    assert "TPU" in p.stderr
