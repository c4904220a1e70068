"""Find a cell's files by the names in ``BENCHMARK.json``.

    BENCHMARK.json                  the cells, metrics and bounds
    bench/configs/<config>.json     a configuration's sizes (``file``)
    bench/configs/<reference>.py    its plain reference, named in the file
    bench/families/<family>.py      its family module, named in the file
    bench/traffic/<traffic>.json    a traffic mix's parameters
    bench/workloads/<cell>.json     a cell's rate and correctness limits
    bench/metrics/<metric>.py       a per-layer metric's reader
    bench/peaks.json                the chips' peaks, by device kind

A later cell, configuration, mix or metric is added by adding files.  A
configuration brings its ``.json``, its plain reference (which imports
nothing of the program) and, for an architecture the harness does not
know yet, its family module (``bench/families/__init__.py`` lists what
one provides).  The generic harness reaches the model only through the
family module.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(ValueError):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def _json(path):
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file
    config_name: str
    traffic: dict           # the mix file
    traffic_name: str
    params: dict            # the cell file: rate, limits
    end_to_end: list        # this cell's end-to-end metric entries
    per_layer: list         # this cell's per-layer metric entries

    def reference(self):
        """The configuration's plain reference module."""
        return importlib.import_module(
            f"bench.configs.{self.config['reference']}")

    def family(self):
        """The configuration's family module."""
        if "family" not in self.config:
            raise SpecError(f"configuration {self.config_name!r} names no "
                            f"family")
        return importlib.import_module(
            f"bench.families.{self.config['family']}")


def _applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def load(cell_name, root=ROOT):
    """The cell named ``cell_name`` in ``root``/BENCHMARK.json, with
    everything it needs (``root`` is the checkout; tests give another)."""
    root = Path(root)
    b = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in b["workloads"]}
    if cell_name not in cells:
        raise SpecError(f"no cell {cell_name!r} in BENCHMARK.json "
                        f"(cells: {sorted(cells)})")
    w = cells[cell_name]
    cfgs = {c["name"]: c for c in b["configs"]}
    e2e = [m for m in b["end_to_end"] if _applies(m, cell_name)]
    per_layer = [m for m in b["per_layer"] if _applies(m, cell_name)]
    named = {m["name"] for m in b["end_to_end"]}
    reported = {m["name"] for m in e2e}
    for m in per_layer:
        if m["moves"] not in named:
            raise SpecError(f"per-layer metric {m['name']!r} moves "
                            f"{m['moves']!r}, which is no end-to-end metric")
        if m["moves"] not in reported:
            raise SpecError(f"per-layer metric {m['name']!r} applies to "
                            f"{cell_name!r}, which does not report "
                            f"{m['moves']!r}")
    return Cell(name=cell_name, chips=w["chips"],
                config=_json(root / cfgs[w["config"]]["file"]),
                config_name=w["config"],
                traffic=_json(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                traffic_name=w["traffic"],
                params=_json(root / "bench" / "workloads"
                             / f"{cell_name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def reader(metric_name):
    """The ``read(ctx)`` function of ``bench/metrics/<metric_name>.py``."""
    path = BENCH / "metrics" / f"{metric_name}.py"
    if not path.is_file():
        raise SpecError(f"metric {metric_name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric_name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind):
    """The published peaks of ``device_kind``; a kind not in the table is
    an error, never a default."""
    table = _json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json (kinds: {sorted(table['devices'])})")
    return table["devices"][device_kind]
