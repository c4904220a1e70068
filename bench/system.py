"""The system under test, built through the program's own entry points.

The cell's family module (``bench/families/<family>.py``) gives the
program's ``ArchConfig`` and the weights, made from the seed on the
device.  A configuration with ``pruning`` goes through
``launch.serve.prune`` and ``serve.compile.compile_model(CompileSpec(
keep_dense=False))``, so every projection the family names is served
packed; a dense one is served as it is.  Either is handed to
``serve.engine.ServingEngine`` with the mix's slots and ring.
"""
from __future__ import annotations

import time

import jax
import numpy as np


def _timed(times, name, fn, *args, **kw):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    times[name] = time.perf_counter() - t0
    return out


def build(cell, seed, times):
    """(engine, counts the check compares).  Records the set-up phases in
    ``times``."""
    from repro.launch.serve import prune
    from repro.serve.compile import CompileSpec, compile_model
    from repro.serve.engine import ServingEngine

    cfg, mix = cell.config, cell.traffic
    model = cell.family()
    acfg = model.arch_config(cfg)
    params = _timed(times, "init", model.init, cfg, seed)
    unpacked = 0
    if cfg.get("pruning"):
        masked, masks, spec = _timed(times, "mask", prune, params, acfg,
                                     cfg["pruning"]["rate"])
        del params
        t0 = time.perf_counter()
        params, report = compile_model(masked, masks, spec,
                                       spec=CompileSpec(keep_dense=False))
        times["pack"] = time.perf_counter() - t0
        del masked, masks
        unpacked = len(model.PRUNED) - sum(model.is_pruned(r.path)
                                           for r in report.packed)
    t0 = time.perf_counter()
    eng = ServingEngine(params, acfg, n_slots=mix["n_slots"],
                        seq_cap=mix["seq_cap"])
    del params
    times["engine"] = time.perf_counter() - t0
    # projections served otherwise than the configuration states: not
    # packed where it prunes, or retired to masked-dense by validation
    return eng, {"unpacked_projections": unpacked
                 + eng.stats["degraded_layers"]}


def warm(eng, mix, times):
    """Compile every program the mix's traffic runs: one request of each
    prompt length, two tokens each, through the engine itself (the
    admission prefill at each length, the slot write, the serving step,
    the slot release)."""
    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    for p, _ in mix["prompt_lengths"]:
        eng.submit(rng.integers(1, eng.cfg.vocab, p).tolist(), 2)
    eng.run()
    times["warm"] = time.perf_counter() - t0
