"""Chaos benchmark: serving throughput under injected faults plus the
deterministic recovery bound, all driven by the seeded injectors in
``repro.testing.faults``.

Three rows on the sparse-compiled smoke LM (packed, ``keep_dense=True``
so the degrade path has its masked-dense fallback):

* ``faults,healthy`` — baseline closed-loop decode tok/s (same engine
  shape as ``bench_serving``).
* ``faults,degraded`` — the SAME workload after a seeded bit-flip
  corrupts one packed layout: the engine degrades that layer to
  masked-dense at construction and keeps serving.
  ``degraded_throughput_ratio`` (degraded tok/s / healthy tok/s) is the
  acceptance metric: the floor is 0.8x (enforced here AND gated at the
  wall threshold by ``benchmarks.compare`` against the committed
  baseline) — degraded mode must cost bounded throughput, never an
  outage.
* ``faults,recovery`` — deterministic quarantine recovery:
  ``recovery_steps`` counts engine steps from a NaN-poisoned slot's
  quarantine eviction to the freed slot's re-admission from the queue
  (expected 1; gated LOWER-is-better at the strict threshold — growth
  means eviction stopped freeing capacity promptly).

Emitted to BENCH_faults.json under ``run.py --json`` and gated by
``benchmarks.compare`` like the other suites.
"""
import time

import jax
import numpy as np

from repro import configs
from repro.core import reweighted as RW
from repro.launch.serve import sparse_spec
from repro.models import transformer as T
from repro.serve.compile import CompileSpec, compile_model
from repro.serve.engine import ServingEngine
from repro.testing import faults as F
from repro.train.trainer import apply_masks

ARCH = "yi-9b"
SEQ_CAP = 48
DEGRADED_FLOOR = 0.8    # acceptance: degraded tok/s >= 0.8x healthy


def _packed_smoke_lm():
    cfg = configs.get(ARCH, smoke=True)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    masks = RW.magnitude_block_masks(params, sparse_spec(cfg), None, rate=0.6)
    params = apply_masks(params, masks)
    params, _ = compile_model(params, masks, sparse_spec(cfg),
                              spec=CompileSpec(keep_dense=True))
    return params, cfg


def _prompts(cfg, n, prompt_len=16):
    rng = np.random.RandomState(0)
    lens = (prompt_len, max(2, prompt_len // 2))
    return [rng.randint(1, cfg.vocab, size=lens[i % 2]).tolist()
            for i in range(n)]


def _throughput(params, cfg, prompts, new_tokens, n_slots=4):
    """(wall_s, engine) for one closed-loop run; an untimed warm-up run
    first so the timed pass measures steady-state serving, not tracing."""
    for timed in (False, True):
        eng = ServingEngine(params, cfg, n_slots=n_slots, seq_cap=SEQ_CAP)
        for p in prompts:
            eng.submit(p, new_tokens)
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        if timed:
            return dt, eng


def bench(fast=True):
    params, cfg = _packed_smoke_lm()
    new_tokens = 24 if fast else 32
    n_req = 8 if fast else 16
    prompts = _prompts(cfg, n_req)
    rows = []

    # -- healthy baseline -------------------------------------------------
    dt_h, eng_h = _throughput(params, cfg, prompts, new_tokens)
    healthy = eng_h.stats["tokens"] / dt_h
    rows.append(("faults,healthy", dt_h / eng_h.stats["steps"] * 1e6,
                 f"tok_per_s={healthy:.1f};"
                 f"requests={eng_h.stats['finished']};"
                 f"steps={eng_h.stats['steps']}"))

    # -- degraded mode: seeded bit-flip -> masked-dense fallback ----------
    bad, rec = F.bitflip_packed_leaf(params, seed=0)
    dt_d, eng_d = _throughput(bad, cfg, prompts, new_tokens)
    if eng_d.stats["degraded_layers"] < 1:
        raise RuntimeError("bit-flip was not detected: no layer degraded")
    if eng_d.stats["finished"] != eng_h.stats["finished"]:
        raise RuntimeError("degraded engine dropped requests")
    degraded = eng_d.stats["tokens"] / dt_d
    ratio = degraded / healthy
    if ratio < DEGRADED_FLOOR:
        raise RuntimeError(
            f"degraded throughput ratio {ratio:.2f} below the "
            f"{DEGRADED_FLOOR:g}x acceptance floor ({degraded:.1f} vs "
            f"{healthy:.1f} tok/s)")
    rows.append(("faults,degraded", dt_d / eng_d.stats["steps"] * 1e6,
                 f"tok_per_s={degraded:.1f};"
                 f"degraded_throughput_ratio={ratio:.2f};"
                 f"degraded_layers={eng_d.stats['degraded_layers']};"
                 f"fault={rec.target};"
                 f"acceptance_floor={DEGRADED_FLOOR:g}x"))

    # -- quarantine recovery bound (deterministic, no wall clock) ---------
    eng = ServingEngine(params, cfg, n_slots=2, seq_cap=SEQ_CAP)
    rids = [eng.submit(p, new_tokens) for p in prompts[:3]]
    eng.step()                                   # admit the first two
    victim = rids[1]
    F.nan_slot(eng, eng.requests[victim].slot)
    while eng.requests[victim].status != "quarantined":
        eng.step()
    q_step = eng.stats["steps"]
    while eng.requests[rids[2]].status == "queued":
        eng.step()
    recovery = eng.stats["steps"] - q_step
    eng.run()
    rows.append(("faults,recovery", 0.0,
                 f"recovery_steps={recovery};"
                 f"quarantined={eng.stats['quarantined']};"
                 f"finished={eng.stats['finished']}"))
    return rows
