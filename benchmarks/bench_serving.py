"""Continuous-batching serving benchmark: decode throughput and batch
occupancy of ``serve.engine.ServingEngine`` on the sparse-compiled smoke LM.

Two row groups, both on packed (BCS) params in interpret mode:

* ``serving,B{N}`` — saturated closed-loop decode at N slots, plus a
  ``serving,scaling`` row with ``batch_speedup`` = (B=8 tok/s)/(B=1
  tok/s).  This is THE tentpole metric: one batched launch amortizes the
  packed weights over B requests, so per-launch overhead (dominant in
  interpret mode, HBM weight streaming on real hardware) stops being paid
  per token.  The acceptance floor is 3x; the committed baseline gates it
  (wall-clock, so at the loose wall threshold).
* ``serving,rate{R}`` — open-loop arrival sweep at 8 slots: tokens/s and
  the *deterministic* mean batch occupancy (strictly gated — a scheduler
  change that strands slots shows up here, no wall-clock noise).

Emitted to BENCH_serving.json under ``run.py --json`` and gated by
``benchmarks.compare`` like the other suites (``*_tok_per_s`` and
``batch_speedup`` at the wall threshold, ``mean_occupancy`` strict).
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
from repro.train.trainer import apply_masks

ARCH = "yi-9b"
SEQ_CAP = 48


def _packed_smoke_lm():
    cfg = configs.get(ARCH, smoke=True)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    masks = RW.magnitude_block_masks(params, sparse_spec(cfg), None, rate=0.6)
    params = apply_masks(params, masks)
    params, _ = compile_model(params, masks, sparse_spec(cfg),
                              spec=CompileSpec(keep_dense=False))
    return params, cfg


def _prompts(cfg, n, prompt_len):
    rng = np.random.RandomState(0)
    # two length buckets: exercises the bucketed prefill/slot-write caches
    lens = (prompt_len, max(2, prompt_len // 2))
    return [rng.randint(1, cfg.vocab, size=lens[i % 2]).tolist()
            for i in range(n)]


def _run(params, cfg, prompts, new_tokens, n_slots, arrivals=None):
    """One engine run; returns (wall_s, engine).  A same-shaped warm-up
    engine runs first so the timed run measures steady-state serving, not
    tracing."""
    for timed in (False, True):
        eng = ServingEngine(params, cfg, n_slots=n_slots, seq_cap=SEQ_CAP)
        for i, p in enumerate(prompts):
            eng.submit(p, new_tokens,
                       arrival=arrivals[i] if arrivals else 0)
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        if timed:
            return dt, eng


def bench(fast=True):
    params, cfg = _packed_smoke_lm()
    # enough decode steps that the B=8 run's 2*8 serial prefills stop
    # dominating the wall clock (pure decode scales ~6x at B=8; short
    # requests would hide that behind prefill cost)
    new_tokens = 24 if fast else 32
    prompt_len = 16
    rows = []

    # -- saturated decode scaling: B=1 vs B=8, same per-request work ------
    tok_per_s = {}
    for n_slots in (1, 8):
        prompts = _prompts(cfg, 2 * n_slots, prompt_len)
        dt, eng = _run(params, cfg, prompts, new_tokens, n_slots)
        tps = eng.stats["tokens"] / dt
        tok_per_s[n_slots] = tps
        rows.append((f"serving,B{n_slots}", dt / eng.stats["steps"] * 1e6,
                     f"tok_per_s={tps:.1f};"
                     f"mean_occupancy={eng.mean_occupancy():.2f};"
                     f"requests={eng.stats['finished']};"
                     f"steps={eng.stats['steps']}"))
    speedup = tok_per_s[8] / tok_per_s[1]
    rows.append(("serving,scaling", 0.0,
                 f"batch_speedup={speedup:.2f}x;"
                 f"b1_tok_per_s={tok_per_s[1]:.1f};"
                 f"b8_tok_per_s={tok_per_s[8]:.1f};"
                 "acceptance_floor=3x"))

    # -- open-loop arrival sweep at 8 slots -------------------------------
    n_req = 12 if fast else 32
    for rate in (0.25, 1.0, 4.0):
        prompts = _prompts(cfg, n_req, prompt_len)
        arrivals = [int(i / rate) for i in range(n_req)]
        dt, eng = _run(params, cfg, prompts, new_tokens, 8, arrivals)
        rows.append((f"serving,rate{rate:g}", dt / eng.stats["steps"] * 1e6,
                     f"tok_per_s={eng.stats['tokens'] / dt:.1f};"
                     f"mean_occupancy={eng.mean_occupancy():.2f};"
                     f"admitted={eng.stats['admitted']};"
                     f"evicted={eng.stats['evicted']}"))
    return rows
