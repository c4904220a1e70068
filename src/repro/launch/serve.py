"""Serving driver: batched prefill + fused-scan decode with an optionally
pruned-and-compiled model — the whole §4.3 pipeline from the CLI:
map schemes -> one-shot masks -> compile_model (BCS packing) -> generate.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --smoke
  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke --sparse

With ``--batch-size``/``--arrival-rate`` the continuous-batching engine
replaces the one-shot ``generate`` call: a simulated open-loop workload
(requests arriving at a fixed rate, mixed prompt lengths) streams through
``serve.engine.ServingEngine``, with a periodic log line reporting batch
occupancy, admitted/evicted counts, and the pack-cache counters:

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \\
      --sparse --batch-size 8 --arrival-rate 1.5 --requests 24
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import numpy as np

from repro import configs
from repro.core import reweighted as RW
from repro.data.pipeline import synthetic_batch
from repro.kernels import bsr_matmul as BM
from repro.kernels.ops import pack_cache_stats
from repro.models import transformer as T
from repro.serve.compile import CompileSpec, compile_model, compiled_summary
from repro.serve.engine import ServingEngine, generate
from repro.train.trainer import apply_masks

PROJ_RE = r"(attn/w[qkvo]|(ffn|moe)/(gate|up|down))/w"
SSM_RE = r"ssm/(in_proj|out_proj)/w"


def sparse_spec(cfg):
    """The served block-pruning spec for ``cfg``: attention, FFN and MoE
    projections, plus the SSM in/out projections.

    At published widths (d_model a multiple of 128) every block is the
    (128, 128) lane tile, the block the Pallas kernel lowers for TPU (see
    ``kernels.bsr_matmul.tpu_refusal``).  Smoke widths are narrower
    than one tile, so they keep (16, 16) blocks, and (16, 8) for the SSM
    projections (the smoke mamba2 in_proj is 296 = 37*8 wide); those run
    in interpret mode only, and ``compile_model`` refuses them on TPU."""
    if cfg.d_model % BM.LANE == 0:
        fc = ssm = (BM.LANE, BM.LANE)
    else:
        fc, ssm = (16, 16), (16, 8)
    return [(PROJ_RE, RW.SchemeChoice("block", fc)),
            (SSM_RE, RW.SchemeChoice("block", ssm))]


def init_params(cfg, seed=0):
    """Random ``init_lm`` params for ``cfg``, initialized by one compiled
    program: run op by op, a model at published widths spends over a
    minute dispatching on a TPU."""
    return jax.jit(T.init_lm, static_argnums=1)(jax.random.PRNGKey(seed),
                                                cfg)


def prune(params, cfg, rate):
    """One-shot magnitude block pruning under ``sparse_spec(cfg)``:
    (masked params, masks, spec).  The masks come from one compiled
    program, for the reason ``init_params`` gives."""
    spec = sparse_spec(cfg)
    masks = jax.jit(lambda p: RW.magnitude_block_masks(
        p, spec, None, rate=rate))(params)
    return apply_masks(params, masks), masks, spec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--sparse", action="store_true",
                    help="block-prune, compile to BCS, serve on the "
                         "Pallas sparse kernel")
    ap.add_argument("--prune-rate", type=float, default=0.6)
    ap.add_argument("--artifacts", default=None, metavar="DIR",
                    help="AOT artifact store: load the packed layouts "
                         "from DIR when the model digest matches "
                         "(checksum-verified + validated), else pack "
                         "fresh and publish — kills the cold start on "
                         "replica restart")
    ap.add_argument("--batch-size", type=int, default=0, metavar="SLOTS",
                    help="continuous-batching engine slot count; > 0 "
                         "switches from one-shot generate to the "
                         "ServingEngine open-loop workload")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="simulated open-loop arrivals per decode step "
                         "(default: saturate — everything arrives at "
                         "step 0); implies the engine path")
    ap.add_argument("--requests", type=int, default=16,
                    help="engine path: number of simulated requests")
    ap.add_argument("--seq-cap", type=int, default=128,
                    help="engine path: per-slot KV ring capacity")
    ap.add_argument("--log-every", type=int, default=8,
                    help="engine path: steps between periodic log lines")
    args = ap.parse_args(argv)

    if args.artifacts:
        # surface the store's structured warm-start / fallback reasons
        logging.basicConfig(level=logging.INFO)
    cfg = configs.get(args.arch, smoke=args.smoke)
    params = init_params(cfg)
    b = synthetic_batch(0, 0, args.batch, args.prompt_len, cfg.vocab,
                        frontend_tokens=cfg.n_frontend_tokens
                        if cfg.family in ("encdec", "vlm") else 0,
                        d_model=cfg.d_model)
    if args.sparse:
        params, masks, spec = prune(params, cfg, args.prune_rate)
        t0 = time.time()
        params, report = compile_model(params, masks, spec,
                                       spec=CompileSpec(keep_dense=False),
                                       artifact_dir=args.artifacts)
        dt_compile = time.time() - t0
        print(f"compile_model in {dt_compile:.2f}s"
              + (f" (artifact store: {args.artifacts})"
                 if args.artifacts else "") + ":")
        print(compiled_summary(report))

    mode = "sparse" if args.sparse else "dense"
    if args.batch_size or args.arrival_rate:
        _run_engine(params, cfg, args, mode)
        return

    t0 = time.time()
    out = jax.block_until_ready(
        generate(params, cfg, b["tokens"], args.new_tokens,
                 frontend=b.get("frontend")))
    dt = time.time() - t0
    print(f"{args.arch} [{mode}]: generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s incl. compile)")
    print("sample:", out[0][:16].tolist())


def _run_engine(params, cfg, args, mode):
    """Simulated open-loop serving: ``--requests`` prompts of mixed lengths
    arrive at ``--arrival-rate`` per step and stream through the
    continuous-batching engine; the periodic log line surfaces the
    observability counters (occupancy, admitted/evicted, pack cache)."""
    n_slots = args.batch_size or 8
    eng = ServingEngine(params, cfg, n_slots=n_slots, seq_cap=args.seq_cap)
    rng = np.random.RandomState(0)
    rate = args.arrival_rate
    # mixed prompt-length buckets exercise the per-bucket prefill cache
    lengths = (args.prompt_len, max(2, args.prompt_len // 2),
               max(2, 3 * args.prompt_len // 4))
    for i in range(args.requests):
        prompt = rng.randint(1, cfg.vocab,
                             size=lengths[i % len(lengths)]).tolist()
        eng.submit(prompt, args.new_tokens,
                   arrival=int(i / rate) if rate else 0)

    t0 = time.time()
    while eng.sched.has_work():
        eng.step()
        if eng.stats["steps"] % args.log_every == 0:
            s, pc = eng.stats, pack_cache_stats()
            print(f"step {s['steps']:>4}: occupancy "
                  f"{eng.mean_occupancy():.2f} admitted {s['admitted']} "
                  f"evicted {s['evicted']} queued {eng.sched.queued()} "
                  f"tokens {s['tokens']} | pack cache hits {pc['hits']} "
                  f"misses {pc['misses']} evictions {pc['evictions']}")
    dt = time.time() - t0
    s = eng.stats
    print(f"{args.arch} [{mode}, engine B={n_slots}"
          + (f", rate={rate}/step" if rate else ", saturated")
          + f"]: {s['finished']}/{args.requests} requests, {s['tokens']} "
          f"tokens in {dt:.2f}s ({s['tokens'] / dt:.1f} tok/s incl. "
          f"prefills), mean occupancy {eng.mean_occupancy():.2f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
