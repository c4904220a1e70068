"""End-to-end training driver: mesh -> sharded init -> (optional pruning
schedule) -> train loop with checkpoint/restart, straggler monitoring, and
deterministic data shards.

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --smoke \
      --steps 50 --prune --target-rate 0.6
"""
from __future__ import annotations

import argparse
import time

import jax

from repro import configs
from repro.core import reweighted as RW
from repro.core.mapper_rule import lm_layers, map_rules
from repro.data.pipeline import synthetic_batch
from repro.distributed import checkpoint as CKPT
from repro.distributed import sharding as SH
from repro.distributed.elastic import StragglerMonitor, rebuild_mesh
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T
from repro.train.trainer import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--prune", action="store_true")
    ap.add_argument("--target-rate", type=float, default=0.6)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)

    cfg = configs.get(args.arch, smoke=args.smoke)
    mesh = make_local_mesh() if args.model_parallel == 1 else \
        rebuild_mesh(model_parallel=args.model_parallel)
    dist = SH.make_dist(mesh, cfg, args.batch)

    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    p_shard = SH.param_shardings(params, cfg, mesh)
    params = jax.device_put(params, p_shard)

    reweighted = None
    masks, alphas = None, None
    spec = None
    if args.prune:
        layers = lm_layers(cfg, tokens=args.batch * args.seq)
        spec, report = map_rules(layers, dataset_hard=False,
                                 compression=1 / (1 - args.target_rate))
        # snap blocks to the (possibly smoke-sized) layer dims
        spec = [(p, RW.SchemeChoice(c.scheme, (
            min(c.block[0], 8), min(c.block[1], 16))) if c.scheme != "none"
            else c) for p, c in spec]
        reweighted = RW.ReweightedConfig(spec=tuple(spec), lam=1e-3)
        alphas = RW.init_alphas(params, spec)

    opt_init, train_step = make_train_step(cfg, dist=dist, lr=args.lr,
                                           reweighted=reweighted)
    opt_state = opt_init(params)
    train_step = jax.jit(train_step)

    start = 0
    if args.resume:
        restored, step0 = CKPT.restore(args.ckpt_dir,
                                       {"params": params, "opt": opt_state})
        if restored is not None:
            params, opt_state = restored["params"], restored["opt"]
            start = step0
            print(f"resumed from step {start}")

    mon = StragglerMonitor()
    prune_at = int(args.steps * 0.6) if args.prune else None
    for step in range(start, args.steps):
        if reweighted and step and step % reweighted.reweight_every == 0 \
                and (prune_at is None or step < prune_at):
            alphas = RW.update_alphas(params, reweighted)
        if prune_at is not None and step == prune_at:
            tau = RW.global_threshold(params, spec, args.target_rate)
            masks = RW.masks_for_spec(params, spec, threshold=tau)
            alphas = None
            rep = RW.sparsity_report(params, masks)["__overall__"]
            print(f"step {step}: pruned -> density {rep['density']:.3f} "
                  f"(compression {rep['compression']:.2f}x)")
        batch = synthetic_batch(
            0, step, args.batch, args.seq, cfg.vocab,
            frontend_tokens=cfg.n_frontend_tokens
            if cfg.family in ("encdec", "vlm") else 0, d_model=cfg.d_model)
        t0 = time.time()
        params, opt_state, metrics = train_step(params, opt_state, batch,
                                                masks, alphas)
        dt = time.time() - t0
        if mon.observe(dt):
            print(f"step {step}: straggler detected ({dt:.2f}s) — backup "
                  f"shard recompute would trigger here")
        if step % 10 == 0:
            print(f"step {step}: loss {float(metrics['loss']):.4f} "
                  f"({dt*1e3:.0f} ms)")
        if step and step % args.ckpt_every == 0:
            CKPT.save(args.ckpt_dir, step,
                      {"params": params, "opt": opt_state})
    print(f"final loss {float(metrics['loss']):.4f}")
    return params, masks


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
