#!/usr/bin/env python3
"""Chip smoke: serve a block-pruned Yi-9B on a TPU through the entry points.

  python chip_smoke.py               # one chip: the served path, checked
  python chip_smoke.py --four-chips  # four chips: tensor-parallel vs tp=1

Every width is Yi-9B's as published (``configs/yi_9b.py``); only the depth
is cut, to ``N_LAYERS`` of 48, so the model fits one 16 GB chip.  Weights
are random, made from ``--seed``, and packed afresh on every run.

One chip runs the path ``launch/serve.py`` serves, through its own
functions: ``init_params`` -> ``prune`` (``magnitude_block_masks`` at rate
0.6 in (128, 128) blocks, ``apply_masks``) -> ``compile_model`` ->
``ServingEngine`` (8 slots) -> 8 requests to the end.  It fails unless every request finishes, no layer is degraded to
masked-dense, every projection is packed, the engine's first-token logits
match the masked-dense model's prefill within ``LOGIT_TOL``, and the
served step program holds a Pallas TPU kernel (``tpu_custom_call``).

``--four-chips`` runs only the tensor-parallel path and what it is compared
with: ``CompileSpec(tp=4)`` -> ``shard_packed_tree`` over a (1, 4) mesh ->
``make_dist`` -> ``ServingEngine(dist=...)``, against the same requests
at tp=1 in this process.  Every packed leaf must span the 4 chips, and
the logits of the prefill and of the teacher-forced decode steps must
agree with tp=1 within ``LOGIT_TOL``.  Served tokens are compared and
reported, not required to be identical: on the chip, XLA partitions the
dense decode ops over the mesh (the softmax over the S-sharded KV cache
reduces across chips) and sums them in another order than one chip
does, and a random-weight model has near-ties that this flips.

Each comparison is checked against a planted fault: the same model with
two column blocks (one chip) or two column shards (four chips) of layer
0's ``wq`` swapped must differ from the reference by more than
``LOGIT_TOL``, or the run fails.

The figures printed on the way (phase times, tokens, peak device bytes)
are smoke figures, not benchmark metrics.  The last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every check passed.  Without a TPU the script exits non-zero and
prints no result: it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_LAYERS = 8            # of Yi-9B's 48: ~2.8 GB of bf16 layers + ~1 GB
                        # embedding and head, with room for the f32 masks
PRUNE_RATE = 0.6
N_SLOTS = 8
SEQ_CAP = 512
PROMPT_LENS = (256, 384)
NEW_TOKENS = (16, 24, 32)
N_REQUESTS = 8
# logits of one model computed two ways (packed vs masked-dense, tp=4 vs
# tp=1): max |diff| over max |ref|.  Both sides are bf16 models that
# accumulate in fp32 and differ in the order of partial sums, so in bf16
# roundings.  On the chip, sound readings stay below 9e-3 and planted
# faults (``planted_fault``) read 6.7e-2 and above (PERF.md); the limit
# sits between.
LOGIT_TOL = 0.02


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def log(msg):
    print(msg, flush=True)


def _timed(fn, *args, **kw):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def build(cfg, seed):
    """Random Yi-width params, block-pruned by the entry point's own code:
    (masked params, masks, spec, phase times)."""
    from repro.launch.serve import init_params, prune

    params, t_init = _timed(init_params, cfg, seed)
    (masked, masks, spec), t_mask = _timed(prune, params, cfg, PRUNE_RATE)
    del params
    return masked, masks, spec, {"init": t_init, "mask": t_mask}


def pack(masked, masks, spec, tp=1):
    """``compile_model`` with the dense weights dropped; every projection
    the spec matches must come out packed."""
    from repro.launch.serve import PROJ_RE
    from repro.serve.compile import CompileSpec, compile_model

    t0 = time.perf_counter()
    params, report = compile_model(masked, masks, spec,
                                   spec=CompileSpec(keep_dense=False, tp=tp))
    dt = time.perf_counter() - t0
    check(not report.skipped,
          f"projections skipped: {[(r.path, r.reason) for r in report.skipped]}")
    packed = [r.path for r in report.packed]
    check(len(packed) == 7 and all(re.search(PROJ_RE, p) for p in packed),
          f"expected the 7 attention/FFN projections packed, got {packed}")
    if tp > 1:
        # a layer whose column-block count tp does not divide (Yi's FFN
        # gate/up: 11008 = 86 blocks of 128) stays unsharded, replicated
        want = {r.path: tp if (r.shape[1] // r.block[1]) % tp == 0 else None
                for r in report.packed}
        got = {r.path: r.shards for r in report.packed}
        check(got == want, f"tp={tp} shards per layer {got}, expected {want}")
        log(f"tp={tp} column-sharded: {[p for p, s in got.items() if s]}; "
            f"replicated: {[p for p, s in got.items() if not s]}")
    return params, report, dt


def requests(cfg, seed):
    """``N_REQUESTS`` (prompt, new tokens) pairs over the prompt lengths."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, cfg.vocab,
                          PROMPT_LENS[i % len(PROMPT_LENS)]).tolist(),
             NEW_TOKENS[i % len(NEW_TOKENS)]) for i in range(N_REQUESTS)]


def serve(params, cfg, reqs, dist=None):
    """Run ``reqs`` through a ``ServingEngine`` to the end: (engine, request
    ids, first-step seconds incl. compile, seconds for the rest)."""
    from repro.serve.engine import ServingEngine

    eng = ServingEngine(params, cfg, n_slots=N_SLOTS, seq_cap=SEQ_CAP,
                        dist=dist)
    rids = [eng.submit(p, n) for p, n in reqs]
    t0 = time.perf_counter()
    eng.step()                     # admits all 8: both prefills + the step
    t1 = time.perf_counter()
    eng.run()
    t2 = time.perf_counter()
    for rid, (_, n) in zip(rids, reqs):
        req = eng.requests[rid]
        check(req.status == "finished" and len(req.tokens) == n,
              f"request {rid}: status {req.status}, "
              f"{len(req.tokens)}/{n} tokens")
    check(eng.stats["degraded_layers"] == 0,
          f"{eng.stats['degraded_layers']} layers degraded to masked-dense")
    return eng, rids, t1 - t0, t2 - t1


def first_logits(params, cfg, prompt, dist=None):
    """Last-position logits of the engine's own prefill program, fp32."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import engine as E

    logits, _ = E._jit_prefill(cfg, dist)(
        params, jnp.asarray([prompt], jnp.int32), None)
    return np.asarray(logits[0, -1].astype(jnp.float32))


def rel_err(got, ref):
    import numpy as np
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def compare_logits(got, ref, what):
    import numpy as np
    err = rel_err(got, ref)
    log(f"  {what}: max|diff|/max|ref| = {err:.3e} (tol {LOGIT_TOL}), "
        f"argmax {int(got.argmax())} vs {int(ref.argmax())}")
    check(np.all(np.isfinite(got)), f"{what}: non-finite logits")
    check(err <= LOGIT_TOL, f"{what}: logits differ by {err:.3e}")


def planted_fault(params):
    """``params`` with positions 0 and 1 of axis 1 of layer 0's ``wq``
    values swapped in every degree bin: two column blocks, or on a
    tensor-parallel layout two column shards — what a misplaced block or
    shard would serve."""
    import dataclasses
    import jax
    import numpy as np

    lay = params["layers"]["attn"]["wq"]["packed"]
    values = []
    for v in lay.values:
        h = np.array(v)
        h[0] = np.take(h[0], [1, 0, *range(2, h.shape[1])], axis=0)
        values.append(jax.device_put(h, v.sharding))
    bad = jax.tree_util.tree_map(lambda a: a, params)
    bad["layers"]["attn"]["wq"]["packed"] = dataclasses.replace(
        lay, values=tuple(values))
    return bad


def check_control(bad, ref, what):
    err = rel_err(bad, ref)
    log(f"  control, {what} with a planted fault: {err:.3e}")
    check(err > LOGIT_TOL, f"{what}: the comparison misses a planted wrong "
          f"block or shard ({err:.3e} <= {LOGIT_TOL})")


def decode_logits(params, cfg, prompt, tokens, dist=None):
    """Logits of the decode path ``generate`` runs, teacher-forced: the
    prompt's prefill, then one ``decode_step`` per token of ``tokens``;
    (len(tokens), vocab) fp32."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import engine as E

    _, cache = E._jit_prefill(cfg, dist)(
        params, jnp.asarray([prompt], jnp.int32), None)
    out = []
    for t, tok in enumerate(tokens):
        logits, cache = E._jit_decode_step(cfg, dist)(
            params, jnp.asarray([[tok]], jnp.int32), cache,
            jnp.full((1, 1), len(prompt) + t, jnp.int32))
        out.append(np.asarray(logits[0, -1].astype(jnp.float32)))
    return np.stack(out)


def one_per_length(reqs, rids):
    seen = {}
    for rid, (p, _) in zip(rids, reqs):
        seen.setdefault(len(p), (rid, p))
    return list(seen.values())


def step_hlo(eng):
    """The served step program's lowered text."""
    import jax.numpy as jnp
    return eng._step_fn.lower(
        eng.params, jnp.asarray(eng.tok), eng.cache, jnp.asarray(eng.pos),
        jnp.asarray(eng.cap)).as_text()


def smoke_one_chip(cfg, seed):
    masked, masks, spec, times = build(cfg, seed)
    params, _, times["pack"] = pack(masked, masks, spec)
    del masks
    reqs = requests(cfg, seed)
    eng, rids, times["first step (compile incl.)"], times["serve"] = serve(
        params, cfg, reqs)
    log(f"smoke: {eng.stats['finished']}/{len(reqs)} requests finished, "
        f"{eng.stats['tokens']} tokens served in {eng.stats['steps']} "
        "engine steps")
    log("first-token logits, packed engine vs masked-dense prefill:")
    for rid, prompt in one_per_length(reqs, rids):
        got = first_logits(eng.params, cfg, prompt)
        check(int(got.argmax()) == eng.requests[rid].tokens[0],
              f"request {rid}: engine's first token is not its prefill's "
              "argmax")
        ref = first_logits(masked, cfg, prompt)
        compare_logits(got, ref, f"prompt of {len(prompt)}")
        check_control(first_logits(planted_fault(eng.params), cfg, prompt),
                      ref, f"prompt of {len(prompt)}")
    return eng, times


def _spans_mesh(params, n):
    """Every leaf of every packed layout lives on ``n`` devices, and the
    values of each column-sharded layout hold 1/n of its shard axis per
    device."""
    import jax
    from repro.core.packed import PackedLayout

    def layouts(node):
        if isinstance(node, dict):
            for k, v in node.items():
                if k == "packed" and isinstance(v, PackedLayout):
                    yield v
                else:
                    yield from layouts(v)

    n_leaves = 0
    for lay in layouts(params):
        for leaf in jax.tree_util.tree_leaves(lay):
            n_leaves += 1
            check(len(leaf.sharding.device_set) == n,
                  f"a packed leaf {leaf.shape} spans "
                  f"{len(leaf.sharding.device_set)} devices, not {n}")
        for v in lay.values if lay.n_shards else ():
            ax = v.ndim - 5
            check(v.sharding.shard_shape(v.shape)[ax] * n == v.shape[ax],
                  f"a packed values leaf {v.shape} is not split {n} ways")
    check(n_leaves > 0, "no packed layout found")
    return n_leaves


def smoke_four_chips(cfg, seed, tp=4):
    from repro.distributed import sharding as SH
    from repro.launch.mesh import make_local_mesh

    masked, masks, spec, times = build(cfg, seed)
    p1, _, t1 = pack(masked, masks, spec, tp=1)
    p4, _, t4 = pack(masked, masks, spec, tp=tp)
    del masked, masks
    times["pack tp=1"], times[f"pack tp={tp}"] = t1, t4
    mesh = make_local_mesh(tp=tp)
    p4 = SH.shard_packed_tree(p4, mesh)
    n_leaves = _spans_mesh(p4, tp)
    log(f"every leaf of the packed layouts ({n_leaves}) spans {tp} devices")
    dist = SH.make_dist(mesh, cfg, N_SLOTS)
    reqs = requests(cfg, seed)
    e1, r1, times["tp=1 first step"], times["tp=1 serve"] = serve(
        p1, cfg, reqs)
    e4, r4, times[f"tp={tp} first step"], times[f"tp={tp} serve"] = serve(
        p4, cfg, reqs, dist=dist)
    log(f"first-token logits, tp={tp} vs tp=1:")
    bad4 = planted_fault(p4)
    for rid, prompt in one_per_length(reqs, r4):
        ref = first_logits(p1, cfg, prompt)
        compare_logits(first_logits(p4, cfg, prompt, dist), ref,
                       f"prompt of {len(prompt)}")
        check_control(first_logits(bad4, cfg, prompt, dist), ref,
                      f"prompt of {len(prompt)}")
    log(f"decode logits, tp={tp} vs tp=1, teacher-forced on the tp=1 tokens:")
    for rid, prompt in one_per_length(reqs, r1):
        toks = e1.requests[rid].tokens
        got, ref = (decode_logits(p, cfg, prompt, toks, d)
                    for p, d in ((p4, dist), (p1, None)))
        worst = max(range(len(toks)), key=lambda t: rel_err(got[t], ref[t]))
        compare_logits(got[worst], ref[worst],
                       f"prompt of {len(prompt)}, {len(toks)} steps, worst "
                       f"step {worst}")
    out4 = [e4.requests[a].tokens for a in r4]
    out1 = [e1.requests[b].tokens for b in r1]
    n_same = sum(a == b for a, b in zip(out4, out1))
    log(f"smoke: served tokens tp={tp} vs tp=1: {n_same}/{len(reqs)} "
        f"requests identical, {e4.stats['tokens']} tokens each")
    for i, (a, b) in enumerate(zip(out4, out1)):
        if a != b:
            t = next(j for j, (x, y) in enumerate(zip(a, b)) if x != y)
            log(f"  request {i}: first differs at token {t}")
    return e4, times


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel phase on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    dev = devices[0]
    cfg = configs.get("yi-9b").replace(n_layers=N_LAYERS)
    log(f"config: yi-9b at published widths (d_model {cfg.d_model}, d_ff "
        f"{cfg.d_ff}, heads {cfg.n_heads}/{cfg.n_kv_heads}, vocab "
        f"{cfg.vocab}); depth cut to {cfg.n_layers} of 48 layers")
    log(f"device: {dev.device_kind} x{len(devices)}")
    try:
        if args.four_chips:
            eng, times = smoke_four_chips(cfg, args.seed)
        else:
            eng, times = smoke_one_chip(cfg, args.seed)
        check("tpu_custom_call" in step_hlo(eng),
              "the served step program holds no Pallas TPU kernel")
        log("the served step program holds tpu_custom_call")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    for name, t in times.items():
        log(f"smoke time {name}: {t:.3f} s")
    for d in devices[:n_chips]:
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
        log(f"smoke peak_bytes_in_use {d}: {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
