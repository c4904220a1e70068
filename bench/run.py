#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration through the program's entry points from
the seed, warms every program its traffic runs, then serves the cell's
open-loop traffic through ``ServingEngine``: a warm-up stretch, the
measured window of ``--seconds``, and with ``--trace 1`` a profiled
stretch after it.  Then it frees the program and checks what the window
served against the configuration's plain reference (``bench/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``, each number compared beside its limit.
The same checks are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TRACE_S = 5.0                      # length of the profiled stretch
TRACE_DIR = ROOT / ".bench_trace"  # git-ignored; removed after reading


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_or_exit(chips):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"bench: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        sys.exit(2)
    return devs[:chips]


def compile_events():
    """Host times of JAX's backend-compile events from now on (a program
    compiled, or loaded from the persistent cache), and counts of the
    persistent cache's hits and misses."""
    import jax
    from jax._src import dispatch
    seen, cache = [], {"hits": 0, "misses": 0}

    def on(event, duration, **kw):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            seen.append(time.perf_counter())

    def on_cache(event, **kw):
        for k in cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache[k] += 1
    jax.monitoring.register_event_duration_secs_listener(on)
    jax.monitoring.register_event_listener(on_cache)
    return seen, cache


def enable_cache():
    """JAX's persistent compilation cache at the program's fixed path in
    the checkout (or $JAX_COMPILATION_CACHE_DIR), every program kept."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def bytes_in_use(devs):
    """Device memory in use now, on the fullest of ``devs``."""
    return max(((d.memory_stats() or {}).get("bytes_in_use", 0)
                for d in devs), default=0)


def serve(cell, eng, seed, seconds, trace, devs=()):
    """Drive the cell's traffic; returns (record, window, the bytes in use
    on the fullest of ``devs`` when the window closed).  Times are seconds
    after the traffic started (``record.t0``)."""
    import jax
    from bench import driver, traffic

    mix = cell.traffic
    plan = traffic.schedule(mix, cell.params["rate_per_s"], seconds, seed)
    window = traffic.window_bounds(mix, seconds)
    stop_at = window[1] + (TRACE_S if trace else 0.0)
    vocab = cell.config["vocab_size"]
    prompts = lambda d: traffic.prompt_ids(seed, d, vocab)  # noqa: E731
    mem = {}
    hooks = [(window[1], lambda: mem.update(at_close=bytes_in_use(devs)))]
    annotate = None
    if trace:
        span = {}

        def start():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans, no Python calls
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            span["w"] = jax.profiler.TraceAnnotation("bench.window")
            span["w"].__enter__()

        def stop():
            span["w"].__exit__(None, None, None)
            jax.profiler.stop_trace()

        hooks += [(window[1], start), (stop_at, stop)]

        def annotate(i):
            return jax.profiler.TraceAnnotation("bench.step", index=i)
    record = driver.drive(
        eng, plan, prompts, stop_at=stop_at,
        must_finish=lambda d: d.stretch == "window",
        hard_end=window[1] + mix["drain_s"], on_time=hooks,
        annotate=annotate)
    return record, window, mem.get("at_close")


def compare(cell, seed, record, counts, controls=()):
    """The checks of what the window served, made once the program is
    freed: (checks, correct, the reference's masks, {control: (checks,
    correct)}).  A control puts in the served tokens' place the tokens
    the reference computed at that precision ranks first, and is judged
    by the same limits.  Every one of the reference's masks
    (``block_keeps``) is tried; each number is the least over them, and
    the masks returned are those that explain the served tokens best."""
    import numpy as np
    from bench import check
    ref = cell.reference()
    cases = check.cases(record.entries, seed, cell.config["vocab_size"])
    values = dict(counts)
    values["short_outputs"] = sum(
        len(e.tokens) != e.due.max_new_tokens for e in record.entries
        if e.status == "finished")
    log(f"check: {len(cases)} requests, "
        f"{sum(len(c[1]) for c in cases)} served tokens compared "
        f"(prompts {[len(c[0]) for c in cases]})")
    keeps = ref.block_keeps(cell.config, seed)
    widest = {key: [] for key in ("served",) + tuple(controls)}
    for i, keep in enumerate(keeps):
        rows = check.served_gaps(ref, cell.config, seed, cases,
                                 cell.traffic["output"]["max"], keep, controls)
        for key in widest:
            g = (np.concatenate([r[key] for r in rows]) if rows
                 else np.zeros(0))
            log(f"check {key}, masks {i + 1} of {len(keeps)}: gaps over "
                f"{g.size} tokens: mean {g.mean():.4g}, share above 0 "
                f"{np.mean(g > 0):.4g}, widest {g.max():.4g}" if g.size
                else f"check {key}: no tokens")
            widest[key].append(float(g.max()) if g.size else np.inf)

    def judged(key):
        gap = min(widest[key])
        return check.verdict(
            dict(values, served_gap_max=gap if np.isfinite(gap) else None),
            cell.params["limits"])
    checks, ok = judged("served")
    keep = keeps[int(np.argmin(widest["served"]))]
    return checks, ok, keep, {c: judged(c) for c in controls}


def per_layer(cell, ctx):
    from bench import spec
    out = {}
    for m in cell.per_layer:
        v = spec.reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(cell, seed, seconds, trace, devs):
    """One run of ``cell`` on ``devs``: the result line's dict."""
    from bench import spec, stats, system, traffic
    dev = devs[0]
    log(f"bench: {cell.name} seed {seed} on {dev.device_kind} "
        f"x{len(devs)}; compile cache {enable_cache()}")
    compiles, cache = compile_events()
    peaks = spec.peaks(dev.device_kind) if trace else None

    times = {}
    eng, counts = system.build(cell, seed, times)
    system.warm(eng, cell.traffic, times)
    log(traffic.describe(cell.traffic, cell.params["rate_per_s"], seconds))
    log("set-up: " + ", ".join(f"{k} {v:.3f} s" for k, v in times.items())
        + f"; before traffic {time.perf_counter() - T_START:.3f} s; "
        f"programs {len(compiles)}, persistent cache hits {cache['hits']}, "
        f"misses {cache['misses']}")
    record, window, in_window = serve(cell, eng, seed, seconds, trace, devs)
    setup_s = record.t0 - T_START
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    log(f"device memory: peak {peak} B (set-up included), in use when the "
        f"window closed {in_window} B")
    e2e, n = stats.end_to_end(record, window, seconds)
    log(f"window: {n}; engine steps {len(record.steps)}")
    del eng
    gc.collect()

    t0 = time.perf_counter()
    checks, correct, keep, _ = compare(cell, seed, record, counts)
    log(f"reference check: {time.perf_counter() - t0:.3f} s")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak),
              "memory_in_window_bytes": int(in_window)}
    result = {"correct": correct, "attempted": n["attempted"],
              "failed": n["failed"]}
    if trace:
        from bench import context, trace as tr
        red = tr.reduce(tr.load(TRACE_DIR))
        tr.remove(TRACE_DIR)
        ctx = context.Context(
            cfg=cell.config, model=cell.family(), keep=keep, peaks=peaks,
            n_slots=cell.traffic["n_slots"], red=red, record=record,
            window=window, compiles=[t - record.t0 for t in compiles])
        result["metrics"] = per_layer(cell, ctx)
        device.update(busy_s=red.busy(),
                      window_s=red.window[1] - red.window[0])
        result["device"] = device
        result["breakdown"] = tr.breakdown(red)
    else:
        e2e["setup_s"] = setup_s
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    return result


def main(argv=None):
    args = parse(argv)
    from bench import spec
    try:
        cell = spec.load(args.workload)
    except spec.SpecError as e:
        log(f"bench: {e}")
        return 2
    devs = devices_or_exit(cell.chips)
    result = run(cell, args.seed, args.seconds, args.trace, devs)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
