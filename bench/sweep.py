#!/usr/bin/env python3
"""Find a cell's knee once: the highest open-loop rate the engine sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 2,3,4

Builds the cell once, then serves its traffic at each rate in turn (a
warm-up stretch, a window of ``--seconds``, then a drain), and prints per
rate the TTFT of the window's first and second halves, what was still
queued when the window closed, and tokens per second completed against
offered.  A rate is sustained when the second half's TTFT is no worse
than the first's and the queue is not growing.  The cell file records the
knee and the rate it serves at (0.8 of it); the benchmark's runs never
search for a rate.  Only this script's own output is printed; it decides
nothing itself.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent),
                str(Path(__file__).resolve().parent.parent / "src")]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from bench import driver, run, spec, stats, system, traffic
    cell = spec.load(args.workload)
    run.devices_or_exit(cell.chips)
    run.enable_cache()
    times = {}
    eng, _ = system.build(cell, args.seed, times)
    system.warm(eng, cell.traffic, times)
    run.log(f"set-up {times}")
    mix = cell.traffic
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        seed = args.seed + i
        plan = traffic.schedule(mix, rate, args.seconds, seed)
        ws, we = traffic.window_bounds(mix, args.seconds)
        queued = []
        rec = driver.drive(
            eng, plan,
            lambda d: traffic.prompt_ids(seed, d, cell.config["vocab_size"]),
            stop_at=we, must_finish=lambda d: False, hard_end=we,
            on_time=[(we, lambda: queued.append(eng.sched.queued()))])
        half = (ws + we) / 2
        e2e, n = stats.end_to_end(rec, (ws, we), args.seconds)
        t0 = time.perf_counter()
        eng.run()                      # drain before the next rate
        offered = sum(e.due.max_new_tokens for e in rec.entries
                      if ws <= e.due.due < we) / args.seconds
        first = [stats.percentile([
            (e.times[0] if e.times else rec.end) - e.due.due
            for e in rec.entries if a <= e.due.due < b], 90)
            for a, b in ((ws, half), (half, we))]
        print(json.dumps({
            "rate_per_s": rate, "ttft_p90_s_halves": first,
            "queued_at_close": queued[0] if queued else None,
            "no_first_token": n["failed"], "attempted": n["attempted"],
            "tok_s": e2e["tok_s"], "offered_tok_s": offered,
            "itl_p50_ms": e2e["itl_p50_ms"], "itl_p99_ms": e2e["itl_p99_ms"],
            "drain_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
