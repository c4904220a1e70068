#!/usr/bin/env python3
"""The control of a cell's comparison, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, in one process: build the cell from the seed, serve a
window of its traffic at its own rate, finish every request in flight,
free the program, then judge the same sample of finished requests twice
by the cell's limits, through the comparison a benchmark run makes
(``run.compare``):

  program   the served tokens (the readings that set a limit's lower end);
  float8    in their place, the tokens the plain reference computed in
            float8 ranks first at each position: the control, one step of
            precision below the served bfloat16, which has to come out
            not correct (the readings that set the upper end).

One JSON line per seed, with each reading's checks and ``correct``.  The
benchmark's own runs never run the control.
"""
import argparse
import gc
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent),
                str(Path(__file__).resolve().parent.parent / "src")]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from bench import run, spec, system
    cell = spec.load(args.workload)
    devs = run.devices_or_exit(cell.chips)
    run.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        eng, counts = system.build(cell, seed, {})
        system.warm(eng, cell.traffic, {})
        record, _, _ = run.serve(cell, eng, seed, args.seconds, 0, devs)
        eng.run()                       # finish the longest requests too
        for e in record.entries:
            req = eng.requests[e.rid]
            e.tokens, e.status = list(req.tokens), req.status
        del eng
        gc.collect()
        checks, ok, _, ctl = run.compare(cell, seed, record, counts,
                                         controls=("float8",))
        out = {"seed": seed, "program": {"correct": ok, "checks": checks}}
        out.update({c: {"correct": k_ok, "checks": k}
                    for c, (k, k_ok) in ctl.items()})
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
