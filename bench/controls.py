#!/usr/bin/env python3
"""Readings for the limits of a cell's correctness comparison.

    python3 bench/controls.py --workload <cell> --seeds 1,2,3 --seconds 15

For each seed, in one process: a run of the cell with a short window, the
numbers it compares (the program's readings, from which a limit's lower
end is set), and the same numbers from the control — the cell's reference
computed in the precision below the configuration's, in the program's
place (its readings set the upper end).  Prints one JSON line per seed
and, last, the largest program reading and the smallest control reading
of each number.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from bench.run import start
    spec, cell, config, traffic, device, driver = start(args.workload,
                                                        args.rehearse)
    program, control = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        r = driver.run(cell=cell, config=config, traffic=traffic, seed=seed,
                       seconds=args.seconds, trace=False,
                       rehearse=args.rehearse, device=device,
                       t_start=time.perf_counter(), control=True)
        got = {k: v for k, (v, _) in r.checks.items()}
        ctl = r.record["control"]
        print(json.dumps({"seed": seed, "program": got, "control": ctl}),
              flush=True)
        for k, v in got.items():
            program[k] = max(program.get(k, v), v)
        for k, v in ctl.items():
            control[k] = min(control.get(k, v), v)
    print(json.dumps({"program_max": program, "control_min": control}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
