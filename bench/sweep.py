#!/usr/bin/env python3
"""Find the highest rate a serving cell sustains: run its mix at each of
several offered rates, one after another in one process.

    python3 bench/sweep.py --workload <cell> --rates 1,2,3 --seconds 40 \
        --seed <n>

Prints one JSON line per rate: offered and completed rates, the queue
left at the window's close, and the end-to-end metrics.  A rate is
sustained while the queue at the close stays near empty and the tokens
completed keep pace with the tokens offered.  The cell's own traffic file
keeps the rate chosen from such a sweep as a number.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from bench.run import start
    spec, cell, config, traffic, device, driver = start(args.workload,
                                                        args.rehearse)
    for rate in (float(x) for x in args.rates.split(",")):
        t = dict(traffic, rate_per_s=rate)
        if args.rehearse:
            t["rehearsal"] = dict(t["rehearsal"], traffic=dict(
                t["rehearsal"]["traffic"], rate_per_s=rate))
        r = driver.run(cell=cell, config=config, traffic=t, seed=args.seed,
                       seconds=args.seconds, trace=False,
                       rehearse=args.rehearse, device=device,
                       t_start=time.perf_counter())
        print(json.dumps({
            "rate_per_s": rate,
            "submitted": r.attempted,
            "completed": r.record["completed"],
            "queued_at_close": r.record["backlog_at_close"],
            "correct": r.correct,
            "metrics": r.end_to_end,
            "mfu_serve_step": r.record["mfu_serve_step"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
