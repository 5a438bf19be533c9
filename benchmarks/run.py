"""Benchmark driver: one bench per paper table/figure + kernels + roofline.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAMES]
                                            [--check-budgets]

Prints ``bench,config,metric,value`` CSV rows and writes
results/bench.json.  ``--only`` takes a comma-separated subset.  Each
bench runs in a child process of its own and this script never imports
JAX, so whichever process runs JAX is the only one holding the chip.
Figure map:

    fig4   distributed join scaling            (paper Fig. 4)
    groupby  local groupby backend sweep       (sort vs bucketed hash)
    sort   local OrderBy backend sweep         (xla vs multi-pass radix)
    setops local semi-join backend sweep       (sortmerge vs hash probe)
    outofcore  morsel-driven join/groupby past device memory (10M+ rows)
    fig12  sequential data engineering         (paper Fig. 12)
    fig13  data-parallel data engineering      (paper Figs. 13-15)
    fig16  DDP deep learning on CPU            (paper Figs. 16/17)
    kernels  Pallas kernel micro-benchmarks
    roofline per-(arch×cell×mesh) roofline table (assignment §Roofline)
    serve  continuous-batching serve soak fused with feature joins

Perf-regression gate: ``--check-budgets`` snapshots the committed
``results/bench.json`` timings as per-row budgets *before* running,
re-runs the selected benches, and fails (exit 1) if any ``seconds`` row
regresses past ``--budget-factor`` (default 1.5x) its budget, or any
*throughput* row (``tokens_per_sec`` / ``rows_per_sec`` — lower is
worse) falls below its budget divided by the factor.  Rows are matched
by (bench, config, metric, rows), so a ``--fast`` gate run only
compares against committed fast-size baselines.
"""
from __future__ import annotations

import argparse
import importlib
import os
import subprocess
import sys

from .common import REPO, load_results, row_key

# bench name -> module whose ``run(fast)`` it calls (imported only in the
# bench's own child process)
BENCHES = {
    "fig4": "bench_join",
    "groupby": "bench_groupby",
    "sort": "bench_sort",
    "setops": "bench_setops",
    "outofcore": "bench_outofcore",
    "fig12": "bench_sequential_de",
    "fig13": "bench_dataparallel_de",
    "fig16": "bench_ddp_train",
    "kernels": "bench_kernels",
    "roofline": "bench_roofline",
    "serve": "bench_serve_e2e",
}

# metrics where lower is WORSE: gated as a lower bound (value must stay
# above budget / factor), unlike ``seconds`` which gates as an upper
# bound
THROUGHPUT_METRICS = ("tokens_per_sec", "rows_per_sec")


def check_budgets(budgets: dict, factor: float) -> list[str]:
    """Compare the saved ``seconds`` (upper-bound) and throughput
    (lower-bound) rows against the snapshotted budgets; rows a bench
    didn't re-run compare equal and pass trivially.  Returns the
    regression report lines."""
    failures = []
    checked = 0
    for r in load_results():
        metric = r.get("metric")
        if metric != "seconds" and metric not in THROUGHPUT_METRICS:
            continue
        budget = budgets.get(row_key(r))
        if budget is None or budget <= 0:
            continue                      # new row: no budget yet
        checked += 1
        if metric == "seconds":
            if r["value"] > factor * budget:
                failures.append(
                    f"  {r['bench']}/{r['config']} (rows={r.get('rows')}): "
                    f"{r['value']:.3f}s vs budget {budget:.3f}s "
                    f"({r['value'] / budget:.2f}x > {factor}x)")
        elif r["value"] < budget / factor:
            failures.append(
                f"  {r['bench']}/{r['config']} (rows={r.get('rows')}): "
                f"{metric} {r['value']:.1f} vs budget {budget:.1f} "
                f"({budget / max(r['value'], 1e-9):.2f}x below, "
                f"> {factor}x allowed)")
    print(f"# budget check: {checked} rows checked, "
          f"{len(failures)} regressions", flush=True)
    return failures


def run_bench_child(name: str, fast: bool) -> None:
    """Run one bench in a fresh child process (raises if it fails)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"), env.get("PYTHONPATH")) if p)
    subprocess.run([sys.executable, "-m", "benchmarks.run", "--bench", name]
                   + (["--fast"] if fast else []),
                   cwd=REPO, env=env, check=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced sizes (CI smoke)")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names "
                         f"(choices: {', '.join(sorted(BENCHES))})")
    ap.add_argument("--check-budgets", action="store_true",
                    help="fail (exit 1) if a re-run 'seconds' row "
                         "regresses past --budget-factor x its committed "
                         "results/bench.json value")
    ap.add_argument("--budget-factor", type=float, default=1.5)
    ap.add_argument("--bench", choices=sorted(BENCHES),
                    help=argparse.SUPPRESS)    # child: run one bench here
    args = ap.parse_args()
    if args.bench:
        importlib.import_module(f"benchmarks.{BENCHES[args.bench]}") \
            .run(fast=args.fast)
        return
    if args.only:
        names = args.only.split(",")
        unknown = [n for n in names if n not in BENCHES]
        if unknown:
            ap.error(f"unknown bench(es) {unknown}; "
                     f"choices: {', '.join(sorted(BENCHES))}")
    else:
        names = list(BENCHES)
    budgets = {}
    if args.check_budgets:              # snapshot before benches overwrite
        budgets = {row_key(r): r["value"] for r in load_results()
                   if r.get("metric") == "seconds"
                   or r.get("metric") in THROUGHPUT_METRICS}
    print("bench,config,metric,value")
    for name in names:
        print(f"# --- {name} ---", flush=True)
        run_bench_child(name, args.fast)
    if args.check_budgets:
        failures = check_budgets(budgets, args.budget_factor)
        if failures:
            print("PERF BUDGET EXCEEDED:", flush=True)
            print("\n".join(failures), flush=True)
            sys.exit(1)
        print("# budget check passed", flush=True)


if __name__ == "__main__":
    main()
