"""Benchmark utilities: timing, CSV records, subprocess multi-device runs."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Callable

import numpy as np

from repro.launch.env import child_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(REPO, "results")


def timeit(fn: Callable, *, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds per call (after warmup compiles)."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


class Reporter:
    """Collects (bench, config, metric, value) rows; prints CSV; saves."""

    def __init__(self, name: str):
        self.name = name
        self.rows: list[dict] = []

    def add(self, config: str, metric: str, value, **extra):
        row = {"bench": self.name, "config": config, "metric": metric,
               "value": float(value), **extra}
        self.rows.append(row)
        print(f"{self.name},{config},{metric},{value:.6g}", flush=True)

    def save(self):
        os.makedirs(RESULTS, exist_ok=True)
        path = os.path.join(RESULTS, "bench.json")
        existing = []
        if os.path.exists(path):
            with open(path) as f:
                existing = json.load(f)
        # replace per (bench, config, metric, rows) row — not the whole
        # bench — so a --fast run refreshes its own (smaller-``rows``)
        # rows without wiping the full-size baselines the perf gate
        # compares against (and vice versa)
        fresh = {row_key(r) for r in self.rows}
        existing = [r for r in existing if row_key(r) not in fresh]
        with open(path, "w") as f:
            json.dump(existing + self.rows, f, indent=1)


def row_key(r: dict) -> tuple:
    """Identity of a bench.json row: same bench/config/metric at the same
    problem size."""
    return (r.get("bench"), r.get("config"), r.get("metric"),
            r.get("rows"))


def load_results() -> list[dict]:
    path = os.path.join(RESULTS, "bench.json")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return json.load(f)


def run_subprocess_bench(script: str, n_devices: int, *args,
                         timeout: int = 900) -> dict:
    """Run a bench script on ``n_devices`` devices (forced host devices on
    the CPU, appended to ``XLA_FLAGS``); parse its last JSON line."""
    env = child_env(n_devices)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", script),
         *map(str, args)],
        env=env, capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"{script} failed:\n{proc.stderr[-2000:]}")
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(f"no JSON result line in {script} output")
