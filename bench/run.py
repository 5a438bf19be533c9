#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse]

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration file (``bench/configs/<config>.json``) names the driver
(``bench/drivers/<kind>.py``) and the sizes; its traffic file
(``bench/traffic/<traffic>.json``) holds the parameters of the mix, the
limits of the correctness comparison and how long to trace.  The run
builds its data or weights from ``--seed``, warms every shape the cell
uses (set-up), measures for ``--seconds``, checks what the timed path
produced against a plain reference, and prints one JSON object as the
last line of standard output: the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics from a device trace of the window.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.  ``--rehearse`` is for checking the
harness on the CPU at the traffic file's toy sizes; it is never a chip
measurement.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def resolve(name: str, root: Path = ROOT) -> tuple[dict, dict, dict, dict]:
    """``(spec, cell, config, traffic)`` of the cell called ``name``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return spec, cell, config, traffic


def start(name: str, rehearse: bool):
    """What every entry point (this one, ``controls.py``, ``sweep.py``)
    does before it drives a cell: resolve it, keep compiled programs in
    the persistent cache, find the chips and load the cell's driver.
    Returns ``(spec, cell, config, traffic, device, driver)``."""
    spec, cell, config, traffic = resolve(name)
    chips = int(cell["chips"])
    if rehearse:
        os.environ.setdefault("REPRO_KERNEL_IMPL", "pallas_interpret")
        from repro.launch.env import ensure_host_devices, on_cpu
        if on_cpu():
            ensure_host_devices(chips, sys.argv)

    from repro.launch.env import enable_compile_cache
    enable_compile_cache()
    import jax
    # every program of the cell is kept, however fast it compiled, so a
    # second run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from bench import harness
    device = harness.require_device(chips, allow_cpu=rehearse)
    driver = importlib.import_module(f"bench.drivers.{config['kind']}")
    return spec, cell, config, traffic, device, driver


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU stand-in at toy sizes (never a chip run)")
    args = ap.parse_args()
    t_start = time.perf_counter()
    spec, cell, config, traffic, device, driver = start(args.workload,
                                                        args.rehearse)
    from bench import harness
    harness.log(f"cell {cell['name']} config {cell['config']} traffic "
                f"{cell['traffic']} device {device} seed {args.seed}")
    run = driver.run(cell=cell, config=config, traffic=traffic,
                     seed=args.seed % (1 << 64), seconds=args.seconds,
                     trace=bool(args.trace), rehearse=args.rehearse,
                     device=device, t_start=t_start)
    line = harness.result_line(run, spec, cell, device, bool(args.trace))
    harness.log(f"whole run {time.perf_counter() - t_start:.3f}s")
    harness.print_checks(run)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
