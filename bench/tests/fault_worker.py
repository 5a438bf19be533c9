"""Worker for ``test_bench_checks.py``: the four-chip table cell at toy
size on four host devices, once sound and once with the exchange between
chips left out (``all_to_all`` returns its input, so every row stays on
the shard that sent it).  Prints ``{"sound": bool, "no_exchange": bool}``,
each the run's ``correct``.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python bench/tests/fault_worker.py
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    import jax

    from bench.drivers import table_query as TQ
    from bench.run import resolve

    _, cell, config, traffic = resolve("fig4-join-200m.join_groupby.w4")
    device = {"platform": "cpu", "kind": "cpu", "count": 4}

    def once():
        return TQ.run(cell=cell, config=config, traffic=traffic, seed=5,
                      seconds=0.2, trace=False, rehearse=True,
                      device=device, t_start=time.perf_counter()).correct

    sound = once()
    jax.lax.all_to_all = lambda x, *a, **k: x
    print(json.dumps({"sound": sound, "no_exchange": once()}))


if __name__ == "__main__":
    main()
