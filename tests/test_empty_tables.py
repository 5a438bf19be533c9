"""Driver for zero-row / empty-shard coverage of the distributed
operators (dist_join, dist_groupby, dist_sort, dist_isin) at world
sizes 1/2/4 — subprocess workers with forced host devices."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _empty_conformance(world, backend):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={world}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "dist", "empty_conformance.py"),
         str(world), backend],
        env=env, capture_output=True, text=True, timeout=1800)
    sys.stdout.write(proc.stdout[-4000:])
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, \
        f"empty conformance failed (world={world}, {backend})"
    assert "EMPTY CONFORMANCE PASSED" in proc.stdout


@pytest.mark.parametrize("world", [1, 2, 4])
def test_empty_table_conformance(world):
    _empty_conformance(world, "default")


@pytest.mark.parametrize("world", [1, 2, 4])
def test_empty_table_conformance_hash_backends(world):
    """Hash join, groupby and semi-join, radix sort: reached only by
    argument."""
    _empty_conformance(world, "hash")
