"""Driver for the multi-device distributed checks.

Runs tests/dist/dist_checks.py in a subprocess with 8 forced host devices
so the main pytest process keeps the single real CPU device.
"""
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def test_distributed_operator_checks():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "dist", "dist_checks.py")],
        env=env, capture_output=True, text=True, timeout=1800)
    sys.stdout.write(proc.stdout[-4000:])
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "distributed checks failed"
    assert "DIST CHECKS PASSED" in proc.stdout
