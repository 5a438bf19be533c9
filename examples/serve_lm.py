"""Batched LM serving example (deliverable b, serving kind).

    PYTHONPATH=src python examples/serve_lm.py

Prefill a batch of prompts, then greedy-decode continuation tokens with
the static KV cache.  Thin wrapper over the serving launcher
(repro.launch.serve).
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
cmd = [sys.executable, "-m", "repro.launch.serve",
       "--arch", "lm100m", "--reduced",
       "--batch", "4", "--prompt-len", "32", "--gen", "16"]
env = dict(os.environ)
env["PYTHONPATH"] = os.path.join(REPO, "src")
print("+", " ".join(cmd))
sys.exit(subprocess.run(cmd, env=env).returncode)
