"""Process set-up shared by every entry point: compile cache and host
devices.

Two rules hold for every program of this repo.

* **One compile cache.**  :func:`enable_compile_cache` turns on JAX's
  persistent compilation cache.  Where ``JAX_COMPILATION_CACHE_DIR`` is
  set, JAX reads it itself and it stays the only cache setting; otherwise
  the cache lives at the fixed ``<repo>/.jax_cache``.  The path is part of
  the cache's key, so it is never built from a temp name, a pid or the
  time.
* **Forced host devices are a CPU device.**  ``--xla_force_host_platform_
  device_count`` stands in for chips only when JAX runs on the CPU.  On a
  chip it is never set, and a mesh larger than the chips there are raises
  (``core.context.make_mesh``) instead of shrinking.

Both must run before JAX picks its backend, so this module imports JAX
lazily.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO / ".jax_cache"
COUNT_FLAG = "--xla_force_host_platform_device_count"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def on_cpu() -> bool:
    """``JAX_PLATFORMS`` puts the CPU first — known before JAX starts,
    which is when the host device count must already be in place."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"


def with_host_devices(xla_flags: str, n: int) -> str:
    """``xla_flags`` with the host device count set to ``n``: other flags
    are kept, a stale count is replaced."""
    flags = [f for f in xla_flags.split() if not f.startswith(COUNT_FLAG)]
    return " ".join(flags + [f"{COUNT_FLAG}={n}"])


def child_env(n_devices: int) -> dict:
    """Environment for a child process that needs ``n_devices`` devices:
    on the CPU the host device count is appended to ``XLA_FLAGS``; on a
    chip the environment passes through unchanged."""
    env = dict(os.environ)
    if on_cpu():
        env["XLA_FLAGS"] = with_host_devices(env.get("XLA_FLAGS", ""),
                                             n_devices)
    return env


def ensure_host_devices(n: int, argv: list[str]) -> None:
    """On the CPU, re-exec ``python *argv`` once with ``n`` host devices
    (no-op when the count is already in place, so the re-exec ends).  On
    a chip, or for one device, does nothing."""
    if n <= 1 or not on_cpu():
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if f"{COUNT_FLAG}={n}" in flags.split():
        return
    os.environ["XLA_FLAGS"] = with_host_devices(flags, n)
    os.execv(sys.executable, [sys.executable] + list(argv))
