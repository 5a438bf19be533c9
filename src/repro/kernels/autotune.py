"""Per-backend tuning table for the radix / bucketing kernel family.

Every kernel in this package is shaped by two static knobs:

* ``radix_bits`` — digits per LSD counting-sort pass (``kernels/radix_sort``):
  more bits means fewer passes but a wider ``2**radix_bits`` one-hot per
  pass;
* ``tile`` — rows per Pallas grid step (``hash_partition``,
  ``fused_bucketing``, ``radix_sort``): wider tiles amortize grid overhead
  but grow the per-step ``tile x P`` one-hot's VMEM footprint.

The right trade-off depends on the backend (interpreted CPU vs real TPU
VPU) and the problem size, so callers resolve the knobs through
:func:`tuned` instead of hard-coding them.  Resolution order:

1. ``REPRO_RADIX_BITS`` / ``REPRO_TILE`` env overrides (highest priority —
   the escape hatch for a known-good setting);
2. the process-local cache, keyed by ``(knob, backend, dtype,
   capacity_bucket)`` where ``capacity_bucket`` is the capacity rounded up
   to a power of two (so one sweep covers a whole size class);
3. with ``REPRO_AUTOTUNE=1``, a first-use measurement sweep over the
   candidate values (timed on a synthetic workload of the bucketed
   capacity, result cached);
4. otherwise the static per-backend default.

The sweep is deliberately cheap (one warmup + one timed run per
candidate, capacity capped) — it pays for itself on any workload that
reuses a size class, and the cache means it runs once per process.
"""
import functools
import os
import time

# per-backend defaults: the interpreted/ref paths on CPU favor fewer
# one-hot columns per pass; the compiled Pallas path defaults match the
# TPU-aligned shapes the kernels were written for (tile and one-hot width
# as multiples of the 128-lane VPU registers).
_DEFAULTS = {
    "radix_bits": {"ref": 8, "pallas": 8, "pallas_interpret": 8},
    "tile": {"ref": 1024, "pallas": 1024, "pallas_interpret": 1024},
}
# candidate grids for the measurement sweep.  radix_bits candidates keep
# the per-pass one-hot narrow enough to materialize on any backend
# (2**11 = 2048 columns at most); any tile candidate is VMEM-safe because
# the tile kernels scan 128 rows at a time (``tile_scan``).
_CANDIDATES = {
    "radix_bits": (4, 8, 11),
    "tile": (512, 1024, 2048),
}
_ENV = {"radix_bits": "REPRO_RADIX_BITS", "tile": "REPRO_TILE"}
_SWEEP_CAP = 1 << 16   # rows of synthetic data per timed candidate

_cache: dict = {}


def clear_cache() -> None:
    """Drop all cached tuning decisions (tests / fresh sweeps)."""
    _cache.clear()


def _env_int(name: str):
    v = os.environ.get(name, "").strip()
    return int(v) if v else None


def _capacity_bucket(capacity: int) -> int:
    return 1 << max(0, int(capacity - 1).bit_length()) if capacity > 1 else 1


def _time_once(fn) -> float:
    fn()                                   # warmup (trace + compile)
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _sweep(knob: str, backend: str, capacity: int) -> int:
    """Measure each candidate on a synthetic workload, return the fastest."""
    import jax
    import jax.numpy as jnp

    from .radix_sort.ops import _radix_permutation

    n = max(8, min(capacity, _SWEEP_CAP))
    # deterministic pseudo-random keys (a Weyl sequence): enough entropy
    # to exercise every digit pass without jax.random's setup cost
    col = (jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761)) \
        .astype(jnp.int32)
    invalid = jnp.zeros((n,), bool)
    best, best_t = None, None
    for cand in _CANDIDATES[knob]:
        kw = {"radix_bits": cand} if knob == "radix_bits" else {"tile": cand}

        def run(kw=kw):
            jax.block_until_ready(_radix_permutation(
                (col,), invalid, impl=backend, **{
                    "radix_bits": _DEFAULTS["radix_bits"][backend],
                    "tile": _DEFAULTS["tile"][backend], **kw}))

        t = _time_once(run)
        if best_t is None or t < best_t:
            best, best_t = cand, t
    return best


def tuned(knob: str, backend: str, capacity: int,
          dtype: str = "int32") -> int:
    """Resolve ``knob`` ('radix_bits' | 'tile') for one kernel call.

    ``backend`` is the kernel impl string ('ref' | 'pallas' |
    'pallas_interpret'); ``capacity`` the row capacity the kernel will
    run at (bucketed to a power of two for the cache key).
    """
    env = _env_int(_ENV[knob])
    if env is not None:
        return env
    key = (knob, backend, str(dtype), _capacity_bucket(capacity))
    if key not in _cache:
        if os.environ.get("REPRO_AUTOTUNE", "") == "1":
            _cache[key] = _sweep(knob, backend, key[3])
        else:
            _cache[key] = _DEFAULTS[knob].get(backend,
                                              _DEFAULTS[knob]["ref"])
    return _cache[key]


def radix_params(backend: str, capacity: int, radix_bits=None, tile=None):
    """(radix_bits, tile) with ``None`` entries resolved via :func:`tuned`
    — the shared resolver for the radix/bucketing op wrappers."""
    if radix_bits is None:
        radix_bits = tuned("radix_bits", backend, capacity)
    if tile is None:
        tile = tuned("tile", backend, capacity)
    return radix_bits, tile
