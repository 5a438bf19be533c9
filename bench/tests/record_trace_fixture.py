"""Record the small device trace that ``test_bench_trace_reduce.py`` reads.

    python bench/tests/record_trace_fixture.py [out_dir]

Run on a TPU.  Traces two calls of one jitted program (an XLA sort, a
scatter-add, a bf16 matmul and the ``hash_partition`` Pallas kernel), each
under a host span ``pipeline_call``, with a host span ``host_wait`` between
them that leaves the device idle.  Prints every plane and line of the
trace with a few events, so the layout the reduction relies on can be read.
Copy the ``.xplane.pb`` it names to ``bench/tests/data/fixture.xplane.pb``.
"""
import glob
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))


def main():
    out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace_fixture"
    import jax
    import jax.numpy as jnp

    from repro.kernels.hash_partition import radix_histogram_ranks

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace_fixture: needs a TPU")

    def fixture_step(keys, vals, a):
        s = jnp.sort(keys)
        acc = jnp.zeros((4096,), jnp.float32).at[keys % 4096].add(vals)
        m = (a @ a).astype(jnp.float32).sum()
        hist, ranks = radix_histogram_ranks(keys % 512, 512, impl="pallas")
        return s[:8], acc.sum() + m, hist, ranks[:8]

    step = jax.jit(fixture_step)
    k = jax.random.randint(jax.random.PRNGKey(0), (1 << 20,), 0, 1 << 30)
    v = jax.random.normal(jax.random.PRNGKey(1), (1 << 20,))
    a = jax.random.normal(jax.random.PRNGKey(2), (1024, 1024), jnp.bfloat16)
    jax.block_until_ready(step(k, v, a))
    jax.profiler.start_trace(out)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("pipeline_call"):
            jax.block_until_ready(step(k, v, a))
        with jax.profiler.TraceAnnotation("host_wait"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{out}/**/*.xplane.pb", recursive=True))[-1]
    print("xplane", path, os.path.getsize(path))
    pd = jax.profiler.ProfileData.from_file(path)
    for pl in pd.planes:
        print("PLANE", pl.name, [(k, v) for k, v in pl.stats][:8])
        for ln in pl.lines:
            evs = list(ln.events)
            print("  LINE", repr(ln.name), len(evs))
            for e in evs[:12]:
                print("    ", repr(e.name), e.start_ns, e.duration_ns,
                      [(k, v) for k, v in e.stats][:10])


if __name__ == "__main__":
    main()
