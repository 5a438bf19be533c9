"""Subprocess worker: out-of-core morsel-driven join + groupby at a given
parallelism.

Usage: XLA_FLAGS=...device_count=W \
           python _subproc_outofcore.py W rows chunk [source]

Fig4-shaped data at out-of-core scale: a ``rows``-row fact table with 10%
key uniqueness streamed in ``chunk``-row morsels against a resident
``rows/10``-row dimension build side (one row per key, so the join emits
exactly ``rows`` rows).  The timed run is the full streaming pass —
distribute every chunk, run it through the cached pipeline, collect the
output morsels — i.e. end-to-end out-of-core throughput including the
one-time compile (amortized over the chunk count, as in production).

``source`` is ``ram`` (default) or ``memmap``: the memmap leg spills the
probe columns to disk files and streams them back as ``np.memmap``
views — the truly-larger-than-memory path, where each morsel's rows are
paged in from disk by the chunk slice itself (``ChunkedTable`` chunks
are slices, so nothing is materialized until distribution).

Prints one JSON line:
{"world": W, "rows": N, "chunk_rows": C, "chunks": k, "source": s,
 "join_seconds": s, "join_out_rows": M, "join_dropped": d,
 "groupby_seconds": s2, "groups": g, "groupby_dropped": d2}
"""
import json
import math
import os
import sys
import tempfile
import time

import numpy as np


def _to_memmap(cols: dict, tmpdir: str) -> dict:
    out = {}
    for name, v in cols.items():
        path = os.path.join(tmpdir, f"{name}.bin")
        mm = np.memmap(path, dtype=v.dtype, mode="w+", shape=v.shape)
        mm[:] = v
        mm.flush()
        out[name] = np.memmap(path, dtype=v.dtype, mode="r",
                              shape=v.shape)
    return out


def main():
    world = int(sys.argv[1])
    rows = int(sys.argv[2])
    chunk = int(sys.argv[3])
    source = sys.argv[4] if len(sys.argv) > 4 else "ram"
    from repro.launch.env import enable_compile_cache
    enable_compile_cache()
    import jax
    from repro.core import morsel as M
    from repro.core.context import make_context, make_mesh

    ctx = make_context(make_mesh((world,), ("data",)))
    rng = np.random.default_rng(0)
    nkeys = max(rows // 10, 1)
    left = {"k": rng.integers(0, nkeys, rows).astype(np.int32),
            "lv": rng.normal(size=rows).astype(np.float32)}
    right = {"k": np.arange(nkeys, dtype=np.int32),
             "rv": rng.normal(size=nkeys).astype(np.float32)}
    tmpdir = None
    if source == "memmap":
        tmpdir = tempfile.mkdtemp(prefix="outofcore_")
        left = _to_memmap(left, tmpdir)
    probe = M.ChunkedTable(left, chunk)
    out_rows = 0

    def sink(part):
        nonlocal out_rows               # stream, never materialize
        out_rows += len(part["k"])

    t0 = time.perf_counter()
    _, dropped = M.chunked_dist_join(
        ctx, probe, right, left_on=["k"],
        build_capacity_per_shard=math.ceil(nkeys / world * 2),
        sink=sink)
    join_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    g, gdropped = M.chunked_dist_groupby(
        ctx, probe, ["k"], {"lv": ["sum", "count"]},
        group_capacity_per_shard=math.ceil(nkeys / world * 2))
    groupby_s = time.perf_counter() - t0

    print(json.dumps({
        "world": world, "rows": rows, "chunk_rows": chunk,
        "chunks": probe.num_chunks, "source": source,
        "join_seconds": join_s, "join_out_rows": out_rows,
        "join_dropped": int(dropped),
        "groupby_seconds": groupby_s, "groups": len(g["k"]),
        "groupby_dropped": int(gdropped)}))
    if tmpdir is not None:
        for f in os.listdir(tmpdir):
            os.unlink(os.path.join(tmpdir, f))
        os.rmdir(tmpdir)


if __name__ == "__main__":
    main()
