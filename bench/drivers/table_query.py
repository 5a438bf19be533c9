"""Driver for table deployments: the paper's Fig. 4 join feeding a groupby.

Data: two tables of ``rows_per_table_per_chip x chips`` rows, an int32 key
and a float32 value, keys drawn from the traffic's key distribution over
``rows x key_uniqueness`` values once (the same keys for every seed), the
order of the rows and the values from the seed.  Capacities are the
program's own planner's (``plan_dist_join_sizes``).  Query (one
``DistributedPipeline`` program, then ``collect_table``): inner join on
``k`` -> per-shard combine (sum ``lv``, sum and count ``rv``) ->
``dist_groupby`` -> ``select`` keys ``% select_mod == 0`` -> collect.

The window runs whole calls until ``--seconds`` have passed and waits
for the last.  Every call's answer is compared with a float64 numpy
reference once the window has closed.
"""
from __future__ import annotations

import time

import numpy as np

from bench import harness

KEY_DRAW = 2108      # the generator of the key draw that every seed shares


def make_tables(rows: int, seed: int, key_uniqueness: float,
                key_dist: str):
    """Two tables of ``rows`` rows: int32 key, float32 value.

    Each side's keys are one draw from the key distribution, the same for
    every seed; the seed orders them and draws the values.  So every seed
    joins the same number of pairs per key, the program's planner sizes
    every seed alike, and the programs the first run compiled serve them
    all."""
    nkeys = max(1, int(rows * key_uniqueness))
    if key_dist != "uniform":
        raise ValueError(f"unknown key distribution {key_dist!r}")
    rng = np.random.default_rng(seed)

    def keys(side: int) -> np.ndarray:
        drawn = np.random.default_rng([KEY_DRAW, side]).integers(
            0, nkeys, rows, dtype=np.int32)
        return rng.permutation(drawn)
    left = {"k": keys(0), "lv": rng.standard_normal(rows, dtype=np.float32)}
    right = {"k": keys(1), "rv": rng.standard_normal(rows, dtype=np.float32)}
    return left, right, nkeys


def join_groupby(plan: dict, groups_cap: int, select_mod: int):
    """join -> combine -> dist_groupby -> select, as one program; returns
    (groups, join rows, join/combine/groupby/select drops)."""
    import jax
    import jax.numpy as jnp

    from repro.core import dist_ops as D, local_ops as L
    from repro.core.table import Table

    def shrink(t):
        acc = Table(columns={n: jnp.zeros((groups_cap,), v.dtype)
                             for n, v in t.columns.items()},
                    nvalid=jnp.int32(0))
        return L.append_rows(acc, t)

    def pipeline(c, a, b):
        j, jdrop = D.dist_join(c, a, b, left_on=["k"],
                               out_capacity=plan["out_capacity"],
                               shuffle_sizes=plan["shuffle_sizes"])
        part, pdrop = shrink(L.groupby_aggregate(
            j, ["k"], {"lv": "sum", "rv": ["sum", "count"]}))
        g, gdrop = D.dist_groupby(
            c, part, ["k"], {"lv_sum": "sum", "rv_sum": "sum",
                             "rv_count": "sum"},
            overcommit=float(c.world_size))
        s, sdrop = shrink(L.select(g, g.columns["k"] % select_mod == 0))
        return (s, j.nvalid, jdrop, jax.lax.psum(pdrop, c.row_axes), gdrop,
                jax.lax.psum(sdrop, c.row_axes))
    return pipeline


def reference(left, right, nkeys: int, select_mod: int) -> dict:
    """What the query must answer, in float64, with each sum's sum of
    absolute terms (the scale its rounding error is measured against)."""
    k_l, k_r = left["k"], right["k"]
    cl = np.bincount(k_l, minlength=nkeys).astype(np.int64)
    cr = np.bincount(k_r, minlength=nkeys).astype(np.int64)
    lv = left["lv"].astype(np.float64)
    rv = right["rv"].astype(np.float64)
    pairs = cl * cr
    keys = np.flatnonzero((pairs > 0) & (np.arange(nkeys) % select_mod == 0))
    return {
        "join_rows": int(pairs.sum()),
        "k": keys,
        "count": pairs[keys],
        "lv_sum": (np.bincount(k_l, lv, nkeys) * cr)[keys],
        "lv_abs": (np.bincount(k_l, np.abs(lv), nkeys) * cr)[keys],
        "rv_sum": (np.bincount(k_r, rv, nkeys) * cl)[keys],
        "rv_abs": (np.bincount(k_r, np.abs(rv), nkeys) * cl)[keys],
    }


def compare(answer: dict, want: dict) -> dict:
    """The numbers compared for one call's answer (0 is exact)."""
    got = answer["groups"]
    order = np.argsort(got["k"], kind="stable")
    gk = got["k"][order]
    missing = np.setdiff1d(want["k"], gk).size
    extra = np.setdiff1d(gk, want["k"]).size + (gk.size - np.unique(gk).size)
    out = {"join_rows_gap": abs(answer["join_rows"] - want["join_rows"]),
           "rows_dropped": sum(answer["drops"]),
           "group_keys_gap": missing + extra}
    if missing or extra:
        out.update(count_gap=float("inf"), sum_err=float("inf"))
        return out
    cnt = got["rv_count_sum"][order].astype(np.float64)
    out["count_gap"] = float(np.max(np.abs(cnt - want["count"]), initial=0))
    errs = [np.abs(got[g][order].astype(np.float64) - want[w]) /
            np.maximum(want[a], 1e-30)
            for g, w, a in (("lv_sum_sum", "lv_sum", "lv_abs"),
                            ("rv_sum_sum", "rv_sum", "rv_abs"))]
    out["sum_err"] = float(max(np.max(e, initial=0) for e in errs))
    return out


def control_sum_err(left, right, nkeys: int, want: dict) -> float:
    """The control: the reference's sums computed in bfloat16, the
    precision below the configuration's float32 values, on the device;
    returns their ``sum_err`` against the float64 reference."""
    import jax.numpy as jnp
    bf = jnp.bfloat16
    keys = jnp.asarray(want["k"])
    cl = jnp.zeros(nkeys, bf).at[left["k"]].add(jnp.ones_like(left["k"], bf))
    cr = jnp.zeros(nkeys, bf).at[right["k"]].add(
        jnp.ones_like(right["k"], bf))
    ls = jnp.zeros(nkeys, bf).at[left["k"]].add(jnp.asarray(left["lv"], bf))
    rs = jnp.zeros(nkeys, bf).at[right["k"]].add(
        jnp.asarray(right["rv"], bf))
    got = {"k": np.asarray(want["k"]),
           "lv_sum_sum": np.asarray((ls * cr)[keys], np.float64),
           "rv_sum_sum": np.asarray((rs * cl)[keys], np.float64),
           "rv_count_sum": want["count"]}
    return compare({"groups": got, "join_rows": want["join_rows"],
                    "drops": [0]}, want)["sum_err"]


def worst(readings: list[dict]) -> dict:
    return {k: max(r[k] for r in readings) for k in readings[0]}


class Cell:
    """The cell's program and data, built from the seed (set-up)."""

    def __init__(self, config: dict, traffic: dict, world: int, seed: int,
                 spans: harness.Spans, rehearse: bool = False):
        from repro.core import dist_ops as D
        from repro.core.context import make_context, make_mesh

        sizes = dict(config["sizes"], **(traffic.get("rehearsal", {})
                                         if rehearse else {}))
        self.rows = int(sizes["rows_per_table_per_chip"]) * world
        self.select_mod = int(traffic["select_mod"])
        self.setup = {}
        t = time.perf_counter()
        self.left, self.right, self.nkeys = make_tables(
            self.rows, seed, float(config["key_uniqueness"]),
            traffic["key_dist"])
        self.setup["data_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.ctx = make_context(make_mesh((world,), ("data",)))
        self.gl = D.distribute_table(self.ctx, self.left)
        self.gr = D.distribute_table(self.ctx, self.right)
        import jax
        jax.block_until_ready((self.gl.columns, self.gr.columns))
        self.setup["distribute_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.plan = D.plan_dist_join_sizes([self.left["k"]],
                                           [self.right["k"]], world=world)
        self.setup["host_plan_s"] = time.perf_counter() - t
        groups_cap = int(traffic["groups_cap_per_key_share"]) * \
            -(-self.nkeys // world)
        self.pipe = D.DistributedPipeline(
            self.ctx, join_groupby(self.plan, groups_cap, self.select_mod))
        self.spans = spans

    def call(self) -> dict:
        """One whole query: the program, then its answer on the host."""
        from repro.core import dist_ops as D
        with self.spans("pipeline_call"):
            out, joined, *drops = self.pipe(self.gl, self.gr)
            groups = D.collect_table(self.ctx, out)
            return {"groups": groups,
                    "join_rows": int(np.asarray(joined).sum()),
                    "drops": [int(np.asarray(d).max()) for d in drops]}

    def compiled(self):
        return self.pipe._jitted.lower(self.gl, self.gr).compile()


def run(*, cell, config, traffic, seed, seconds, trace, rehearse, device,
        t_start, control=False) -> harness.Run:
    import jax

    world = int(cell["chips"])
    spans = harness.Spans()
    compiles = harness.compile_counter()
    c = Cell(config, traffic, world, seed, spans, rehearse)
    t = time.perf_counter()
    c.call()                                        # compile or load, warm
    c.setup["warm_call_s"] = time.perf_counter() - t
    n0, s0 = compiles()
    c.setup["compile_s"] = s0
    setup_s = time.perf_counter() - t_start
    harness.log(f"set-up {setup_s:.3f}s {c.setup} rows/table {c.rows} "
                f"keys {c.nkeys} join out_capacity/shard "
                f"{c.plan['out_capacity']} compiles {n0}")

    tr = harness.Trace(trace, spans, f"{cell['name']}-{seed}", seconds,
                       float(traffic.get("trace_seconds", seconds)))
    answers = []
    t0 = time.perf_counter()
    while True:
        tr.poll(time.perf_counter() - t0)
        answers.append(c.call())
        if time.perf_counter() - t0 >= seconds:
            break
    t1 = time.perf_counter()
    tr.stop()
    traced_calls = sum(1 for n, a, b in spans.records
                       if n == "pipeline_call" and tr.t0 is not None
                       and a >= tr.t0 and b <= tr.t1)
    n1, _ = compiles()
    harness.log(f"window {t1 - t0:.3f}s calls {len(answers)} compiles in "
                f"window {n1 - n0} call seconds "
                f"{[round(b - a, 4) for n, a, b in spans.records[1:21]]}")

    r = harness.Run()
    r.memory_peak_bytes = harness.memory_peak_bytes(
        jax.devices()[:world], [c.compiled()])
    r.attempted = len(answers)
    r.end_to_end = {"table_rows_per_s": 2 * c.rows * len(answers) / (t1 - t0),
                    "setup_s": setup_s}
    r.record = {"cell": cell["name"], "world": world,
                "trace": tr.reduce(world), "traced_calls": traced_calls,
                "host_plan_s": c.setup["host_plan_s"]}
    want = reference(c.left, c.right, c.nkeys, c.select_mod)
    readings = [compare(a, want) for a in answers]
    limits = traffic["limits"]
    r.failed = sum(1 for g in readings
                   if any(not v <= limits[k] for k, v in g.items()))
    for k, v in worst(readings).items():
        r.check(k, v, limits[k])
    if control:
        r.record["control"] = {"sum_err": control_sum_err(
            c.left, c.right, c.nkeys, want)}
    return r
