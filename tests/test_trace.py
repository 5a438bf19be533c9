"""The process-wide span recorder (``repro.trace``): nesting and self time,
the per-name bound, attributes, the profiler's host plane, and the
compile and GC counters."""
import collections
import gc
import time

import pytest

import jax
import jax.numpy as jnp

from repro import trace


@pytest.fixture
def fresh(monkeypatch):
    """An empty buffer, so spans other tests recorded are not read."""
    monkeypatch.setattr(trace, "_records", collections.defaultdict(
        lambda: collections.deque(maxlen=trace.KEEP)))


def test_nesting_parents_and_self_time(fresh):
    with trace.span("t/outer"):
        time.sleep(0.002)
        with trace.span("t/inner"):
            time.sleep(0.02)
        with trace.span("t/other"):
            time.sleep(0.01)
    (outer,), (inner,), (other,) = (trace.records(n) for n in
                                    ("t/outer", "t/inner", "t/other"))
    assert outer.parent is None
    assert inner.parent == other.parent == "t/outer"
    assert outer.start <= inner.start < inner.end <= other.start \
        < other.end <= outer.end
    (less_inner,) = trace.self_times("t/outer", ["t/inner"])
    assert less_inner == pytest.approx(outer.seconds - inner.seconds)
    (self_all,) = trace.self_times("t/outer")
    assert self_all == pytest.approx(
        outer.seconds - inner.seconds - other.seconds)
    assert 0 < self_all < less_inner
    s = trace.summary()["t/outer"]
    assert s["count"] == 1 and s["self_p50_s"] == pytest.approx(self_all)


def test_self_time_pairs_children_with_their_own_parent(fresh):
    for pause in (0.004, 0.001):
        with trace.span("t/step"):
            with trace.span("t/wait"):
                time.sleep(pause)
    with trace.span("t/wait"):              # top level: no parent
        time.sleep(0.01)
    steps = trace.records("t/step")
    waits = [r for r in trace.records("t/wait") if r.parent == "t/step"]
    got = trace.self_times("t/step", ["t/wait"])
    assert got == pytest.approx([s.seconds - w.seconds
                                 for s, w in zip(steps, waits)])


def test_a_name_keeps_its_last_records(fresh):
    for i in range(trace.KEEP + 10):
        with trace.span("t/many", i=i):
            pass
    with trace.span("t/few"):
        pass
    rs = trace.records("t/many")
    assert len(rs) == trace.KEEP
    assert rs[0].attrs["i"] == 10 and rs[-1].attrs["i"] == trace.KEEP + 9
    assert len(trace.records("t/few")) == 1


def test_attributes_are_kept(fresh):
    with trace.span("t/attrs", req_id=7, prompt_len=5, positions=12,
                    req_ids=[1, 2]):
        pass
    (r,) = trace.records("t/attrs")
    assert r.attrs == {"req_id": 7, "prompt_len": 5, "positions": 12,
                       "req_ids": [1, 2]}
    assert trace.records("t/absent") == []


def test_span_decorates_a_function(fresh):
    @trace.span("t/decorated", kind="f")
    def f(x):
        return x + 1
    assert f(1) == 2 and f(2) == 3
    rs = trace.records("t/decorated")
    assert len(rs) == 2 and rs[0].attrs == {"kind": "f"}


def test_a_span_that_raises_is_recorded_and_unwinds(fresh):
    with pytest.raises(ValueError):
        with trace.span("t/raises"):
            raise ValueError("x")
    with trace.span("t/after"):
        pass
    assert len(trace.records("t/raises")) == 1
    assert trace.records("t/after")[0].parent is None


def test_span_lands_on_the_profilers_host_plane(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("t/profiled", req_id=3):
            jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = sorted(tmp_path.glob("**/*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    found = [(p.name, dict(ev.stats)) for p in pd.planes
             if p.name.startswith("/host:") for ln in p.lines
             for ev in ln.events if ev.name == "t/profiled"]
    assert found and found[0][1].get("req_id") == 3


def test_compile_counter_rises_on_a_fresh_jit():
    before = trace.counters()
    c = float(time.perf_counter_ns() % 1_000_003)   # a program never seen
    jax.jit(lambda x: x * 3.0 + c)(jnp.arange(5.0)).block_until_ready()
    after = trace.counters()
    assert after["compiles"] > before["compiles"]
    assert after["compile_s"] > before["compile_s"]


def test_full_collection_is_a_span_inside_what_was_open(fresh):
    n2 = trace.counters()["gc"][2]
    with trace.span("t/holds_gc"):
        gc.collect(2)
    assert trace.counters()["gc"][2] == n2 + 1
    rs = trace.records(trace.GC_SPAN)
    assert rs and rs[-1].parent == "t/holds_gc"
    assert rs[-1].attrs["generation"] == 2
    (outer,) = trace.records("t/holds_gc")
    assert outer.start <= rs[-1].start <= rs[-1].end <= outer.end


def _toy_tables():
    import numpy as np

    from repro.core import dist_ops as D
    from repro.core.context import make_context, make_mesh
    ctx = make_context(make_mesh((1,), ("data",)))
    left = {"k": np.arange(64, dtype=np.int32) % 8,
            "lv": np.ones(64, np.float32)}
    right = {"k": np.arange(32, dtype=np.int32) % 8,
             "rv": np.ones(32, np.float32)}
    return ctx, left, right, D.distribute_table(ctx, left), \
        D.distribute_table(ctx, right)


def test_pipeline_program_is_named_after_its_function_with_scopes():
    from repro.core import dist_ops as D, local_ops as L

    ctx, _, _, a, b = _toy_tables()

    def toy_join_groupby(c, x, y):
        j, jd = D.dist_join(c, x, y, left_on=["k"], out_capacity=512)
        g, gd = D.dist_groupby(c, j, ["k"], {"lv": "sum"})
        return L.select(g, g.columns["k"] % 2 == 0), jd + gd

    pipe = D.DistributedPipeline(ctx, toy_join_groupby)
    out, dropped = pipe(a, b)
    lowered = pipe._jitted.lower(a, b)
    assert lowered.as_text().startswith("module @jit_toy_join_groupby")
    text = lowered.compile().as_text()
    assert "jit_wrapped" not in text
    for scope in ("shuffle", "join/match", "join/expand", "groupby",
                  "select"):
        assert f"/{scope}/" in text, scope
    got = D.collect_table(ctx, out)
    assert list(got["k"]) == [0, 2, 4, 6] and int(dropped.max()) == 0


def test_table_spans_split_collect_from_the_device_wait(fresh):
    from repro.core import dist_ops as D

    ctx, left, right, a, _ = _toy_tables()
    D.plan_dist_join_sizes([left["k"]], [right["k"]], world=1)
    D.collect_table(ctx, a)
    dist = trace.records("table/distribute")
    assert [r.attrs for r in dist] == [{"rows": 64, "world": 1},
                                       {"rows": 32, "world": 1}]
    assert len(trace.records("table/plan_join_sizes")) == 1
    (col,), (wait,) = (trace.records(n) for n in ("table/collect",
                                                  "table/device_wait"))
    assert wait.parent == "table/collect"
    assert col.start <= wait.start <= wait.end <= col.end
    (host,) = trace.self_times("table/collect", ["table/device_wait"])
    assert 0 <= host <= col.seconds
