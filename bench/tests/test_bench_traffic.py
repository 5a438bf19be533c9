"""The generators: the same seed gives the same inputs, another seed
another order of the same work."""
from collections import Counter

import numpy as np
import pytest

from bench import traffic_gen
from bench.drivers import table_query as TQ
from bench.run import resolve

_, _, _, CHAT = resolve("granite-3-2b.chat")
KEYS = {"drug_id": (256, 1.3), "cell_id": (128, 1.3)}


def _plan(seed):
    return traffic_gen.plan(CHAT, 51.0, seed, 49155, KEYS)


def test_chat_plan_repeats_for_a_seed():
    a, b = _plan(7), _plan(7)
    assert [p.due for p in a] == [p.due for p in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [p.keys for p in a] == [p.keys for p in b]


def test_chat_plan_seeds_reorder_the_same_work():
    a, b = _plan(7), _plan(2 ** 31 + 12345)
    assert [p.gen_len for p in a] != [p.gen_len for p in b]
    full = round(CHAT["rate_per_s"] * 51.0)
    assert len(a) == len(b) == full
    assert [p.due for p in a] != [p.due for p in b]
    lo, hi = CHAT["prompt_len"]["min"], CHAT["prompt_len"]["max"]
    for plan in (a, b):
        assert all(lo <= len(p.prompt) <= hi for p in plan)
        assert all(0 <= p.due < 51.0 for p in plan)
        assert all(x.due <= y.due for x, y in zip(plan, plan[1:]))
        assert all(0 <= p.keys["drug_id"] < 256 for p in plan)
    pool = Counter(traffic_gen.lengths(CHAT["gen_len"], full).tolist())
    for plan in (a, b):
        assert not Counter(p.gen_len for p in plan) - pool


def test_chat_arrivals_cluster_as_poisson():
    """Per 5.1 s of the window, the count of arrivals varies across seeds
    as a Poisson process given its count makes it (multinomial), and the
    gaps between arrivals vary as exponential ones do."""
    bins, counts, cv = 10, [], []
    for seed in range(300):
        due = np.array([p.due for p in _plan(seed)])
        counts += np.histogram(due, bins, (0.0, 51.0))[0].tolist()
        g = np.diff(due)
        cv.append(g.std() / g.mean())
    n = round(CHAT["rate_per_s"] * 51.0)
    want = n * (1 / bins) * (1 - 1 / bins)
    assert 0.8 < np.var(counts) / want < 1.2
    assert 0.85 < np.mean(cv) < 1.1


def test_lengths_follow_the_mix():
    x = traffic_gen.lengths(CHAT["prompt_len"], 1001)
    assert np.median(x) == CHAT["prompt_len"]["median"]
    assert x.min() >= 32 and x.max() <= 2048


@pytest.mark.parametrize("seed", [3, 2 ** 32 + 5])
def test_tables_repeat_for_a_seed(seed):
    a = TQ.make_tables(1000, seed, 0.1, "uniform")
    b = TQ.make_tables(1000, seed, 0.1, "uniform")
    c = TQ.make_tables(1000, seed + 1, 0.1, "uniform")
    assert all(np.array_equal(a[0][k], b[0][k]) for k in a[0])
    assert not np.array_equal(a[0]["k"], c[0]["k"])
    assert not np.array_equal(a[0]["lv"], c[0]["lv"])
    assert np.array_equal(np.sort(a[0]["k"]), np.sort(c[0]["k"]))
    assert np.array_equal(np.sort(a[1]["k"]), np.sort(c[1]["k"]))
    assert a[2] == 100 and a[0]["k"].max() < 100


@pytest.mark.parametrize("world", [1, 4])
def test_capacities_do_not_depend_on_the_seed(world):
    from repro.core import dist_ops as D
    rows = 20000 * world
    plans = []
    for seed in (1, 2, 3):
        left, right, nkeys = TQ.make_tables(rows, seed, 0.1, "uniform")
        plan = D.plan_dist_join_sizes([left["k"]], [right["k"]],
                                      world=world)
        plans.append((plan["out_capacity"], plan["shuffle_sizes"]))
    assert plans[0] == plans[1] == plans[2]


def test_balanced_order_spreads_each_stratum_over_the_window():
    rng = np.random.default_rng(4)
    v = np.arange(64)
    got = traffic_gen.balanced(v, rng)
    assert sorted(got) == list(v)
    for k in range(0, 64, traffic_gen.BLOCK):
        assert sorted(got[k:k + 8] // 8) == list(range(8))
