"""Each cell's comparison with its reference, at toy size on the CPU with
the table kernels in interpret mode: it accepts what the program serves,
and it rejects the program with a fault planted underneath the timed
path."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench.drivers import serve_lm as SL
from bench.drivers import table_query as TQ
from bench.run import resolve

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _run(driver, cell, seconds=0.3):
    _, c, config, traffic = resolve(cell)
    return driver.run(cell=c, config=config, traffic=traffic, seed=11,
                      seconds=seconds, trace=False, rehearse=True,
                      device=DEVICE, t_start=time.perf_counter())


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas_interpret")


def _wrap_pipeline(monkeypatch, change):
    from repro.core.table import Table
    orig = TQ.join_groupby

    def broken(plan, cap, mod):
        inner = orig(plan, cap, mod)

        def pipeline(c, a, b):
            cols, n = change(dict(a.columns), a.nvalid)
            return inner(c, Table(columns=cols, nvalid=n), b)
        return pipeline
    monkeypatch.setattr(TQ, "join_groupby", broken)


TABLE = "fig4-join-200m.join_groupby"


def test_table_accepts_the_program(interpret):
    r = _run(TQ, TABLE)
    assert r.correct, r.checks


def test_table_rejects_a_dropped_row(interpret, monkeypatch):
    orig = TQ.Cell.call

    def call(self):
        out = orig(self)
        out["groups"] = {k: v[:-1] for k, v in out["groups"].items()}
        return out
    monkeypatch.setattr(TQ.Cell, "call", call)
    r = _run(TQ, TABLE)
    assert not r.correct and r.checks["group_keys_gap"][0] > 0


def test_table_rejects_half_the_rows_left_out(interpret, monkeypatch):
    _wrap_pipeline(monkeypatch, lambda cols, n: (cols, n // 2))
    r = _run(TQ, TABLE)
    assert not r.correct and r.checks["join_rows_gap"][0] > 0


def test_table_rejects_bf16_sums(interpret, monkeypatch):
    import jax.numpy as jnp

    def bf16(cols, n):
        cols["lv"] = cols["lv"].astype(jnp.bfloat16).astype(jnp.float32)
        return cols, n
    _wrap_pipeline(monkeypatch, bf16)
    r = _run(TQ, TABLE)
    assert not r.correct and r.checks["sum_err"][0] > r.checks["sum_err"][1]


def test_table_control_fails_the_limit():
    left, right, nkeys = TQ.make_tables(20000, 3, 0.1, "uniform")
    want = TQ.reference(left, right, nkeys, 2)
    _, _, _, traffic = resolve(TABLE)
    assert TQ.control_sum_err(left, right, nkeys, want) > \
        traffic["limits"]["sum_err"]


def test_four_chip_table_rejects_a_missing_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("REPRO_KERNEL_IMPL", None)
    out = subprocess.run(
        [sys.executable, str(Path(__file__).parent / "fault_worker.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "no_exchange": False}


CHAT = "granite-3-2b.chat"


def test_chat_accepts_the_program():
    r = _run(SL, CHAT, seconds=1.0)
    assert r.correct, r.checks
    assert r.attempted > 5 and r.failed == 0


def test_chat_rejects_an_altered_token(monkeypatch):
    from repro.serving.batcher import SlotBatch
    orig = SlotBatch.advance
    done = []

    def advance(self, next_tokens, on_token=None):
        act = [s for s in self.active() if self.request_at(s).req_id >= 0]
        if act and not done:
            next_tokens = np.array(next_tokens)
            next_tokens[act[0], 0] = (next_tokens[act[0], 0] + 7) % 512
            done.append(1)
        return orig(self, next_tokens, on_token)
    monkeypatch.setattr(SlotBatch, "advance", advance)
    monkeypatch.setattr(SL, "sample", lambda fin, seed, n, k: list(fin))
    r = _run(SL, CHAT, seconds=1.0)
    assert done and not r.correct
    assert r.checks["logit_gap"][0] > r.checks["logit_gap"][1]


def test_chat_rejects_an_altered_feature(monkeypatch):
    from repro.serving import ServingEngine
    orig = ServingEngine._fetch_features

    def fetch(self, reqs):
        good = orig(self, reqs)
        if good:
            name = next(iter(good[0].features))
            good[0].features[name] += 1.0
        return good
    monkeypatch.setattr(ServingEngine, "_fetch_features", fetch)
    r = _run(SL, CHAT, seconds=1.0)
    assert not r.correct and r.checks["feature_gap"][0] > 0
