"""The per-layer readers of the spans the program records about itself
(``repro.trace``): each reads its value from a recorder filled by hand,
finds nothing outside a traced run or in a program without the recorder,
and no program span takes a name of the benchmark's own spans."""
import collections
import re
import sys

import pytest

from bench import harness, trace_reduce
from bench.run import ROOT
from repro import trace

TRACED = {"trace": {"window_s": 1.0}, "device_kind": "TPU v5 lite"}


def _span(name, start, end, parent=None, **attrs):
    trace._records[name].append(trace.Span(name, start, end, parent, attrs))


@pytest.fixture
def filled(monkeypatch):
    """A recorder holding a short serve run and a short table run."""
    monkeypatch.setattr(trace, "_records", collections.defaultdict(
        lambda: collections.deque(maxlen=trace.KEEP)))
    # decode steps of 40, 30 and 50 ms, waiting 36, 28 and 45 ms on the
    # device: host 4, 2 and 5 ms
    for t, (d, w) in enumerate(((0.040, 0.036), (0.030, 0.028),
                                (0.050, 0.045))):
        _span("serve/device_wait", t + d - w, t + d, "serve/decode")
        _span("serve/decode", t, t + d, "serve/step", active=16)
    for t, f in ((10, 0.016), (11, 0.018), (12, 0.020)):
        _span("serve/feature_fetch", t, t + f, "serve/refill", req_ids=[t])
    for t, (secs, n) in enumerate(((0.150, 1024), (0.152, 512),
                                   (0.149, 2048), (0.160, 512))):
        _span("serve/prefill", 20 + t, 20 + t + secs, "serve/refill",
              req_id=t, slot=t, prompt_len=n, positions=2048)
    # three collects of 7.3 s waits and 3, 6 and 9 ms of host work
    for t, host in ((30, 0.003), (40, 0.006), (50, 0.009)):
        _span("table/device_wait", t, t + 7.3, "table/collect")
        _span("table/collect", t, t + 7.3 + host)
    _span("table/distribute", 1.0, 1.25, rows=100, world=1)
    _span("table/distribute", 2.0, 2.5, rows=100, world=1)
    _span("table/distribute", 3.0, 3.5, "serve/feature_fetch", rows=8,
          world=1)                          # not top level: not ingest
    _span("table/plan_join_sizes", 4.0, 4.736)


WANT = {
    "step_host_ms.serve": 4.0,
    "feature_fetch_refill_ms.serve": 18.0,
    "prefill_turnaround_ms.serve": 150.0,
    "prefill_pad_share.serve": 100 * (1 - (1024 + 512 + 2048 + 512)
                                      / (4 * 2048)),
    "collect_host_ms.table": 6.0,
    "ingest_s.table": 0.75,
    "join_plan_s.table": 0.736,
}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_reads_the_recorder(metric, filled):
    assert harness.load_reader(metric)(TRACED) == pytest.approx(
        WANT[metric], rel=1e-9)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_finds_nothing_without_a_trace(metric, filled):
    assert harness.load_reader(metric)({"trace": None}) is None


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_finds_nothing_in_an_empty_recorder(metric, monkeypatch):
    monkeypatch.setattr(trace, "_records", collections.defaultdict(
        lambda: collections.deque(maxlen=trace.KEEP)))
    assert harness.load_reader(metric)(TRACED) is None


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_finds_nothing_in_a_program_without_the_recorder(
        metric, filled, monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.trace", None)
    monkeypatch.delattr(sys.modules["repro"], "trace")
    assert harness.load_reader(metric)(TRACED) is None


def test_program_spans_do_not_take_the_benchmarks_names():
    names = set()
    for path in (ROOT / "src" / "repro").rglob("*.py"):
        names |= set(re.findall(r'trace\.span\(\s*"([^"]+)"',
                                path.read_text()))
    assert {"serve/step", "serve/prefill", "table/collect"} <= names
    assert not names & set(trace_reduce.SPAN_NAMES)
    assert trace.GC_SPAN not in trace_reduce.SPAN_NAMES
