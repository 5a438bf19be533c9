"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace_fixture.py``): two calls of one program, each under a
``pipeline_call`` span, with a ``host_wait`` span between them."""
from pathlib import Path

import pytest

from bench import trace_reduce

FIXTURE = Path(__file__).parent / "data" / "fixture.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce(FIXTURE)


def test_busy_and_window(red):
    assert len(red["devices"]) == 1
    assert 0 < red["busy_s"] < red["window_s"]


def test_op_kinds_and_kernels(red):
    ops = red["devices"][0]["ops"]
    assert ops["sort"] > 0 and ops["custom"] > 0 and ops["fusion"] > 0
    k = red["devices"][0]["kernels"]
    assert list(k) == ["_radix_histogram_ranks"]
    secs, calls, nbytes = k["_radix_histogram_ranks"]
    assert calls == 2 and secs > 0
    # per call: s32 pid (1024,1,1024) in; hist (1024,1,512), ranks out
    assert nbytes == 2 * 4 * (1024 * 1024 + 1024 * 512 + 1024 * 1024)


def test_programs(red):
    secs, calls = red["devices"][0]["programs"]["jit_fixture_step"]
    assert calls == 2 and abs(secs - red["busy_s"]) < 0.01 * secs


def test_longest_gap_is_named_by_the_host_span(red):
    name, secs = red["idle_gaps"][0]
    assert name == "host_wait" and secs > 0.04
    assert red["top_ops"][0][1] >= red["top_ops"][-1][1]


@pytest.mark.parametrize("text,want", [
    ("%sort.6 = (s32[8]{0}, s32[8]{0}) sort(s32[8]{0} %a), dimensions={0}",
     ("sort.6", "sort", "sort")),
    ("%fusion.1 = f32[4096]{0} fusion(s32[8]{0} %x), kind=kLoop",
     ("fusion.1", "fusion", "fusion")),
    ("%all-to-all.3 = s32[4,8]{1,0} all-to-all(s32[4,8]{1,0} %s)",
     ("all-to-all.3", "all-to-all", "all-to-all")),
    ('%_k.2 = (s32[2]{0}) custom-call(s32[2]{0} %b), '
     'custom_call_target="tpu_custom_call"', ("_k.2", "custom-call",
                                              "custom")),
    ("%all-reduce-start = f32[] all-reduce-start(f32[] %p)",
     ("all-reduce-start", "all-reduce-start", "collective")),
])
def test_parse_op(text, want):
    assert trace_reduce.parse_op(text) == want


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


def test_self_time_leaves_nested_ops_out_of_their_parent():
    evs = [_Ev("while", 0, 100), _Ev("a", 10, 30), _Ev("b", 50, 20),
           _Ev("c", 200, 5)]
    got = {e.name: round(s * 1e9) for e, s in trace_reduce.self_times(evs)}
    assert got == {"while": 50, "a": 30, "b": 20, "c": 5}
