"""Host turnaround of one slot prefill, in ms: the median of the program's
``serve/prefill`` spans (padding, the prefill and cache-insert dispatches,
the first token's sync), from ``repro.trace``."""
from bench import harness
from bench.program_spans import recorder


def read(rec):
    trace = recorder(rec)
    if trace is None:
        return None
    xs = [r.seconds for r in trace.records("serve/prefill")]
    return 1e3 * harness.nearest_rank(xs, 50) if xs else None
