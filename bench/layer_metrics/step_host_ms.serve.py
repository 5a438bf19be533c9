"""Host time of one decode step, in ms: the median over the run's
``serve/decode`` spans of each span less its ``serve/device_wait`` (the
dispatch of the step, the slot bookkeeping and the completions), from the
program's own span recorder (``repro.trace``)."""
from bench import harness
from bench.program_spans import recorder


def read(rec):
    trace = recorder(rec)
    if trace is None:
        return None
    xs = trace.self_times("serve/decode", ["serve/device_wait"])
    return 1e3 * harness.nearest_rank(xs, 50) if xs else None
