"""Seconds of host-side join capacity planning: the sum of the program's
``table/plan_join_sizes`` spans, from ``repro.trace``."""
from bench.program_spans import recorder


def read(rec):
    trace = recorder(rec)
    if trace is None:
        return None
    rs = trace.records("table/plan_join_sizes")
    return sum(r.seconds for r in rs) if rs else None
