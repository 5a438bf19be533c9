"""Seconds of host ingest: the sum of the program's top-level
``table/distribute`` spans (numpy columns laid out per shard and put on
the devices), from ``repro.trace``."""
from bench.program_spans import recorder


def read(rec):
    trace = recorder(rec)
    if trace is None:
        return None
    rs = [r for r in trace.records("table/distribute") if r.parent is None]
    return sum(r.seconds for r in rs) if rs else None
