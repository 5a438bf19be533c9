"""Host time of collecting a pipeline's output table, in ms per call: the
median over the program's ``table/collect`` spans of each span less its
``table/device_wait`` (the copy to the host and the concatenation of the
shards, without the wait for the program), from ``repro.trace``."""
from bench import harness
from bench.program_spans import recorder


def read(rec):
    trace = recorder(rec)
    if trace is None:
        return None
    xs = trace.self_times("table/collect", ["table/device_wait"])
    return 1e3 * harness.nearest_rank(xs, 50) if xs else None
