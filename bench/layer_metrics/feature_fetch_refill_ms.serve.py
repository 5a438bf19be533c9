"""Time of one refill's feature fetch, in ms: the median of the program's
``serve/feature_fetch`` spans (every store's lookup and the copy of the
features onto the requests), from ``repro.trace``."""
from bench import harness
from bench.program_spans import recorder


def read(rec):
    trace = recorder(rec)
    if trace is None:
        return None
    xs = [r.seconds for r in trace.records("serve/feature_fetch")]
    return 1e3 * harness.nearest_rank(xs, 50) if xs else None
