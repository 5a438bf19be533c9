"""80th percentile of the first-token latency over every request due in
the window, in ms: the queueing for a slot that bursts of arrivals cause
(the median, an end-to-end metric, sits at one prefill)."""


def read(rec):
    return rec.get("ttft_p80_ms")
