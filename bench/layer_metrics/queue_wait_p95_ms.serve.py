"""95th percentile of the engine's own ``queue_wait`` series (submit to
admission) over the window's requests, in ms."""


def read(rec):
    return rec.get("queue_wait_p95_ms")
