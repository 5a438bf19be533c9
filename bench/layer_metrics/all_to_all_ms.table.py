"""Device time of all-to-all ops per pipeline call on the chip that spent
the most, in ms: the shuffle over the chip interconnect, waiting for the
slowest chip included."""


def read(rec):
    t, n = rec.get("trace"), rec.get("traced_calls")
    if not t or not n or rec.get("world", 1) < 2:
        return None
    s = max(d["ops"].get("all-to-all", 0.0) for d in t["devices"])
    return 1e3 * s / n if s > 0 else None
