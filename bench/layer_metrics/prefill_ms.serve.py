"""Device time of the slot-prefill program per call, in ms, from the
trace's program executions."""


def read(rec):
    t = rec.get("trace")
    if not t:
        return None
    secs = n = 0
    for d in t["devices"]:
        for name, (s, c) in d["programs"].items():
            if "prefill" in name:
                secs, n = secs + s, n + c
    return 1e3 * secs / n if n else None
