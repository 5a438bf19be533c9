"""Device time of Pallas kernels (``tpu_custom_call``) per pipeline call,
in ms (mean over chips)."""


def read(rec):
    t, n = rec.get("trace"), rec.get("traced_calls")
    if not t or not n:
        return None
    devs = t["devices"]
    s = sum(d["ops"].get("custom", 0.0) for d in devs) / len(devs)
    return 1e3 * s / n if s > 0 else None
