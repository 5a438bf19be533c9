"""Device time of XLA sort ops per pipeline call, in ms (mean over chips):
the local operators' sorts (sort groupby, sortmerge join, scatters that
XLA lowers to sorts)."""


def read(rec):
    t, n = rec.get("trace"), rec.get("traced_calls")
    if not t or not n:
        return None
    devs = t["devices"]
    return 1e3 * sum(d["ops"].get("sort", 0.0) for d in devs) / len(devs) / n
