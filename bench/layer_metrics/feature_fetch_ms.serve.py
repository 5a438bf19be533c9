"""Host time of one feature-store lookup in the window, in ms (mean), on
the benchmark's span around each ``FeatureStore.lookup``."""


def read(rec):
    return rec.get("feature_fetch_ms")
