"""Decode step's share of the HBM roofline, in %: the bytes one step must
move (held weights once, every slot's conv and SSM state read and
written, K and V of the live cached positions read;
``bench/hybrid_flops.decode_bytes``, over the traced steps) over peak HBM
bandwidth times the decode program's device time per call in the trace."""
from bench import harness


def read(rec):
    t, nbytes = rec.get("trace"), rec.get("decode_bytes")
    if not t or not nbytes:
        return None
    secs = n = 0
    for d in t["devices"]:
        for name, (s, c) in d["programs"].items():
            if "decode" in name:
                secs, n = secs + s, n + c
    if not n:
        return None
    bw = harness.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / (bw * secs / n)
