"""The table kernels' share of their roofline, in %: the least time in
which the chip could move each kernel call's operands and results (bytes
reckoned from the shapes in the trace, ``bench/flops.shape_bytes``) at
peak HBM bandwidth, summed, over the kernels' summed device time.  The
kernels are memory-bound counting passes, so bandwidth bounds them."""
from bench import harness


def read(rec):
    t = rec.get("trace")
    if not t:
        return None
    secs = sum(k[0] for d in t["devices"] for k in d["kernels"].values())
    nbytes = sum(k[2] for d in t["devices"] for k in d["kernels"].values())
    if secs <= 0 or nbytes <= 0:
        return None
    bw = harness.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (nbytes / bw) / secs
