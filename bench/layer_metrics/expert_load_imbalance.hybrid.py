"""How unevenly a decode step's routed pairs fall on the held experts
(x): per step, each MoE layer's largest pair count on one held expert over
its mean count per held expert, averaged over the layers; the median over
the run's decode steps.  The counts are the ``expert_pairs`` attribute of
the program's ``serve/decode`` spans (``repro.trace``); 1 is even."""
import numpy as np

from bench.program_spans import recorder


def read(rec):
    trace = recorder(rec)
    if trace is None:
        return None
    per_step = []
    for r in trace.records("serve/decode"):
        pairs = np.asarray(r.attrs.get("expert_pairs", []), np.float64)
        if pairs.ndim != 2 or not pairs.size:
            continue
        mean = pairs.mean(axis=1)
        ok = mean > 0
        if ok.any():
            per_step.append(float(np.mean(pairs.max(axis=1)[ok] / mean[ok])))
    return float(np.median(per_step)) if per_step else None
