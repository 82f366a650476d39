"""Median submit-to-result latency over every request submitted in the
window (those finishing after its close included)."""

import numpy as np


def read(run):
    r = run.records
    sel = r.done & (r.submit_t >= run.t0) & (r.submit_t <= run.t1)
    if not sel.any():
        return None
    return float(np.percentile(r.done_t[sel] - r.submit_t[sel], 50)) * 1e3
