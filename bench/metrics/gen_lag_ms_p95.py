"""How late the load generator handed chunks over, 95th percentile.

A chunk's lag is its submit time less the later of its due time and the end
of the tick before (when the loop was last free to take it).  Waiting behind
a busy server is latency, not lag.
"""

import numpy as np

from bench.stats import percentile


def read(run):
    w = run.in_window & np.isfinite(run.done)
    lag = run.submit[w] - np.maximum(run.due[w], run.free[w])
    p = percentile(np.maximum(lag, 0.0), 95)
    return None if p is None else p * 1e3
