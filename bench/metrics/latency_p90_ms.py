"""90th percentile of request latency over every request of the
window; prints how many requests lie beyond it."""
import sys

import numpy as np


def read(win):
    lat = np.asarray(win.latencies)
    p = float(np.percentile(lat, 90))
    print(f"latency_p90_ms: {len(lat)} requests, {int((lat > p).sum())} "
          f"beyond the 90th percentile", file=sys.stderr, flush=True)
    return p * 1e3
