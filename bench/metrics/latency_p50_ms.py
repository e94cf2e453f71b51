"""Median request latency: send to the return of the call that served
it (host clock), over every request of the window."""
import numpy as np


def read(win):
    return float(np.percentile(win.latencies, 50)) * 1e3
