"""Median time to route, in ms: from a request's admission into the
step loop to its route (sigma and mode known), over every request of
the window, on the host clock (``StepStats.wall``: admitted, routed,
retired). Prints how many requests it read. A program that keeps no
wall stamps reads nothing."""
import math
import sys

import numpy as np


def read(win):
    ttr = []
    for c in win.calls:
        wall = getattr(c.result.step, "wall", None)
        if wall is None:
            return None
        ttr += [routed - admitted for admitted, routed, _ in wall.values()
                if math.isfinite(routed - admitted)]
    if not ttr:
        return None
    print(f"time_to_route_p50_ms: {len(ttr)} requests", file=sys.stderr,
          flush=True)
    return float(np.percentile(ttr, 50)) * 1e3
